"""Concrete optimizers. Reference: python/paddle/optimizer/{sgd,momentum,
adam,adamw,adamax,adagrad,adadelta,rmsprop,lamb}.py.

Each algorithm is one pure ``update_param`` — shared verbatim by the eager
and compiled paths. Moment accumulators are kept in fp32 when the param is
bf16 (multi_precision, default on — master-weights behavior of the
reference's FusedAdam).
"""
from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer


def _acc_dtype(p_raw, multi_precision):
    return jnp.float32 if (multi_precision and p_raw.dtype == jnp.bfloat16) else p_raw.dtype


def _scalar_hyper(v):
    """Hyperparameters may be python floats or (reference-style)
    1-element Tensors; collapse to a jnp scalar."""
    from ..tensor import Tensor

    if isinstance(v, Tensor):
        v = v._data
    if hasattr(v, "reshape") and getattr(v, "ndim", 0) > 0:
        v = v.reshape(())
    return v


def _f32(x):
    return x.astype(jnp.float32)


def _one_f32():
    """f32 scalar 1.0 for beta-power accumulators: device_put of a host
    scalar (jnp.asarray of a python float lowers a convert program — a
    spurious backend compile in a warm AOT-cached process)."""
    import jax
    import numpy as np

    return jax.device_put(np.float32(1.0))


def _zeros_like(p, dtype=None):
    """Zero accumulator matching ``p``. Off-trace this is a host
    allocation + device_put, NOT jnp.zeros_like: the latter is itself a
    tiny XLA program, and moment init would be the only backend compile
    left in a warm AOT-cached fresh process.
    Under an outer trace it stays a traced constant as before."""
    import jax
    import numpy as np

    dt = p.dtype if dtype is None else dtype
    if isinstance(p, jax.core.Tracer):
        return jnp.zeros_like(p, dtype=dt)
    return jax.device_put(np.zeros(np.shape(p), np.dtype(dt)))


def _needs_master(self, p):
    """Low-precision params keep a persistent fp32 master copy in the state
    (reference FusedAdam multi_precision): without it, late-training updates
    smaller than a bf16 ulp round away and training plateaus."""
    return self._multi_precision and p.dtype in (jnp.bfloat16, jnp.float16)


def _master_init(self, p, st):
    if _needs_master(self, p):
        st["master"] = p.astype(jnp.float32)
    return st


def _read_master(st, p):
    return st["master"] if "master" in st else p.astype(jnp.float32)


def _write_master(st, new_p32, p):
    if "master" in st:
        st["master"] = new_p32
    return new_p32.astype(p.dtype)


class SGD(Optimizer):
    def init_param_state(self, p):
        return _master_init(self, p, {})

    def update_param(self, p, g, st, lr, param):
        st = dict(st)
        new_p32 = _read_master(st, p) - lr * _f32(g)
        return _write_master(st, new_p32, p), st


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        if momentum is None:
            raise ValueError("momentum is not set")
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_param_state(self, p):
        return _master_init(self, p, {
            "velocity": _zeros_like(p, dtype=_acc_dtype(p, self._multi_precision))})

    def update_param(self, p, g, st, lr, param):
        st = dict(st)
        v = self._momentum * st["velocity"] + _f32(g)
        if self._nesterov:
            upd = _f32(g) + self._momentum * v
        else:
            upd = v
        st["velocity"] = v.astype(st["velocity"].dtype)
        new_p32 = _read_master(st, p) - lr * upd
        return _write_master(st, new_p32, p), st


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=True,
                 name=None):
        for nm, b in (("beta1", beta1), ("beta2", beta2)):
            if isinstance(b, (int, float)) and not 0 <= b < 1:
                raise ValueError(
                    f"Invalid value of {nm}, expect {nm} in [0, 1).")
        if isinstance(epsilon, (int, float)) and epsilon < 0:
            raise ValueError("Invalid value of epsilon, expect epsilon >= 0.")
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        # Sparse-row semantics (reference: adam_op lazy_mode / the PS
        # accessors, the_one_ps.py:220): rows with all-zero gradient this
        # step — embedding rows no id touched — keep their moments and
        # values untouched instead of decaying toward the update. Applies
        # only to sparse tables (is_sparse_table marker) — the reference
        # likewise restricts lazy_mode to SelectedRows grads; dense params
        # update normally even when their grad happens to be zero. A bare
        # update_param(..., param=None) call treats the param as sparse.
        self._lazy = bool(lazy_mode)

    def _lazy_for(self, g, param):
        return (self._lazy and jnp.ndim(g) >= 2
                and (param is None
                     or getattr(param, "is_sparse_table", False)))

    @staticmethod
    def _touched_rows(g32):
        return jnp.any(g32 != 0, axis=tuple(range(1, g32.ndim)),
                       keepdims=True)

    def init_param_state(self, p):
        dt = _acc_dtype(p, self._multi_precision)
        return _master_init(self, p, {
            "moment1": _zeros_like(p, dtype=dt),
            "moment2": _zeros_like(p, dtype=dt),
            "beta1_pow": _one_f32(),
            "beta2_pow": _one_f32()})

    def _adam_update(self, p, g, st, lr, param=None):
        """Returns (step, new_state, touched_rows_or_None)."""
        b1 = _scalar_hyper(self._beta1)
        b2 = _scalar_hyper(self._beta2)
        eps = _scalar_hyper(self._epsilon)
        g32 = _f32(g)
        m = b1 * st["moment1"] + (1 - b1) * g32
        v = b2 * st["moment2"] + (1 - b2) * g32 * g32
        b1p = st["beta1_pow"] * b1
        b2p = st["beta2_pow"] * b2
        touched = None
        if self._lazy_for(g32, param):
            touched = self._touched_rows(g32)
            m = jnp.where(touched, m, st["moment1"])
            v = jnp.where(touched, v, st["moment2"])
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        step = lr * mhat / (jnp.sqrt(vhat) + eps)
        if touched is not None:
            step = jnp.where(touched, step, 0.0)
        new_st = {"moment1": m.astype(st["moment1"].dtype),
                  "moment2": v.astype(st["moment2"].dtype),
                  "beta1_pow": b1p, "beta2_pow": b2p}
        return step, new_st, touched

    def update_param(self, p, g, st, lr, param):
        step, new_st, _ = self._adam_update(p, g, st, lr, param)
        if "master" in st:
            new_st["master"] = st["master"]
        new_p32 = _read_master(new_st, p) - step
        return _write_master(new_st, new_p32, p), new_st


class AdamW(Adam):
    _decoupled = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name)
        self._wd_coeff = weight_decay if isinstance(weight_decay, float) else \
            getattr(weight_decay, "coeff", 0.01)
        self._apply_decay_param_fun = apply_decay_param_fun

    def update_param(self, p, g, st, lr, param):
        step, new_st, touched = self._adam_update(p, g, st, lr, param)
        if "master" in st:
            new_st["master"] = st["master"]
        decay = self._wd_coeff
        if (self._apply_decay_param_fun is not None and param is not None
                and not self._apply_decay_param_fun(param.name)):
            decay = 0.0
        p32 = _read_master(new_st, p)
        wd = lr * decay * p32
        if touched is not None:
            wd = jnp.where(touched, wd, 0.0)
        new_p32 = p32 - wd - step
        return _write_master(new_st, new_p32, p), new_st


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def init_param_state(self, p):
        return {"moment": _zeros_like(p, dtype=jnp.float32),
                "inf_norm": _zeros_like(p, dtype=jnp.float32),
                "beta1_pow": _one_f32()}

    def update_param(self, p, g, st, lr, param):
        g32 = _f32(g)
        m = self._beta1 * st["moment"] + (1 - self._beta1) * g32
        u = jnp.maximum(self._beta2 * st["inf_norm"], jnp.abs(g32))
        b1p = st["beta1_pow"] * self._beta1
        step = lr * m / ((1 - b1p) * (u + self._epsilon))
        return ((p.astype(jnp.float32) - step).astype(p.dtype),
                {"moment": m, "inf_norm": u, "beta1_pow": b1p})


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def init_param_state(self, p):
        return {"moment": jnp.full_like(p, self._init_acc, dtype=jnp.float32)}

    def update_param(self, p, g, st, lr, param):
        g32 = _f32(g)
        acc = st["moment"] + g32 * g32
        step = lr * g32 / (jnp.sqrt(acc) + self._epsilon)
        return (p.astype(jnp.float32) - step).astype(p.dtype), {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = epsilon, rho

    def init_param_state(self, p):
        return {"avg_squared_grad": _zeros_like(p, dtype=jnp.float32),
                "avg_squared_update": _zeros_like(p, dtype=jnp.float32)}

    def update_param(self, p, g, st, lr, param):
        g32 = _f32(g)
        eg = self._rho * st["avg_squared_grad"] + (1 - self._rho) * g32 * g32
        upd = (jnp.sqrt(st["avg_squared_update"] + self._epsilon) /
               jnp.sqrt(eg + self._epsilon)) * g32
        eu = self._rho * st["avg_squared_update"] + (1 - self._rho) * upd * upd
        return ((p.astype(jnp.float32) - lr * upd).astype(p.dtype),
                {"avg_squared_grad": eg, "avg_squared_update": eu})


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        for nm, v in (("rho", rho), ("epsilon", epsilon),
                      ("momentum", momentum)):
            if v is None:
                raise ValueError(f"{nm} is not set.")
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def init_param_state(self, p):
        st = {"mean_square": _zeros_like(p, dtype=jnp.float32),
              "momentum": _zeros_like(p, dtype=jnp.float32)}
        if self._centered:
            st["mean_grad"] = _zeros_like(p, dtype=jnp.float32)
        return st

    def update_param(self, p, g, st, lr, param):
        g32 = _f32(g)
        ms = self._rho * st["mean_square"] + (1 - self._rho) * g32 * g32
        if self._centered:
            mg = self._rho * st["mean_grad"] + (1 - self._rho) * g32
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
        else:
            mg = None
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * st["momentum"] + lr * g32 / denom
        new_st = {"mean_square": ms, "momentum": mom}
        if mg is not None:
            new_st["mean_grad"] = mg
        return (p.astype(jnp.float32) - mom).astype(p.dtype), new_st


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def init_param_state(self, p):
        return {"moment1": _zeros_like(p, dtype=jnp.float32),
                "moment2": _zeros_like(p, dtype=jnp.float32),
                "beta1_pow": _one_f32(),
                "beta2_pow": _one_f32()}

    def update_param(self, p, g, st, lr, param):
        b1, b2 = self._beta1, self._beta2
        g32 = _f32(g)
        p32 = p.astype(jnp.float32)
        m = b1 * st["moment1"] + (1 - b1) * g32
        v = b2 * st["moment2"] + (1 - b2) * g32 * g32
        b1p = st["beta1_pow"] * b1
        b2p = st["beta2_pow"] * b2
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        wd = self._lamb_wd
        if self._exclude_fn is not None and param is not None and self._exclude_fn(param):
            wd = 0.0
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + wd * p32
        w_norm = jnp.linalg.norm(p32)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_p = p32 - lr * trust * r
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v,
                                       "beta1_pow": b1p, "beta2_pow": b2p}


class LarsMomentum(Optimizer):
    """Momentum with LARS layerwise trust ratio (reference
    fluid/optimizer.py:1975 LarsMomentumOptimizer):

        local_lr = lr * lars_coeff * ||p|| / (||g|| + wd * ||p|| + eps)
        v = mu * v + local_lr * (g + wd * p)
        p = p - v

    Parameters whose name matches ``exclude_from_weight_decay`` skip the
    decay term (and, like the reference, use wd=0 in the trust ratio).
    """

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 grad_clip=None, exclude_from_weight_decay=None,
                 epsilon=0.0, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._lars_coeff = float(lars_coeff)
        self._lars_wd = float(lars_weight_decay)
        self._eps = float(epsilon)
        self._exclude = list(exclude_from_weight_decay or [])

    def init_param_state(self, p):
        return _master_init(self, p, {
            "velocity": _zeros_like(
                p, dtype=_acc_dtype(p, self._multi_precision))})

    def update_param(self, p, g, st, lr, param):
        st = dict(st)
        wd = self._lars_wd
        pname = getattr(param, "name", "") or ""
        if any(tag in pname for tag in self._exclude):
            wd = 0.0
        p32 = _read_master(st, p)
        g32 = _f32(g)
        p_norm = jnp.sqrt(jnp.sum(p32 * p32))
        g_norm = jnp.sqrt(jnp.sum(g32 * g32))
        denom = g_norm + wd * p_norm + self._eps
        local_lr = jnp.where(
            (p_norm > 0) & (denom > 0),
            lr * self._lars_coeff * p_norm / jnp.maximum(denom, 1e-20),
            lr)
        v = (self._momentum * _f32(st["velocity"])
             + local_lr * (g32 + wd * p32))
        st["velocity"] = v.astype(st["velocity"].dtype)
        new_p32 = p32 - v
        return _write_master(st, new_p32, p), st
