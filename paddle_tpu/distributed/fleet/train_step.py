"""Compiled hybrid-parallel train step.

This is the TPU replacement for the reference's whole static-graph executor
path: Fleet meta-optimizers rewrite the Program and launch NCCL ops
(fleet/meta_optimizers/*, sharding/group_sharded_stage{2,3}.py); here ONE
pjit-compiled function contains forward, loss, backward, grad clip and the
optimizer update, with parameter/optimizer-state/batch PartitionSpecs over
the hybrid mesh. XLA GSPMD then emits exactly the ZeRO/TP/DP collectives:

* dp/sharding-sharded batch → grad psum (data parallel)
* stage 1/2: optimizer moments sharded on "sharding" → reduce-scatter +
  all-gather around the update
* stage 3: params sharded on "sharding" → all-gather params in fwd/bwd,
  reduce-scatter grads (ZeRO-3), exactly the reference's
  group_sharded_stage3 semantics
* tp-annotated weights (mp_layers) → Megatron-style partitioning

Donated buffers make the update in-place in HBM.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...autograd.tape import functional_mode
from ...framework.random_seed import functional_key, next_key
from ...jit.api import _swap_params
from ...tensor import Tensor
from .. import mesh as mesh_mod
from ..mesh import data_pspec, infer_param_pspec


def _opt_state_pspec(param_spec: P, leaf_shape, param_shape, stage: int):
    """Moments follow the param spec; stages 1/2 additionally shard
    replicated moments over the sharding axis (ZeRO-1/2). Stage 3 does
    the same for moments of params that stayed tp-sharded-only (their
    param spec deliberately omits "sharding" — see
    mesh.infer_param_pspec)."""
    if len(leaf_shape) == 0:
        return P()
    if tuple(leaf_shape) != tuple(param_shape):
        return P()
    spec = list(param_spec) + [None] * (len(leaf_shape) - len(param_spec))
    import numpy as _np
    used_axes = set()
    for a in spec:
        used_axes.update(a if isinstance(a, tuple) else (a,))
    # stages 1/2 shard every matching moment (pre-existing behavior);
    # stage 3 only bothers for >=1024-elem leaves — tiny moments aren't
    # worth the collective the reshard costs
    if "sharding" not in used_axes and (
            stage in (1, 2)
            or (stage == 3 and int(_np.prod(leaf_shape)) >= 1024)):
        ssize = mesh_mod.mesh_axis_size("sharding")
        if ssize > 1:
            for d in range(len(leaf_shape)):
                if spec[d] is None and leaf_shape[d] % ssize == 0:
                    spec[d] = "sharding"
                    break
    return P(*spec)


class CompiledTrainStep:
    """Callable train step bound to (model, optimizer, loss_fn).

    loss_fn(model, *batch) -> scalar loss Tensor. Batch leaves are sharded
    on the (dp, sharding) axes; call with per-step global batch Tensors.
    """

    def __init__(self, model, optimizer, loss_fn: Callable, strategy=None,
                 amp_level: Optional[str] = None, amp_dtype="bfloat16",
                 donate: bool = True, accumulate_steps: Optional[int] = None,
                 scaler=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.strategy = strategy
        self.stage = strategy.sharding_stage if strategy is not None else 0
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype

        # Gradient accumulation (reference: gradient_merge_optimizer.py
        # k_steps / pipeline accumulate_steps): k micro-steps scanned inside
        # ONE compiled program, fp32 grad accumulation, one update.
        if accumulate_steps is None:
            accumulate_steps = 1
            if strategy is not None:
                if strategy.gradient_merge:
                    accumulate_steps = int(
                        strategy.gradient_merge_configs.get("k_steps", 1))
                elif strategy.pipeline:
                    accumulate_steps = int(
                        strategy.pipeline_configs.get("accumulate_steps", 1))
        self.accumulate_steps = max(1, int(accumulate_steps))

        # Dynamic loss scaling (reference: amp/grad_scaler.py) compiled into
        # the step: scaled loss, unscale grads, found_inf -> skip update and
        # decay the scale; all with lax/where, no host sync.
        self._scaler_cfg = None
        if scaler is not None and getattr(scaler, "_enable", True):
            self._scaler_cfg = {
                "init": float(getattr(scaler, "_scale", 2.0 ** 15)),
                "incr_ratio": float(getattr(scaler, "_incr_ratio", 2.0)),
                "decr_ratio": float(getattr(scaler, "_decr_ratio", 0.5)),
                "incr_every": int(getattr(scaler, "_incr_every", 1000)),
                "decr_every": int(getattr(scaler, "_decr_every", 1)),
                "dynamic": bool(getattr(scaler, "_dynamic", True)),
            }
        self._scaler_state = {
            "scale": jnp.float32(self._scaler_cfg["init"]
                                 if self._scaler_cfg else 1.0),
            "good": jnp.int32(0),
            "bad": jnp.int32(0),
        }
        self.last_found_inf = jnp.asarray(False)

        self._params = dict(model.named_parameters())
        self._buffers = dict(model.named_buffers())
        self._param_vals = {k: p._data for k, p in self._params.items()}
        self._buffer_vals = {k: b._data for k, b in self._buffers.items()}
        self._opt_state = optimizer.init_state(self._param_vals)

        mesh = mesh_mod.get_mesh()
        self._param_specs = {
            k: infer_param_pspec(tuple(p._data.shape), p.pspec, self.stage)
            for k, p in self._params.items()}
        self._opt_specs = {
            k: jax.tree_util.tree_map(
                lambda leaf: _opt_state_pspec(
                    self._param_specs[k], leaf.shape,
                    self._params[k]._data.shape, self.stage),
                self._opt_state[k])
            for k in self._opt_state}
        self._buffer_specs = {k: P() for k in self._buffers}

        def to_sharding(tree_specs):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), tree_specs,
                is_leaf=lambda x: isinstance(x, P))

        in_shardings = (to_sharding(self._param_specs),
                        to_sharding(self._opt_specs),
                        to_sharding(self._buffer_specs),
                        None,   # scaler state: replicated scalars
                        None,   # batch: placed by caller via device_put
                        None,   # rng key: replicated
                        None)   # lr scalar: replicated
        out_shardings = (None,
                         to_sharding(self._param_specs),
                         to_sharding(self._opt_specs),
                         to_sharding(self._buffer_specs),
                         None,   # scaler state
                         None)   # found_inf

        # Commit params, opt state AND buffers to their shardings up front.
        # Leaving any of them uncommitted makes the first call compile a
        # second executable once committed outputs feed call 2.
        self._param_vals = {
            k: jax.device_put(v, NamedSharding(mesh, self._param_specs[k]))
            for k, v in self._param_vals.items()}
        self._opt_state = {
            k: jax.tree_util.tree_map(
                lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
                self._opt_state[k], self._opt_specs[k])
            for k in self._opt_state}
        self._buffer_vals = {
            k: jax.device_put(v, NamedSharding(mesh, self._buffer_specs[k]))
            for k, v in self._buffer_vals.items()}

        donate_argnums = (0, 1, 2, 3) if donate else ()
        self._compiled = jax.jit(self._step, donate_argnums=donate_argnums,
                                 in_shardings=in_shardings,
                                 out_shardings=out_shardings)
        self._mesh = mesh

    # the pure function that gets compiled; lr is an argument (NOT a traced
    # constant) so schedulers take effect without recompiling
    def _step(self, param_vals, opt_state, buffer_vals, scaler_state, batch,
              key, lr):
        scale = scaler_state["scale"]

        def loss_of(pv, bufs, mb, mkey):
            with functional_mode(), _swap_params(self._params, pv), \
                    _swap_params(self._buffers, bufs), \
                    functional_key(mkey):
                if self.amp_level:
                    from ...amp.auto_cast import auto_cast
                    with auto_cast(True, level=self.amp_level,
                                   dtype=self.amp_dtype):
                        loss = self.loss_fn(self.model, *mb)
                else:
                    loss = self.loss_fn(self.model, *mb)
                new_bufs = {k: b._data for k, b in self._buffers.items()}
            lraw = loss._data if isinstance(loss, Tensor) else loss
            lraw = lraw.astype(jnp.float32)
            return lraw * scale, (lraw, new_bufs)

        k_acc = self.accumulate_steps
        if k_acc > 1:
            for leaf in jax.tree_util.tree_leaves(batch):
                if jnp.ndim(leaf) and leaf.shape[0] % k_acc:
                    raise ValueError(
                        f"batch dim {leaf.shape[0]} not divisible by "
                        f"accumulate_steps {k_acc}")
        if k_acc == 1:
            (_, (loss, new_bufs)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals, buffer_vals, batch, key)
        else:
            # split each batch leaf [B, ...] -> [k, B/k, ...] and scan;
            # mean-of-micro-losses == full-batch loss for equal micro sizes
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(k_acc, x.shape[0] // k_acc, *x.shape[1:])
                if jnp.ndim(x) else x, batch)
            keys = jax.random.split(key, k_acc)

            def body(carry, mk):
                acc, bufs = carry
                mb, mkey = mk
                (_, (loss, bufs)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(param_vals, bufs, mb, mkey)
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return (acc, bufs), loss

            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), param_vals)
            (acc, new_bufs), losses = jax.lax.scan(
                body, (acc0, buffer_vals), (micro, keys))
            loss = jnp.mean(losses)
            grads = jax.tree_util.tree_map(
                lambda a, p: (a / k_acc).astype(p.dtype), acc, param_vals)

        if self._scaler_cfg:
            grads = jax.tree_util.tree_map(
                lambda g: g / scale.astype(g.dtype), grads)
            found_inf = jax.tree_util.tree_reduce(
                lambda a, g: jnp.logical_or(a, jnp.any(~jnp.isfinite(g))),
                grads, jnp.asarray(False))
            # poison-free grads for the update; the update is discarded via
            # `where` when found_inf, so zeros keep moments finite
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(found_inf, jnp.zeros_like(g), g), grads)
        else:
            found_inf = jnp.asarray(False)

        # Pin each grad to its PARAM's sharding. Without this, ZeRO-shard
        # moment layouts (e.g. P("tp","sharding")) propagate backward into
        # the autodiff graph and GSPMD reshards [B,S,H] activations to
        # hidden-sharded ("[SPMD] Involuntary full rematerialization");
        # constrained here, the moment reshard happens on the weight-sized
        # gradient instead.
        grads = {
            k: jax.lax.with_sharding_constraint(
                g, NamedSharding(self._mesh, self._param_specs[k]))
            for k, g in grads.items()}
        new_params, new_opt = self.optimizer.apply_gradients_functional(
            param_vals, grads, opt_state, lr, params_ref=self._params)

        if self._scaler_cfg:
            keep = lambda old, new: jax.tree_util.tree_map(
                lambda o, n: jnp.where(found_inf, o, n), old, new)
            new_params = keep(param_vals, new_params)
            new_opt = keep(opt_state, new_opt)
            new_scaler = self._next_scaler_state(scaler_state, found_inf)
        else:
            new_scaler = scaler_state
        return loss, new_params, new_opt, new_bufs, new_scaler, found_inf

    def _next_scaler_state(self, st, found_inf):
        cfg = self._scaler_cfg
        if not cfg["dynamic"]:
            return st
        scale, good, bad = st["scale"], st["good"], st["bad"]
        bad2 = jnp.where(found_inf, bad + 1, jnp.int32(0))
        good2 = jnp.where(found_inf, jnp.int32(0), good + 1)
        shrink = bad2 >= cfg["decr_every"]
        grow = good2 >= cfg["incr_every"]
        new_scale = jnp.where(
            shrink, jnp.maximum(scale * cfg["decr_ratio"], 1.0),
            jnp.where(grow, scale * cfg["incr_ratio"], scale))
        return {"scale": new_scale.astype(jnp.float32),
                "good": jnp.where(grow, jnp.int32(0), good2),
                "bad": jnp.where(shrink, jnp.int32(0), bad2)}

    def __call__(self, *batch):
        raw_batch = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, tuple(batch))
        raw_batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, NamedSharding(self._mesh, data_pspec(jnp.shape(x))))
            if jnp.ndim(x) else x,
            raw_batch)
        key = next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        (loss, self._param_vals, self._opt_state, self._buffer_vals,
         self._scaler_state, self.last_found_inf) = \
            self._compiled(self._param_vals, self._opt_state,
                           self._buffer_vals, self._scaler_state, raw_batch,
                           key, lr)
        # reflect updated state into the eager Layer/optimizer views
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        for k, b in self._buffers.items():
            b._data = self._buffer_vals[k]
        sched = self.optimizer._lr_scheduler()
        if sched is not None:
            sched.step()
        return Tensor(loss)

    def lower(self, *batch):
        """The REAL compiled step lowered on this batch (in/out shardings
        baked): ``.as_text()`` is its StableHLO, ``.compile()`` gives the
        partitioned program's text and ``memory_analysis()``."""
        raw_batch = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x,
            tuple(batch), is_leaf=lambda t: isinstance(t, Tensor))
        raw_batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, NamedSharding(self._mesh, data_pspec(jnp.shape(x))))
            if jnp.ndim(x) else x,
            raw_batch)
        key = jax.random.PRNGKey(0)       # aval-compatible probe key
        lr = jnp.asarray(0.1, jnp.float32)
        return self._compiled.lower(
            self._param_vals, self._opt_state, self._buffer_vals,
            self._scaler_state, raw_batch, key, lr)

    def lower_hlo(self, *batch) -> str:
        """Lowered StableHLO of the real step — the program text
        ``analysis.audit_train_step`` runs the tpu_lint rules over."""
        return self.lower(*batch).as_text()

    def sync_optimizer_state(self):
        """Push compiled-state moments back into the eager optimizer dicts."""
        for k, p in self._params.items():
            # tpu_lint: allow(id-keyed-cache) — p retained by self._params
            self.optimizer._accumulators[id(p)] = self._opt_state[k]

    # -- snapshot surface (resilience.TrainState / CheckpointManager) ------

    def state_dict(self):
        """The compiled step's canonical device state as one pytree —
        params, optimizer moments, buffers and the in-graph loss-scaler
        state. Leaves are (sharded) jax arrays; checkpointing them
        through distributed.checkpoint preserves/reshapes shardings."""
        return {"params": self._param_vals, "opt": self._opt_state,
                "buffers": self._buffer_vals, "scaler": self._scaler_state}

    def load_state_dict(self, state):
        """Restore a state_dict(), re-committing every leaf to this
        step's shardings (so a snapshot from a different mesh lands
        correctly), and reflect params/buffers into the eager views."""
        mesh = self._mesh

        def put(tree, specs):
            return jax.tree_util.tree_map(
                lambda leaf, s: jax.device_put(
                    jnp.asarray(leaf), NamedSharding(mesh, s)),
                tree, specs)

        self._param_vals = put(state["params"], self._param_specs)
        self._opt_state = {k: put(state["opt"][k], self._opt_specs[k])
                           for k in self._opt_state}
        self._buffer_vals = put(state["buffers"], self._buffer_specs)
        self._scaler_state = jax.tree_util.tree_map(
            jnp.asarray, state["scaler"])
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        for k, b in self._buffers.items():
            b._data = self._buffer_vals[k]


def make_train_step(model, optimizer, loss_fn, strategy=None, amp_level=None,
                    amp_dtype="bfloat16", donate=True, accumulate_steps=None,
                    scaler=None) -> CompiledTrainStep:
    return CompiledTrainStep(model, optimizer, loss_fn, strategy, amp_level,
                             amp_dtype, donate, accumulate_steps, scaler)
