"""Comm-efficient multichip training (ROADMAP item 2).

The naive Fleet data-parallel gradient path is ``backward -> full-
precision psum -> replicated update``: every step ships 4 bytes/param
over ICI, every replica redundantly holds the full optimizer state, and
tensor-parallel dots serialize behind their collectives. This module is
the train-step counterpart of PR 11's serving collective-matmuls — one
compiled shard_map program over the Fleet ``(dp, tp)`` mesh axes with
all three comm optimizations composed:

* **Quantized gradient allreduce with error feedback** (EQuARX, arXiv
  2506.17615): the flattened gradient is exchanged as chunked
  ``quantize -> reduce_scatter -> dequant-accumulate -> all_gather``.
  ``grad_compress="int8"`` sends blockwise-scaled int8 (one f32 scale
  per ``qblock`` elements, so an outlier can't crush its block's
  resolution); ``"bf16"`` halves the wire bytes with a cast. What the
  quantizer dropped is carried per replica as **error-feedback
  residuals** — explicit functional state threaded through the step (so
  PR-6 checkpoint/resume stays bitwise) and re-added to the next step's
  gradient: the compression error becomes delayed, not lost.

* **ZeRO-1 optimizer-state sharding** (arXiv 2004.13336) for plain-DP
  configs: the fused update consumes the reduce_scatter shard directly
  — each replica owns ``1/dp`` of the flat moments, updates only its
  own parameter shard, and the updated **params** all_gather (replacing
  the gradient all_gather, so the wire cost is unchanged). Because the
  exchange sums in the same order and the supported optimizers are
  elementwise, ZeRO-1 parameters are **bitwise identical** to the
  replicated-DP run.

* **Overlapped TP training matmuls**: the model traces inside
  ``collective_matmul.explicit_tp``, so Fleet Column/RowParallelLinear
  route their fwd AND bwd dots through the custom-vjp ppermute-ring
  collective-matmuls — no collective serializes after a dot anywhere in
  the train-step HLO (the ``unoverlapped-collective`` tpu_lint rule
  gates the real lowered program via ``analysis.audit_train_step``).

The compiled program resolves through ``aot.CompileService`` with a
mesh-keyed signature, so dryrun arms and warm processes stop
re-lowering: a second process sharing ``PADDLE_TPU_AOT_CACHE_DIR``
compiles 0 train-step programs.

Scope: ``dp`` (with optional ``tp``) meshes. ``sharding``/``pp``/``sep``
degrees, AMP/loss-scaling, gradient accumulation and grad clipping stay
on the GSPMD ``CompiledTrainStep`` path.
"""
from __future__ import annotations

import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..autograd.tape import functional_mode
from ..framework.random_seed import functional_key, next_key
from ..jit.api import _swap_params
from ..observability.metrics import Counter
from ..tensor import Tensor
from . import collective_matmul as cm
from . import mesh as mesh_mod
from .mesh import infer_param_pspec

__all__ = ["CommOptTrainStep", "global_comm_stats"]

#: dp-exchange payload bytes by collective op and wire dtype, counted
#: host-side per step from the static byte plan (the exchange geometry
#: is fixed at construction, so no device work is added)
COLLECTIVE_BYTES = Counter(
    "paddle_collective_bytes_total",
    "gradient-exchange payload bytes by collective op and wire dtype",
    labelnames=("op", "dtype"))

#: live steps, for the pull-time compression-ratio collector
_LIVE_STEPS: "weakref.WeakSet[CommOptTrainStep]" = weakref.WeakSet()

#: optimizers whose update is elementwise with uniform hyperparameters —
#: the precondition for the flat ZeRO-1 shard update being bitwise equal
#: to the per-parameter tree update
_ZERO1_OPTIMIZERS = ("SGD", "Momentum", "Adam", "AdamW")


def _local_shape(shape, spec):
    """Per-device block shape of ``shape`` under PartitionSpec ``spec``."""
    out = list(shape)
    for d, ax in enumerate(tuple(spec)[:len(shape)]):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh_mod.mesh_axis_size(a)
        out[d] //= size
    return tuple(out)


def _is_pspec(x):
    return isinstance(x, P)


def _tree_with_specs(fn, tree, spec_tree):
    """tree_map(fn, tree, spec_tree) that treats PartitionSpec leaves of
    ``spec_tree`` atomically (P is a tuple subclass, so a plain
    two-tree tree_map would descend into it)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = treedef.flatten_up_to(spec_tree)
    return treedef.unflatten([fn(l, s) for l, s in zip(leaves, specs)])


class CommOptTrainStep:
    """Compiled comm-optimized DP(/TP) train step.

    ``loss_fn(model, *batch) -> scalar loss``; batch leaves shard their
    leading dim over ``dp`` (must divide). ``grad_compress`` in
    ``(None, "bf16", "int8")`` selects the gradient wire format;
    ``zero1`` shards the optimizer state; ``tp_overlap=False`` keeps the
    serial ``dot -> collective`` TP forms as the A/B reference arm.
    """

    def __init__(self, model, optimizer, loss_fn: Callable,
                 grad_compress: Optional[str] = None, zero1: bool = False,
                 tp_overlap: bool = True, qblock: int = 1024,
                 strategy=None):
        if grad_compress in ("bfloat16",):
            grad_compress = "bf16"
        if grad_compress not in (None, "bf16", "int8"):
            raise ValueError(
                f"grad_compress must be None|'bf16'|'int8', got "
                f"{grad_compress!r}")
        mesh = mesh_mod.get_mesh()
        for ax in ("sharding", "pp", "sep"):
            if mesh.shape[ax] > 1:
                raise NotImplementedError(
                    f"CommOptTrainStep covers (dp, tp) meshes; {ax} "
                    f"degree {mesh.shape[ax]} stays on the GSPMD "
                    "CompiledTrainStep path")
        if getattr(optimizer, "_grad_clip", None) is not None:
            raise NotImplementedError(
                "grad_clip is not supported on the comm-opt path (the "
                "global norm would need the full gradient before the "
                "sharded exchange)")
        # flat-vector updates (the ZeRO-1 shard consumes the
        # reduce_scatter output directly) need an elementwise optimizer
        # with uniform hyperparameters; when available, the replicated
        # arm uses the SAME flat update (fenced by optimization_barrier)
        # so zero1-on/off stays bitwise-identical — two different tree/
        # flat programs let XLA's algebraic context drift them by 1 ulp
        self._flat_ok = (
            type(optimizer).__name__ in _ZERO1_OPTIMIZERS
            and not getattr(optimizer, "_lazy", False)
            and getattr(optimizer, "_apply_decay_param_fun", None) is None)
        if zero1 and not self._flat_ok:
            raise NotImplementedError(
                f"zero1 needs an elementwise optimizer with uniform "
                f"hyperparameters ({', '.join(_ZERO1_OPTIMIZERS)}, no "
                f"lazy_mode/apply_decay_param_fun); "
                f"{type(optimizer).__name__} does not qualify")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.grad_compress = grad_compress
        self.zero1 = bool(zero1)
        self.tp_overlap = bool(tp_overlap)
        self.qblock = int(qblock)
        self._mesh = mesh
        self.dp = mesh.shape["dp"]
        self.tp = mesh.shape["tp"]

        self._params = dict(model.named_parameters())
        self._buffers = dict(model.named_buffers())

        # explicit-TP weights: only Column/RowParallelLinear know how to
        # consume a sharded weight inside the explicit_tp trace; every
        # other tp-annotated param (e.g. VocabParallelEmbedding) stays
        # replicated and computes the plain replicated forward
        explicit_ids = set()
        if self.tp > 1:
            from .fleet.meta_parallel.mp_layers import (
                ColumnParallelLinear, RowParallelLinear)
            for layer in model.sublayers(include_self=True):
                if isinstance(layer, (ColumnParallelLinear,
                                      RowParallelLinear)):
                    explicit_ids.add(id(layer.weight))
                    if getattr(layer, "bias", None) is not None:
                        explicit_ids.add(id(layer.bias))

        self._param_specs = {}
        for k, p in self._params.items():
            spec = P()
            if id(p) in explicit_ids and p.pspec is not None:
                # normalized: indivisible dims fall back to replicated
                # (the layer detects the full shape and uses F.linear)
                spec = infer_param_pspec(tuple(p._data.shape), p.pspec, 0)
            self._param_specs[k] = spec
        self._param_vals = {
            k: jax.device_put(p._data,
                              NamedSharding(mesh, self._param_specs[k]))
            for k, p in self._params.items()}
        self._buffer_vals = {k: jax.device_put(
            b._data, NamedSharding(mesh, P())) for k, b in
            self._buffers.items()}

        # flat layout over the per-device LOCAL shapes (tp shards)
        self._local_shapes = {
            k: _local_shape(v.shape, self._param_specs[k])
            for k, v in self._param_vals.items()}
        self._sizes = {k: int(np.prod(s)) or 1
                       for k, s in self._local_shapes.items()}
        self._order = list(self._params)
        self.n_local = sum(self._sizes.values())
        align = self.dp * self.qblock if grad_compress == "int8" else self.dp
        self._pad = (-self.n_local) % align
        self.n_pad = self.n_local + self._pad
        self.chunk = self.n_pad // self.dp
        self.nblk = max(1, self.chunk // self.qblock) \
            if grad_compress == "int8" else 0

        # -- functional state -------------------------------------------
        tpd = self.tp

        def blocked(value, shape, dtype=np.float32):
            arr = np.broadcast_to(
                np.asarray(value, dtype),
                (self.dp, tpd) + tuple(shape)).copy()
            spec = P("dp", "tp", *((None,) * len(shape)))
            return jax.device_put(arr, NamedSharding(mesh, spec))

        if self.zero1:
            # each replica owns 1/dp of the flat moments
            shard_probe = jax.device_put(
                np.zeros((self.chunk,), np.float32))
            st0 = optimizer.init_param_state(shard_probe)
            self._opt_state = jax.tree_util.tree_map(
                lambda leaf: blocked(np.asarray(leaf),
                                     np.shape(np.asarray(leaf))), st0)
            self._opt_specs = jax.tree_util.tree_map(
                lambda leaf: P("dp", "tp",
                               *((None,) * np.asarray(leaf).ndim)), st0)
        elif self._flat_ok:
            # replicated arm of the same flat update: full flat moments
            # on every replica (the ZeRO-1 memory baseline)
            probe = jax.device_put(np.zeros((self.n_pad,), np.float32))
            st0 = optimizer.init_param_state(probe)
            self._opt_state = jax.tree_util.tree_map(
                lambda leaf: jax.device_put(
                    np.asarray(leaf), NamedSharding(mesh, P())), st0)
            self._opt_specs = jax.tree_util.tree_map(lambda _: P(), st0)
        else:
            self._opt_state = optimizer.init_state(self._param_vals)
            self._opt_specs = {
                k: jax.tree_util.tree_map(
                    lambda leaf, _k=k: (
                        self._param_specs[_k]
                        if tuple(leaf.shape) ==
                        tuple(self._param_vals[_k].shape) else P()),
                    self._opt_state[k])
                for k in self._opt_state}
            self._opt_state = {
                k: _tree_with_specs(
                    lambda leaf, s: jax.device_put(
                        leaf, NamedSharding(mesh, s)),
                    self._opt_state[k], self._opt_specs[k])
                for k in self._opt_state}

        self._ef = {}
        self._ef_specs = {}
        if grad_compress is not None:
            # e1: what phase 1's quantizer dropped, full flat size per
            # replica; e2: what phase 2's re-quantizer dropped, owned-
            # chunk size per replica (unused under zero1 — params, not
            # re-quantized grads, travel in phase 2)
            self._ef["e1"] = blocked(0.0, (self.n_pad,))
            self._ef_specs["e1"] = P("dp", "tp", None)
            if not self.zero1:
                self._ef["e2"] = blocked(0.0, (self.chunk,))
                self._ef_specs["e2"] = P("dp", "tp", None)

        # donate the state buffers (in-place update in HBM) on real
        # accelerators only: on the CPU backend a DESERIALIZED SPMD
        # executable with input-output aliasing mis-executes (wrong
        # loss / NaN / segfault on teardown), which would
        # poison the warm-start path this program's AOT entry exists
        # for. Same policy as the serving engine's KV buffers.
        donate = (1, 2) if jax.default_backend() != "cpu" else ()
        self._jitted = jax.jit(self._step, donate_argnums=donate)
        self._handle = None
        self._byte_plan = self._make_byte_plan()
        self.steps_run = 0
        _LIVE_STEPS.add(self)

    # -- wire accounting ---------------------------------------------------

    def _make_byte_plan(self):
        """(op, dtype, bytes) per step for the dp gradient exchange —
        logical payload through each collective (per tp rank)."""
        plan = []
        n, chunk, nblk = self.n_pad, self.chunk, self.nblk
        if self.grad_compress == "int8":
            plan.append(("reduce_scatter", "int8", n + 4 * nblk * self.dp))
        elif self.grad_compress == "bf16":
            plan.append(("reduce_scatter", "bf16", 2 * n))
        else:
            plan.append(("reduce_scatter", "f32", 4 * n))
        if self.zero1:
            plan.append(("all_gather", "f32", 4 * n))       # params
        elif self.grad_compress == "int8":
            plan.append(("all_gather", "int8", n + 4 * nblk * self.dp))
        elif self.grad_compress == "bf16":
            plan.append(("all_gather", "bf16", 2 * n))
        else:
            plan.append(("all_gather", "f32", 4 * n))
        return plan

    @property
    def exchange_bytes(self) -> int:
        return sum(b for _, _, b in self._byte_plan)

    @property
    def compression_ratio(self) -> float:
        """fp32-exchange bytes / actual exchange bytes (>= 1)."""
        exact = 8 * self.n_pad
        return exact / max(1, self.exchange_bytes)

    def comm_stats(self) -> dict:
        return {"grad_compress": self.grad_compress, "zero1": self.zero1,
                "tp": self.tp, "dp": self.dp, "n_params": self.n_local,
                "n_pad": self.n_pad, "chunk": self.chunk,
                "exchange_bytes_per_step": self.exchange_bytes,
                "compression_ratio": round(self.compression_ratio, 3),
                "steps": self.steps_run,
                "byte_plan": [
                    {"op": o, "dtype": d, "bytes": b}
                    for o, d, b in self._byte_plan]}

    def optimizer_state_elems_per_replica(self) -> int:
        """Array elements of optimizer state one replica holds — ~1/dp
        of the replicated count under zero1."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self._opt_state):
            n = int(np.prod(leaf.shape)) or 1
            if self.zero1:
                n //= self.dp * self.tp      # leading (dp, tp) block dims
            total += n
        return total

    # -- quantizers ---------------------------------------------------------

    def _quant(self, x):
        """Blockwise int8: x [..., chunk] -> (int8 [..., chunk],
        f32 scales [..., nblk])."""
        nblk = self.nblk
        xb = x.reshape(*x.shape[:-1], nblk, -1)
        s = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-30)
        q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
        return q.reshape(*x.shape), s[..., 0]

    def _dequant(self, q, s):
        qb = q.astype(jnp.float32).reshape(*q.shape[:-1], self.nblk, -1)
        return (qb * s[..., None]).reshape(*q.shape)

    def _flatten(self, tree):
        flat = jnp.concatenate(
            [tree[k].astype(jnp.float32).reshape(-1) for k in self._order])
        if self._pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((self._pad,), jnp.float32)])
        return flat

    def _unflatten(self, flat):
        out, off = {}, 0
        for k in self._order:
            n = self._sizes[k]
            out[k] = flat[off:off + n].reshape(self._local_shapes[k])
            off += n
        return out

    # -- the compiled step --------------------------------------------------

    def _loss_of(self):
        model, params, loss_fn = self.model, self._params, self.loss_fn
        buffers = self._buffers

        def f(pv, bufs, mb, mkey):
            with functional_mode(), _swap_params(params, pv), \
                    _swap_params(buffers, bufs), functional_key(mkey):
                if self.tp > 1:
                    with cm.explicit_tp("tp", self.tp, self.tp_overlap):
                        loss = loss_fn(model, *mb)
                else:
                    loss = loss_fn(model, *mb)
                new_bufs = {k: b._data for k, b in buffers.items()}
            raw = loss._data if isinstance(loss, Tensor) else loss
            return raw.astype(jnp.float32), new_bufs
        return f

    def _exchange(self, g, e1):
        """Phase 1: flat local grad [n_pad] -> (my summed-mean chunk
        [chunk], new e1 residual or None)."""
        dp = self.dp
        if self.grad_compress is None:
            mine = jax.lax.psum_scatter(
                g, "dp", scatter_dimension=0, tiled=True) / dp
            return mine, None
        c = g + e1
        cr = c.reshape(dp, self.chunk)
        if self.grad_compress == "int8":
            q, s = self._quant(cr)
            sent = self._dequant(q, s).reshape(-1)
            qt = jax.lax.all_to_all(q, "dp", split_axis=0, concat_axis=0,
                                    tiled=True)
            st = jax.lax.all_to_all(s, "dp", split_axis=0, concat_axis=0,
                                    tiled=True)
            mine = jnp.mean(self._dequant(qt, st), axis=0)
        else:
            q = cr.astype(jnp.bfloat16)
            sent = q.astype(jnp.float32).reshape(-1)
            qt = jax.lax.all_to_all(q, "dp", split_axis=0, concat_axis=0,
                                    tiled=True)
            mine = jnp.mean(qt.astype(jnp.float32), axis=0)
        return mine, c - sent

    def _gather_grad(self, mine, e2):
        """Phase 2 (non-zero1): owned chunk -> full averaged flat
        gradient [n_pad] on every replica (+ new e2 residual)."""
        if self.grad_compress is None:
            return jax.lax.all_gather(mine, "dp", axis=0, tiled=True), None
        c2 = mine + e2
        if self.grad_compress == "int8":
            q2, s2 = self._quant(c2)
            sent = self._dequant(q2, s2)
            qg = jax.lax.all_gather(q2, "dp", axis=0, tiled=True)
            sg = jax.lax.all_gather(s2, "dp", axis=0, tiled=True)
            g_avg = self._dequant(qg.reshape(self.dp, self.chunk),
                                  sg.reshape(self.dp, self.nblk))
        else:
            q2 = c2.astype(jnp.bfloat16)
            sent = q2.astype(jnp.float32)
            qg = jax.lax.all_gather(q2, "dp", axis=0, tiled=True)
            g_avg = qg.astype(jnp.float32).reshape(self.dp, self.chunk)
        return g_avg.reshape(-1), c2 - sent

    def _flat_update(self, p_vec, g_vec, st, lr):
        """The one flat elementwise update both DP arms share, fenced by
        optimization_barrier: without the fence, the zero1 and
        replicated programs give XLA different fusion/rewrite context
        around the same expressions and the results drift by 1 ulp —
        exactly what the bitwise zero1<->replicated contract forbids."""
        opt = self.optimizer
        p_vec, g_vec, st, lr = jax.lax.optimization_barrier(
            (p_vec, g_vec, st, lr))
        wd = getattr(opt, "_weight_decay", None)
        if wd is not None and not getattr(opt, "_decoupled", False):
            g_vec = g_vec + wd.grad_term(p_vec)
        new_p, new_st = opt.update_param(p_vec, g_vec, st, lr, None)
        return jax.lax.optimization_barrier((new_p, new_st))

    def _step(self, param_vals, opt_state, ef, buffer_vals, batch, keys,
              lr):
        from .mesh import shard_map

        dp, chunk = self.dp, self.chunk
        loss_of = self._loss_of()
        have_bufs = bool(self._buffers)

        def per_device(pv, st, ef_, bufs, mb, key, lr_):
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(pv, bufs, mb, key[0])
            g = self._flatten(grads)
            e1 = ef_.get("e1")
            mine, e1_new = self._exchange(
                g, e1[0, 0] if e1 is not None else None)
            new_ef = {}
            if e1_new is not None:
                new_ef["e1"] = e1_new[None, None]
            if self.zero1:
                i = jax.lax.axis_index("dp")
                flat_p = self._flatten(pv)
                p_shard = jax.lax.dynamic_slice(flat_p, (i * chunk,),
                                                (chunk,))
                st_local = jax.tree_util.tree_map(lambda x: x[0, 0], st)
                new_pshard, new_st = self._flat_update(
                    p_shard, mine, st_local, lr_)
                flat_new = jax.lax.all_gather(new_pshard, "dp", axis=0,
                                              tiled=True)
                upd = self._unflatten(flat_new)
                new_pv = {k: upd[k].astype(pv[k].dtype) for k in pv}
                new_st = jax.tree_util.tree_map(
                    lambda x: jnp.asarray(x)[None, None], new_st)
            else:
                e2 = ef_.get("e2")
                g_avg, e2_new = self._gather_grad(
                    mine, e2[0, 0] if e2 is not None else None)
                if e2_new is not None:
                    new_ef["e2"] = e2_new[None, None]
                if self._flat_ok:
                    flat_p = self._flatten(pv)
                    new_flat, new_st = self._flat_update(
                        flat_p, g_avg, st, lr_)
                    upd = self._unflatten(new_flat)
                    new_pv = {k: upd[k].astype(pv[k].dtype) for k in pv}
                else:
                    g_tree = self._unflatten(g_avg)
                    grads_t = {k: g_tree[k].astype(pv[k].dtype)
                               for k in pv}
                    new_pv, new_st = \
                        self.optimizer.apply_gradients_functional(
                            pv, grads_t, st, lr_,
                            params_ref=self._params)
            if have_bufs:
                # running-stat buffers: dp-mean keeps them replicated
                # (cross-replica BN semantics); int buffers pass through
                new_bufs = {
                    k: (jax.lax.pmean(v, "dp")
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in new_bufs.items()}
            return (loss.reshape(1, 1), new_pv, new_st, new_ef,
                    new_bufs)

        is_t = lambda t: isinstance(t, Tensor)  # noqa: E731
        batch_specs = jax.tree_util.tree_map(
            lambda x: P(*(("dp",) + (None,) * (len(x.shape) - 1)))
            if len(x.shape) else P(), batch, is_leaf=is_t)
        buf_specs = {k: P() for k in buffer_vals}
        fn = shard_map(
            per_device, mesh=self._mesh,
            in_specs=(self._param_specs, self._opt_specs, self._ef_specs,
                      buf_specs, batch_specs, P("dp", None), P()),
            out_specs=(P("dp", "tp"), self._param_specs, self._opt_specs,
                       self._ef_specs, buf_specs),
            check_vma=False)
        return fn(param_vals, opt_state, ef, buffer_vals, batch, keys, lr)

    # -- program resolution (aot.CompileService) ----------------------------

    def _aot_key_parts(self):
        from ..aot import keys as _akeys
        import sys
        arch = tuple(type(m).__name__
                     for m in self.model.sublayers(include_self=True))
        return ("fleet:commopt",
                tuple(sorted((a, int(s))
                             for a, s in self._mesh.shape.items())),
                self.grad_compress, self.zero1, self.tp_overlap,
                self.qblock, arch,
                _akeys.code_token(sys.modules[__name__], cm,
                                  type(self.optimizer), self.loss_fn))

    def _args(self, batch, keys, lr):
        return (self._param_vals, self._opt_state, self._ef,
                self._buffer_vals, batch, keys, lr)

    def _resolve(self, args):
        if self._handle is None:
            from ..aot import get_service
            self._handle = get_service().get(
                "fleet:commopt", args=args,
                key_parts=self._aot_key_parts(), jitted=self._jitted,
                origin="train:commopt")
        return self._handle

    def aot_stats(self) -> dict:
        h = self._handle
        return {} if h is None else {h.source: 1}

    def lower_hlo(self, *batch) -> str:
        """Lowered StableHLO of the REAL step program on this batch —
        the text ``analysis.audit_train_step`` runs the program rules
        (``unoverlapped-collective`` above all) over."""
        raw = self._raw_batch(batch)
        keys = jax.random.split(jax.random.PRNGKey(0), self.dp)
        lr = jnp.asarray(0.1, jnp.float32)
        return self._jitted.lower(*self._args(raw, keys, lr)).as_text()

    # -- stepping -----------------------------------------------------------

    def _raw_batch(self, batch):
        # is_leaf unwrap: actually REMOVES the Tensor pytree nodes (a
        # plain tree_map would rewrap), so the program args are bare
        # arrays — what the AOT signature renderer expects
        raw = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x,
            tuple(batch), is_leaf=lambda t: isinstance(t, Tensor))
        for leaf in jax.tree_util.tree_leaves(raw):
            if jnp.ndim(leaf) and leaf.shape[0] % self.dp:
                raise ValueError(
                    f"batch dim {leaf.shape[0]} not divisible by "
                    f"dp={self.dp}")
        return raw

    def __call__(self, *batch):
        raw = self._raw_batch(batch)
        keys = jax.random.split(next_key(), self.dp)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        args = self._args(raw, keys, lr)
        h = self._resolve(args)
        (loss, self._param_vals, self._opt_state, self._ef,
         self._buffer_vals) = h.call(*args)
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        for k, b in self._buffers.items():
            b._data = self._buffer_vals[k]
        self.steps_run += 1
        for op, dtype, nbytes in self._byte_plan:
            COLLECTIVE_BYTES.labels(op=op, dtype=dtype).inc(nbytes)
        sched = self.optimizer._lr_scheduler()
        if sched is not None:
            sched.step()
        # per-replica losses are identical across tp; fixed-order host
        # mean over dp (no scalar all_reduce needs to ride in the HLO)
        lmean = np.asarray(loss)[:, 0].mean(dtype=np.float32)
        return Tensor(jnp.asarray(lmean))

    # -- snapshot surface (resilience.TrainState / CheckpointManager) -------

    def state_dict(self):
        """Canonical device state: params, (sharded) optimizer moments,
        error-feedback residuals, buffers — plus the layout metadata a
        re-meshed restore needs to re-shard the flat state."""
        def i64(v):
            # 0-d ndarray: orbax's standard handler rejects bare numpy
            # scalar types but checkpoints ndarrays fine
            return np.asarray(int(v), np.int64)

        return {"params": self._param_vals, "opt": self._opt_state,
                "ef": self._ef, "buffers": self._buffer_vals,
                "meta": {"dp": i64(self.dp), "tp": i64(self.tp),
                         "n_local": i64(self.n_local),
                         "n_pad": i64(self.n_pad),
                         "zero1": i64(self.zero1),
                         "compress": i64({"int8": 1, "bf16": 2}
                                         .get(self.grad_compress, 0))}}

    def _reshard_flat(self, leaf, n_valid):
        """[dp0, tp, chunk0] owner-sharded flat state -> this mesh's
        [dp, tp, chunk] layout (positions preserved; padding rebuilt)."""
        arr = np.asarray(leaf)
        dp0 = arr.shape[0]
        if dp0 == self.dp and arr.shape[-1] == self.chunk:
            return jnp.asarray(arr)
        flat = arr.transpose(1, 0, *range(2, arr.ndim)).reshape(
            self.tp, -1)[:, :n_valid]
        out = np.zeros((self.tp, self.n_pad), np.float32)
        out[:, :n_valid] = flat
        return jnp.asarray(
            out.reshape(self.tp, self.dp, self.chunk).transpose(1, 0, 2))

    def load_state_dict(self, state):
        mesh = self._mesh

        def put(leaf, spec):
            return jax.device_put(jnp.asarray(np.asarray(leaf)),
                                  NamedSharding(mesh, spec))

        meta = state.get("meta") or {}
        dp0 = int(np.asarray(meta.get("dp", self.dp)))
        tp0 = int(np.asarray(meta.get("tp", self.tp)))
        n_valid = min(int(np.asarray(meta.get("n_local", self.n_local))),
                      self.n_local)
        if tp0 != self.tp:
            raise NotImplementedError(
                f"restore across tp degrees ({tp0} -> {self.tp}) is not "
                "supported — tp re-shards the parameters themselves")
        self._param_vals = {
            k: put(state["params"][k], self._param_specs[k])
            for k in self._param_vals}
        if self.zero1:
            def reshard(leaf, spec):
                arr = np.asarray(leaf)
                if arr.ndim == 2:
                    # scalar accumulators (beta pows) are [dp0, tp] with
                    # one identical value: replicate onto the new layout
                    return put(np.broadcast_to(
                        arr[0, 0], (self.dp, self.tp)).copy(), spec)
                return put(self._reshard_flat(arr, n_valid), spec)
            self._opt_state = _tree_with_specs(
                reshard, state["opt"], self._opt_specs)
        elif self._flat_ok:
            def repad(leaf, spec):
                arr = np.asarray(leaf)
                if arr.ndim == 1 and arr.shape[0] != self.n_pad:
                    out = np.zeros((self.n_pad,), arr.dtype)
                    out[:n_valid] = arr[:n_valid]
                    arr = out
                return put(arr, spec)
            self._opt_state = _tree_with_specs(
                repad, state["opt"], self._opt_specs)
        else:
            self._opt_state = {
                k: _tree_with_specs(put, state["opt"][k],
                                    self._opt_specs[k])
                for k in self._opt_state}
        new_ef = {}
        for k in self._ef:
            stored = state.get("ef", {}).get(k)
            if stored is None:
                continue
            arr = np.asarray(stored)
            if k == "e1":
                if arr.shape[0] == self.dp and arr.shape[-1] == self.n_pad:
                    new_ef[k] = put(arr, self._ef_specs[k])
                else:
                    # re-mesh: per-replica residuals are full-size; sum
                    # them into replica 0 so no dropped error is lost
                    # (Σ residual preserved; EF re-spreads in a few steps)
                    total = arr.sum(axis=0)[..., :n_valid]
                    out = np.zeros((self.dp, self.tp, self.n_pad),
                                   np.float32)
                    out[0, :, :n_valid] = total
                    new_ef[k] = put(out, self._ef_specs[k])
            else:   # e2: owner-sharded like the flat moments
                new_ef[k] = put(self._reshard_flat(arr, n_valid),
                                self._ef_specs[k])
        for k in self._ef:
            if k not in new_ef:
                new_ef[k] = self._ef[k]
        self._ef = new_ef
        self._buffer_vals = {k: put(state["buffers"][k], P())
                             for k in self._buffer_vals}
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        for k, b in self._buffers.items():
            b._data = self._buffer_vals[k]


def global_comm_stats() -> dict:
    """Aggregated live comm-opt step stats (profiler `comm:` line and
    the pull-time observability collector)."""
    steps = [s for s in list(_LIVE_STEPS)]
    out = {"steps": len(steps), "total_steps_run": 0, "arms": []}
    for s in steps:
        out["total_steps_run"] += s.steps_run
        out["arms"].append(s.comm_stats())
    return out
