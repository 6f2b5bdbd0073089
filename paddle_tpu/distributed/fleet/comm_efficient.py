"""Communication-efficient data-parallel training: LocalSGD and DGC.

Reference: distributed/fleet/meta_optimizers/localsgd_optimizer.py:12
(k local updates between parameter averages) and dgc_optimizer.py:1
(Deep Gradient Compression: top-k gradient sparsification with momentum
correction; Lin et al.). The reference rewrites the static Program to
insert c_allreduce every k steps / sparse allgather ops.

TPU-native redesign — both are ONE compiled pjit program each:

* LocalSGD: parameters carry an explicit leading replica axis [dp, ...]
  sharded over the mesh "dp" axis, the per-replica update is a vmap (XLA
  maps it with zero communication — each dp group touches only its own
  slice), and every k-th step a mean over the replica axis (one ICI
  all-reduce) re-synchronizes. The k-1 silent steps have NO gradient
  collective at all — the exact comm saving LocalSGD exists for.

* DGC: gradients are computed per-replica inside shard_map over "dp"
  (again no automatic psum), momentum-corrected into local residuals
  (u, v), and only each replica's top-k residual entries travel: an
  all_gather of 2k (index, value) words replaces the full-size
  all-reduce — N/k-fold less traffic at 99.9%% sparsity. Every replica
  rebuilds the combined sparse gradient locally and applies the same
  SGD update, so parameters stay bitwise replicated.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...autograd.tape import functional_mode
from ...framework.random_seed import functional_key, next_key
from ...jit.api import _swap_params
from ...tensor import Tensor
from .. import mesh as mesh_mod

__all__ = ["LocalSGDTrainStep", "DGCTrainStep",
           "CompressedAllreduceTrainStep", "GeoSGDTrainStep"]


def _loss_of(model, params, loss_fn):
    def f(pv, mb, mkey):
        with functional_mode(), _swap_params(params, pv), \
                functional_key(mkey):
            loss = loss_fn(model, *mb)
        raw = loss._data if isinstance(loss, Tensor) else loss
        return raw.astype(jnp.float32)
    return f


def _split_batch(batch, n):
    def split(x):
        if jnp.ndim(x) == 0:
            return x
        if x.shape[0] % n:
            raise ValueError(f"batch dim {x.shape[0]} not divisible by "
                             f"dp={n}")
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


# shared flatten/unflatten + spec plumbing for the shard_map-based steps

def _tree_layout(pv):
    shapes = {k: v.shape for k, v in pv.items()}
    sizes = {k: int(np.prod(v.shape)) or 1 for k, v in pv.items()}
    return list(pv), shapes, sizes


def _flatten_by(tree, order, pad=0):
    flat = jnp.concatenate(
        [tree[k].astype(jnp.float32).reshape(-1) for k in order])
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat


def _unflatten_by(flat, order, shapes, sizes):
    out, off = {}, 0
    for k in order:
        n = sizes[k]
        out[k] = flat[off:off + n].reshape(shapes[k])
        off += n
    return out


def _shardmap_specs(param_vals, micro):
    """(replicated-params spec tree, dp-leading batch spec tree). Tensor
    is itself a registered pytree — map with Tensor as the leaf so the
    result is a (prefix) spec tree, not Tensors wrapping specs."""
    is_leaf = lambda t: isinstance(t, Tensor)
    spec_rep = jax.tree_util.tree_map(lambda _: P(), param_vals,
                                      is_leaf=is_leaf)
    spec_dp0 = jax.tree_util.tree_map(
        lambda x: P(*(("dp",) + (None,) * (len(x.shape) - 1)))
        if len(x.shape) else P(),
        micro, is_leaf=is_leaf)
    return spec_rep, spec_dp0


class LocalSGDTrainStep:
    """Compiled LocalSGD step. ``k_steps=1`` is exact synchronous DP
    (average every step); larger k trades staleness for k-fold fewer
    parameter synchronizations."""

    def __init__(self, model, optimizer, loss_fn: Callable, k_steps=4,
                 begin_step=1, strategy=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.k_steps = max(1, int(k_steps))
        # reference localsgd_optimizer begin_step: fully synchronous
        # (average every step) until this step count, then go local
        self.begin_step = max(0, int(begin_step))
        mesh = mesh_mod.get_mesh()
        self.dp = mesh.shape["dp"]
        self._params = dict(model.named_parameters())

        def rep(x):
            return jnp.broadcast_to(x[None], (self.dp,) + x.shape)

        pv = {k: p._data for k, p in self._params.items()}
        self._param_vals = {k: rep(v) for k, v in pv.items()}
        self._opt_state = jax.tree_util.tree_map(
            rep, optimizer.init_state(pv))
        self._count = jnp.zeros((), jnp.int32)

        def shard_leading(leaf):
            return jax.device_put(
                leaf, NamedSharding(mesh, P(*(("dp",) +
                                              (None,) * (leaf.ndim - 1)))))

        self._param_vals = jax.tree_util.tree_map(shard_leading,
                                                  self._param_vals)
        self._opt_state = jax.tree_util.tree_map(shard_leading,
                                                 self._opt_state)
        self._mesh = mesh
        self._compiled = jax.jit(self._step, donate_argnums=(0, 1, 2))

    def _step(self, param_vals, opt_state, count, batch, key, lr):
        loss_of = _loss_of(self.model, self._params, self.loss_fn)
        micro = _split_batch(batch, self.dp)
        keys = jax.random.split(key, self.dp)

        def per_replica(pv, st, mb, mkey):
            loss, grads = jax.value_and_grad(loss_of)(pv, mb, mkey)
            newp, newst = self.optimizer.apply_gradients_functional(
                pv, grads, st, lr, params_ref=self._params)
            return loss, newp, newst

        # scalar batch leaves are shared across replicas, not mapped
        is_leaf = lambda t: isinstance(t, Tensor)
        micro_axes = jax.tree_util.tree_map(
            lambda x: 0 if len(x.shape) else None, micro, is_leaf=is_leaf)
        losses, newp, newst = jax.vmap(
            per_replica, in_axes=(0, 0, micro_axes, 0))(
            param_vals, opt_state, micro, keys)
        count = count + 1
        do_avg = ((count % self.k_steps) == 0) | (count <= self.begin_step)
        newp = jax.tree_util.tree_map(
            lambda x: jnp.where(
                do_avg,
                jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape),
                x),
            newp)
        return losses.mean(), newp, newst, count

    def __call__(self, *batch):
        raw = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, tuple(batch))
        loss, self._param_vals, self._opt_state, self._count = \
            self._compiled(self._param_vals, self._opt_state, self._count,
                           raw, next_key(),
                           jnp.asarray(self.optimizer.get_lr(), jnp.float32))
        # reflect replica-0 into the eager parameters
        for k, p in self._params.items():
            p._data = self._param_vals[k][0]
        sched = self.optimizer._lr_scheduler()
        if sched is not None:
            sched.step()
        return Tensor(loss)


class GeoSGDTrainStep:
    """Geo-SGD for the recsys/PS stack (reference
    distributed/ps/the_one_ps.py:655 geo sparse tables; fleet geo mode is
    DistributedStrategy.a_sync with a_sync_configs["k_steps"] > 0).

    The reference's geo workers update their local copy of each table
    for k steps, push the accumulated DELTA to the parameter server,
    and the server applies the SUM of worker deltas. TPU-native
    redesign, one compiled pjit program: parameters carry a leading
    replica axis [dp, ...] (row-sharded dims keep their table pspec, so
    an embedding lives [dp, V/shards, D] over a dp×sharding mesh), the
    per-replica update is a vmap with zero communication, and every
    k-th step the geo merge runs::

        merged = base + sum_r(replica_r - base);  base <- merged

    — one ICI all-reduce per k steps, with SUM-of-deltas (not mean)
    semantics exactly like the geo PS. Between merges replicas drift at
    most k optimizer steps (the geo staleness bound)."""

    def __init__(self, model, optimizer, loss_fn: Callable, k_steps=8,
                 strategy=None):
        if int(k_steps) < 1:
            raise NotImplementedError(
                "a_sync with k_steps == 0 is the pure-async PS mode; "
                "a single-controller mesh has no async analog — use "
                "geo (k_steps >= 1) or synchronous training")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.k_steps = int(k_steps)
        mesh = mesh_mod.get_mesh()
        self.dp = mesh.shape["dp"]
        self._params = dict(model.named_parameters())

        def rep(x):
            return jnp.broadcast_to(x[None], (self.dp,) + x.shape)

        pv = {k: p._data for k, p in self._params.items()}
        self._base = dict(pv)  # last merged state, no replica axis
        self._param_vals = {k: rep(v) for k, v in pv.items()}
        self._opt_state = jax.tree_util.tree_map(
            rep, optimizer.init_state(pv))
        self._count = jnp.zeros((), jnp.int32)

        def lead_spec(name, leaf_ndim):
            p = self._params.get(name)
            pspec = getattr(p, "pspec", None) if p is not None else None
            if pspec is not None and len(tuple(pspec)) == leaf_ndim - 1:
                return P(*(("dp",) + tuple(pspec)))
            return P(*(("dp",) + (None,) * (leaf_ndim - 1)))

        self._param_vals = {
            k: jax.device_put(v, NamedSharding(mesh, lead_spec(k, v.ndim)))
            for k, v in self._param_vals.items()}
        self._base = {
            k: jax.device_put(
                v, NamedSharding(
                    mesh,
                    getattr(self._params[k], "pspec", None)
                    or P(*((None,) * v.ndim))))
            for k, v in self._base.items()}
        # moments mirror their param's shape, so they take the SAME
        # sharded spec (a replicated m/v for a row-sharded table would
        # multiply optimizer memory by the sharding degree)
        self._opt_state = {
            k: jax.tree_util.tree_map(
                lambda leaf, _k=k: jax.device_put(
                    leaf, NamedSharding(mesh, lead_spec(_k, leaf.ndim))),
                st)
            for k, st in self._opt_state.items()}
        self._mesh = mesh
        self._compiled = jax.jit(self._step, donate_argnums=(0, 1, 2, 3))

    def _step(self, param_vals, base, opt_state, count, batch, key, lr):
        loss_of = _loss_of(self.model, self._params, self.loss_fn)
        micro = _split_batch(batch, self.dp)
        keys = jax.random.split(key, self.dp)

        def per_replica(pv, st, mb, mkey):
            loss, grads = jax.value_and_grad(loss_of)(pv, mb, mkey)
            newp, newst = self.optimizer.apply_gradients_functional(
                pv, grads, st, lr, params_ref=self._params)
            return loss, newp, newst

        is_leaf = lambda t: isinstance(t, Tensor)  # noqa: E731
        micro_axes = jax.tree_util.tree_map(
            lambda x: 0 if len(x.shape) else None, micro, is_leaf=is_leaf)
        losses, newp, newst = jax.vmap(
            per_replica, in_axes=(0, 0, micro_axes, 0))(
            param_vals, opt_state, micro, keys)
        count = count + 1
        do_merge = (count % self.k_steps) == 0

        # lax.cond, NOT jnp.where: where would compute both branches, so
        # the cross-replica delta sum (an ICI all-reduce over "dp") would
        # run every step — forfeiting the k-fold comm saving geo exists
        # for. Under cond the collective only executes on merge steps.
        def _merged(args):
            p, b = args
            out = {k: b[k] + (p[k] - b[k][None]).sum(axis=0)  # SUM deltas
                   for k in p}
            return ({k: jnp.broadcast_to(out[k][None], p[k].shape)
                     for k in p}, out)

        def _local(args):
            p, b = args
            return dict(p), dict(b)

        newp, newbase = jax.lax.cond(do_merge, _merged, _local,
                                     (newp, base))
        return losses.mean(), newp, newbase, newst, count

    def __call__(self, *batch):
        raw = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, tuple(batch))
        (loss, self._param_vals, self._base, self._opt_state,
         self._count) = self._compiled(
            self._param_vals, self._base, self._opt_state, self._count,
            raw, next_key(),
            jnp.asarray(self.optimizer.get_lr(), jnp.float32))
        # reflect replica-0 into the eager parameters
        for k, p in self._params.items():
            p._data = self._param_vals[k][0]
        sched = self.optimizer._lr_scheduler()
        if sched is not None:
            sched.step()
        return Tensor(loss)

    def replica_divergence(self) -> float:
        """Max abs difference of any parameter across replicas — 0.0
        right after a merge step (the geo staleness bound's floor)."""
        worst = 0.0
        for v in self._param_vals.values():
            if v.shape[0] > 1:
                spread = jnp.abs(v - v[:1]).max()
                worst = max(worst, float(spread))
        return worst


class DGCTrainStep:
    """Compiled DGC step (sparsity in [0, 1), e.g. 0.99 sends the top 1%%
    of momentum-corrected residual entries per replica per step)."""

    def __init__(self, model, loss_fn: Callable, optimizer=None,
                 learning_rate=0.1, momentum=None, sparsity=0.99,
                 clip_norm=None, strategy=None):
        self.model = model
        self.loss_fn = loss_fn
        # DGC folds the momentum into the residual correction (reference
        # DGCMomentumOptimizer wraps Momentum); the outer update is plain
        # SGD at the optimizer's (scheduled) lr. Adam-family optimizers
        # have no DGC formulation — reject rather than silently alter.
        self._optimizer = optimizer
        if optimizer is not None:
            from ...optimizer.algorithms import SGD, Momentum
            if not isinstance(optimizer, (SGD, Momentum)):
                raise TypeError(
                    f"DGC requires SGD/Momentum, got "
                    f"{type(optimizer).__name__}")
            if momentum is None:
                momentum = getattr(optimizer, "_momentum", 0.0)
        self.momentum = float(0.9 if momentum is None else momentum)
        self.lr = float(learning_rate if optimizer is None
                        else optimizer.get_lr())
        # DGC paper §3.2 local gradient clipping: bound each replica's
        # gradient norm by clip_norm/sqrt(dp) BEFORE accumulation, so the
        # delayed lump a residual releases stays bounded.
        self.clip_norm = None if clip_norm is None else float(clip_norm)
        mesh = mesh_mod.get_mesh()
        self.dp = mesh.shape["dp"]
        self._mesh = mesh
        self._params = dict(model.named_parameters())
        pv = {k: p._data for k, p in self._params.items()}
        self._order, self._shapes, self._sizes = _tree_layout(pv)
        self._N = sum(self._sizes.values())
        self.k = max(1, int(round(self._N * (1.0 - float(sparsity)))))
        self._param_vals = pv
        # per-replica residual state, [dp, N] sharded on dp
        z = jnp.zeros((self.dp, self._N), jnp.float32)
        sh = NamedSharding(mesh, P("dp", None))
        self._u = jax.device_put(z, sh)
        self._v = jax.device_put(z, sh)
        self._compiled = jax.jit(self._step, donate_argnums=(1, 2))

    def _flatten(self, tree):
        return _flatten_by(tree, self._order)

    def _unflatten(self, flat):
        return _unflatten_by(flat, self._order, self._shapes, self._sizes)

    def _step(self, param_vals, u, v, batch, key, lr):
        from ..mesh import shard_map

        loss_of = _loss_of(self.model, self._params, self.loss_fn)
        micro = _split_batch(batch, self.dp)
        keys = jax.random.split(key, self.dp)
        kk, mom, dp, N = self.k, self.momentum, self.dp, self._N

        def per_replica(pv, u, v, mb, mkey):
            # inside shard_map: u, v, mb, mkey are this replica's shard
            # with the leading dp axis of size 1 (scalars stay scalars)
            u, v = u[0], v[0]
            mb = jax.tree_util.tree_map(
                lambda x: x[0] if jnp.ndim(x) else x, mb)
            loss, grads = jax.value_and_grad(loss_of)(pv, mb, mkey[0])
            g = self._flatten(grads)
            if self.clip_norm is not None:
                bound = self.clip_norm / (dp ** 0.5)
                norm = jnp.sqrt(jnp.sum(g * g))
                g = g * jnp.minimum(1.0, bound / jnp.maximum(norm, 1e-12))
            u = mom * u + g                       # momentum correction
            v = v + u
            _, idx = jax.lax.top_k(jnp.abs(v), kk)
            vals = v[idx]
            # clear sent entries from the local residuals
            v = v.at[idx].set(0.0)
            u = u.at[idx].set(0.0)
            # 2k words over ICI instead of N: gather everyone's selection
            gidx = jax.lax.all_gather(idx, "dp")     # [dp, k]
            gval = jax.lax.all_gather(vals, "dp")    # [dp, k]
            g_comb = jnp.zeros((N,), jnp.float32).at[
                gidx.reshape(-1)].add(gval.reshape(-1)) / dp
            loss = jax.lax.pmean(loss, "dp")
            return loss[None], g_comb[None], u[None], v[None]

        spec_rep, spec_dp0 = _shardmap_specs(param_vals, micro)
        fn = shard_map(
            per_replica, mesh=self._mesh,
            in_specs=(spec_rep, P("dp", None), P("dp", None), spec_dp0,
                      P("dp", None)),
            out_specs=(P("dp"), P(None, None), P("dp", None),
                       P("dp", None)),
            check_vma=False)
        loss, g_comb, u, v = fn(param_vals, u, v, micro, keys)
        g_tree = self._unflatten(g_comb[0])
        newp = {k: (param_vals[k].astype(jnp.float32)
                    - lr * g_tree[k]).astype(param_vals[k].dtype)
                for k in param_vals}
        return loss.mean(), newp, u, v

    def __call__(self, *batch):
        raw = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, tuple(batch))
        lr = (self._optimizer.get_lr() if self._optimizer is not None
              else self.lr)
        loss, self._param_vals, self._u, self._v = self._compiled(
            self._param_vals, self._u, self._v, raw, next_key(),
            jnp.asarray(lr, jnp.float32))
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        if self._optimizer is not None:
            sched = self._optimizer._lr_scheduler()
            if sched is not None:
                sched.step()
        return Tensor(loss)


class CompressedAllreduceTrainStep:
    """Data-parallel step whose gradient all-reduce runs compressed.

    Reference: fleet/meta_optimizers/fp16_allreduce_optimizer.py:1 (cast
    grads to fp16 for the NCCL allreduce, cast back for the update).
    Both modes run the SAME two-phase reduce — an explicit
    reduce-scatter (all_to_all of per-destination chunks) + local mean +
    all_gather — with the wire payload compressed:

    * dtype="bfloat16": chunks travel as bf16 — half the ICI bytes of
      fp32.
    * dtype="int8": EQuARX-style quantized allreduce (arxiv 2506.17615):
      chunks are quantized BLOCKWISE (one scale per _QBLOCK elements, so
      a single outlier can't crush its whole chunk's resolution), int8 +
      scales travel, replicas dequantize/average/re-quantize — ~4x
      fewer wire bytes than fp32.

    The optimizer itself is unrestricted (grads arrive averaged and
    full-precision at the update), unlike DGC's SGD-only formulation.
    """

    _QBLOCK = 1024  # int8 quantization block (elements per scale)

    def __init__(self, model, optimizer, loss_fn: Callable,
                 dtype="bfloat16", strategy=None):
        if dtype not in ("bfloat16", "int8"):
            raise ValueError(f"unsupported compression dtype {dtype!r}")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.dtype = dtype
        mesh = mesh_mod.get_mesh()
        self.dp = mesh.shape["dp"]
        self._mesh = mesh
        self._params = dict(model.named_parameters())
        pv = {k: p._data for k, p in self._params.items()}
        self._order, self._shapes, self._sizes = _tree_layout(pv)
        n = sum(self._sizes.values())
        self._N = n
        # int8 needs whole quantization blocks per chunk; bf16 only needs
        # dp-divisibility (padding to blocks would ship >10x extra zeros
        # for small models)
        self._pad = (-n) % (self.dp * self._QBLOCK if dtype == "int8"
                            else self.dp)
        self._param_vals = pv
        self._opt_state = optimizer.init_state(pv)
        # donate only the optimizer state: params are the model's live
        # buffers (donating them would invalidate any pre-step alias)
        self._compiled = jax.jit(self._step, donate_argnums=(1,))

    def _flatten(self, tree):
        return _flatten_by(tree, self._order, pad=self._pad)

    def _unflatten(self, flat):
        return _unflatten_by(flat, self._order, self._shapes, self._sizes)

    def _step(self, param_vals, opt_state, batch, key, lr):
        from ..mesh import shard_map

        loss_of = _loss_of(self.model, self._params, self.loss_fn)
        micro = _split_batch(batch, self.dp)
        keys = jax.random.split(key, self.dp)
        dp, mode = self.dp, self.dtype
        chunk = (self._N + self._pad) // dp
        nblk = max(1, chunk // self._QBLOCK)

        def quant_blocks(x):
            """x [..., chunk] → (int8 [..., chunk], scales [..., nblk])."""
            xb = x.reshape(*x.shape[:-1], nblk, -1)
            s = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
            s = jnp.maximum(s, 1e-30)
            q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
            return q.reshape(*x.shape), s[..., 0]

        def dequant_blocks(q, s):
            qb = q.astype(jnp.float32).reshape(*q.shape[:-1], nblk, -1)
            return (qb * s[..., None]).reshape(*q.shape)

        def per_replica(pv, mb, mkey):
            mb = jax.tree_util.tree_map(
                lambda x: x[0] if jnp.ndim(x) else x, mb)
            loss, grads = jax.value_and_grad(loss_of)(pv, mb, mkey[0])
            g = self._flatten(grads)
            # phase 1: compress per destination chunk, all_to_all.
            # [dp, chunk]: row d is the chunk destined for replica d;
            # after the tiled all_to_all, row j is MY chunk as computed
            # by replica j.
            gc = g.reshape(dp, chunk)
            if mode == "bfloat16":
                q1t = jax.lax.all_to_all(gc.astype(jnp.bfloat16), "dp",
                                         split_axis=0, concat_axis=0,
                                         tiled=True)
                mine = jnp.mean(q1t.astype(jnp.float32), axis=0)
                q2g = jax.lax.all_gather(mine.astype(jnp.bfloat16), "dp")
                g_avg = q2g.astype(jnp.float32).reshape(-1)
            else:
                q1, s1 = quant_blocks(gc)
                q1t = jax.lax.all_to_all(q1, "dp", split_axis=0,
                                         concat_axis=0, tiled=True)
                s1t = jax.lax.all_to_all(s1, "dp", split_axis=0,
                                         concat_axis=0, tiled=True)
                # local dequant + average of my chunk
                mine = jnp.mean(dequant_blocks(q1t, s1t), axis=0)
                # phase 2: re-quantize the averaged chunk, all_gather
                q2, s2 = quant_blocks(mine)
                q2g = jax.lax.all_gather(q2, "dp")       # [dp, chunk]
                s2g = jax.lax.all_gather(s2, "dp")       # [dp, nblk]
                g_avg = dequant_blocks(q2g, s2g).reshape(-1)
            loss = jax.lax.pmean(loss, "dp")
            return loss[None], g_avg[None]

        spec_rep, spec_dp0 = _shardmap_specs(param_vals, micro)
        fn = shard_map(
            per_replica, mesh=self._mesh,
            in_specs=(spec_rep, spec_dp0, P("dp", None)),
            out_specs=(P("dp"), P(None, None)),
            check_vma=False)
        loss, g_avg = fn(param_vals, micro, keys)
        g_tree = self._unflatten(g_avg[0])
        grads = {k: g_tree[k].astype(param_vals[k].dtype)
                 for k in param_vals}
        new_p, new_s = self.optimizer.apply_gradients_functional(
            param_vals, grads, opt_state, lr, params_ref=self._params)
        return loss.mean(), new_p, new_s

    def __call__(self, *batch):
        raw = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, tuple(batch))
        loss, self._param_vals, self._opt_state = self._compiled(
            self._param_vals, self._opt_state, raw, next_key(),
            jnp.asarray(self.optimizer.get_lr(), jnp.float32))
        for k, p in self._params.items():
            p._data = self._param_vals[k]
        sched = self.optimizer._lr_scheduler()
        if sched is not None:
            sched.step()
        return Tensor(loss)
