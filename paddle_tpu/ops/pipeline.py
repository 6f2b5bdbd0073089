"""SPMD pipeline parallelism over the mesh "pp" axis.

The reference's pipeline engine (python/paddle/distributed/fleet/
meta_parallel/pipeline_parallel.py) is a rank-local scheduler: each pp rank
owns a stage, runs 1F1B, and p2p-sends activations over NCCL. On TPU the
whole schedule is ONE SPMD program instead: stage weights carry a leading
[num_stages, ...] dim sharded over "pp", microbatches march through the
stages with lax.ppermute each tick, and XLA overlaps the permute DMA with
stage compute. Every device executes the same code — bubbles are ticks
where a stage multiplies garbage, masked out of the result.

Schedule: GPipe-style single loop of M + P - 1 ticks (M microbatches, P
stages). 1F1B's memory advantage is recovered by wrapping the stage fn in
jax.checkpoint (remat) rather than by reordering — under jit the backward
runs the same ring in reverse (AD transposes ppermute).

Differentiable end-to-end; use inside jit/pjit with the global mesh.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.mesh import get_mesh, partial_manual


def _pvary(x, axis_name):
    """Mark x device-varying over axis_name."""
    return jax.lax.pcast(x, axis_name, to="varying")


def _shift_right(x, axis_name, n):
    """Send stage p's activation to stage p+1 (non-circular: stage 0
    receives zeros, last stage's output falls off)."""
    return jax.lax.ppermute(x, axis_name,
                            perm=[(i, i + 1) for i in range(n - 1)])


def _pipeline_local(stage_params, microbatches, stage_fn, axis_name, n_stages,
                    n_micro):
    """Per-device pipeline loop. stage_params: this stage's param chunk
    (leading dim = layers-per-stage). microbatches: [M, ...] (replicated).
    Returns [M, ...] final-stage outputs (replicated via psum)."""
    p = jax.lax.axis_index(axis_name)
    mb_shape = microbatches.shape[1:]
    # pvary: loop state is device-varying from the start so scan/where keep
    # consistent varying-manual-axes types under check_vma
    state = _pvary(jnp.zeros(mb_shape, microbatches.dtype), axis_name)
    outputs = _pvary(jnp.zeros(microbatches.shape, microbatches.dtype),
                     axis_name)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped; bubbles masked later)
        feed = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, n_micro - 1), keepdims=False)
        x = jnp.where(p == 0, feed, state)
        y = stage_fn(stage_params, x)
        # last stage emits microbatch t - (P-1) at tick t
        out_idx = t - (n_stages - 1)
        is_out = jnp.logical_and(p == n_stages - 1, out_idx >= 0)
        slot = jnp.clip(out_idx, 0, n_micro - 1)
        cur = jax.lax.dynamic_index_in_dim(outputs, slot, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_out, y, cur).astype(outputs.dtype), slot, 0)
        state = _shift_right(y, axis_name, n_stages)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state, outputs), jnp.arange(n_micro + n_stages - 1))
    # outputs live only on the last stage; replicate across the ring
    return jax.lax.psum(
        jnp.where(p == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


def spmd_pipeline(stage_fn: Callable, stacked_params, x, *, mesh=None,
                  axis_name: str = "pp", n_micro: int | None = None):
    """Run a homogeneous layer stack as a pipeline over the "pp" mesh axis.

    stage_fn(local_params, x) -> y applies ONE stage (its chunk of layers).
    stacked_params: pytree whose leaves have a leading [total_layers or
    n_stages*k, ...] dim, sharded over "pp" in contiguous chunks.
    x: [batch, ...] global input; it is split into ``n_micro`` microbatches
    along dim 0 (default: one per stage).

    Returns y with the same batch dim, computed as stages applied in order.
    """
    if mesh is None:
        mesh = get_mesh()
    n_stages = mesh.shape[axis_name]
    if n_stages == 1:
        return stage_fn(stacked_params, x)
    n_micro = n_micro or n_stages
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if leaf.shape[0] % n_stages:
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} leading dim "
                f"{leaf.shape[0]} not divisible by {n_stages} pp stages")
    micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stacked_params)
    fn = partial_manual(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name, n_stages=n_stages,
                          n_micro=n_micro),
        mesh, {axis_name}, in_specs=(param_specs, P()), out_specs=P())
    out = fn(stacked_params, micro)
    return out.reshape(b, *out.shape[2:])


# ---------------------------------------------------------------------------
# 1F1B: fused forward+backward schedule with bounded activation memory
# ---------------------------------------------------------------------------

def _shift_left(x, axis_name, n):
    """Send stage p's cotangent to stage p-1."""
    return jax.lax.ppermute(x, axis_name,
                            perm=[(i, i - 1) for i in range(1, n)])


def _pipeline_1f1b_local(stage_params, last_params, micro_x, micro_tgt,
                         stage_fn, last_fn, axis_name, n_stages, n_micro):
    """Per-device 1F1B loop (reference schedule:
    fleet/meta_parallel/pipeline_parallel.py:82 forward_backward_pipeline).

    Device p at tick t runs the FORWARD of microbatch f = t - p and the
    BACKWARD of microbatch b = t - 2P + 2 + p (when valid) — the steady
    state is exactly one-forward-one-backward. A microbatch's stage input
    is held in a rotating buffer of 2P slots and its forward is recomputed
    at backward time (remat), so peak activation memory is O(P)
    microbatches per device, independent of M — 1F1B's memory contract —
    versus O(M + P) for the GPipe scan above.

    Returns (mean loss, param-chunk grads, d loss/d micro_x).
    """
    P_ = n_stages
    M = n_micro
    p = jax.lax.axis_index(axis_name)
    mb_shape = micro_x.shape[1:]
    dt = micro_x.dtype
    S = 2 * P_  # rotating input-buffer slots

    def pv(x):
        return _pvary(x, axis_name)

    state_y = pv(jnp.zeros(mb_shape, dt))          # activation moving right
    state_ct = pv(jnp.zeros(mb_shape, dt))         # cotangent moving left
    buf = pv(jnp.zeros((S,) + mb_shape, dt))       # saved stage inputs
    dx_out = pv(jnp.zeros((M,) + mb_shape, dt))    # d loss / d micro_x
    grad_acc = jax.tree_util.tree_map(
        lambda l: pv(jnp.zeros(l.shape, jnp.float32)), stage_params)
    last_grad_acc = jax.tree_util.tree_map(
        lambda l: pv(jnp.zeros(jnp.shape(l), jnp.float32)), last_params)
    loss_acc = pv(jnp.float32(0.0))

    is_first = p == 0
    is_last = p == P_ - 1
    seed = jnp.float32(1.0 / M)  # d(mean over microbatches)/d(mb loss)

    def comb(chunk, lastp, x, tgt):
        y = stage_fn(chunk, x)
        # Non-last stages evaluate last_fn at zeros: its value/partials are
        # masked there anyway, and real intermediate activations could
        # overflow a loss head (exp/log in bf16) into inf partials that
        # 0*inf=NaN-poison grad_acc through the masked vjp. The `where`
        # also cuts the y-cotangent path on non-last stages exactly.
        y_loss = jnp.where(is_last, y, jnp.zeros_like(y))
        return last_fn(lastp, y_loss, tgt), y

    def tick(carry, t):
        (state_y, state_ct, buf, dx_out, grad_acc, last_grad_acc,
         loss_acc) = carry
        f = t - p                    # fwd microbatch index at this device
        b = t - 2 * P_ + 2 + p       # bwd microbatch index at this device
        f_ok = jnp.logical_and(f >= 0, f < M)
        b_ok = jnp.logical_and(b >= 0, b < M)
        fc = jnp.clip(f, 0, M - 1)
        bc = jnp.clip(b, 0, M - 1)

        # ---- forward of microbatch f ----
        x_in = jnp.where(is_first,
                         jax.lax.dynamic_index_in_dim(micro_x, fc, 0, False),
                         state_y)
        tgt_f = jax.lax.dynamic_index_in_dim(micro_tgt, fc, 0, False)
        loss_f, y_f = comb(stage_params, last_params, x_in, tgt_f)
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(is_last, f_ok),
            loss_f.astype(jnp.float32) / M, 0.0)
        # guarded write: drain ticks (f out of range) must not clobber the
        # clamped slot while its microbatch still awaits backward
        slot = jnp.mod(fc, S)
        old_slot = jax.lax.dynamic_index_in_dim(buf, slot, 0, False)
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, jnp.where(f_ok, x_in.astype(dt), old_slot), slot, 0)

        # ---- backward of microbatch b (forward recomputed = remat) ----
        # last stage: b == f, its loss seeds the cotangent directly
        x_saved = jnp.where(
            is_last, x_in,
            jax.lax.dynamic_index_in_dim(buf, jnp.mod(bc, S), 0, False))
        tgt_b = jax.lax.dynamic_index_in_dim(micro_tgt, bc, 0, False)
        _, vjp = jax.vjp(lambda c, lp, x: comb(c, lp, x, tgt_b),
                         stage_params, last_params, x_saved)
        bmask = b_ok.astype(jnp.float32)
        ct_loss = jnp.where(is_last, seed, 0.0) * bmask
        ct_y = jnp.where(is_last, jnp.zeros_like(state_ct),
                         state_ct) * bmask.astype(dt)
        g_chunk, g_last, g_x = vjp((ct_loss, ct_y))
        grad_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), grad_acc, g_chunk)
        last_grad_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), last_grad_acc, g_last)
        dx_out = jax.lax.dynamic_update_index_in_dim(
            dx_out,
            jnp.where(jnp.logical_and(is_first, b_ok), g_x.astype(dt),
                      jax.lax.dynamic_index_in_dim(dx_out, bc, 0, False)),
            bc, 0)

        # ---- boundary transfers ----
        state_y = _shift_right(y_f.astype(dt), axis_name, P_)
        state_ct = _shift_left(g_x.astype(dt), axis_name, P_)
        return (state_y, state_ct, buf, dx_out, grad_acc, last_grad_acc,
                loss_acc), None

    n_ticks = M + 2 * P_ - 2
    carry = (state_y, state_ct, buf, dx_out, grad_acc, last_grad_acc,
             loss_acc)
    carry, _ = jax.lax.scan(tick, carry, jnp.arange(n_ticks))
    _, _, _, dx_out, grad_acc, last_grad_acc, loss_acc = carry

    # loss and head grads live on the last stage, dx on the first:
    # replicate via psum
    loss = jax.lax.psum(jnp.where(is_last, loss_acc, 0.0), axis_name)
    last_grads = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(jnp.where(is_last, g, jnp.zeros_like(g)),
                               axis_name), last_grad_acc)
    dx = jax.lax.psum(jnp.where(is_first, dx_out, jnp.zeros_like(dx_out)),
                      axis_name)
    return loss, grad_acc, last_grads, dx


def pipeline_1f1b(stage_fn: Callable, last_fn: Callable, stacked_params, x,
                  targets, *, last_params=None, mesh=None,
                  axis_name: str = "pp", n_micro: int | None = None):
    """Fused forward+backward 1F1B pipeline over the "pp" mesh axis.

    Unlike :func:`spmd_pipeline` (forward-only; AD produces a GPipe-shaped
    backward holding O(M) microbatch activations), this runs the
    reference's 1F1B schedule
    (fleet/meta_parallel/pipeline_parallel.py:82): each device alternates
    one microbatch forward with one microbatch backward, recomputing the
    stage forward at backward time, so peak activation memory is O(P)
    microbatches.

    stage_fn(local_params, x) -> y applies one stage.
    last_fn(last_params, y, tgt) -> scalar per-microbatch loss, applied
    after the final stage (e.g. final norm + lm-head + cross entropy);
    ``last_params`` (replicated pytree, may be empty) gets grads too.
    Returns (loss, param_grads, last_param_grads, dx).
    """
    if mesh is None:
        mesh = get_mesh()
    if last_params is None:
        last_params = {}
        user_last_fn = last_fn
        last_fn = lambda lp, y, tgt: user_last_fn(y, tgt)  # noqa: E731
    n_stages = mesh.shape[axis_name]
    n_micro = n_micro or max(n_stages, 1)
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    micro_x = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    micro_t = targets.reshape(n_micro, b // n_micro, *targets.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stacked_params)
    last_specs = jax.tree_util.tree_map(lambda l: P(), last_params)
    fn = partial_manual(
        functools.partial(_pipeline_1f1b_local, stage_fn=stage_fn,
                          last_fn=last_fn, axis_name=axis_name,
                          n_stages=n_stages, n_micro=n_micro),
        mesh, {axis_name},
        in_specs=(param_specs, last_specs, P(), P()),
        out_specs=(P(), param_specs, last_specs, P()))
    loss, grads, last_grads, dx = fn(stacked_params, last_params, micro_x,
                                     micro_t)
    return loss, grads, last_grads, dx.reshape(b, *dx.shape[2:])
