"""Grouped/ragged matmul for MoE expert dispatch.

The MoE layer computes expert FFNs as batched einsums over the
capacity-padded dispatch tensor: ``[E, C, d] @ [E, d, f]``. XLA runs the
FULL ``E*C`` rows even though only ``counts[e] <= C`` rows per expert
hold real tokens — under imbalanced routing most of that is multiplying
zeros. This kernel is the ragged form: per-expert row counts are a
scalar-prefetch operand, row tiles entirely past ``counts[e]`` skip the
MXU work and write zeros, and partially-valid tiles mask their tail, so
compute scales with actual load instead of worst-case capacity
(megablocks-style, arXiv 2211.15841).

``ragged_group_matmul`` is the raw kernel; :func:`ragged_dot` wraps it
with a custom VJP (dx reuses the ragged kernel with the same counts; dw
is a dense per-group contraction over the already-masked operands) so it
drops into the MoE training path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_group_matmul", "ragged_dot",
           "ragged_group_matmul_reference"]


def _kernel(counts_ref, x_ref, w_ref, o_ref, *, block_m):
    g = pl.program_id(0)
    i = pl.program_id(1)
    cnt = counts_ref[g]
    row0 = i * block_m

    @pl.when(row0 >= cnt)
    def _all_pad():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(row0 < cnt)
    def _compute():
        acc = jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        o_ref[0] = jnp.where(rows < cnt, acc, 0.0).astype(o_ref.dtype)


def ragged_group_matmul(x, w, counts, *, block_m=None, block_n=None,
                        out_dtype=None, interpret=False):
    """x [G, C, K], w [G, K, N], counts [G] int32 -> [G, C, N] where rows
    ``>= counts[g]`` of each group are zero and row tiles entirely past
    ``counts[g]`` skip their dot. Tiles default to the tuner's choice."""
    G, C, K = x.shape
    G2, K2, N = w.shape
    assert (G, K) == (G2, K2), (x.shape, w.shape)
    if block_m is None or block_n is None:
        from ... import tuner as _tuner
        cfg = _tuner.get_config(
            "ragged_matmul", shapes=(tuple(x.shape), tuple(w.shape)),
            dtype=str(x.dtype))
        block_m = block_m or cfg.get("block_m", 128)
        block_n = block_n or cfg.get("block_n", 128)
    bm = min(int(block_m), C)
    bn = min(int(block_n), N)
    cp = (C + bm - 1) // bm * bm
    np_ = (N + bn - 1) // bn * bn
    if cp != C:
        x = jnp.pad(x, ((0, 0), (0, cp - C), (0, 0)))
    if np_ != N:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, np_ - N)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, cp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, bm, K), lambda g, i, j, cr: (g, i, 0)),
            pl.BlockSpec((1, K, bn), lambda g, i, j, cr: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, cr: (g, i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_m=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, cp, np_), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(counts.astype(jnp.int32), x, w)
    return out[:, :C, :N]


def ragged_group_matmul_reference(x, w, counts, out_dtype=None):
    """Masked dense einsum — the CPU parity oracle."""
    C = x.shape[1]
    valid = jnp.arange(C)[None, :] < counts[:, None]          # [G, C]
    y = jnp.einsum("gck,gkn->gcn", x, w,
                   preferred_element_type=jnp.float32)
    y = jnp.where(valid[..., None], y, 0.0)
    return y.astype(out_dtype or x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def ragged_dot(x, w, counts, interpret=False):
    """Differentiable ragged grouped matmul (the MoE expert-FFN form):
    ``y[g, c] = x[g, c] @ w[g]`` for ``c < counts[g]``, else 0."""
    return ragged_group_matmul(x, w, counts, interpret=interpret)


def _ragged_fwd(x, w, counts, interpret):
    return ragged_dot(x, w, counts, interpret), (x, w, counts)


def _ragged_bwd(interpret, res, dy):
    x, w, counts = res
    # dy rows past counts are zero by construction of the forward
    dx = ragged_group_matmul(dy, jnp.swapaxes(w, 1, 2), counts,
                             interpret=interpret).astype(x.dtype)
    valid = (jnp.arange(x.shape[1])[None, :]
             < counts[:, None])[..., None].astype(x.dtype)
    dw = jnp.einsum("gck,gcn->gkn", x * valid, dy,
                    preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw, None


ragged_dot.defvjp(_ragged_fwd, _ragged_bwd)
