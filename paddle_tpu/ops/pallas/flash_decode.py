"""Paged flash-decode: single-token attention over a block-paged KV pool.

The serving engine's fused decode step advances every active slot ONE
token against the shared paged KV pool. The XLA reference path
(``text.generation._llama_decode_layer_paged``) gathers each slot's
contiguous [T, kv, hd] view through its block table and materializes the
full [S, H, T] score matrix in fp32. At serving lengths that gather +
score tensor is the step's HBM bill.

This kernel is the pallas analog: the block table rows are
scalar-prefetch operands, so each grid step DMAs exactly ONE pool block
straight from its scattered location (no [S, T] gather materializes) and
folds it into an online softmax — the same one-pass accumulation as
flash attention, specialised to a single query row per slot. Table
entries past a slot's causal bound point at the reserved trash block;
they are fetched (the block loop is static) but masked out of the
accumulation, so stale or shared-suffix blocks can never leak into a
neighbour's output.

GQA maps query head ``h`` onto kv head ``h // (H // n_kv)``; the grid
tiles kv heads ``kv_heads_per_step`` at a time (the tuner's knob — more
heads per step amortizes the block DMA, fewer keeps VMEM small). The
pool block is ``(1, block_size, g, hd)`` with the tile ``g`` second to
last, so the TPU lowering's (8, 128) rule admits only the tiles of
:func:`legal_kv_heads_per_step`.

Numerics match flash attention: bf16 operands into the MXU, fp32
accumulation and softmax stats. The result is not bitwise-equal to the
gathered reference (different reduction order) but token-identical
through the engine (same contract as TP serving).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode", "flash_decode_reference",
           "legal_kv_heads_per_step"]

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128


def _kernel(tables_ref, wp_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_size, num_blocks, g, group):
    s = pl.program_id(0)
    j = pl.program_id(2)
    G = g * group

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    wp = wp_ref[s]

    # blocks whose first position is already past the causal bound hold
    # nothing attendable (trash-redirected table tail) — skip the math
    @pl.when(j * block_size <= wp)
    def _compute():
        q = q_ref[0].reshape(g, group, q_ref.shape[-1])      # [g, grp, hd]
        k = k_ref[0]                                         # [bs, g, hd]
        v = v_ref[0]
        # scores per kv-head batch: [g, group, bs], fp32 accumulation
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2)
        sc = jnp.where(pos <= wp, sc, _MASK_VALUE)

        s2 = sc.reshape(G, block_size)
        m_prev = m_scr[:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s2 - m_next)
        p = jnp.where((pos <= wp).reshape(1, block_size), p, 0.0)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        pv = jax.lax.dot_general(
            p.reshape(g, group, block_size).astype(v.dtype), v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)              # [g, grp, hd]
        acc_scr[:] = acc_scr[:] * alpha + pv.reshape(G, acc_scr.shape[-1])
        m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def legal_kv_heads_per_step(n_kv):
    """kv-head tiles the TPU lowering accepts, smallest first: a block's
    second-to-last dim is a multiple of 8 or the whole axis."""
    return [g for g in range(8, n_kv, 8) if n_kv % g == 0] + [n_kv]


def flash_decode(q, kc_pool, vc_pool, tables, write_pos, *, scale=None,
                 kv_heads_per_step=None, interpret=False):
    """One-token paged attention: q [S, H, hd] against pools
    [n_blocks, block_size, n_kv, hd] through per-slot block tables
    [S, max_blocks] (int32), attending positions ``<= write_pos`` [S].
    Returns [S, H, hd] in q's dtype.

    ``kv_heads_per_step`` tiles the kv-head axis (one of
    :func:`legal_kv_heads_per_step`); defaults to the tuner's choice for
    the shape.
    """
    S, H, hd = q.shape
    nb, bs, n_kv, _ = kc_pool.shape
    if H % n_kv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {n_kv}")
    group = H // n_kv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    g = kv_heads_per_step
    if g is None:
        from ... import tuner as _tuner
        g = _tuner.get_config(
            "flash_decode", shapes=((S, H, hd), tuple(kc_pool.shape)),
            dtype=str(q.dtype))["kv_heads_per_step"]
    g = int(g)
    if n_kv % g:
        raise ValueError(f"kv_heads_per_step={g} must divide n_kv={n_kv}")
    G = g * group
    mb = tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, n_kv // g, mb),
        in_specs=[
            # q heads for kv-head tile kvb are the contiguous range
            # [kvb*g*group, (kvb+1)*g*group)
            pl.BlockSpec((1, G, hd), lambda s, kvb, j, tr, wr: (s, kvb, 0)),
            pl.BlockSpec((1, bs, g, hd),
                         lambda s, kvb, j, tr, wr: (tr[s, j], 0, kvb, 0)),
            pl.BlockSpec((1, bs, g, hd),
                         lambda s, kvb, j, tr, wr: (tr[s, j], 0, kvb, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd),
                               lambda s, kvb, j, tr, wr: (s, kvb, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=float(scale), block_size=bs, num_blocks=mb, g=g,
        group=group)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), write_pos.astype(jnp.int32), q, kc_pool,
      vc_pool)


def flash_decode_reference(q, kc_pool, vc_pool, tables, write_pos,
                           scale=None):
    """The gathered XLA math (exactly ``_llama_decode_layer_paged``'s
    attention block): the CPU parity oracle for the kernel."""
    S, H, hd = q.shape
    n_kv = kc_pool.shape[2]
    bs = kc_pool.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    kview = kc_pool[tables].reshape(S, -1, n_kv, hd)
    vview = vc_pool[tables].reshape(S, -1, n_kv, hd)
    kh = jnp.repeat(kview, H // n_kv, axis=2)
    vh = jnp.repeat(vview, H // n_kv, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q, kh,
                   preferred_element_type=jnp.float32) * scale
    T = kview.shape[1]
    valid = jnp.arange(T)[None, :] <= write_pos[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bthd->bhd", p, vh).astype(q.dtype)
