"""Flash attention as a Pallas TPU kernel (fwd + bwd, causal, GQA).

This is the TPU-native analog of the reference's fused attention CUDA path
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, exposed through
paddle.nn.functional.scaled_dot_product_attention): one pass over KV blocks
with an online softmax so the [L, L] score matrix never materializes in HBM.

Layout: paddle flash-attn layout [batch, seq, heads, head_dim] at the API
boundary; kernels run on [batch, heads, seq, head_dim].

The backward pass saves (out, logsumexp) and recomputes attention
probabilities blockwise (standard flash attention backward):
    delta = rowsum(dO * O)
    p     = exp(s - lse)
    ds    = p * (dO @ V^T - delta) * scale
    dq    = ds @ K ; dk = ds^T @ Q ; dv = p^T @ dO

GQA is handled by mapping query head h onto KV head h // group in the
BlockSpec index maps; dk/dv are produced per query head and group-summed in
XLA outside the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# large finite negative instead of -inf: keeps exp() well-defined for rows
# that are entirely masked inside one block
_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128  # m/l scratch stores row stats broadcast across one lane tile


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _block_mask(iq, ik, block_q, block_k, causal, kv_len, offset):
    """Validity mask for one [block_q, block_k] score tile.

    Causal uses bottom-right alignment (matches _xla_sdpa's tril with
    k = Lk - Lq): query row i may attend keys 0..(i + offset) where
    offset = Lk - Lq.
    """
    col = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = col < kv_len
    if causal:
        row = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, row + offset >= col)
    return mask


def _block_visible(iq, ik, block_q, block_k, causal, offset):
    """False when the whole tile is above the causal diagonal (skippable)."""
    if not causal:
        return True
    return ik * block_k <= iq * block_q + block_q - 1 + offset


def _recompute_p_ds(q, k, v, do, lse, delta, mask, scale):
    """Shared backward-block math: p from saved lse, then ds.

    Operands stay in their storage dtype (bf16) so the dots run in the
    MXU's native mode; accumulation and softmax math are fp32. Returns
    (p, ds) with ds already carrying the score scale.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


_PARALLEL_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, num_kv, kv_len, offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_block_visible(iq, ik, block_q, block_k, causal, offset))
    def _compute():
        # bf16 operands straight into the MXU; fp32 accumulation only
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        mask = _block_mask(iq, ik, block_q, block_k, causal, kv_len, offset)
        s = jnp.where(mask, s, _MASK_VALUE)

        m_prev = m_scr[:, :1]                                   # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        p = jnp.where(mask, p, 0.0)
        l_next = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ik == num_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(safe_l)).astype(jnp.float32)


def _out(shape, dtype, *like):
    """A pallas_call output that varies over the manual mesh axes its
    inputs ``like`` vary over: a shard_map that checks varying axes asks
    every output to say (the set is empty anywhere else)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """q: [B, Hq, Lq, D], k/v: [B, Hkv, Lk, D] → (out, lse[B, Hq, Lq])."""
    B, Hq, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, _ceil_to(Lq, 8))
    block_k = min(block_k, _ceil_to(Lk, 8))
    qp = _ceil_to(Lq, block_q)
    kp = _ceil_to(Lk, block_k)
    if qp != Lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qp - Lq), (0, 0)))
    if kp != Lk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kp - Lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kp - Lk), (0, 0)))
    num_q, num_kv = qp // block_q, kp // block_k
    grid = (B, Hq, num_q, num_kv)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv=num_kv, kv_len=Lk, offset=Lk - Lq)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            _out((B, Hq, qp, D), q.dtype, q, k, v),
            _out((B, Hq, qp, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_PARALLEL_SEMANTICS,
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Lq], lse[:, :, :Lq, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, block_q, block_k, num_kv,
                   kv_len, offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_block_visible(iq, ik, block_q, block_k, causal, offset))
    def _compute():
        k = k_ref[0, 0]
        mask = _block_mask(iq, ik, block_q, block_k, causal, kv_len, offset)
        _, ds = _recompute_p_ds(
            q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], mask, scale)
        acc_scr[:] += jnp.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    @pl.when(ik == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, num_q, kv_len, offset):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_block_visible(iq, ik, block_q, block_k, causal, offset))
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        mask = _block_mask(iq, ik, block_q, block_k, causal, kv_len, offset)
        p, ds = _recompute_p_ds(
            q, k_ref[0, 0], v_ref[0, 0], do,
            lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], mask, scale)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k, interpret):
    B, Hq, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, _ceil_to(Lq, 8))
    block_k = min(block_k, _ceil_to(Lk, 8))
    qp = _ceil_to(Lq, block_q)
    kp = _ceil_to(Lk, block_k)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # [B, Hq, Lq]
    if qp != Lq:
        pad_q = ((0, 0), (0, 0), (0, qp - Lq), (0, 0))
        q = jnp.pad(q, pad_q)
        do = jnp.pad(do, pad_q)
        # padded q rows: lse=0 → p=exp(mask)=huge? no: mask kills all their
        # cols only when causal; keep them inert via lse=+inf surrogate
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, qp - Lq)),
                      constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, qp - Lq)))
    if kp != Lk:
        pad_k = ((0, 0), (0, 0), (0, kp - Lk), (0, 0))
        k = jnp.pad(k, pad_k)
        v = jnp.pad(v, pad_k)
    num_q, num_kv = qp // block_q, kp // block_k

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta_l = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv=num_kv,
                          kv_len=Lk, offset=Lk - Lq),
        grid=(B, Hq, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=_out((B, Hq, qp, D), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_PARALLEL_SEMANTICS,
        interpret=interpret,
    )(q, k, v, do, lse_l, delta_l)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          kv_len=Lk, offset=Lk - Lq),
        grid=(B, Hq, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, iq: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, iq: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            _out((B, Hq, kp, D), k.dtype, q, k, v, do),
            _out((B, Hq, kp, D), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_PARALLEL_SEMANTICS,
        interpret=interpret,
    )(q, k, v, do, lse_l, delta_l)

    dk = dk[:, :, :Lk]
    dv = dv[:, :, :Lk]
    if group > 1:  # GQA: sum query-head grads into each KV head
        dk = dk.reshape(B, Hkv, group, Lk, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Lk, D).sum(axis=2)
    return dq[:, :, :Lq], dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper ([B, H, L, D] layout)
# ---------------------------------------------------------------------------

def _where_it_is(fn):
    return fn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bhld(q, k, v, place, static):
    return place(lambda *a: _fwd(*a, *static))(q, k, v)[0]


def _flash_fwd_rule(q, k, v, place, static):
    out, lse = place(lambda *a: _fwd(*a, *static))(q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(place, static, res, do):
    return place(lambda *a: _bwd(*a, *static))(*res, do)


_flash_bhld.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False, place=None):
    """Flash attention on paddle layout [batch, seq, heads, head_dim].

    GQA supported when q heads are a multiple of kv heads. Returns the same
    layout/dtype as q. Differentiable (custom flash backward kernels).
    Block sizes default to 256x512 (VMEM-sized for D<=256 on v5e+) and can
    be pinned via PADDLE_TPU_FLASH_BLOCK_Q / PADDLE_TPU_FLASH_BLOCK_K.

    ``place`` maps a per-device function over a mesh: it takes the forward
    or the backward kernel call — arrays laid out [batch, heads, ...] in,
    the same out — and returns it wrapped in a shard_map. The two are
    placed one by one, inside the custom_vjp, so jax never differentiates
    through a shard_map here (0.9.0 cannot, where one nests in another).
    """
    import os

    if block_q is None:
        block_q = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_Q", 256))
    if block_k is None:
        block_k = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_K", 512))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    if qh.shape[1] % kh.shape[1] != 0:
        raise ValueError(
            f"q heads {qh.shape[1]} not a multiple of kv heads {kh.shape[1]}")
    out = _flash_bhld(qh, kh, vh, place or _where_it_is,
                      (causal, float(scale), int(block_q), int(block_k),
                       bool(interpret)))
    return jnp.swapaxes(out, 1, 2)
