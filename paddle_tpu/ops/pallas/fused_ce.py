"""Fused LM-head cross-entropy as a pallas kernel, vocab-sharded.

The chunked scan in ``nn.functional.fused_ce`` already avoids the
[N, V] logits tensor; this is its pallas form plus the tensor-parallel
composition:

* :func:`fused_ce_stats` — ONE kernel pass over vocab tiles computing
  the per-row online-logsumexp triple ``(m, s, label_logit)``. Logits
  exist only as a [block_n, block_v] VMEM tile; nothing full-width ever
  reaches HBM. The tile sizes are the tuner's knobs.
* :func:`fused_ce_loss` — single-device loss with a custom VJP whose
  backward re-walks vocab chunks (jax.checkpoint-style recompute) using
  the saved stats, so the gradient is O(N*chunk) memory too.
* :func:`sharded_vocab_ce` — the TP form, called INSIDE shard_map with
  the vocab axis sharded: each device runs the kernel over its local
  shard (label rows owned elsewhere simply contribute 0), then the
  per-device triples merge over a ``ppermute`` RING — the PR-11
  machinery; the HLO carries no all_reduce — and the backward ring-sums
  the per-shard dhidden partials the same way (psum-free end to end).

Exact math (fp32 accumulation), not an approximation: single-device
parity vs the dense log-softmax reference is a registration requirement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_ce_stats", "fused_ce_loss", "sharded_vocab_ce",
           "fused_ce_reference"]

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128


def _stats_kernel(h_ref, w_ref, lab_ref, m_out, s_out, lab_out, m_scr,
                  l_scr, lab_scr, *, block_v, num_v, v_width, vocab_offset):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        lab_scr[:] = jnp.zeros_like(lab_scr)

    logits = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bn, bv]
    col = vocab_offset + j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    valid = col < vocab_offset + v_width
    logits = jnp.where(valid, logits, _MASK_VALUE)

    m_prev = m_scr[:, :1]
    m_next = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.where(valid, jnp.exp(logits - m_next), 0.0)
    l_scr[:] = jnp.broadcast_to(
        alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
    # a label owned by another vocab shard may still land on a padding
    # column of THIS shard's tile range — require validity, not just id
    # equality, or the mask value would leak into the label accumulator
    hit = jnp.logical_and(col == lab_ref[:], valid)    # [bn, bv]
    lab_scr[:] += jnp.broadcast_to(
        jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True),
        lab_scr.shape)

    @pl.when(j == num_v - 1)
    def _finalize():
        m_out[:] = m_scr[:]
        s_out[:] = l_scr[:]
        lab_out[:] = lab_scr[:]


def fused_ce_stats(hidden, w, labels, *, vocab_offset=0, block_n=None,
                   block_v=None, interpret=False):
    """Online-logsumexp stats of ``hidden @ w`` against ``labels``:
    hidden [N, H], w [H, V], labels [N] int -> (m [N], s [N], lab [N])
    fp32, where ``nll = log(s) + m - lab`` once all vocab shards merged.
    ``vocab_offset`` positions this shard's columns in the global vocab
    (labels outside the shard contribute 0 to ``lab``)."""
    N, H = hidden.shape
    V = w.shape[1]
    if block_n is None or block_v is None:
        from ... import tuner as _tuner
        cfg = _tuner.get_config(
            "fused_ce", shapes=(tuple(hidden.shape), tuple(w.shape)),
            dtype=str(hidden.dtype))
        block_n = block_n or cfg.get("block_n", 128)
        block_v = block_v or cfg.get("block_v", 1024)
    bn = min(int(block_n), N)
    bv = min(int(block_v), V)
    np_ = (N + bn - 1) // bn * bn
    vp = (V + bv - 1) // bv * bv
    if np_ != N:
        hidden = jnp.pad(hidden, ((0, np_ - N), (0, 0)))
        labels = jnp.pad(labels, (0, np_ - N), constant_values=-1)
    if vp != V:
        w = jnp.pad(w, ((0, 0), (0, vp - V)))
    num_v = vp // bv

    kernel = functools.partial(
        _stats_kernel, block_v=bv, num_v=num_v, v_width=V,
        vocab_offset=int(vocab_offset))
    m, s, lab = pl.pallas_call(
        kernel,
        grid=(np_ // bn, num_v),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((np_, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((np_, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(hidden, w, labels.astype(jnp.int32)[:, None])
    return m[:N, 0], s[:N, 0], lab[:N, 0]


def _nll_grads_chunked(hidden, w, labels, m, s, g, chunk):
    """Backward over vocab chunks: dlogits = (softmax - onehot) * g
    reconstructed per chunk from the saved stats; never [N, V]."""
    N, H = hidden.shape
    V = w.shape[1]
    nc = (V + chunk - 1) // chunk
    vp = nc * chunk
    wpad = jnp.pad(w, ((0, 0), (0, vp - V))) if vp != V else w
    wc = wpad.reshape(H, nc, chunk).transpose(1, 0, 2)     # [nc, H, chunk]
    lse = m + jnp.log(s)                                   # [N]
    offs = jnp.arange(nc, dtype=jnp.int32) * chunk

    def body(dh, args):
        w_c, off = args
        logits = jnp.dot(hidden, w_c,
                         preferred_element_type=jnp.float32)
        col = off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        p = jnp.where(col < V, jnp.exp(logits - lse[:, None]), 0.0)
        d = (p - (col == labels[:, None])) * g[:, None]    # [N, chunk]
        dh = dh + jnp.dot(d, w_c.T, preferred_element_type=jnp.float32)
        dw_c = jnp.dot(hidden.astype(jnp.float32).T, d,
                       preferred_element_type=jnp.float32)
        return dh, dw_c

    dh0 = jnp.zeros((N, H), jnp.float32)
    dh, dwc = jax.lax.scan(jax.checkpoint(body), dh0, (wc, offs))
    dw = dwc.transpose(1, 0, 2).reshape(H, vp)[:, :V]
    return dh.astype(hidden.dtype), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_ce_loss(hidden, w, labels, block_n=None, block_v=None,
                  interpret=False):
    """Mean cross-entropy of ``hidden @ w`` vs ``labels`` without the
    [N, V] logits (single-device; see :func:`sharded_vocab_ce` for TP).
    hidden [N, H], w [H, V], labels [N] int -> scalar fp32."""
    m, s, lab = fused_ce_stats(hidden, w, labels, block_n=block_n,
                               block_v=block_v, interpret=interpret)
    return jnp.mean(jnp.log(s) + m - lab)


def _ce_fwd(hidden, w, labels, block_n, block_v, interpret):
    m, s, lab = fused_ce_stats(hidden, w, labels, block_n=block_n,
                               block_v=block_v, interpret=interpret)
    loss = jnp.mean(jnp.log(s) + m - lab)
    return loss, (hidden, w, labels, m, s)


def _ce_bwd(block_n, block_v, interpret, res, ct):
    hidden, w, labels, m, s = res
    N = hidden.shape[0]
    g = jnp.full((N,), ct / N, jnp.float32)
    chunk = int(block_v or 1024)
    dh, dw = _nll_grads_chunked(hidden, w, labels.astype(jnp.int32), m, s,
                                g, chunk)
    return dh, dw, None


fused_ce_loss.defvjp(_ce_fwd, _ce_bwd)


def fused_ce_reference(hidden, w, labels):
    """Dense log-softmax oracle (materializes [N, V]; tests only)."""
    logits = jnp.dot(hidden, w,
                     preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                            axis=1)[:, 0])


# ---------------------------------------------------------------------------
# tensor-parallel composition (inside shard_map, vocab axis sharded)
# ---------------------------------------------------------------------------

def _ring_merge_stats(m, s, lab, axis_name, tp):
    """Merge per-shard (m, s, lab) triples over a ppermute ring: tp-1
    hops, each merging the circulating neighbour copy into the local
    accumulator (log-sum-exp for s, plain sum for lab). No all_reduce."""
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    am, as_, al = m, s, lab
    cm, cs, cl = m, s, lab
    for _ in range(tp - 1):
        cm = jax.lax.ppermute(cm, axis_name, perm)
        cs = jax.lax.ppermute(cs, axis_name, perm)
        cl = jax.lax.ppermute(cl, axis_name, perm)
        mx = jnp.maximum(am, cm)
        as_ = as_ * jnp.exp(am - mx) + cs * jnp.exp(cm - mx)
        am = mx
        al = al + cl
    return am, as_, al


def _ring_sum(x, axis_name, tp):
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    acc, c = x, x
    for _ in range(tp - 1):
        c = jax.lax.ppermute(c, axis_name, perm)
        acc = acc + c
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def sharded_vocab_ce(hidden, w_local, labels, axis_name, tp,
                     block_n=None, block_v=None, interpret=False):
    """Mean CE with the vocab axis sharded over ``axis_name`` (call
    inside shard_map): hidden [N, H] replicated, w_local [H, V/tp],
    labels [N] global ids. Per-shard kernel stats merge over a ppermute
    ring, and the backward ring-sums the per-shard dhidden partials —
    the program's collectives are collective_permute ONLY."""
    loss, _ = _sharded_fwd(hidden, w_local, labels, axis_name, tp,
                           block_n, block_v, interpret)
    return loss


def _sharded_fwd(hidden, w_local, labels, axis_name, tp, block_n, block_v,
                 interpret):
    v_local = w_local.shape[1]
    idx = jax.lax.axis_index(axis_name)
    off = (idx * v_local).astype(jnp.int32)
    # the kernel's vocab_offset is static; offset the LABELS instead so
    # one lowering serves every ring position
    local_labels = labels.astype(jnp.int32) - off
    m, s, lab = fused_ce_stats(hidden, w_local, local_labels,
                               block_n=block_n, block_v=block_v,
                               interpret=interpret)
    m, s, lab = _ring_merge_stats(m, s, lab, axis_name, tp)
    loss = jnp.mean(jnp.log(s) + m - lab)
    return loss, (hidden, w_local, local_labels, m, s)


def _sharded_bwd(axis_name, tp, block_n, block_v, interpret, res, ct):
    """shard_map transposition note: the replicated-INPUT (hidden)
    cotangent is psummed across devices by the transpose, so the total
    over devices is what must be right — returning the ring-summed full
    dhidden scaled by THIS device's share of the output cotangent sums
    to ``ct_total * dh``. The sharded-input (w_local) cotangent is
    local-only, so it needs the ring-summed TOTAL cotangent. Both forms
    hold regardless of how shard_map splits a replicated output's
    cotangent across devices (equal shares or all-on-one)."""
    hidden, w_local, local_labels, m, s = res
    N = hidden.shape[0]
    unit = jnp.full((N,), 1.0 / N, jnp.float32)
    chunk = int(block_v or 1024)
    dh_unit, dw_unit = _nll_grads_chunked(hidden, w_local, local_labels,
                                          m, s, unit, chunk)
    ct = jnp.asarray(ct, jnp.float32)
    ct_total = _ring_sum(ct, axis_name, tp)
    dh = _ring_sum(dh_unit.astype(jnp.float32), axis_name, tp) * ct
    return (dh.astype(hidden.dtype),
            (dw_unit.astype(jnp.float32) * ct_total).astype(w_local.dtype),
            None)


sharded_vocab_ce.defvjp(_sharded_fwd, _sharded_bwd)
