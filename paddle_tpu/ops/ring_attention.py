"""Ring attention: sequence/context-parallel exact attention over a mesh axis.

The reference scales sequence length via tensor parallelism only (its
sep_degree plumbing in python/paddle/distributed/fleet/base/topology.py is a
communicator group without a ring kernel); here long sequences are
first-class: Q/K/V are sharded along the sequence dim over the ``sep`` mesh
axis, each device computes flash blocks against the KV shard it currently
holds, and KV shards rotate around the ring with ``lax.ppermute`` so ICI
transfers overlap compute. Online-softmax merging makes the result exact.

The backward is a second ring pass (custom_vjp): dq accumulates locally
while (dk, dv) partial sums travel with the rotating KV shards — the
standard ring-attention gradient, using the saved global logsumexp so no
per-step residuals are kept.

Call :func:`ring_attention_local` inside shard_map / pjit-manual code, or
:func:`ring_attention` on full arrays (it builds the shard_map).

Layouts follow paddle flash-attn: [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG_INF = -jnp.inf


def _chunk_attn_xla(q, k, v, scale, causal):
    """Chunk pair attention returning (out [B,L,H,D], lse [B,L,H])."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale  # [B,H,Lq,D]
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(cm, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Lq]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if causal:
        p = jnp.where(cm, p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,H,Lq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(l > 0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)
    return (jnp.swapaxes(o, 1, 2).astype(q.dtype),
            jnp.swapaxes(lse, 1, 2))                          # [B,Lq,H]


def _chunk_attn(q, k, v, scale, causal):
    """Route the chunk pair through the pallas flash kernel on TPU (or
    where PADDLE_TPU_ATTENTION says), placed over the mesh axes the ring's
    shard_map left automatic."""
    from ..nn.functional.attention import flash_placement, forced_path

    forced = forced_path()
    if forced == "flash" or (forced is None and q.shape[1] >= 128
                             and jax.default_backend() == "tpu"):
        from .pallas.flash_attention import _fwd
        place = flash_placement(q.shape[0], q.shape[2], k.shape[2])
        o, lse = (place or (lambda fn: fn))(
            lambda *qkv: _fwd(*qkv, causal, scale, 128, 128, False))(
                *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
        return jnp.swapaxes(o, 1, 2), jnp.swapaxes(lse, 1, 2)
    return _chunk_attn_xla(q, k, v, scale, causal)


def _merge(o1, lse1, o2, lse2):
    """Merge two normalized partial attentions (online softmax)."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.exp(lse1 - m_safe)          # exp(-inf) = 0 for absent parts
    w2 = jnp.exp(lse2 - m_safe)
    l = w1 + w2
    l_safe = jnp.maximum(l, 1e-30)
    o = (o1.astype(jnp.float32) * (w1 / l_safe)[..., None]
         + o2.astype(jnp.float32) * (w2 / l_safe)[..., None])
    lse = jnp.where(l > 0, m_safe + jnp.log(l_safe), _NEG_INF)
    return o.astype(o1.dtype), lse


def _rot(x, axis_name, n):
    """Rotate shard to the next device on the ring (i → i+1)."""
    return jax.lax.ppermute(x, axis_name,
                            perm=[(i, (i + 1) % n) for i in range(n)])


def _chunk_grads(q, k, v, do, lse, delta, scale, causal):
    """Flash-style recompute gradients for one chunk pair.

    All inputs in [B,L,H,D] / [B,L,H]; returns (dq, dk, dv) with kv grads
    group-summed for GQA.
    """
    B, Lq, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)            # [B,Hq,Lq,D]
    kh = jnp.repeat(jnp.swapaxes(k, 1, 2).astype(jnp.float32), rep, axis=1)
    vh = jnp.repeat(jnp.swapaxes(v, 1, 2).astype(jnp.float32), rep, axis=1)
    doh = jnp.swapaxes(do, 1, 2).astype(jnp.float32)
    lseh = jnp.swapaxes(lse, 1, 2)                            # [B,Hq,Lq]
    deltah = jnp.swapaxes(delta, 1, 2)

    s = jnp.einsum("bhqd,bhkd->bhqk", qh * scale, kh,
                   preferred_element_type=jnp.float32)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(cm, s, _NEG_INF)
    lse_safe = jnp.where(jnp.isfinite(lseh), lseh, 0.0)
    p = jnp.exp(s - lse_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    dp = jnp.einsum("bhqd,bhkd->bhqk", doh, vh,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - deltah[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kh)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qh)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, doh)
    if rep > 1:
        dk = dk.reshape(B, Hkv, rep, *dk.shape[2:]).sum(axis=2)
        dv = dv.reshape(B, Hkv, rep, *dv.shape[2:]).sum(axis=2)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# ---------------------------------------------------------------------------
# the ring (runs inside shard_map; arrays are per-device shards)
# ---------------------------------------------------------------------------

def _ring_fwd_pass(q, k, v, axis_name, n, causal, scale):
    idx = jax.lax.axis_index(axis_name)
    B, Lq, Hq, _ = q.shape
    o = jnp.zeros(q.shape, jnp.float32).astype(q.dtype)
    lse = jnp.full((B, Lq, Hq), _NEG_INF, jnp.float32)
    for s in range(n):
        # at step s this device holds kv chunk j = (idx - s) mod n:
        #   s == 0 → diagonal (causal within chunk); s > 0 → j < idx
        #   unless idx < s (wraparound ⇒ j > idx: skipped under causal)
        o_c, lse_c = _chunk_attn(q, k, v, scale, causal and s == 0)
        if causal and s > 0:
            keep = (idx >= s)
            lse_c = jnp.where(keep, lse_c, _NEG_INF)
            o_c = jnp.where(keep, o_c, 0.0)
        o, lse = _merge(o, lse, o_c, lse_c)
        if s != n - 1:
            k = _rot(k, axis_name, n)
            v = _rot(v, axis_name, n)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention_local(q, k, v, axis_name, n, causal, scale):
    """Exact attention over sequence shards; call inside shard_map.

    q/k/v: local shards [B, L/n, H, D] along the ``axis_name`` ring of size
    n. Returns the local output shard [B, L/n, H, D].
    """
    o, _ = _ring_fwd_pass(q, k, v, axis_name, n, causal, scale)
    return o


def _ring_fwd_rule(q, k, v, axis_name, n, causal, scale):
    o, lse = _ring_fwd_pass(q, k, v, axis_name, n, causal, scale)
    return o, (q, k, v, o, lse)


def _ring_bwd_rule(axis_name, n, causal, scale, res, do):
    q, k, v, o, lse = res
    idx = jax.lax.axis_index(axis_name)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for s in range(n):
        dq_c, dk_c, dv_c = _chunk_grads(q, k, v, do, lse, delta, scale,
                                        causal and s == 0)
        if causal and s > 0:
            keep = (idx >= s)
            dq_c = jnp.where(keep, dq_c, 0.0)
            dk_c = jnp.where(keep, dk_c, 0.0)
            dv_c = jnp.where(keep, dv_c, 0.0)
        dq = dq + dq_c
        dk = dk + dk_c
        dv = dv + dv_c
        # rotate kv and their grad accumulators together; after the final
        # rotation (n total) dk/dv arrive back at their home device
        k = _rot(k, axis_name, n)
        v = _rot(v, axis_name, n)
        dk = _rot(dk, axis_name, n)
        dv = _rot(dv, axis_name, n)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention_local.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(q, k, v, mesh=None, axis_name="sep", causal=False,
                   scale=None):
    """Ring attention on full arrays [B, L, H, D]; builds the shard_map.

    L must divide evenly by the ``axis_name`` mesh axis size.
    """
    from ..distributed.mesh import get_mesh, partial_manual

    if mesh is None:
        mesh = get_mesh()
    n = mesh.shape[axis_name]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        # differentiable path (the raw pallas _fwd has no VJP rule)
        from ..nn.functional.attention import sdpa_raw
        return sdpa_raw(q, k, v, causal=causal, scale=float(scale))
    if q.shape[1] % n:
        raise ValueError(f"seq len {q.shape[1]} not divisible by {n}")
    spec = P(None, axis_name, None, None)
    # manual only over the ring axis: batch/head placement on the other mesh
    # axes (dp/sharding/tp) stays with the GSPMD partitioner, so this nests
    # inside the pjit train step
    fn = partial_manual(
        functools.partial(ring_attention_local, axis_name=axis_name, n=n,
                          causal=causal, scale=float(scale)),
        mesh, {axis_name}, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
