"""Ulysses-style all-to-all sequence parallelism.

Complement to ring attention (`ops/ring_attention.py`) for long
sequences: instead of rotating KV shards around a ring, two
`lax.all_to_all` collectives re-shard the activations — sequence-sharded
[B, L/P, H, D] becomes head-sharded [B, L, H/P, D], each device runs
ordinary (flash) attention over the FULL sequence for its head slice,
and the inverse all-to-all restores sequence sharding. Communication is
O(L·H·D/P) per device independent of the number of steps (vs the ring's
P ppermute rounds), riding ICI as two fused collectives — the better
trade when head count ≥ mesh axis size and the whole sequence fits one
device's attention working set.

The reference has no such kernel (its sep_degree is a communicator
group, python/paddle/distributed/fleet/base/topology.py); this is the
DeepSpeed-Ulysses recipe built TPU-first. all_to_all is linear, so jax
autodiff derives the backward (the transpose of an all_to_all is the
reverse all_to_all) — no custom VJP needed.

Layouts follow paddle flash-attn: [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ulysses_attention_local(q, k, v, axis_name, n, causal, scale):
    """Per-device body; call inside shard_map. q/k/v: [B, L/n, H, D]
    shards with H % n == 0 (KV heads are repeated up if needed)."""
    h = q.shape[2]
    if h % n:
        raise ValueError(f"num heads {h} not divisible by axis size {n}")
    kvh = k.shape[2]
    rep = h // kvh if kvh != h else 1
    if kvh != h and h % kvh:
        raise ValueError(f"GQA heads {h} vs {kvh}")
    if rep > 1 and kvh % n:
        # uneven KV split: replicate up-front (costlier collectives)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        rep = 1

    def seq_to_head(x):  # [B, L/n, H, D] -> [B, L, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    # GQA with kvh % n == 0 stays grouped through the collectives (1/rep
    # the KV bytes — the whole point of Ulysses); the contiguous head
    # chunks line up (q chunk i covers kv chunk i) and sdpa_raw
    # broadcasts grouped KV heads locally.
    qh = seq_to_head(q)
    kh = seq_to_head(k)
    vh = seq_to_head(v)

    from ..nn.functional.attention import sdpa_raw

    out = sdpa_raw(qh, kh, vh, causal=causal, scale=scale)
    # [B, L, H/n, D] -> [B, L/n, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis_name="sep", causal=False,
                      scale=None):
    """All-to-all sequence-parallel attention on full arrays
    [B, L, H, D]; builds the shard_map. L and H must divide by the
    ``axis_name`` mesh axis size."""
    from ..distributed.mesh import get_mesh, partial_manual

    if mesh is None:
        mesh = get_mesh()
    n = mesh.shape[axis_name]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        from ..nn.functional.attention import sdpa_raw

        return sdpa_raw(q, k, v, causal=causal, scale=float(scale))
    if q.shape[1] % n:
        raise ValueError(f"seq len {q.shape[1]} not divisible by {n}")
    if q.shape[2] % n:
        raise ValueError(f"num heads {q.shape[2]} not divisible by {n}")
    spec = P(None, axis_name, None, None)
    fn = partial_manual(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          n=n, causal=causal, scale=float(scale)),
        mesh, {axis_name}, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
