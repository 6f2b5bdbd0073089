"""Serving-engine introspection helpers for the audit front end.

Kept out of ``audit.py`` so the serving package is only imported when an
engine is actually being audited.
"""
from __future__ import annotations


def lower_decode_program(engine) -> str:
    """Lower the engine's fused decode step against its live state and
    return the StableHLO text — the engine's own jitted program (one
    device, or the ``shard_map`` over its mesh with the ring
    collective-matmuls), so dtype/padding/collective rules audit real
    serving HLO, not a proxy. The layer loop is one ``while`` whose
    carry holds the K and V pools flat over layers, ``[L*nb, bs, kv,
    hd]``; the text shows them reshaped back to ``[L, nb, bs, kv, hd]``
    on return."""
    import jax.numpy as jnp

    args = (engine._w, engine.cache.kc, engine.cache.vc,
            engine.cache.block_tables.copy(), jnp.asarray(engine._tok),
            jnp.asarray(engine._cur), engine.cache.active.copy(),
            jnp.asarray(engine._keys), engine._temps.copy(),
            jnp.asarray(engine._vmask)) + engine._moe_in()
    return engine._decode.lower(*args, **engine._paged_statics).as_text()
