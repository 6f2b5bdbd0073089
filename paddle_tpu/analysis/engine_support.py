"""Serving-engine introspection helpers for the audit front end.

Kept out of ``audit.py`` so the serving package is only imported when an
engine is actually being audited.
"""
from __future__ import annotations


def engine_donates(engine) -> bool:
    """True when the engine was built on the donating prefill/decode
    programs (KV buffers/pool updated in place). For the paged layout
    "in place" is the whole of it: the decode, chunk and verify
    programs carry the pool through their layer loop
    (``serving.engine._scan_layers_over_pool``), so a donated pool is
    the output's buffer and only the rows written move."""
    from ..serving import engine as E

    if getattr(engine, "tp", 1) > 1:
        # TP programs are per-mesh shard_map jits, not the module-level
        # constants — the engine records its donation policy directly
        return bool(engine._donate)
    return engine._decode in (E._DECODE_DONATED, E._PAGED_DECODE_DONATED)


def lower_decode_program(engine) -> str:
    """Lower the engine's fused decode step against its live state and
    return the StableHLO text — the same program the engine executes
    (slot, paged or tensor-parallel layout), so dtype/padding/collective
    rules audit real serving HLO, not a proxy. In the paged and tp
    programs the layer loop is one ``while`` whose carry holds the K and
    V pools flat over layers, ``[L*nb, bs, kv, hd]``; the text shows
    them reshaped back to ``[L, nb, bs, kv, hd]`` on return."""
    import jax
    import jax.numpy as jnp

    from ..serving.engine import (_PAGED_DECODE_STATICS, _STATICS,
                                  _decode_impl, _paged_decode_impl)

    if getattr(engine, "tp", 1) > 1:
        # the engine's own jitted shard_map program (statics baked):
        # this is the SPMD decode the mesh executes, ring collective-
        # matmuls included
        lowered = engine._decode.lower(
            engine._w, engine.cache.kc, engine.cache.vc,
            engine.cache.block_tables.copy(),
            jnp.asarray(engine._tok), jnp.asarray(engine._cur),
            engine.cache.active.copy(), jnp.asarray(engine._keys),
            engine._temps.copy(), jnp.asarray(engine._vmask))
        return lowered.as_text()
    if getattr(engine, "kv_layout", "slot") == "paged":
        args = (engine._w, jnp.asarray(engine.cache.kc),
                jnp.asarray(engine.cache.vc),
                jnp.asarray(engine.cache.block_tables),
                jnp.asarray(engine._tok), jnp.asarray(engine._cur),
                jnp.asarray(engine.cache.active),
                jnp.asarray(engine._keys), jnp.asarray(engine._temps),
                jnp.asarray(engine._vmask))
        lowered = jax.jit(_paged_decode_impl,
                          static_argnames=_PAGED_DECODE_STATICS).lower(
            *args, **engine._decode_statics)
        return lowered.as_text()
    args = (engine._w, jnp.asarray(engine.cache.kc),
            jnp.asarray(engine.cache.vc), jnp.asarray(engine._tok),
            jnp.asarray(engine._cur), jnp.asarray(engine.cache.active),
            jnp.asarray(engine._keys), jnp.asarray(engine._temps),
            jnp.asarray(engine._vmask))
    lowered = jax.jit(_decode_impl,
                      static_argnames=_STATICS).lower(
        *args, **engine._statics)
    return lowered.as_text()
