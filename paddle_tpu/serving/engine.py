"""Iteration-level (continuous) batching engine over the stacked-weight
Llama/GPT decode path (and, through the llama bodies, a decoder whose
layers differ in kind and route their feed-forward: ``_make_arch``).

Design (ROADMAP north star: serve concurrent, asynchronously arriving
requests without ever recompiling):

- ``submit()`` enqueues a request; admission prefills it **directly into
  its KV storage** with a program bucketed to the next power-of-two
  prompt length (bounded compile count: one prefill program per bucket).
  The KV cache is paged: fixed-size blocks drawn from a shared pool
  through host-side block tables (runtime operands — zero extra
  lowerings): requests hold ``ceil(len/block_size)`` blocks instead of
  worst-case ``max_len`` lines, common prompt prefixes are deduped
  through a refcounted radix index, prompts longer than
  ``prefill_chunk`` prefill in block-aligned chunks co-scheduled with
  decode, and pool exhaustion preempts (token-identical replay later).
- ``step()`` advances ALL decode-active slots one token with a single
  fused jitted decode program of static shape ``[n_slots, ...]`` — new
  requests join between steps, finished ones free their slot/blocks
  without disturbing neighbours. Steady-state XLA programs:
  n_buckets prefills + 1 decode (+ 1 chunk program if chunking ever
  ran), enforced by tools/check_serving_compiles.py.
- Per-request PRNG: each request owns a key chain seeded at admission
  and split once per decode step, so sampled output is a function of
  (prompt, seed, gen kwargs) only — independent of co-batched traffic.
  The chain matches batch ``generate(seed=...)`` exactly for B=1.
- The decode math is ``text/generation.py``'s module-level per-layer
  bodies: the engine and batch ``generate()`` trace the same python, so
  there is one lowering to keep conformant (greedy outputs are
  token-identical).

The engine is single-threaded and step-driven: callers (or
``RequestHandle.result()`` / ``drain()``) pump ``step()``; all host-side
bookkeeping is numpy so nothing but the two jitted programs ever reaches
the device.

``Engine(tp=N)`` shards the whole program set over a ``tp`` mesh axis
(one engine across N chips): column-parallel qkv/gate-up, row-parallel
o-/down-proj, vocab-sharded head, kv-heads-split paged pool — each
program is the single-device one traced with the static ``tp=N`` inside
ONE shard_map SPMD lowering (budget unchanged), whose TP dots are
overlapped collective-matmuls
(``distributed.collective_matmul``), and sampling runs on the
ring-gathered full logits with the same PRNG chains, so output stays
token-identical to the single-device engine. Host-side bookkeeping,
scheduling, prefix sharing and the adopt()/skip replay machinery are
untouched by sharding.

``Engine(speculative=SpecConfig(...))`` flips the latency shape:
instead of one fused decode step per token, a draft proposer (host-side
n-gram lookahead or a small same-family model) proposes k tokens and
ONE chunk-shaped verify program scores them at k+1 positions with
token-identical acceptance — emitted tokens and consumed PRNG splits
are byte-equal to the non-speculative engine for greedy AND sampled
decoding (see serving/speculative.py). ``submit(logit_mask=...)``
threads a per-request vocab mask through every sampled position
(prefill, decode, chunk and verify) as a runtime operand — constrained
decoding with zero extra lowerings, replay/migration-safe.

The engine stamps itself (``time.perf_counter()``, always on): every
``step()`` with decode-active rows leaves a ``StepRecord`` (begin,
admitted, chunked, scheduled, dispatched, fetched, end, and what its
thread went through: CPU seconds, switches, faults, collector pauses),
every program launch a ``LaunchRecord`` (called, dispatched,
fetched) and every admission attempt an ``AdmitRecord`` (begin, placed,
end) in the bounded rings of ``self.metrics`` (serving/metrics.py), and
every ``submit()`` a ``SubmitRecord`` (entry, return: admission runs
inside it when a slot is free; its thread's account too). The decode
launch's called -> fetched is what
``metrics.mark_decode`` is given, so the ITL estimate behind
``retry_after_s``, brownout and fleet routing covers the token fetch; and
when the span tracer is on the same stamps become ``serving.step`` with
its ``serving.schedule / dispatch / fetch / emit`` children, the
launches' ``serving.prefill``, ``serving.prefill_chunk``, ``spec.verify``,
``spec.draft`` spans, and ``serving.submit_call``.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..observability import tracing as _tracing
from ..observability.compile_attr import compile_scope as _compile_scope
from ..tensor import Tensor
from .kv_cache import PagedKVCache
from .metrics import (AdmitRecord, EngineMetrics, LaunchRecord,
                      RequestMetrics, StepRecord, SubmitRecord,
                      thread_counters, thread_delta)
from .scheduler import (EngineOverloaded, FIFOScheduler,  # noqa: F401
                        PriorityScheduler)

__all__ = ["Engine", "RequestHandle", "EngineOverloaded", "RequestTimeout",
           "RequestShed", "RequestCancelled", "AdoptMismatch",
           "DEFAULT_RETRY_AFTER_S"]

#: Conservative retry-after hint (seconds) when the engine has no basis
#: for a live estimate — a cold engine (no decode history yet) or an
#: idle one (nothing active, the queue blocked on the token watermark).
#: Roughly one prefill + a few decode steps on any real deployment;
#: overridable per engine via ``Engine(default_retry_after_s=...)``.
DEFAULT_RETRY_AFTER_S = 1.0


class RequestTimeout(TimeoutError):
    """A request exceeded its ``max_time_s`` deadline: its KV slot was
    reclaimed and ``result()`` raises this instead of blocking forever.
    Tokens generated before the deadline remain on ``handle.tokens``.
    ``replica`` names the fleet replica that held the request when it
    expired (None outside a ReplicaFleet)."""

    def __init__(self, message, replica=None):
        super().__init__(message)
        self.replica = replica


class RequestShed(RuntimeError):
    """The request was evicted from the queue by overload brownout
    (``serving.resilience.EngineSupervisor`` past its ITL SLO): retry
    after ``retry_after_s`` seconds, by which point the engine expects
    to be back under its latency target. ``replica`` names the fleet
    replica that shed it (None outside a ReplicaFleet)."""

    def __init__(self, message, retry_after_s=None, replica=None):
        super().__init__(message)
        self.retry_after_s = retry_after_s
        self.replica = replica


class RequestCancelled(RuntimeError):
    """The request was cancelled (client abandoned the stream) before
    finishing; tokens generated before cancellation stay on
    ``handle.tokens``."""


class AdoptMismatch(RuntimeError):
    """``Engine.adopt()`` refused a handle whose origin engine served a
    DIFFERENT model/config/sampling fingerprint: replaying its token
    history here would silently produce divergent tokens. Cross-replica
    migration (and supervisor rebuild) is only token-identical between
    engines over the same model — tp degree and KV geometry may differ
    (adopt replays from tokens, not KV bytes), the math may not."""


# ---------------------------------------------------------------------------
# jitted programs (module-level: every Engine over the same model/geometry
# shares the compile cache)
# ---------------------------------------------------------------------------

def _layer_body(kind, arch, tp, *, n_heads, n_kv, eps, theta,
                block_size=None, window=None, moe_k=0, valid=None,
                attn_scale=None, router=()):
    """Which per-layer body of ``text/generation.py`` serves a program:
    by the program's ``kind``, the ``arch`` and whether the program is
    sharded (``tp > 1``: the ``_tp`` bodies, run inside ``shard_map``),
    with the keywords that body takes bound; its operands stay the
    program's to pass. The gpt bodies know neither grouped heads nor a
    rotary table. A window and routed experts (``moe_k`` and the rows
    that count, ``valid``) exist in the one-device bodies alone:
    ``Engine.__init__`` refuses a model that has them to an engine with
    ``tp > 1``, and so does this. The latent bodies (``attn_scale``, the
    model's softmax scale, and ``router``, its further routing arguments)
    exist for one device and for no verify program."""
    from ..text import generation as G

    name = {"prefill": "prefill_layer", "decode": "decode_layer_paged",
            "chunk": "chunk_layer", "verify": "verify_layer"}[kind]
    if arch == "latent":
        if tp > 1 or kind == "verify":
            raise ValueError("the latent bodies are neither tensor-parallel "
                             "nor a verify program's")
        kw = dict(n_heads=n_heads, eps=eps, attn_scale=attn_scale,
                  moe_k=moe_k, valid=valid, router=router)
        if kind != "prefill":
            kw["block_size"] = block_size
        return functools.partial(getattr(G, f"_latent_{name}"), **kw)
    kw = {"n_heads": n_heads}
    if arch == "llama":
        kw.update(n_kv=n_kv, eps=eps, theta=theta)
    if kind != "prefill":
        kw["block_size"] = block_size
    if tp > 1:
        if window is not None or moe_k:
            raise ValueError("the tensor-parallel bodies take no window "
                             "and no routed feed-forward")
        return functools.partial(getattr(G, f"_{arch}_{name}_tp"), tp=tp,
                                 **kw)
    if arch == "llama" and kind != "verify":
        kw.update(window=window, moe_k=moe_k, valid=valid)
    return functools.partial(getattr(G, f"_{arch}_{name}"), **kw)


def _head(hidden, w, tp):
    """Logits of ``hidden`` (``[h]`` or ``[rows, h]``, after the final
    norm). On one device a plain matmul. Sharded, the head's columns are
    split over the vocabulary and ride a ring all-gather matmul, so every
    device samples from the FULL logits row and the token stream is the
    single-device engine's."""
    if tp == 1:
        return hidden @ w["head"]
    from ..text import generation as G

    if hidden.ndim == 1:
        return G.matmul_allgather(hidden[None], w["head"], G._TP_AXIS,
                                  tp)[0]
    return G.matmul_allgather(hidden, w["head"], G._TP_AXIS, tp)


def _paged_prefill_impl(w, kc, vc, tok, cur_pos, keys, ids, n_prompt, slot,
                        seed, skip, temp, table_row, skip_write, vmask,
                        moe=None, *, arch, n_heads, n_kv, eps, theta,
                        do_sample, top_k, top_p, block_size, kinds=None,
                        window=None, moe_k=0, tp=1, attn_scale=None,
                        router=()):
    """Prefill one request (ids [1, Lb], right-padded to its bucket):
    the same full causal forward as ``generate()``'s (so the first
    sampled token is bit-identical to it), sample the first token and
    register the request's PRNG chain. One compile per bucket length Lb.
    K/V lands in the paged pool through the slot's block-table row — a
    block-aligned masked scatter. Positions below ``skip_write``
    (radix-shared prefix, already resident from the producing request)
    and at/above ``n_prompt`` (bucket padding) redirect into the trash
    block, so shared blocks are NEVER rewritten and prefix sharing
    cannot perturb a co-batched neighbour.

    ``skip`` (int32 operand, 0 on normal admission) is the supervisor
    replay path: the admission-seeded key chain is fast-forwarded past
    the ``skip`` splits the crashed engine incarnation already consumed,
    so a request re-prefilled as ``prompt + tokens_emitted_so_far``
    samples its next token with exactly the key the uninterrupted run
    would have used. Being a runtime operand, replay shares the ONE
    prefill program per bucket with normal admission.

    ``kinds`` / ``window`` / ``moe_k`` describe a model whose layers
    differ in kind and route their feed-forward (``_make_arch``); its
    counters ``moe`` come in last and go out last, with the picks of the
    prompt's own positions added. ``attn_scale`` / ``router`` are a
    latent model's (``arch`` ``latent``: the llama program around the
    latent bodies, and ``vc`` None, for its cache has one pool).

    ``tp > 1``: the program runs INSIDE ``shard_map`` over the ``tp``
    mesh axis (``_tp_jitted``). Every weight leaf and the KV pool arrive
    as per-device shards: attention runs over the local head group, the
    row-parallel projections reassemble replicated activations through
    ppermute-pipelined collective-matmuls (``_layer_body``), and the
    token is sampled from the ring-gathered full logits (``_head``)."""
    from ..text import generation as G

    Lb = ids.shape[1]
    if arch != "gpt":
        x = jnp.take(w["embed"], ids, axis=0)
        pos = jnp.arange(Lb)
        real = jnp.arange(Lb) < n_prompt
        stack, at = _stack_of(arch, w), (pos,)      # the rotary positions
    else:
        pos = jnp.arange(Lb)
        x = jnp.take(w["wte"], ids, axis=0) + w["wpe"][pos][None]
        stack, at, real = {k: w[k] for k in G._GPT_STACK_KEYS}, (), None

    def layer_of(win):
        body = _layer_body("prefill", arch, tp, n_heads=n_heads, n_kv=n_kv,
                           eps=eps, theta=theta, window=win, moe_k=moe_k,
                           valid=real, attn_scale=attn_scale, router=router)
        return lambda xc, lw: body(xc, lw, *at)

    x, kvs = _scan_layers(_by_kind(layer_of, kinds, window), stack, x)
    if moe is not None:
        moe = _count_picks(moe, kvs[-1], real, moe_k)
    if arch != "gpt":
        hlast = jax.lax.dynamic_index_in_dim(
            G._rms(x, w["norm"], eps)[0], n_prompt - 1, 0, keepdims=False)
    else:
        xlast = jax.lax.dynamic_index_in_dim(x[0], n_prompt - 1, 0,
                                             keepdims=False)
        hlast = G._ln(xlast, w["lnfw"], w["lnfb"])
    logits0 = _head(hlast, w, tp)

    j = jnp.arange(Lb)
    writable = (j >= skip_write) & (j < n_prompt)
    dest = jnp.where(writable,
                     table_row[j // block_size] * block_size
                     + j % block_size,
                     j % block_size)             # trash block rows
    L, nb, bs = kc.shape[0], kc.shape[1], kc.shape[2]
    kvh, hd = kc.shape[3], kc.shape[4]
    # written as rows of the pool flat over layers, the form the chunk and
    # decode programs write and gather (``generation._paged_view``):
    # scattered a layer's slab at a time, a pool whose lines fill under a
    # tile (4 KV heads) is relaid whole around the scatter (four copies of
    # 1.07 GB at depth 8, compiled for a described v5e); at 32 heads a
    # 256-token bucket reads 9.49 ms this way against 9.28 by slabs (my
    # chip run, PR 27), so there is one form
    rows = (dest[None, :] + (nb * bs) * jnp.arange(L)[:, None]).reshape(
        L * Lb)

    def written(pool, lines):
        if lines.shape[-1] < hd:       # a line padded to whole lanes
            lines = jnp.pad(lines, [(0, 0)] * (lines.ndim - 1)
                            + [(0, hd - lines.shape[-1])])
        return pool.reshape(L * nb * bs, kvh, hd).at[rows].set(
            lines[:, 0].reshape(L * Lb, kvh, hd)).reshape(L, nb, bs, kvh, hd)

    kc = written(kc, kvs[0])
    if vc is not None:
        vc = written(vc, kvs[1])

    key = jax.random.PRNGKey(seed)
    key = jax.lax.fori_loop(0, skip,
                            lambda _, k: jax.random.split(k)[0], key)
    key, sk = jax.random.split(key)
    logits0 = jnp.where(vmask > 0, logits0, -jnp.inf)
    logits_f = G._filter_logits(logits0[None], temp, do_sample, top_k,
                                top_p)
    if do_sample:
        tok0 = jax.random.categorical(sk, logits_f, axis=-1)[0]
    else:
        tok0 = jnp.argmax(logits_f, axis=-1)[0]
    tok0 = tok0.astype(jnp.int32)
    tok = tok.at[slot].set(tok0)
    cur_pos = cur_pos.at[slot].set(n_prompt.astype(jnp.int32))
    keys = keys.at[slot].set(key)
    if moe is not None:
        return kc, vc, tok, cur_pos, keys, tok0, moe
    return kc, vc, tok, cur_pos, keys, tok0


def _by_kind(make, kinds, window):
    """The per-layer bodies of a program's layer loop: ``make(window)``
    once for a model whose layers are all alike (``kinds`` None), else
    one for each layer, a ``sliding_attention`` layer with the model's
    window and any other without."""
    if kinds is None:
        return make(None)
    return tuple(make(window if k == "sliding_attention" else None)
                 for k in kinds)


def _stack_of(arch, w):
    """The per-layer leaves of a stacked weight tree."""
    from ..text import generation as G

    return G._latent_stack(w) if arch == "latent" else G._llama_stack(w)


def _layer_of(stack, i):
    """Layer ``i``'s leaves of a weight stack whose leaves are ``[L, ...]``
    arrays or a tuple of the layers' own arrays (an expert bank's; every
    leaf of a latent model's, with None where layer ``i`` has no such
    leaf: those are left out)."""
    return {k: a[i] for k, a in stack.items()
            if not isinstance(a, (tuple, list)) or a[i] is not None}


def _stacked(ys):
    """What the layers of an unrolled loop returned, each a flat tuple,
    stacked ``[L, ...]`` value by value; a value that only some layers
    return (a routed layer's picks in a model with dense layers too) is
    stacked over those."""
    return tuple(jnp.stack([y[i] for y in ys if len(y) > i])
                 for i in range(max(map(len, ys))))


def _scan_layers(layer, stack, x):
    """The layer loop of the prefill programs: ``layer(x, lw) -> (x,
    ys)``, the ``ys`` of every layer stacked ``[L, ...]``. A tuple of
    bodies is a model whose layers differ in kind, one body a layer: its
    loop is unrolled (see ``_scan_layers_over_pool``)."""
    if not isinstance(layer, tuple):
        return jax.lax.scan(layer, x, stack)
    ys = []
    for i, body in enumerate(layer):
        x, y = body(x, _layer_of(stack, i))
        ys.append(y)
    return x, _stacked(ys)


def _scan_layers_over_pool(layer, stack, x, kc, vc, block_ids, row_ids):
    """The layer loop of the paged programs that write the pool (decode,
    chunk, verify, the tp copies). The pools ride ``lax.scan`` as a
    CARRY, flat over layers — ``[L, nb, bs, kv, hd]`` bitcast to ONE pool
    of ``L*nb`` blocks — so a donated pool is the output's buffer and
    each layer's rows are scattered in place; as ``xs`` in / ``ys`` out
    both whole pools were copied, sliced and re-stacked every call.

    ``layer(x, lw, kc_pool, vc_pool, block_ids, row_ids)`` is a per-layer
    body of ``text/generation.py`` with the rest bound: to it a pool of
    ``L*nb`` blocks is a pool, and layer ``i`` gets its indices moved to
    its range, ``block_ids + i*nb`` and ``row_ids + i*nb*bs`` (block
    ``i*nb`` is that layer's trash block). Ids are int32, so the pool's
    rows over all layers, ``L*nb*bs``, stay under 2**31 (196 704 at 6 x
    2049 x 16).

    A tuple of bodies is a model whose layers differ in kind
    (``_by_kind``), one body a layer, and its loop is unrolled: a window
    layer's shapes differ from a full one's, and the expert banks are
    the model's own arrays, a tuple of ``L`` that no scan can take as
    ``xs``. Stacking them for a scan over periods of the pattern would
    hold nine tenths of the model twice while the engine is built (6.3
    GB more at the 8 layers the benchmark's cell serves, whose build
    peaks at 11.2 of the chip's 15.75 GB: my chip run, PR 27), and the
    plain reference reads the model's copy afterwards. The flat pool is
    threaded through the layers the same way.

    Returns ``(x, kc, vc)``, the pools shaped as they came, and after
    them, stacked ``[L, ...]``, whatever a body returns beyond its three
    (a routed layer's picks). ``vc`` is None where the cache has one pool
    (a latent model's, whose loop is unrolled), and comes back None."""
    L, nb, bs = kc.shape[:3]
    assert L * nb * bs < 2 ** 31, "pool rows over all layers overflow int32"
    flat = (L * nb,) + kc.shape[2:]
    if isinstance(layer, tuple):
        k2, more = kc.reshape(flat), []
        v2 = None if vc is None else vc.reshape(flat)
        for i, body in enumerate(layer):
            x, k2, v2, *rest = body(x, _layer_of(stack, i), k2, v2,
                                    block_ids + i * nb,
                                    row_ids + i * (nb * bs))
            more.append(tuple(rest))
        return (x, k2.reshape(kc.shape),
                None if vc is None else v2.reshape(vc.shape)) \
            + _stacked(more)

    def one(cx, lw_i):
        lw, i = lw_i
        x2, k2, v2 = layer(cx["x"], lw, cx["kc"], cx["vc"],
                           block_ids + i * nb, row_ids + i * (nb * bs))
        return {"x": x2, "kc": k2, "vc": v2}, None

    cx, _ = jax.lax.scan(
        one, {"x": x, "kc": kc.reshape(flat), "vc": vc.reshape(flat)},
        (stack, jnp.arange(L, dtype=jnp.int32)))
    return cx["x"], cx["kc"].reshape(kc.shape), cx["vc"].reshape(vc.shape)


def _count_picks(moe, picks, valid, k, decode=False):
    """The routed layers' counters (``serving/metrics.py``) after one
    program call whose rows made ``picks`` ``[L, held]``. An engine that
    holds a share of the experts also counts every pick its ``valid`` rows
    made, ``k`` a row a layer, on experts held here or not."""
    out = dict(moe, expert_tokens=moe["expert_tokens"] + picks)
    if "picks" in moe:
        out["picks"] = moe["picks"] + jnp.sum(
            valid, dtype=jnp.int32) * (k * picks.shape[0])
    if decode:
        out["experts_hit"] = moe["experts_hit"] + jnp.sum(
            picks > 0, axis=1, dtype=jnp.int32)
        out["decode_calls"] = moe["decode_calls"] + 1
    return out


def _paged_decode_impl(w, kc, vc, tables, tok, cur_pos, active, keys,
                       temps, vmasks, moe=None, *, arch, n_heads, n_kv, eps,
                       theta, do_sample, top_k, top_p, block_size, kinds=None,
                       window=None, moe_k=0, tp=1, attn_scale=None,
                       router=()):
    """One fused paged decode step: every decode-active slot advances a
    token at its own position, writing K/V through its block table
    (inactive rows scatter into the trash block so a freed slot's stale
    table can never corrupt the pool) and attending over the blocks of
    the pool its table names, read in place on a TPU
    (``generation._paged_decode_attention``; a slot that does not decode
    is given the position -1 and sees, and reads, nothing). ONE program
    for the life of the engine — the block table is a plain runtime
    operand of static shape; the pool is a carry of the layer loop
    (``_scan_layers_over_pool``), written in place when donated.
    ``kinds`` / ``window`` / ``moe_k`` / ``moe`` / ``attn_scale`` /
    ``router`` as in the prefill program; the routed layers compute and
    count the active rows alone.
    ``tp > 1`` as in the prefill program: each device scatters its
    kv-head shard into its pool shard (the LOCAL shard is the carry) and
    attends over its local head group; the o-/down-projections and the
    vocab head are overlapped collective-matmuls, so the decode HLO
    holds ``collective_permute`` ops alone — nothing serializes after a
    dot."""
    from ..text import generation as G

    S = tok.shape[0]
    rows = jnp.arange(S)
    blk = tables[rows, cur_pos // block_size]
    dest = jnp.where(active, blk * block_size + cur_pos % block_size,
                     cur_pos % block_size)
    seen = jnp.where(active, cur_pos, -1)   # the last position a row sees
    if arch != "gpt":
        xt = jnp.take(w["embed"], tok, axis=0)[:, None]
        stack = _stack_of(arch, w)
        at = (seen, cur_pos)            # ... and its rotary position
    else:
        xt = (jnp.take(w["wte"], tok, axis=0)
              + jnp.take(w["wpe"], cur_pos, axis=0))[:, None]
        stack, at = {k: w[k] for k in G._GPT_STACK_KEYS}, (seen,)

    def layer_of(win):
        body = _layer_body("decode", arch, tp, n_heads=n_heads, n_kv=n_kv,
                           eps=eps, theta=theta, block_size=block_size,
                           window=win, moe_k=moe_k, valid=active,
                           attn_scale=attn_scale, router=router)
        return lambda xc, lw, kc_p, vc_p, blocks, rows: body(
            xc, lw, kc_p, vc_p, blocks, rows, *at)

    xt, kc, vc, *picks = _scan_layers_over_pool(
        _by_kind(layer_of, kinds, window), stack, xt, kc, vc, tables, dest)
    if arch != "gpt":
        hidden = G._rms(xt[:, 0], w["norm"], eps)
    else:
        hidden = G._ln(xt[:, 0], w["lnfw"], w["lnfb"])
    logits = _head(hidden, w, tp)
    logits = jnp.where(vmasks > 0, logits, -jnp.inf)

    split = jax.vmap(jax.random.split)(keys)        # [S, 2, 2]
    new_keys, sks = split[:, 0], split[:, 1]
    logits_f = G._filter_logits(logits, temps, do_sample, top_k, top_p)
    if do_sample:
        nxt = jax.vmap(jax.random.categorical)(sks, logits_f)
    else:
        nxt = jnp.argmax(logits_f, axis=-1)
    nxt = nxt.astype(jnp.int32)
    nxt = jnp.where(active, nxt, tok)
    new_keys = jnp.where(active[:, None], new_keys, keys)
    cur2 = jnp.where(active, cur_pos + 1, cur_pos)
    if moe is not None:
        return nxt, kc, vc, cur2, new_keys, _count_picks(
            moe, picks[0], active, moe_k, decode=True)
    return nxt, kc, vc, cur2, new_keys


def _paged_chunk_impl(w, kc, vc, tok, cur_pos, keys, ids, chunk_start,
                      n_prompt, slot, table_row, skip_write, is_final,
                      seed, skip, temp, vmask, moe=None, *, arch, n_heads,
                      n_kv, eps, theta, do_sample, top_k, top_p, block_size,
                      kinds=None, window=None, moe_k=0, tp=1,
                      attn_scale=None, router=()):
    """One block-aligned prefill CHUNK of one slot, co-schedulable with
    the fused decode step: processes ``ids`` ([1, C], global positions
    ``chunk_start + j``) through every layer, scattering its K/V into
    the pool (shared-prefix / pad positions trash-redirected) and
    attending over the slot's gathered view. The SAME program serves
    every chunk of every long prompt (mid or final — ``is_final`` is a
    runtime operand gating the sampling side effects), so chunked
    prefill costs exactly ONE extra lowering, independent of prompt
    length. Sampling uses the admission-seeded PRNG chain with the
    supervisor-replay ``skip`` fast-forward, like the one-shot paths.
    The pool is a carry of the layer loop, as in the decode program;
    ``kinds`` / ``window`` / ``moe_k`` / ``moe`` / ``tp`` /
    ``attn_scale`` / ``router`` as in the prefill one."""
    from ..text import generation as G

    C = ids.shape[1]
    gpos = chunk_start + jnp.arange(C)
    writable = (gpos >= skip_write) & (gpos < n_prompt)
    wdest = jnp.where(writable,
                      table_row[gpos // block_size] * block_size
                      + gpos % block_size,
                      gpos % block_size)
    if arch != "gpt":
        x = jnp.take(w["embed"], ids, axis=0)
        stack = _stack_of(arch, w)
    else:
        x = jnp.take(w["wte"], ids, axis=0) + w["wpe"][gpos][None]
        stack = {k: w[k] for k in G._GPT_STACK_KEYS}

    def layer_of(win):
        def layer(xc, lw, kc_p, vc_p, blocks, rows):
            body = _layer_body(
                "chunk", arch, tp, n_heads=n_heads, n_kv=n_kv, eps=eps,
                theta=theta, block_size=block_size, window=win,
                moe_k=moe_k, attn_scale=attn_scale, router=router,
                valid=gpos < n_prompt if arch != "gpt" else None)
            return body(xc, lw, kc_p, vc_p, blocks, gpos, rows)
        return layer

    x, kc, vc, *picks = _scan_layers_over_pool(
        _by_kind(layer_of, kinds, window), stack, x, kc, vc, table_row,
        wdest)
    li = jnp.clip(n_prompt - 1 - chunk_start, 0, C - 1)
    if arch != "gpt":
        hlast = jax.lax.dynamic_index_in_dim(
            G._rms(x, w["norm"], eps)[0], li, 0, keepdims=False)
    else:
        xlast = jax.lax.dynamic_index_in_dim(x[0], li, 0,
                                             keepdims=False)
        hlast = G._ln(xlast, w["lnfw"], w["lnfb"])
    logits0 = _head(hlast, w, tp)

    key = jax.random.PRNGKey(seed)
    key = jax.lax.fori_loop(0, skip,
                            lambda _, k: jax.random.split(k)[0], key)
    key, sk = jax.random.split(key)
    logits0 = jnp.where(vmask > 0, logits0, -jnp.inf)
    logits_f = G._filter_logits(logits0[None], temp, do_sample, top_k,
                                top_p)
    if do_sample:
        tok0 = jax.random.categorical(sk, logits_f, axis=-1)[0]
    else:
        tok0 = jnp.argmax(logits_f, axis=-1)[0]
    tok0 = tok0.astype(jnp.int32)
    fin = is_final.astype(bool)
    tok = jnp.where(fin, tok.at[slot].set(tok0), tok)
    cur_pos = jnp.where(fin,
                        cur_pos.at[slot].set(n_prompt.astype(jnp.int32)),
                        cur_pos)
    keys = jnp.where(fin, keys.at[slot].set(key), keys)
    if moe is not None:
        return kc, vc, tok, cur_pos, keys, tok0, _count_picks(
            moe, picks[0], gpos < n_prompt, moe_k)
    return kc, vc, tok, cur_pos, keys, tok0


def _spec_verify_impl(w, kc, vc, keys, ids, start, slot, table_row,
                      n_write, temp, vmask, *, arch, n_heads, n_kv, eps,
                      theta, do_sample, top_k, top_p, block_size):
    """Speculative verify: ONE fused pass over a k-token draft chunk of
    one slot, scoring k+1 positions (the chunked-prefill program shape
    — ``generation._llama/_gpt_verify_layer`` share the chunk-layer
    math). ``ids`` [1, k+1] = [last emitted token, d_1..d_k] at global
    positions ``start + j``; candidate K/V scatters through the slot's
    block-table row with positions at/above ``n_write`` (draft width
    clamped by remaining budget / max_len) trash-redirected; the pool
    is a carry of the layer loop, as in the decode and chunk programs.

    Token-identical acceptance, on-device half: starting from the
    slot's CURRENT chain key (``keys[slot]``), each position re-runs the
    request's own sampling with exactly the split the non-speculative
    decode step would have consumed — returns the k+1 chain-sampled
    tokens plus the key-chain state after each split. The host accepts
    draft tokens while they equal the chain samples, emits the first
    mismatch's chain sample as the corrective token, rewinds ``cur`` to
    the accepted length and restores ``keys[slot]`` to the matching
    chain state — so tokens AND consumed PRNG splits are byte-equal to
    the non-speculative engine (greedy and sampled), and adopt()/replay
    machinery is untouched. ``vmask`` [V] is the request's vocab mask
    (all-ones when unconstrained), applied exactly as in the decode
    program."""
    from ..text import generation as G

    K1 = ids.shape[1]
    gpos = start + jnp.arange(K1)
    writable = jnp.arange(K1) < n_write
    wdest = jnp.where(writable,
                      table_row[gpos // block_size] * block_size
                      + gpos % block_size,
                      gpos % block_size)
    if arch == "llama":
        x = jnp.take(w["embed"], ids, axis=0)
        stack = {k: w[k] for k in G._LLAMA_STACK_KEYS}
    else:
        x = jnp.take(w["wte"], ids, axis=0) + w["wpe"][gpos][None]
        stack = {k: w[k] for k in G._GPT_STACK_KEYS}
    body = _layer_body("verify", arch, 1, n_heads=n_heads, n_kv=n_kv,
                       eps=eps, theta=theta, block_size=block_size)

    def layer(xc, lw, kc_p, vc_p, blocks, rows):
        return body(xc, lw, kc_p, vc_p, blocks, gpos, rows)

    x, kc, vc = _scan_layers_over_pool(layer, stack, x, kc, vc, table_row,
                                       wdest)
    if arch == "llama":
        logits = G._rms(x, w["norm"], eps)[0] @ w["head"]
    else:
        logits = G._ln(x[0], w["lnfw"], w["lnfb"]) @ w["head"]
    logits = jnp.where(vmask[None, :] > 0, logits, -jnp.inf)   # [K1, V]

    def samp(key, logits_i):
        key, sk = jax.random.split(key)
        lf = G._filter_logits(logits_i[None], temp, do_sample, top_k,
                              top_p)
        if do_sample:
            t = jax.random.categorical(sk, lf, axis=-1)[0]
        else:
            t = jnp.argmax(lf, axis=-1)[0]
        return key, (t.astype(jnp.int32), key)

    _, (samples, chain) = jax.lax.scan(samp, keys[slot], logits)
    return kc, vc, samples, chain


_STATICS = ("arch", "n_heads", "n_kv", "eps", "theta", "do_sample",
            "top_k", "top_p")
_PAGED_STATICS = _STATICS + ("block_size",)
# kinds / window / moe_k: a model whose layers differ in kind and route
# their feed-forward (``_make_arch``); tp: a sharded engine's, baked into
# its ``shard_map`` programs (``_tp_jitted``). A model or an engine without
# them passes none, so its programs and their cache keys are what they were
# attn_scale / router: a latent model's softmax scale and routing arguments
_PROGRAM_STATICS = _PAGED_STATICS + ("kinds", "window", "moe_k", "tp",
                                     "attn_scale", "router")

_CODE_TOKEN = None


def _serving_code_token():
    """AOT cache-key component covering every source file the serving
    programs trace through: editing the math invalidates persisted
    executables instead of silently reviving stale ones."""
    global _CODE_TOKEN
    if _CODE_TOKEN is None:
        import sys

        from ..aot import keys as _akeys
        from ..distributed import collective_matmul as _cm
        from ..nn import routed_ffn as _rf
        from ..ops.pallas import paged_attention as _pa
        from ..text import generation as G
        from ..text import sambay as _sb
        from . import sambay_programs as _sp
        from . import speculative as _spec
        _CODE_TOKEN = _akeys.code_token(G, _cm, _pa, _rf, _spec, _sb, _sp,
                                        sys.modules[__name__])
    return _CODE_TOKEN


#: (mesh, kind, arch, donate, statics) -> jitted shard_map program.
#: Module-level like the single-device programs: every engine (and every
#: supervisor-rebuilt incarnation) over an EQUAL mesh + geometry shares
#: one SPMD lowering per program kind — jax.sharding.Mesh hashes by
#: device ids + axis names, so a rebuilt engine's fresh-but-equal mesh
#: still hits this cache and re-traces nothing in-process.
_TP_PROGRAMS: dict = {}


def _tp_jitted(mesh, kind, arch, donate, statics_items):
    """Build (or fetch) the jitted ``shard_map`` of one paged program
    (``kind``: prefill, decode or chunk) over the ``tp`` axis of
    ``mesh``. Statics, ``tp`` among them, are BAKED via closure
    (shard_map has no static-kwarg channel); they live in the cache key
    and in the engine's AOT key parts instead."""
    key = (mesh, kind, arch, donate, statics_items)
    fn = _TP_PROGRAMS.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    from ..distributed.mesh import shard_map
    from ..text import generation as G

    impl, n_rest = {"prefill": (_paged_prefill_impl, 12),
                    "decode": (_paged_decode_impl, 7),
                    "chunk": (_paged_chunk_impl, 14)}[kind]
    wspec = G._llama_tp_specs() if arch == "llama" else G._gpt_tp_specs()
    kv = P(None, None, None, "tp", None)
    R = P()
    # the weights, the two pool shards, then the program's other operands,
    # all replicated
    in_specs = (wspec, kv, kv) + (R,) * n_rest
    if kind == "decode":
        out_specs = (R, kv, kv, R, R)
    else:
        out_specs = (kv, kv, R, R, R, R)
    body = functools.partial(impl, **dict(statics_items))
    sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    fn = jax.jit(sm, donate_argnums=(1, 2) if donate else ())
    _TP_PROGRAMS[key] = fn
    return fn


_PAGED_PREFILL = jax.jit(_paged_prefill_impl,
                         static_argnames=_PROGRAM_STATICS)
_PAGED_PREFILL_DONATED = jax.jit(
    _paged_prefill_impl, static_argnames=_PROGRAM_STATICS,
    donate_argnums=(1, 2))
_PAGED_DECODE = jax.jit(_paged_decode_impl, static_argnames=_PROGRAM_STATICS)
_PAGED_DECODE_DONATED = jax.jit(
    _paged_decode_impl, static_argnames=_PROGRAM_STATICS,
    donate_argnums=(1, 2))
_PAGED_CHUNK = jax.jit(_paged_chunk_impl, static_argnames=_PROGRAM_STATICS)
_PAGED_CHUNK_DONATED = jax.jit(
    _paged_chunk_impl, static_argnames=_PROGRAM_STATICS,
    donate_argnums=(1, 2))
_SPEC_VERIFY = jax.jit(_spec_verify_impl, static_argnames=_PAGED_STATICS)
_SPEC_VERIFY_DONATED = jax.jit(_spec_verify_impl,
                               static_argnames=_PAGED_STATICS,
                               donate_argnums=(1, 2))
# one row of the vocab masks the decode program keeps on the device
_SET_ROW = jax.jit(lambda masks, slot, row: masks.at[slot].set(row))


def _make_arch(model):
    """Weight stack + static hyperparams for a supported CausalLM."""
    from ..text import generation as G

    name = type(model).__name__
    c = model.config
    hd = getattr(c, "head_dim", None) \
        or c.hidden_size // c.num_attention_heads
    if name == "LlamaForCausalLM":
        w = G._stacked_weights(model)
        hp = dict(arch="llama", n_heads=c.num_attention_heads,
                  n_kv=c.num_key_value_heads, eps=c.rms_norm_eps,
                  theta=c.rope_theta)
        kvh = c.num_key_value_heads
        dtype = w["embed"].dtype
    elif name == "MellumForCausalLM":
        # the llama bodies, told what differs: each layer's kind, the
        # window of the sliding ones, and the experts a token is routed
        # to; each layer's rotary table, the router and the expert banks
        # ride the stacked weights (theta is never read)
        w = model.stacked_weights()
        hp = dict(arch="llama", n_heads=c.num_attention_heads,
                  n_kv=c.num_key_value_heads, eps=c.rms_norm_eps,
                  theta=0.0, kinds=tuple(c.layer_types),
                  window=c.sliding_window, moe_k=c.num_experts_per_tok)
        kvh = c.num_key_value_heads
        dtype = w["embed"].dtype
    elif name == "KimiK2ForCausalLM":
        # the llama program around the latent bodies: one "KV head" whose
        # line is the latent beside the shared rotary key, padded to whole
        # lanes (576 -> 640: the chip lays a narrower line out so anyway),
        # and no V pool; dense and routed layers as kinds; the softmax
        # scale and the routing arguments as statics (n_kv and theta are
        # never read)
        w = model.stacked_weights()
        hp = dict(arch="latent", n_heads=c.num_attention_heads, n_kv=1,
                  eps=c.rms_norm_eps, theta=0.0, kinds=c.layer_kinds(),
                  moe_k=c.num_experts_per_tok, attn_scale=c.rope()[2],
                  router=c.router())
        kvh, hd = 1, -(-(c.kv_lora_rank + c.qk_rope_head_dim) // 128) * 128
        dtype = w["embed"].dtype
    elif name == "Phi4FlashForCausalLM":
        # programs of its own (``sambay_programs.py``) over bodies of its
        # own: three kinds of state a slot. A cached line is a KV PAIR's,
        # ``[k1 | k2]`` beside ``[v1 | v2]``: half the KV heads at twice
        # the head size, whole lanes (theta is never read)
        w = model.stacked_weights()
        hp = dict(arch="sambay", theta=0.0, **model.serving_statics())
        kvh, hd = c.num_key_value_heads // 2, 2 * hd
        dtype = w["embed"].dtype
    elif name == "GPTForCausalLM":
        w = G._gpt_stacked_weights(model)
        hp = dict(arch="gpt", n_heads=c.num_attention_heads,
                  n_kv=c.num_attention_heads, eps=1e-5, theta=0.0)
        kvh = c.num_attention_heads
        dtype = w["wte"].dtype
    else:
        raise TypeError(
            f"serving.Engine supports LlamaForCausalLM / GPTForCausalLM / "
            f"MellumForCausalLM / KimiK2ForCausalLM / Phi4FlashForCausalLM, "
            f"got {name}")
    # what one position keeps in one layer's pool, and whether a V pool of
    # the same lines stands beside it
    geo = dict(n_layers=c.num_hidden_layers, line=(kvh, hd),
               values=hp["arch"] != "latent", dtype=dtype,
               max_pos=c.max_position_embeddings)
    if hp["arch"] == "latent":
        # the bytes of a line as published: what a roofline counts
        geo["line_bytes"] = (c.kv_lora_rank + c.qk_rope_head_dim) \
            * jnp.dtype(dtype).itemsize
    if hp["arch"] == "sambay":
        # what a position keeps, by the kind of its layer: whole lines in
        # the pool (the one full layer), at most a window of lines (the
        # sliding layers), a state a slot (the recurrent layers), nothing
        # (the gated units and the query-only layers); ``readers``: the
        # layers that read the pool's one layer, the full one among them
        n = {k: hp["kinds"].count(k) for k in set(hp["kinds"])}
        di = c.d_inner
        geo.update(
            n_layers=n["full_attention"], vocab=c.vocab_size,
            window=(n["sliding_attention"], c.sliding_window),
            recurrent={"ssm": (n["mamba"], (c.mamba_d_state, di), "float32"),
                       "conv": (n["mamba"], (c.mamba_d_conv - 1, di), dtype)},
            readers=n["full_attention"] + n["cross_attention"])
    return w, hp, geo


def _model_fingerprint(model, hp, statics, eos_token_id, w):
    """Cheap, deterministic identity of the token math an engine runs:
    model class + config + arch hyperparams + engine-wide sampling
    statics + the stacked-weight tree spec (keys/shapes/dtypes). Two
    engines with equal fingerprints produce identical token streams for
    the same (prompt, seed, gen kwargs) — the ``adopt()`` migration
    precondition. Deliberately EXCLUDES tp degree, mesh, KV layout and
    block geometry: adopt replays from tokens, not KV bytes, so those
    may differ across the migration. Metadata only (never hashes weight
    bytes, never runs a device op): construction stays compile-free and
    cheap on sharded weights."""
    import hashlib

    cfg = getattr(model, "config", None)
    try:
        import dataclasses
        cfg_repr = repr(sorted(dataclasses.asdict(cfg).items()))
    except TypeError:
        cfg_repr = repr(cfg)
    wspec = tuple(sorted(
        (k, tuple((tuple(a.shape), str(a.dtype))
                  for a in jax.tree.leaves(v))) for k, v in w.items()))
    parts = (type(model).__name__, cfg_repr,
             tuple(sorted(hp.items())), tuple(sorted(statics.items())),
             eos_token_id, wspec)
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]


class RequestHandle:
    """One submitted request: streams tokens as the engine decodes.

    ``tokens`` grows as the engine steps; ``on_token(handle, token)``
    fires per token (first one during prefill — that stamp is the TTFT);
    ``result()`` pumps the engine until this request finishes and
    returns the full sequence (prompt + generated) as int32 numpy.
    """

    def __init__(self, engine, request_id, prompt_ids, max_new_tokens,
                 temperature, seed, on_token, max_time_s=None, priority=0,
                 logit_mask=None):
        self._engine = engine
        self.request_id = request_id
        self.prompt_ids = prompt_ids
        self.n_prompt = int(prompt_ids.shape[0])
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.on_token = on_token
        self.priority = int(priority)
        # per-request vocab mask (constrained decoding); adopt()/replay
        # carries it, so a migrated request stays constrained
        self.logit_mask = logit_mask
        self.max_time_s = None if max_time_s is None else float(max_time_s)
        self.deadline = (None if max_time_s is None
                         else time.monotonic() + float(max_time_s))
        self.tokens = []
        self.finished = False
        # "eos" | "length" | "timeout" | "shed" | "cancelled"
        self.finish_reason = None
        self.retry_after_s = None      # stamped when shed under brownout
        # fleet identity: which replica currently serves this handle
        # (restamped on adopt/migration) and the origin engine's model
        # fingerprint (the adopt() compatibility guard)
        self.replica_id = getattr(engine, "replica_id", None)
        self.model_fingerprint = getattr(engine, "model_fingerprint",
                                         None)
        self.slot = None
        self.metrics = RequestMetrics()
        # one trace id for the request's whole lifecycle — minted
        # whether or not tracing is on (ledgers/chaos verdicts refer to
        # it), and kept by adopt() so a token-identical replay on a
        # rebuilt engine links to the original request's trace
        self.trace_id = _tracing.new_trace_id()
        self._queued_t = self.metrics.submit_time

    def result(self):
        while not self.finished:
            self._engine.step()
        if self.finish_reason == "timeout":
            where = (f" on replica {self.replica_id}"
                     if self.replica_id is not None else "")
            raise RequestTimeout(
                f"request {self.request_id} exceeded max_time_s="
                f"{self.max_time_s} after {len(self.tokens)} tokens"
                f"{where}; its slot was reclaimed",
                replica=self.replica_id)
        if self.finish_reason == "shed":
            where = (f" by replica {self.replica_id}"
                     if self.replica_id is not None else "")
            raise RequestShed(
                f"request {self.request_id} (priority {self.priority}) "
                f"was shed under overload{where}; retry after "
                f"{self.retry_after_s}s", retry_after_s=self.retry_after_s,
                replica=self.replica_id)
        if self.finish_reason == "cancelled":
            raise RequestCancelled(
                f"request {self.request_id} was cancelled after "
                f"{len(self.tokens)} tokens")
        return np.concatenate(
            [self.prompt_ids, np.asarray(self.tokens, np.int32)])

    def __repr__(self):
        state = self.finish_reason or (
            "decoding" if self.slot is not None else "queued")
        return (f"RequestHandle(id={self.request_id}, prompt={self.n_prompt}"
                f", tokens={len(self.tokens)}, {state})")


class _ChunkState:
    """Host bookkeeping of one in-progress chunked prefill."""

    __slots__ = ("h", "ids", "n_eff", "n_shared", "next", "skip")

    def __init__(self, h, ids, n_eff, n_shared, start):
        self.h = h
        self.ids = np.ascontiguousarray(ids, np.int32)
        self.n_eff = int(n_eff)
        self.n_shared = int(n_shared)
        self.next = int(start)          # next chunk-start position
        self.skip = len(h.tokens)       # PRNG fast-forward (replay)


class Engine:
    """Continuous-batching serving engine (see module docstring).

    Sampling mode (do_sample/top_k/top_p) is engine-wide — it is baked
    into the two compiled programs. Temperature, seed and length are
    per-request (plain runtime operands).
    """

    def __init__(self, model, n_slots=8, max_len=None, *, do_sample=False,
                 top_k=0, top_p=None, eos_token_id=None,
                 min_prompt_bucket=8, token_budget=None, max_queue=None,
                 base_seed=0, donate=None, compile_budget=None,
                 default_retry_after_s=DEFAULT_RETRY_AFTER_S,
                 block_size=16, n_blocks=None,
                 prefill_chunk=None, prefix_sharing=True, tp=1,
                 mesh=None, replica_id=None, speculative=None):
        self._w, self._hp, geo = _make_arch(model)
        if self._hp["arch"] == "sambay":
            # a radix hit would need the recurrent state and the window's
            # lines at the hit's boundary, and no snapshot is kept
            for asked, missing in (
                    (int(tp) > 1, "tp > 1: no tensor-parallel program "
                     "carries recurrent state or window rings"),
                    (speculative is not None, "speculative=...: the verify "
                     "program carries no recurrent state"),
                    (bool(prefix_sharing), "prefix_sharing=True: no "
                     "recurrent state or window lines are kept at a block "
                     "boundary for a sharer to start from (pass "
                     "prefix_sharing=False)")):
                if asked:
                    raise ValueError(
                        f"serving.Engine cannot serve "
                        f"{type(model).__name__} with {missing}")
        elif "kinds" in self._hp:
            # a model of layer kinds with routed experts runs through the
            # single-device programs alone: what else it is asked for is
            # refused by name, never served another way
            lacks = ("no latent attention, no routed share and no shared "
                     "expert" if self._hp["arch"] == "latent"
                     else "no routed feed-forward and takes no window")
            for asked, missing in (
                    (int(tp) > 1, "tp > 1: the tensor-parallel bodies have "
                     + lacks.replace("takes", "take")),
                    (speculative is not None, "speculative=...: the verify "
                     "body has " + lacks)):
                if asked:
                    raise ValueError(
                        f"serving.Engine cannot serve "
                        f"{type(model).__name__} with {missing}")
        #: fleet identity: stamped onto handles and carried by
        #: RequestTimeout/RequestShed/EngineOverloaded (None standalone)
        self.replica_id = replica_id
        self.tp = int(tp)
        self._mesh = None
        self._n_layers = geo["n_layers"]
        if self.tp > 1:
            mesh = self._init_tp(mesh)
        elif mesh is not None:
            raise ValueError("mesh= requires tp > 1")
        self.n_slots = int(n_slots)
        self.max_len = int(max_len if max_len is not None
                           else geo["max_pos"])
        if self.max_len > geo["max_pos"] and self._hp["arch"] == "gpt":
            raise ValueError("max_len exceeds the position table")
        self.eos_token_id = eos_token_id
        self.min_prompt_bucket = int(min_prompt_bucket)
        self._statics = dict(self._hp, do_sample=bool(do_sample),
                             top_k=int(top_k),
                             top_p=None if top_p is None else float(top_p))
        # the adopt()/migration compatibility token (see the helper):
        # engines over the same model + sampling statics — regardless of
        # tp degree or KV geometry — share it and may exchange handles
        self.model_fingerprint = _model_fingerprint(
            model, self._hp, self._statics, eos_token_id, self._w)
        # speculative decoding (draft-verify; see serving/speculative.py):
        # the TP decode shards attention over the mesh, the verify
        # program does not
        self.spec = speculative
        if self.spec is not None:
            from .speculative import SpecConfig
            if not isinstance(self.spec, SpecConfig):
                raise TypeError("speculative= takes a SpecConfig")
            if self.tp > 1:
                raise ValueError("speculative decoding is not supported "
                                 "with tp > 1 yet")
        self.prefix_sharing = bool(prefix_sharing)
        self._chunking = []        # in-progress chunked prefills
        self.chunk_used = False    # the +1 chunk lowering, once traced
        self.block_size = int(block_size)
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < self.block_size \
                    or prefill_chunk % self.block_size:
                raise ValueError(
                    "prefill_chunk must be a block-aligned multiple "
                    f"of block_size={self.block_size}")
        self.prefill_chunk = prefill_chunk
        self.cache = PagedKVCache(geo["n_layers"], self.n_slots,
                                  self.max_len, geo["line"], geo["dtype"],
                                  block_size=self.block_size,
                                  n_blocks=n_blocks, values=geo["values"],
                                  window=geo.get("window"),
                                  recurrent=geo.get("recurrent"),
                                  folded="window" in geo)
        self._paged_statics = dict(self._statics,
                                   block_size=self.block_size)
        # threaded device state (numpy until the first jit call)
        self._tok = np.zeros(self.n_slots, np.int32)
        self._cur = np.zeros(self.n_slots, np.int32)
        self._keys = np.zeros((self.n_slots, 2), np.uint32)
        self._temps = np.ones(self.n_slots, np.float32)
        # per-request vocab masks (grammar/JSON-constrained decoding):
        # a plain [n_slots, V] runtime operand of the decode AND verify
        # programs — all-ones rows are unconstrained, so the feature
        # costs zero lowerings and leaves unmasked sampling bit-exact
        self._vocab = int(geo.get("vocab") or self._w["head"].shape[-1])
        self._vmask = np.ones((self.n_slots, self._vocab), np.float32)
        # the decode program's copy of the masks stays on the device: a row
        # changes only when a slot's request does, and is written then
        # (``_mask_row``); uploaded whole every step it was 6.55 MB of the
        # dense cell's dispatch, and 51 MB at 64 slots x 200 064 rows
        self._vmask_dev = None
        self._vmask_plain = np.ones(self.n_slots, bool)
        if self.tp > 1:
            # commit the KV pool (head dim split over tp) and the small
            # replicated state up front so every program call sees one
            # stable sharded signature — the AOT keys then match the
            # save_lm precompile probes operand for operand
            from jax.sharding import NamedSharding, PartitionSpec as P
            kvP = NamedSharding(mesh, P(None, None, None, "tp", None))
            rep = NamedSharding(mesh, P())
            self.cache.kc = jax.device_put(self.cache.kc, kvP)
            self.cache.vc = jax.device_put(self.cache.vc, kvP)
            self._tok = jax.device_put(self._tok, rep)
            self._cur = jax.device_put(self._cur, rep)
            self._keys = jax.device_put(self._keys, rep)
        # PriorityScheduler degenerates to strict FIFO when every request
        # uses the default priority and carries no deadline
        self.scheduler = PriorityScheduler(
            token_budget=token_budget or self.n_slots * self.max_len,
            max_queue=max_queue or max(4 * self.n_slots, 16))
        self.default_retry_after_s = float(default_retry_after_s)
        # flipped by serving.resilience.EngineSupervisor when this
        # incarnation is replaced after a fault: an abandoned wedged step
        # thread that later unblocks must not mutate replayed handles
        self._condemned = False
        self.metrics = EngineMetrics()
        self.metrics.replica = replica_id
        if "wr" in self._w:
            # the routed layers' counters live on the device, threaded
            # through the programs beside the pool (last argument in,
            # last value out); only a snapshot fetches them
            # (a layer's counters are of the experts held here; an engine
            # that holds a share also counts the picks made in all)
            wr, held = self._w["wr"], self._w["wg"][-1].shape[0]
            if isinstance(wr, tuple):      # the layers' own, None if dense
                routers = [r for r in wr if r is not None]
                layers, scored = len(routers), routers[0].shape[-1]
            else:
                layers, scored = wr.shape[0], wr.shape[-1]
            self.metrics.moe = {
                "expert_tokens": jnp.zeros((layers, held), jnp.int32),
                "experts_hit": jnp.zeros((layers,), jnp.int32),
                "decode_calls": jnp.zeros((), jnp.int32)}
            if held != scored:
                self.metrics.moe["picks"] = jnp.zeros((), jnp.int32)
        if "line_bytes" in geo:
            self.metrics.latent = EngineMetrics.latent_counters(
                int(geo["line_bytes"]))
        if "readers" in geo:
            self.metrics.recurrent = EngineMetrics.recurrent_counters(
                geo["readers"], self.cache.bytes_by_kind())
        self._steps = 0           # step() calls so far: the next index
        self._step = None         # index of the step() now running
        # tracer on: spans of the running step's launches, held until
        # the step closes and their parent's id is known
        self._held_spans = []
        self._by_slot = [None] * self.n_slots
        self._next_id = 0
        self.base_seed = int(base_seed)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        # (kind, bucket) -> aot.AotProgram: every program invocation
        # routes through the shared compile service, so a warm on-disk
        # cache (or a save_lm artifact's precompiled program set)
        # deserializes executables instead of compiling — zero XLA
        # backend compiles for a fresh process's first token
        self._aot: dict = {}
        if self.tp > 1:
            arch = self._hp["arch"]
            items = tuple(sorted(dict(self._paged_statics,
                                      tp=self.tp).items()))
            self._tp_statics_items = items
            self._prefill = _tp_jitted(mesh, "prefill", arch, donate,
                                       items)
            self._decode = _tp_jitted(mesh, "decode", arch, donate, items)
            self._chunk = _tp_jitted(mesh, "chunk", arch, donate, items)
            # baked into the shard_map programs: no call passes a static
            self._paged_statics = {}
        elif self._hp["arch"] == "sambay":
            from . import sambay_programs as sp
            self._prefill = sp.PREFILL_DONATED if donate else sp.PREFILL
            self._decode = sp.DECODE_DONATED if donate else sp.DECODE
            self._chunk = sp.CHUNK_DONATED if donate else sp.CHUNK
        else:
            self._prefill = (_PAGED_PREFILL_DONATED if donate
                             else _PAGED_PREFILL)
            self._decode = (_PAGED_DECODE_DONATED if donate
                            else _PAGED_DECODE)
            self._chunk = _PAGED_CHUNK_DONATED if donate else _PAGED_CHUNK
        # compile ledger: which prefill bucket lengths this engine has
        # actually traced (each is one XLA program; + 1 fused decode).
        # ``compile_budget`` is the declared cap the compile-budget lint
        # rule (paddle_tpu.analysis) gates on — None means unbudgeted.
        self.buckets_seen = set()
        self.compile_budget = (None if compile_budget is None
                               else int(compile_budget))
        # speculative-program ledger (compile-budget rule): the verify
        # program is ONE extra lowering once any slot verifies; a model
        # draft additionally pays its own prefill buckets + one fused
        # draft decode (ngram / custom proposers are host-side: zero)
        self.verify_used = False
        self.draft_buckets_seen = set()
        self.draft_decode_used = False
        if self.spec is not None:
            from .speculative import make_runtime
            self._verify = (_SPEC_VERIFY_DONATED if donate
                            else _SPEC_VERIFY)
            self._spec = make_runtime(self, self.spec, model)
        else:
            self._verify = None
            self._spec = None
        self.metrics.tp = self.tp
        if self.tp > 1:
            g = self.tp_geometry()
            self.metrics.kv_pool_bytes_per_device = \
                g["kv_pool_bytes_per_device"]
            self.metrics.collectives_per_decode_step = \
                g["collectives_per_decode_step"]

    # -- tensor parallelism -----------------------------------------------

    def _init_tp(self, mesh):
        """Validate the tp geometry and commit the stacked weights to
        the mesh: column-parallel qkv/gate-up, row-parallel o-/down-proj
        (GPT: the fused qkv columns pre-permuted to device-major order),
        vocab-sharded head, everything else replicated. Returns the
        mesh; the engine's three programs are then shard_map SPMD
        lowerings over it — still exactly buckets + decode (+ chunk)."""
        from jax.sharding import NamedSharding

        from ..distributed import mesh as mesh_mod
        from ..text import generation as G

        tp = self.tp
        if mesh is None:
            mesh = mesh_mod.build_mesh(tp=tp)
        if dict(mesh.shape).get("tp", 1) != tp:
            raise ValueError(
                f"mesh tp axis {dict(mesh.shape).get('tp', 1)} != tp={tp}")
        self._mesh = mesh
        arch = self._hp["arch"]
        nh, nkv = self._hp["n_heads"], self._hp["n_kv"]
        V = int(self._w["head"].shape[-1])
        f = int(self._w["wg"].shape[-1] if arch == "llama"
                else self._w["wfc1"].shape[-1])
        h = int(self._w["wq"].shape[1] if arch == "llama"
                else self._w["wqkv"].shape[1])
        for name, dim in (("num_attention_heads", nh),
                          ("num_key_value_heads", nkv),
                          ("vocab (head columns)", V),
                          ("intermediate_size", f), ("hidden_size", h)):
            if dim % tp:
                raise ValueError(
                    f"tp={tp} does not divide {name}={dim}")
        w = dict(self._w)
        if arch == "gpt":
            perm = G._gpt_qkv_tp_permutation(h, tp)
            w["wqkv"] = w["wqkv"][..., perm]
            w["bqkv"] = w["bqkv"][..., perm]
        specs = (G._llama_tp_specs() if arch == "llama"
                 else G._gpt_tp_specs())
        self._w = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                   for k, v in w.items()}
        return mesh

    def tp_geometry(self):
        """Mesh geometry at a glance (stats()/audit_engine/profiler):
        tp axis size, per-device KV pool bytes, and the collective count
        one fused decode step issues — all ppermute ring hops; an
        undersharded or serial-collective engine is visible here before
        it is visible in a profile. None on single-device engines."""
        if self.tp <= 1:
            return None
        from ..distributed.collective_matmul import (
            ppermutes_per_gather, ppermutes_per_rowparallel)
        V = int(self._w["head"].shape[-1])      # jax Array shape: global
        per_layer = 2 * ppermutes_per_rowparallel(self.tp)
        head = ppermutes_per_gather(self.tp, V // self.tp)
        return {
            "tp": self.tp,
            "devices": [str(d) for d in self._mesh.devices.flat],
            "kv_pool_bytes_per_device": self.cache.nbytes() // self.tp,
            "kv_heads_per_device": self.cache.kv_heads // self.tp,
            "weight_sharding": "column(qkv/gate-up) row(o/down) "
                               "vocab(head)",
            "collectives_per_decode_step": (
                self._n_layers * per_layer + head),
            "collective_kind": "collective_permute (overlapped ring)",
        }

    # -- AOT program routing ----------------------------------------------

    def _aot_key_parts(self, kind):
        parts = ("serving", kind, self._donate, _serving_code_token())
        if self.tp > 1:
            # statics are baked into the shard_map closure (not call-site
            # kwargs), so they pin program identity here instead
            parts = parts + ("tp", self._tp_statics_items)
        return parts

    def _run_program(self, kind, hkey, jitted, args, statics, origin):
        """Invoke one engine program through the shared compile service.
        The handle is resolved once per (kind, bucket) and cached; with
        no persistent cache configured this is a plain passthrough to
        the module-level jitted program (pre-AOT behavior)."""
        h = self._aot.get(hkey)
        if h is None:
            from ..aot import get_service
            h = get_service().get(
                f"serving:{kind}", args=args, statics=statics,
                key_parts=self._aot_key_parts(kind), jitted=jitted,
                origin=origin)
            self._aot[hkey] = h
        return h.call(*args, **statics)

    def aot_stats(self) -> dict:
        """Per-provenance program counts (audit_engine warm-start
        visibility): disk-exec entries cost a fresh process nothing."""
        out: dict = {}
        for h in self._aot.values():
            out[h.source] = out.get(h.source, 0) + 1
        return out

    def _aot_buckets(self):
        out, b = [], self.min_prompt_bucket
        while True:
            out.append(min(b, self.max_len))
            if b >= self.max_len:
                return out
            b <<= 1

    def _aot_probe_specs(self, buckets=None):
        """(kind, hkey, jitted, abstract args, statics, origin) for every
        program this engine geometry can run — ShapeDtypeStruct probes
        mirroring the live call sites operand for operand, so the
        signatures save_lm precompiles under are exactly the ones a
        serving process looks up."""
        def sds(a, sharding=None):
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype,
                                        sharding=sharding)

        S = self.n_slots
        rep = None
        if self.tp > 1:
            # probes must mirror the live sharded signatures (weights /
            # pool committed to the mesh, small state replicated)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..text import generation as G
            specs = (G._llama_tp_specs() if self._hp["arch"] == "llama"
                     else G._gpt_tp_specs())
            w = {k: sds(v, NamedSharding(self._mesh, specs[k]))
                 for k, v in self._w.items()}
            kvP = NamedSharding(self._mesh, P(None, None, None, "tp",
                                              None))
            kc, vc = sds(self.cache.kc, kvP), sds(self.cache.vc, kvP)
            rep = NamedSharding(self._mesh, P())
        else:
            w = jax.tree_util.tree_map(sds, self._w)
            kc, vc = jax.tree.map(sds, (self.cache.kc, self.cache.vc))
        tok = jax.ShapeDtypeStruct((S,), np.int32, sharding=rep)
        cur = jax.ShapeDtypeStruct((S,), np.int32, sharding=rep)
        keys = jax.ShapeDtypeStruct((S, 2), np.uint32, sharding=rep)
        temps = jax.ShapeDtypeStruct((S,), np.float32)
        active = jax.ShapeDtypeStruct((S,), np.bool_)
        vmasks = jax.ShapeDtypeStruct((S, self._vocab), np.float32)
        i32 = jax.ShapeDtypeStruct((), np.int32)
        u32 = jax.ShapeDtypeStruct((), np.uint32)
        f32 = jax.ShapeDtypeStruct((), np.float32)
        if buckets is None:
            buckets = self._aot_buckets()
        specs = []
        vrow = jax.ShapeDtypeStruct((self._vocab,), np.float32)
        moe = tuple(jax.tree.map(sds, a) for a in self._moe_in())
        mb = self.cache.block_tables.shape[1]
        trow = jax.ShapeDtypeStruct((mb,), np.int32)
        tables = jax.ShapeDtypeStruct((S, mb), np.int32)
        for Lb in buckets:
            ids = jax.ShapeDtypeStruct((1, int(Lb)), np.int32)
            specs.append((
                "prefill", ("prefill", int(Lb)), self._prefill,
                (w, kc, vc, tok, cur, keys, ids, i32, i32, u32, i32,
                 f32, trow, i32, vrow) + moe,
                self._paged_statics, f"prefill:L{Lb}"))
        specs.append((
            "decode", ("decode",), self._decode,
            (w, kc, vc, tables, tok, cur, active, keys, temps,
             vmasks) + moe,
            self._paged_statics, "decode"))
        if self.spec is not None:
            K1 = self.spec.k + 1
            sids = jax.ShapeDtypeStruct((1, K1), np.int32)
            specs.append((
                "verify", ("verify", K1), self._verify,
                (w, kc, vc, keys, sids, i32, i32, trow, i32, f32,
                 vrow),
                self._paged_statics, "spec.verify"))
            specs.extend(self._spec.probe_specs(buckets))
        if self.prefill_chunk is not None:
            ids = jax.ShapeDtypeStruct((1, self.prefill_chunk),
                                       np.int32)
            specs.append((
                "chunk", ("chunk",), self._chunk,
                (w, kc, vc, tok, cur, keys, ids, i32, i32, i32, trow,
                 i32, i32, u32, i32, f32, vrow) + moe,
                self._paged_statics, "chunk"))
        return specs

    def precompile_aot(self, dest_dir, buckets=None):
        """Compile + serialize this engine's full program set (decode +
        every prefill bucket + the chunk program when configured) into
        ``dest_dir`` — the ``save_lm`` artifact path. Nothing executes:
        probes are abstract. Returns the service stats of the build."""
        from ..aot import CompileService
        svc = CompileService(cache_dir=dest_dir, enabled=True)
        for kind, hkey, jitted, args, statics, origin in \
                self._aot_probe_specs(buckets):
            svc.get(f"serving:{kind}", args=args, statics=statics,
                    key_parts=self._aot_key_parts(kind), jitted=jitted,
                    origin=origin)
        return svc.stats()

    # -- stamps (module docstring) ----------------------------------------

    def _moe_in(self):
        """What a program takes last, beside the pool: the routed layers'
        counters, or the state of recurrent and window layers
        (``PagedKVCache.state``); a one-tuple, or none where the model has
        neither."""
        if self.cache.state is not None:
            return (self.cache.state,)
        return () if self.metrics.moe is None else (self.metrics.moe,)

    def _moe_out(self, out):
        """Keep what a program returned last of the above; the rest of its
        values, as a model that has neither returns them."""
        if self.cache.state is not None:
            *out, self.cache.state = out
        elif self.metrics.moe is not None:
            *out, self.metrics.moe = out
        return out

    def _mask_row(self, slot, mask):
        """Slot ``slot``'s vocab mask from now on (None: every token). The
        host's copy serves the programs of one slot; the decode program's
        copy lives on the device and takes the row only where it differs
        from what is there (a slot whose old and new request are both
        unconstrained costs nothing)."""
        plain = mask is None
        self._vmask[slot] = 1.0 if plain else mask
        if self._vmask_dev is not None and not (
                plain and self._vmask_plain[slot]):
            self._vmask_dev = _SET_ROW(self._vmask_dev, np.int32(slot),
                                       self._vmask[slot])
        self._vmask_plain[slot] = plain

    def _launched(self, program, called, dispatched, fetched, span=None,
                  h=None, tokens=0, radix_tokens=0, **attrs):
        """Record one program launch from the stamps its call site took;
        with the tracer on, the same stamps are the span ``span``."""
        self.metrics.mark_launch(LaunchRecord(
            program, self._step, called, dispatched, fetched,
            None if h is None else h.request_id, tokens, radix_tokens))
        if span is None or not _tracing._ENABLED:
            return
        if h is not None:
            attrs["request_id"] = h.request_id
        ev = (span, called, fetched or dispatched,
              None if h is None else h.trace_id, attrs)
        if self._step is None:
            self._launch_spans([ev])
        else:
            self._held_spans.append(ev)

    def _launch_spans(self, held, step_span=None, schedule_span=None,
                      scheduled=0.0):
        """Write held launch spans: one called before the step was
        ``scheduled`` (a prefill, a chunk) hangs under its schedule
        phase, a later one (verify, draft) under the step itself."""
        for name, t0, t1, trace_id, attrs in held:
            _tracing.span_event(
                name, t0, t1, cat="serving", trace_id=trace_id,
                parent=schedule_span if t0 < scheduled else step_span,
                **attrs)

    # -- request intake ---------------------------------------------------

    def _bucket(self, n):
        b = self.min_prompt_bucket
        while b < n:
            b <<= 1
        return min(b, self.max_len)

    @staticmethod
    def _as_ids(prompt):
        if isinstance(prompt, Tensor):
            prompt = np.asarray(prompt._data)
        ids = np.asarray(prompt, np.int32)
        if ids.ndim == 2 and ids.shape[0] == 1:
            ids = ids[0]
        if ids.ndim != 1:
            raise ValueError(
                f"prompt must be a 1-D token sequence, got {ids.shape}")
        return ids

    def submit(self, prompt, max_new_tokens=32, temperature=1.0,
               seed=None, on_token=None, max_time_s=None, priority=0,
               logit_mask=None):
        """Enqueue a request; returns a RequestHandle immediately. The
        request prefills as soon as a slot + token budget admit it (often
        inside this call). Raises EngineOverloaded past max_queue.

        ``max_time_s`` is a wall-clock deadline covering queueing AND
        decoding: a request still unfinished when it expires frees its
        KV slot at the next step and ``result()`` raises
        :class:`RequestTimeout` — a wedged or runaway request can never
        occupy the engine forever.

        ``priority`` is the admission class (0 = most important): lower
        numbers admit first, and overload brownout
        (:class:`~paddle_tpu.serving.resilience.EngineSupervisor`) sheds
        the highest-numbered queued classes first. Within a class,
        deadline-carrying requests admit earliest-deadline-first and
        the rest keep strict FIFO (see PriorityScheduler).

        ``logit_mask`` (grammar/JSON-constrained decoding) is a [vocab]
        mask (bool or numeric, nonzero = allowed) applied to EVERY
        sampled position of THIS request — prefill (the first token),
        decode, chunked prefill and speculative verify — as a plain
        runtime operand: zero new lowerings, co-batched neighbours
        untouched, and adopt()/replay re-samples under the same mask so
        constrained requests migrate token-identically."""
        begin = time.perf_counter()
        before = thread_counters()
        ids = self._as_ids(prompt)
        if ids.shape[0] < 1:
            raise ValueError("empty prompt")
        if logit_mask is not None:
            m = np.asarray(logit_mask)
            if m.shape != (self._vocab,):
                raise ValueError(
                    f"logit_mask must have shape ({self._vocab},), got "
                    f"{m.shape}")
            logit_mask = (m > 0).astype(np.float32)
            if not logit_mask.any():
                raise ValueError("logit_mask allows no tokens")
        if max_time_s is not None and float(max_time_s) <= 0:
            raise ValueError("max_time_s must be positive")
        if ids.shape[0] + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({ids.shape[0]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len={self.max_len}")
        cap = (self.cache.pool.n_blocks - 1) * self.block_size
        if ids.shape[0] + int(max_new_tokens) + 1 > cap:
            raise ValueError(
                f"prompt ({ids.shape[0]}) + max_new_tokens "
                f"({max_new_tokens}) can never fit the KV pool "
                f"({cap} token lines) — raise n_blocks")
        rid = self._next_id
        self._next_id += 1
        h = RequestHandle(
            self, rid, ids, max_new_tokens, temperature,
            self.base_seed + rid if seed is None else seed, on_token,
            max_time_s=max_time_s, priority=priority,
            logit_mask=logit_mask)
        self.metrics.requests_submitted += 1
        if _tracing._ENABLED:
            _tracing.instant("serving.submit", cat="serving",
                             trace_id=h.trace_id, request_id=rid,
                             n_prompt=h.n_prompt, priority=h.priority)
        try:
            self.scheduler.enqueue(h, retry_after_s=self._retry_after_hint())
        except EngineOverloaded:
            self.metrics.requests_rejected += 1
            raise
        self._admit()
        self._submitted(h, begin, before)
        return h

    def _submitted(self, h, begin, before):
        """Close the stamps of a ``submit()`` / ``adopt()`` call that
        began at ``begin`` with its thread's counters at ``before``."""
        end = time.perf_counter()
        self.metrics.mark_submit(SubmitRecord(
            h.request_id, begin, end, thread_delta(before)))
        if _tracing._ENABLED:
            _tracing.span_event("serving.submit_call", begin, end,
                                cat="serving", trace_id=h.trace_id,
                                request_id=h.request_id)

    def _retry_after_hint(self):
        """Seconds until a slot plausibly frees: the rolling inter-token
        latency p95 (histogram-backed — the same tail estimate brownout
        sheds on, deliberately conservative) times the shortest
        remaining active request. A cold engine (no decode history yet)
        or an idle one (no active requests — the queue is blocked on
        the token watermark, not on slots) has no basis for an estimate
        and returns the documented conservative
        ``default_retry_after_s``, so clients ALWAYS get a finite
        back-off."""
        itl = self.metrics.itl_p95()
        remaining = [h.max_new_tokens - len(h.tokens)
                     for h in self._by_slot if h is not None]
        if itl is None or not remaining:
            return self.default_retry_after_s
        return round(itl * max(1, min(remaining)), 3)

    def _admit(self):
        # a request that finishes during its own prefill (eos first token,
        # or max_new_tokens=1) frees its slot immediately — loop so the
        # queue keeps draining into freshly freed slots
        while True:
            popped = self.scheduler.pop_admissible(
                self.cache.n_free, free_tokens=self.cache.free_tokens())
            if not popped:
                return
            for h in popped:
                if not self._admit_one(h):
                    # the pool could not cover it even after radix
                    # eviction (free_tokens was an optimistic estimate):
                    # back to the queue head, nothing overtakes it
                    self.scheduler.release(h)
                    self.scheduler.requeue(h)
                    return

    @staticmethod
    def _full_ids(h):
        """prompt + already-emitted tokens (the replay/adopt sequence)."""
        if not h.tokens:
            return h.prompt_ids
        return np.concatenate(
            [h.prompt_ids, np.asarray(h.tokens, np.int32)])

    def _admit_one(self, h):
        """Admit ``h`` into a free slot, False where the pool cannot cover
        it even after radix eviction; either way leave its
        ``AdmitRecord``."""
        # supervisor replay (adopt()) re-prefills prompt + the k tokens
        # the crashed incarnation already emitted and fast-forwards the
        # PRNG chain k splits — the next sampled token is exactly what
        # the uninterrupted run would have produced. Normal admission is
        # the k=0 degenerate case (same program). Preemption on pool
        # exhaustion re-enters through the same path.
        begin = time.perf_counter()
        evicted = self.cache.radix.evicted
        full = self._full_ids(h)
        slot = self.cache.alloc(h.request_id)
        # wire block-table coverage for [0, n_eff] (prompt + replay
        # tokens + the first decode write line); the radix index shares
        # any cached full-block prefix (memory dedup + skipped chunk
        # compute), copy-on-write on a partial tail block
        match_ids = full if self.prefix_sharing else full[:0]
        admitted = self.cache.admit(slot, match_ids, len(full) + 1)
        placed = time.perf_counter()
        if admitted is None:
            self.cache.free(slot)
            h.slot = None
        else:
            self._place(h, slot, full, *admitted)
        self.metrics.mark_admit(AdmitRecord(
            h.request_id, self._step, begin, placed, time.perf_counter(),
            0 if admitted is None else min(admitted[0], len(full)),
            self.cache.radix.evicted - evicted))
        return admitted is not None

    def _place(self, h, slot, full, n_shared, cow):
        """Start ``h``, admitted into ``slot`` with ``n_shared`` tokens of
        ``full`` from the radix index: queue its chunked prefill, or
        launch its bucket prefill and emit its first token."""
        k = len(h.tokens)
        n_eff = len(full)
        h.slot = slot
        self._by_slot[slot] = h
        self._temps[slot] = h.temperature
        self._mask_row(slot, h.logit_mask)
        self.metrics.prompt_tokens += n_eff
        self.metrics.prefix_hit_tokens += min(n_shared, n_eff)
        if cow:
            self.metrics.cow_copies += 1
        if self.prefill_chunk is not None and n_eff > self.prefill_chunk:
            # long prompt: prefill in block-aligned chunks co-scheduled
            # with decode (one chunk per step) — the slot is occupied
            # but joins the fused decode only after its final chunk.
            # Fully-shared leading chunks are skipped outright (the
            # radix already holds their KV): start at the chunk holding
            # the first non-shared position, clamped so the chunk with
            # the last prompt token (the sampling row) always runs.
            C = self.prefill_chunk
            start = (min(n_shared, n_eff - 1) // C) * C
            if _tracing._ENABLED:
                _tracing.span_event("serving.queue", h._queued_t,
                                    time.perf_counter(), cat="serving",
                                    trace_id=h.trace_id,
                                    request_id=h.request_id)
            self._chunking.append(
                _ChunkState(h, full, n_eff, n_shared, start))
            self.metrics.chunked_prefills += 1
            return
        self.metrics.mark_scan(n_eff, first=True, replay=k > 0)
        Lb = self._bucket(n_eff)
        self.buckets_seen.add(Lb)
        ids = np.zeros((1, Lb), np.int32)
        ids[0, :n_eff] = full
        called = time.perf_counter()
        if _tracing._ENABLED:
            _tracing.span_event("serving.queue", h._queued_t, called,
                                cat="serving", trace_id=h.trace_id,
                                request_id=h.request_id)
        with _compile_scope(f"prefill:L{Lb}"):
            out = self._run_program(
                "prefill", ("prefill", Lb), self._prefill,
                (self._w, self.cache.kc, self.cache.vc, self._tok,
                 self._cur, self._keys, ids, np.int32(n_eff),
                 np.int32(slot), np.uint32(h.seed), np.int32(k),
                 np.float32(h.temperature),
                 self.cache.block_tables[slot].copy(),
                 np.int32(n_shared), self._vmask[slot].copy())
                + self._moe_in(),
                self._paged_statics, f"prefill:L{Lb}")
        (self.cache.kc, self.cache.vc, self._tok, self._cur,
         self._keys, tok0) = self._moe_out(out)
        dispatched = time.perf_counter()
        tok0 = int(tok0)
        self._launched(f"prefill:L{Lb}", called, dispatched,
                       time.perf_counter(), "serving.prefill", h,
                       tokens=n_eff, radix_tokens=min(n_shared, n_eff),
                       bucket=Lb, replay_k=k, n_shared=n_shared)
        self.metrics.prefills += 1
        self.cache.cur_pos[slot] = n_eff
        if self.prefix_sharing:
            self.cache.commit_prefix(slot, full)
        self._emit(h, tok0)
        if self._spec is not None and not h.finished:
            self._spec.on_admit(h, full)

    def _chunk_tick(self):
        """Advance the oldest in-progress chunked prefill by ONE chunk
        (then the fused decode step runs for everyone else — long
        prompts never block active decodes for more than a chunk)."""
        cs = self._chunking[0]
        h = cs.h
        C = self.prefill_chunk
        start = cs.next
        end = min(start + C, cs.n_eff)
        ids = np.zeros((1, C), np.int32)
        ids[0, :end - start] = cs.ids[start:end]
        is_final = end >= cs.n_eff
        called = time.perf_counter()
        with _compile_scope("chunk"):
            out = self._run_program(
                "chunk", ("chunk",), self._chunk,
                (self._w, self.cache.kc, self.cache.vc, self._tok,
                 self._cur, self._keys, ids, np.int32(start),
                 np.int32(cs.n_eff), np.int32(h.slot),
                 self.cache.block_tables[h.slot].copy(),
                 np.int32(cs.n_shared), np.int32(1 if is_final else 0),
                 np.uint32(h.seed), np.int32(cs.skip),
                 np.float32(h.temperature),
                 self._vmask[h.slot].copy()) + self._moe_in(),
                self._paged_statics, "chunk")
        (self.cache.kc, self.cache.vc, self._tok, self._cur,
         self._keys, tok0) = self._moe_out(out)
        dispatched = time.perf_counter()
        fetched = None
        if is_final:
            # only the final chunk's token is sampled state: the others'
            # results stay on the device, unfetched
            tok0 = int(tok0)
            fetched = time.perf_counter()
        self._launched(
            "chunk", called, dispatched, fetched,
            "serving.prefill_chunk", h, tokens=end - start,
            radix_tokens=max(0, min(cs.n_shared, end) - start),
            start=start, final=is_final)
        self.chunk_used = True
        self.metrics.mark_chunk(end)
        self.metrics.mark_scan(end - start, first=start == 0,
                               replay=cs.skip > 0)
        cs.next = end
        if is_final:
            self._chunking.pop(0)
            self.metrics.prefills += 1
            self.cache.cur_pos[h.slot] = cs.n_eff
            if self.prefix_sharing:
                self.cache.commit_prefix(h.slot, cs.ids)
            self._emit(h, tok0)
            if self._spec is not None and not h.finished:
                self._spec.on_admit(h, cs.ids)

    # -- paged pool pressure ----------------------------------------------

    def _decode_active(self):
        """Decode-step row mask: occupied slots minus those still mid-
        chunked-prefill (they hold their slot but have no sampled state
        yet)."""
        if not self._chunking:
            return self.cache.active
        m = self.cache.active.copy()
        for cs in self._chunking:
            m[cs.h.slot] = False
        return m

    def _ensure_decode_capacity(self, active_mask):
        """Every decode-active slot needs a writable block for its next
        line. On pool exhaustion (after radix eviction) the least
        important active request is PREEMPTED — its blocks free, it
        re-queues, and later re-admission replays prompt + emitted
        tokens with the PRNG-chain fast-forward, so its final output is
        token-identical (same machinery as supervisor adopt())."""
        for slot in np.nonzero(active_mask)[0]:
            slot = int(slot)
            h = self._by_slot[slot]
            if h is None:
                continue
            while not self.cache.ensure(slot, int(self.cache.cur_pos[slot])):
                victim = self._pick_preempt_victim(exclude=h)
                if victim is None:
                    raise RuntimeError(
                        "KV pool exhausted with a single active request "
                        "— unreachable given the submit() capacity check")
                self._preempt(victim)
                if h.slot is None:
                    break      # the needing slot itself got preempted

    def _pick_preempt_victim(self, exclude):
        cand = [x for x in self._by_slot
                if x is not None and x is not exclude]
        if not cand:
            return None
        # least important class first, newest arrival within it —
        # mirrors brownout shedding order
        return max(cand, key=lambda x: (x.priority, x.request_id))

    def _preempt(self, h):
        slot = h.slot
        self._by_slot[slot] = None
        self.cache.free(slot)
        h.slot = None
        h._queued_t = time.perf_counter()
        self._chunking = [cs for cs in self._chunking if cs.h is not h]
        self.scheduler.release(h)
        self.scheduler.requeue(h)
        self.metrics.preemptions += 1
        _tracing.instant("serving.preempt", cat="serving",
                         trace_id=h.trace_id, request_id=h.request_id,
                         tokens=len(h.tokens))

    def adopt(self, handle):
        """Re-inject a handle from a previous engine incarnation
        (EngineSupervisor rebuild-and-replay): the handle keeps its
        identity, seed, priority and emitted tokens; admission
        re-prefills ``prompt + tokens`` and resumes the PRNG chain at
        the right split index, so decoding continues token-identically
        to the uninterrupted run.

        Raises :class:`AdoptMismatch` when the handle's origin engine
        served a different model/config/sampling fingerprint — replaying
        its history here would silently diverge. tp degree and KV
        geometry are NOT part of the fingerprint (tp=2 -> tp=1 adoption
        is token-identical: the replay runs from tokens, not KV
        bytes)."""
        fp = getattr(handle, "model_fingerprint", None)
        if fp is not None and fp != self.model_fingerprint:
            raise AdoptMismatch(
                f"request {handle.request_id} originates from an engine "
                f"with model fingerprint {fp} but this engine serves "
                f"{self.model_fingerprint}: adopting would replay its "
                "token history through different math and silently "
                "diverge — migrate only between replicas of the SAME "
                "model/config/sampling configuration")
        handle.slot = None
        handle._engine = self
        handle.replica_id = self.replica_id
        handle.model_fingerprint = self.model_fingerprint
        begin = handle._queued_t = time.perf_counter()
        before = thread_counters()
        self._next_id = max(self._next_id, handle.request_id + 1)
        self.metrics.requests_submitted += 1
        if _tracing._ENABLED:
            _tracing.instant("serving.adopt", cat="serving",
                             trace_id=handle.trace_id,
                             request_id=handle.request_id,
                             replayed_tokens=len(handle.tokens))
        self.scheduler.enqueue(handle,
                               retry_after_s=self._retry_after_hint())
        self._admit()
        self._submitted(handle, begin, before)
        return handle

    def cancel(self, handle):
        """Client abandoned the stream mid-request: a queued handle
        drops out of the scheduler, an active one frees its KV slot at
        once (co-batched neighbours untouched — per-request PRNG chains
        keep their output unchanged). ``result()`` raises
        :class:`RequestCancelled`. Returns False if already finished."""
        if handle.finished:
            return False
        if handle.slot is None:
            self.scheduler.remove(handle)
        self._finish(handle, "cancelled")
        return True

    def shed_queued(self, protect_priority=0, retry_after_s=None):
        """Brownout degradation: evict the single lowest-priority class
        of queued requests (classes <= ``protect_priority`` are never
        shed). Evicted handles finish with reason ``"shed"`` and their
        ``result()`` raises :class:`RequestShed` carrying a finite
        ``retry_after_s``. Returns the evicted handles."""
        if retry_after_s is None:
            retry_after_s = self._retry_after_hint()
        out = self.scheduler.shed_lowest(protect_priority)
        for h in out:
            h.retry_after_s = retry_after_s
            self._finish(h, "shed")
        return out

    # -- the decode loop --------------------------------------------------

    def _expire(self):
        """Enforce per-request deadlines: expired queued requests drop
        before ever taking a slot; expired active ones free their slot
        and resolve with a timeout."""
        now = time.monotonic()
        for h in self.scheduler.drop_expired(now):
            self._finish(h, "timeout")
        for h in list(self._by_slot):
            if h is not None and h.deadline is not None \
                    and now > h.deadline:
                self._finish(h, "timeout")

    def step(self):
        """One engine iteration: expire overdue requests, admit waiting
        ones into free slots, advance ONE chunk of any in-progress
        chunked prefill, then advance every decode-active slot one token
        with the fused decode step (gathering K/V through block
        tables; preempting on pool exhaustion first). Returns the number
        of requests that were decoding this step."""
        if self._condemned:
            return 0     # a supervisor replaced this engine incarnation
        self._step = self._steps
        self._steps += 1
        try:
            return self._step_stamped()
        finally:
            self._step = None
            if self._held_spans:      # the step raised, or decoded nothing
                self._launch_spans(self._held_spans)
                self._held_spans = []

    def _step_stamped(self):
        """``step()``'s body. The clock is read at each boundary once;
        the reads become the step's ``StepRecord`` and, with the tracer
        on, its spans."""
        begin = time.perf_counter()
        before = thread_counters()
        launches = self.metrics.launches_recorded
        self._expire()
        self._admit()
        admitted = chunked = time.perf_counter()
        if self._chunking:
            self._chunk_tick()
            chunked = time.perf_counter()
        active = self._decode_active()
        self._ensure_decode_capacity(active)
        active = self._decode_active()     # preemption may shrink it
        n_active = int(active.sum())
        self.metrics.sample(self.cache.occupancy,
                            self.scheduler.queue_depth,
                            active=self.cache.n_active,
                            pool_free=self.cache.pool.n_free,
                            pool_total=self.cache.pool.n_blocks - 1)
        if not n_active:
            return 0
        kind = ("decode" if self.metrics.launches_recorded == launches
                else "admit")
        scheduled = time.perf_counter()
        if self._spec is not None:
            kind = "spec"
            dispatched, fetched = self._spec_step(active, n_active)
        else:
            dispatched, fetched = self._decode_once(active, n_active)
        end = time.perf_counter()
        rec = StepRecord(self._step, kind, begin, scheduled,
                         dispatched or end, fetched or end, end, n_active,
                         admitted, chunked, thread_delta(before))
        self.metrics.mark_step(rec)
        if _tracing._ENABLED:
            sid = _tracing.span_event("serving.step", begin, end,
                                      cat="serving", step=rec.index,
                                      kind=kind, n_active=n_active)
            phases = [_tracing.span_event(f"serving.{phase}", t0, t1,
                                          cat="serving", parent=sid)
                      for phase, t0, t1 in rec.intervals()]
            self._launch_spans(self._held_spans, sid, phases[0], scheduled)
            self._held_spans = []
        return n_active

    def _decode_once(self, active, n_active):
        """One fused decode-step invocation over ``active`` rows: every
        active slot advances exactly one token. Returns when its call
        had returned and when its tokens were on the host."""
        called = time.perf_counter()
        self.metrics.mark_lines_seen(self.cache.cur_pos[active] + 1,
                                     self._hp.get("window"))
        if self._vmask_dev is None:
            self._vmask_dev = jax.device_put(
                self._vmask, None if self._mesh is None else
                jax.sharding.NamedSharding(self._mesh,
                                           jax.sharding.PartitionSpec()))
        with _compile_scope("decode"):
            out = self._run_program(
                "decode", ("decode",), self._decode,
                (self._w, self.cache.kc, self.cache.vc,
                 self.cache.block_tables.copy(), self._tok,
                 self._cur, active, self._keys, self._temps,
                 self._vmask_dev) + self._moe_in(),
                self._paged_statics, "decode")
        nxt, self.cache.kc, self.cache.vc, self._cur, self._keys = \
            self._moe_out(out)
        self._tok = nxt
        dispatched = time.perf_counter()
        toks = np.asarray(nxt)
        fetched = time.perf_counter()
        self.metrics.mark_decode(fetched - called)
        self._launched("decode", called, dispatched, fetched,
                       tokens=n_active)
        for slot in np.nonzero(active)[0]:
            h = self._by_slot[int(slot)]
            self._emit(h, int(toks[slot]))
        return dispatched, fetched

    # -- speculative decoding (draft-verify; serving/speculative.py) ------

    @staticmethod
    def _host(a):
        """Writable host copy of a (possibly device) state vector."""
        a = np.asarray(a)
        return a if a.flags.writeable else a.copy()

    def _ensure_spec_capacity(self, h, k_eff):
        """Reserve writable blocks for the verify chunk's k_eff+1
        candidate lines (positions cur..cur+k_eff), preempting like the
        decode path on pool exhaustion. False when ``h`` itself got
        preempted along the way (the caller skips its verify)."""
        base = int(self.cache.cur_pos[h.slot])
        for pos in range(base, base + k_eff + 1):
            while not self.cache.ensure(h.slot, pos):
                victim = self._pick_preempt_victim(exclude=h)
                if victim is None:
                    return False     # lone request: clamp handled upstream
                self._preempt(victim)
                if h.slot is None:
                    return False
        return True

    def _spec_step(self, active, n_active):
        """One speculative engine iteration: propose k tokens per
        eligible slot (host n-gram lookahead or the fused draft-model
        decode), verify each slot's chunk in ONE chunk-shaped program
        invocation, and emit the accepted prefix + one chain-sampled
        token — between 1 and k+1 tokens per slot per step, always
        byte-equal to what the non-speculative engine would emit.
        Slots with no proposal (no n-gram match, draft width clamped to
        zero near max_new/max_len) take the plain fused decode step, so
        the decode program stays live in mixed traffic."""
        k = self.spec.k
        cand, plain = [], np.zeros(self.n_slots, bool)
        for slot in np.nonzero(active)[0]:
            slot = int(slot)
            h = self._by_slot[slot]
            if h is None:
                continue
            remaining = h.max_new_tokens - len(h.tokens)
            p = int(self.cache.cur_pos[slot])
            k_cap = min(k, remaining - 1, self.max_len - 1 - p)
            if k_cap >= 1:
                cand.append((h, k_cap))
            else:
                plain[slot] = True
        proposals = self._spec.propose_all(cand) if cand else {}
        plan = []
        for h, k_cap in cand:
            props = proposals.get(h.slot)
            if props is None or len(props) == 0:
                plain[h.slot] = True
            else:
                plan.append((h, np.asarray(props[:k_cap], np.int32)))
        # the step's stamps: the first target launch's dispatched and
        # the last one's fetched (None where nothing was launched)
        dispatched = fetched = None
        if plain.any():
            dispatched, fetched = self._decode_once(plain, int(plain.sum()))
        for h, props in plan:
            if h.finished or h.slot is None:
                continue        # finished/preempted earlier this step
            if not self._ensure_spec_capacity(h, len(props)):
                continue        # preempted while reserving draft lines
            d, fetched = self._verify_one(h, props)
            dispatched = dispatched or d
        return dispatched, fetched

    def _verify_one(self, h, props):
        """Verify one slot's draft chunk and emit its accepted tokens
        (token-identical acceptance — see ``_spec_verify_impl``)."""
        slot, k_eff = h.slot, len(props)
        p = int(self.cache.cur_pos[slot])
        K1 = self.spec.k + 1
        ids = np.zeros((1, K1), np.int32)
        ids[0, 0] = h.tokens[-1]
        ids[0, 1:1 + k_eff] = props
        called = time.perf_counter()
        with _compile_scope("verify"):
            out = self._run_program(
                "verify", ("verify", K1), self._verify,
                (self._w, self.cache.kc, self.cache.vc, self._keys, ids,
                 np.int32(p), np.int32(slot),
                 self.cache.block_tables[slot].copy(),
                 np.int32(k_eff + 1), np.float32(h.temperature),
                 self._vmask[slot].copy()),
                self._paged_statics, "spec.verify")
        self.cache.kc, self.cache.vc, samples, chain = out
        self.verify_used = True
        dispatched = time.perf_counter()
        samples = np.asarray(samples)
        chain = np.asarray(chain)
        fetched = time.perf_counter()
        self._launched("spec.verify", called, dispatched, fetched,
                       "spec.verify", h, tokens=k_eff + 1, k=k_eff)
        m = 0
        while m < k_eff and samples[m] == props[m]:
            m += 1
        e = m + 1           # accepted drafts + the corrective/bonus token
        # host-side rewind/advance: the slot continues exactly as if it
        # had taken e fused decode steps — tok/cur/keys jump to the
        # post-acceptance chain state; rejected candidate lines sit past
        # the causal bound and are rewritten before ever being readable
        tok_h = self._host(self._tok)
        cur_h = self._host(self._cur)
        keys_h = self._host(self._keys)
        tok_h[slot] = samples[e - 1]
        cur_h[slot] = p + e
        keys_h[slot] = chain[e - 1]
        self._tok, self._cur, self._keys = tok_h, cur_h, keys_h
        self.metrics.mark_decode(fetched - called, tokens=e)
        self.metrics.spec_steps += 1
        self.metrics.spec_proposed_tokens += k_eff
        self.metrics.spec_accepted_tokens += m
        self.metrics.spec_emitted_tokens += e
        for t in samples[:e]:
            self._emit(h, int(t))
            if h.finished:
                return dispatched, fetched
        self._spec.after_verify(h, int(samples[e - 1]), p + e)
        return dispatched, fetched

    def _emit(self, h, token):
        if self._condemned:
            # an abandoned wedged step thread unblocked after the
            # supervisor rebuilt: the handle now lives on the
            # replacement engine — dropping the stale emission keeps the
            # replayed stream token-identical
            return
        h.tokens.append(token)
        h.metrics.mark_token()
        self.metrics.tokens_generated += 1
        self.cache.cur_pos[h.slot] = h.n_prompt + len(h.tokens) - 1
        if h.on_token is not None:
            h.on_token(h, token)
        if self.eos_token_id is not None and token == self.eos_token_id:
            self._finish(h, "eos")
        elif len(h.tokens) >= h.max_new_tokens:
            self._finish(h, "length")

    def _finish(self, h, reason):
        h.finished = True
        h.finish_reason = reason
        h.metrics.mark_finished()
        if _tracing.enabled():
            m = h.metrics
            if m.first_token_time is not None:
                # the request's whole decode phase as one span (first
                # token out of prefill -> finish)
                _tracing.span_event(
                    "serving.decode", m.first_token_time, m.finish_time,
                    cat="serving", trace_id=h.trace_id,
                    request_id=h.request_id, tokens=len(h.tokens))
            _tracing.instant("serving.finish", cat="serving",
                             trace_id=h.trace_id,
                             request_id=h.request_id, reason=reason,
                             tokens=len(h.tokens))
        if h.slot is not None:         # queued-only timeouts held no slot
            self._by_slot[h.slot] = None
            # every block the slot holds is released here —
            # shared-prefix refcounts drop and private blocks (including
            # the already-written chunks of a cancelled/timed-out
            # mid-prefill request) return to the pool
            self.cache.free(h.slot)
            self.scheduler.release(h)
            if self._chunking:
                self._chunking = [cs for cs in self._chunking
                                  if cs.h is not h]
        if reason == "timeout":
            self.metrics.requests_timed_out += 1
        elif reason == "cancelled":
            self.metrics.requests_cancelled += 1
        elif reason == "shed":
            self.metrics.requests_shed += 1
        else:
            self.metrics.requests_completed += 1

    def drain(self):
        """Pump step() until every submitted request has finished."""
        while self.scheduler.queue_depth or self.cache.n_active:
            self.step()

    def generate_all(self, prompts, **gen_kwargs):
        """Submit a list of prompts, drain, return the handles."""
        handles = [self.submit(p, **gen_kwargs) for p in prompts]
        self.drain()
        return handles

    def stats(self):
        out = {**self.metrics.snapshot(),
               "n_slots": self.n_slots, "max_len": self.max_len,
               "active": self.cache.n_active,
               "queue_depth": self.scheduler.queue_depth,
               "kv_cache_bytes": self.cache.nbytes(),
               "prefill_buckets": sorted(self.buckets_seen),
               "chunk_program": self.chunk_used,
               "compile_budget": self.compile_budget,
               **self.cache.pool_stats(),
               "prefill_chunk": self.prefill_chunk,
               "prefix_sharing": self.prefix_sharing}
        if self.spec is not None:
            ar = self.metrics.acceptance_rate()
            out["speculative"] = {
                "k": self.spec.k, "draft": self.spec.draft_kind(),
                "verify_used": self.verify_used,
                "draft_buckets_seen": sorted(self.draft_buckets_seen),
                "draft_decode_used": self.draft_decode_used,
                "acceptance_rate": (None if ar is None
                                    else round(ar, 4))}
        out["tp"] = self.tp
        if self.tp > 1:
            out["mesh"] = self.tp_geometry()
        return out
