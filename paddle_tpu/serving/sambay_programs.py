"""The serving engine's three programs for a SambaY decoder
(``_make_arch``'s ``arch`` ``sambay``; ``text/models/phi4flash.py``):
paged prefill, chunk and decode, over the bodies of ``text/sambay.py``.

They take what the llama programs of ``engine.py`` take, in the same
order, and one argument more, last in and last out: ``state``, what a slot
keeps beside the paged pool (``PagedKVCache.state``):

- ``wk`` / ``wv`` ``[n_window_layers, 1 + n_slots * window / bs, bs * n,
  w]`` (folded like the pool, ``PagedKVCache(folded=True)``): every window layer's lines, a ring of ``window`` lines a slot
  (``sambay.ring_tables``; block 0 of a layer is its trash);
- ``ssm`` ``[n_mamba, n_slots, d_state, d_inner]`` float32 and ``conv``
  ``[n_mamba, n_slots, d_conv - 1, d_inner]``: every recurrent layer's
  state and the last inputs of its convolution.

The pool ``kc`` / ``vc`` has ONE layer: the full-attention layer's lines,
which the query-only layers read and never write. The gated units' memory
is a value of the running program. A prompt's first rows (a prefill, or
the chunk at position 0) start from a zero state, so a slot's previous
tenant leaves nothing behind; replay after a pre-emption is a prefill or
chunks over ``prompt + tokens`` like any other.

The layer loop is unrolled (five kinds of layer, every weight the
model's own array). Pools that span layers are threaded flat over layers,
indices moved to the layer's range, so a donated pool is written in
place (``engine._scan_layers_over_pool`` says why).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..text import generation as G
from ..text import sambay as S

STATICS = ("arch", "n_heads", "n_kv", "eps", "theta", "do_sample", "top_k",
           "top_p", "block_size", "kinds", "window")


def _logits(hidden, w):
    """The tied head: ``hidden`` against the embedding's rows."""
    return jnp.einsum("...h,vh->...v", hidden, w["embed"])


def _first_token(logits0, seed, skip, temp, vmask, do_sample, top_k, top_p):
    """Sample a prompt's first token with the request's own key chain
    (``skip`` splits already consumed: a replay); ``(token, key)``."""
    key = jax.random.PRNGKey(seed)
    key = jax.lax.fori_loop(0, skip,
                            lambda _, k: jax.random.split(k)[0], key)
    key, sk = jax.random.split(key)
    logits0 = jnp.where(vmask > 0, logits0, -jnp.inf)
    logits_f = G._filter_logits(logits0[None], temp, do_sample, top_k, top_p)
    if do_sample:
        tok0 = jax.random.categorical(sk, logits_f, axis=-1)[0]
    else:
        tok0 = jnp.argmax(logits_f, axis=-1)[0]
    return tok0.astype(jnp.int32), key


def _flat(pool):
    """A pool over layers ``[L, nb, ...]`` as one pool of ``L * nb``."""
    return pool.reshape((pool.shape[0] * pool.shape[1],) + pool.shape[2:])


def prefill_impl(w, kc, vc, tok, cur_pos, keys, ids, n_prompt, slot, seed,
                 skip, temp, table_row, skip_write, vmask, state, *, arch,
                 n_heads, n_kv, eps, theta, do_sample, top_k, top_p,
                 block_size, kinds, window):
    """Prefill one request (``ids`` ``[1, Lb]``, right-padded to its
    bucket) from nothing: the model's own full forward, then what the slot
    keeps of it: the full layer's lines through ``table_row``, each window
    layer's newest ``window`` lines into the slot's ring, each recurrent
    layer's state and convolution inputs after the last token.
    ``skip_write`` is the llama programs' (a shared prefix's end) and is
    not read: the engine refuses prefix sharing for this class."""
    keep = jnp.arange(ids.shape[1]) < n_prompt
    x, ssm, conv, wlines, (k, v) = S.layers_prefill(
        S.stack_of(w), jnp.take(w["embed"], ids, axis=0), keep, n_prompt,
        kinds=kinds, n_heads=n_heads, n_kv=n_kv, eps=eps, window=window)
    hlast = jax.lax.dynamic_index_in_dim(
        G._ln(x, w["normw"], w["normb"], eps)[0], n_prompt - 1, 0,
        keepdims=False)
    kc = S.pool_write(kc[0], table_row, k[0], 0, n_prompt, block_size)[None]
    vc = S.pool_write(vc[0], table_row, v[0], 0, n_prompt, block_size)[None]
    wk, wv = _flat(state["wk"]), _flat(state["wv"])
    nbw = state["wk"].shape[1]
    ring = S.ring_tables(tok.shape[0], window, block_size)[slot]
    for li, (kw, vw) in enumerate(wlines):
        wk = S.ring_write(wk, ring + li * nbw, kw[0], 0, n_prompt,
                          window=window)
        wv = S.ring_write(wv, ring + li * nbw, vw[0], 0, n_prompt,
                          window=window)
    state = {"wk": wk.reshape(state["wk"].shape),
             "wv": wv.reshape(state["wv"].shape),
             "ssm": state["ssm"].at[:, slot].set(ssm),
             "conv": state["conv"].at[:, slot].set(conv)}
    tok0, key = _first_token(_logits(hlast, w), seed, skip, temp, vmask,
                             do_sample, top_k, top_p)
    return (kc, vc, tok.at[slot].set(tok0),
            cur_pos.at[slot].set(n_prompt.astype(jnp.int32)),
            keys.at[slot].set(key), tok0, state)


def chunk_impl(w, kc, vc, tok, cur_pos, keys, ids, chunk_start, n_prompt,
               slot, table_row, skip_write, is_final, seed, skip, temp,
               vmask, state, *, arch, n_heads, n_kv, eps, theta, do_sample,
               top_k, top_p, block_size, kinds, window):
    """One prefill CHUNK of one slot (``ids`` ``[1, C]`` at positions
    ``chunk_start + j``) from what the slot keeps: each recurrent layer's
    scan starts from the slot's state and convolution inputs (zeros at
    position 0) and leaves the new ones; a window layer's rows see the
    ring beside the chunk; the full layer's and the query-only layers'
    rows see the slot's lines in the pool, as far as the chunk reaches. ONE program for every chunk of
    every prompt; ``is_final`` gates the sampled state."""
    C = ids.shape[1]
    gpos = chunk_start + jnp.arange(C)
    keep = gpos < n_prompt
    n_keep = jnp.clip(n_prompt - chunk_start, 0, C)
    x = jnp.take(w["embed"], ids, axis=0)
    stack = S.stack_of(w)
    kc0, vc0 = kc[0], vc[0]
    wk, wv = _flat(state["wk"]), _flat(state["wv"])
    nbw = state["wk"].shape[1]
    ring = S.ring_tables(tok.shape[0], window, block_size)[slot]
    ssm, conv = state["ssm"], state["conv"]
    fresh = chunk_start == 0
    att = dict(n_heads=n_heads, n_kv=n_kv, eps=eps)
    mem = None
    n_m = n_w = 0
    for i, kind in enumerate(kinds):
        lw = S.layer_of(stack, i)
        if kind == "mamba":
            x, s, c, mem = S.mamba_chunk(
                x, lw, jnp.where(fresh, 0.0, ssm[n_m, slot]),
                jnp.where(fresh, 0, conv[n_m, slot]).astype(conv.dtype),
                keep, n_keep, eps=eps)
            ssm, conv = ssm.at[n_m, slot].set(s), conv.at[n_m, slot].set(c)
            n_m += 1
        elif kind == "gmu":
            x = S.gmu(x, lw, mem, eps=eps)
        elif kind == "sliding_attention":
            x, wk, wv = S.window_chunk(
                x, lw, wk, wv, ring + n_w * nbw, gpos, n_keep, layer=i,
                window=window, **att)
            n_w += 1
        else:
            x, kc0, vc0 = S.full_chunk(x, lw, kc0, vc0, table_row, gpos,
                                       n_keep, layer=i,
                                       block_size=block_size, **att)
    state = {"wk": wk.reshape(state["wk"].shape),
             "wv": wv.reshape(state["wv"].shape), "ssm": ssm, "conv": conv}
    li = jnp.clip(n_prompt - 1 - chunk_start, 0, C - 1)
    hlast = jax.lax.dynamic_index_in_dim(
        G._ln(x, w["normw"], w["normb"], eps)[0], li, 0, keepdims=False)
    tok0, key = _first_token(_logits(hlast, w), seed, skip, temp, vmask,
                             do_sample, top_k, top_p)
    fin = is_final.astype(bool)
    tok = jnp.where(fin, tok.at[slot].set(tok0), tok)
    cur_pos = jnp.where(
        fin, cur_pos.at[slot].set(n_prompt.astype(jnp.int32)), cur_pos)
    keys = jnp.where(fin, keys.at[slot].set(key), keys)
    return kc0[None], vc0[None], tok, cur_pos, keys, tok0, state


def decode_impl(w, kc, vc, tables, tok, cur_pos, active, keys, temps,
                vmasks, state, *, arch, n_heads, n_kv, eps, theta,
                do_sample, top_k, top_p, block_size, kinds, window):
    """One fused decode step: every decode-active slot advances a token.
    A recurrent layer steps its state (an inactive slot's stays); a window
    layer writes line ``pos % window`` of the slot's ring and reads the
    ring; the full layer writes the pool through the block table and reads
    it, and each query-only layer reads the same lines again
    (``ops/pallas/paged_attention.py`` on a TPU, every time)."""
    n = tok.shape[0]
    rows = jnp.arange(n)
    bs = block_size
    dest = jnp.where(active,
                     tables[rows, cur_pos // bs] * bs + cur_pos % bs,
                     cur_pos % bs)
    seen = jnp.where(active, cur_pos, -1)
    rtab = S.ring_tables(n, window, bs)
    rpos = cur_pos % window
    rdest = jnp.where(active, rtab[rows, rpos // bs] * bs + rpos % bs,
                      rpos % bs)
    rseen = jnp.where(active, jnp.minimum(cur_pos, window - 1), -1)
    x = jnp.take(w["embed"], tok, axis=0)[:, None]
    stack = S.stack_of(w)
    kc0, vc0 = kc[0], vc[0]
    wk, wv = _flat(state["wk"]), _flat(state["wv"])
    nbw = state["wk"].shape[1]
    ssm, conv = state["ssm"], state["conv"]
    att = dict(n_heads=n_heads, n_kv=n_kv, eps=eps, block_size=bs)
    mem = None
    n_m = n_w = 0
    for i, kind in enumerate(kinds):
        lw = S.layer_of(stack, i)
        if kind == "mamba":
            x, s, c, mem = S.mamba_decode(x, lw, ssm[n_m], conv[n_m],
                                          active, eps=eps)
            ssm, conv = ssm.at[n_m].set(s), conv.at[n_m].set(c)
            n_m += 1
        elif kind == "gmu":
            x = S.gmu(x, lw, mem, eps=eps)
        elif kind == "sliding_attention":
            x, wk, wv = S.attention_decode(
                x, lw, wk, wv, rtab + n_w * nbw, rdest + n_w * (nbw * bs),
                rseen, layer=i, **att)
            n_w += 1
        else:
            x, kc0, vc0 = S.attention_decode(x, lw, kc0, vc0, tables, dest,
                                             seen, layer=i, **att)
    state = {"wk": wk.reshape(state["wk"].shape),
             "wv": wv.reshape(state["wv"].shape), "ssm": ssm, "conv": conv}
    logits = _logits(G._ln(x[:, 0], w["normw"], w["normb"], eps), w)
    logits = jnp.where(vmasks > 0, logits, -jnp.inf)
    split = jax.vmap(jax.random.split)(keys)
    new_keys, sks = split[:, 0], split[:, 1]
    logits_f = G._filter_logits(logits, temps, do_sample, top_k, top_p)
    if do_sample:
        nxt = jax.vmap(jax.random.categorical)(sks, logits_f)
    else:
        nxt = jnp.argmax(logits_f, axis=-1)
    nxt = jnp.where(active, nxt.astype(jnp.int32), tok)
    return (nxt, kc0[None], vc0[None],
            jnp.where(active, cur_pos + 1, cur_pos),
            jnp.where(active[:, None], new_keys, keys), state)


# the state's place among the arguments: donated with the pool
PREFILL = jax.jit(prefill_impl, static_argnames=STATICS)
PREFILL_DONATED = jax.jit(prefill_impl, static_argnames=STATICS,
                          donate_argnums=(1, 2, 15))
DECODE = jax.jit(decode_impl, static_argnames=STATICS)
DECODE_DONATED = jax.jit(decode_impl, static_argnames=STATICS,
                         donate_argnums=(1, 2, 10))
CHUNK = jax.jit(chunk_impl, static_argnames=STATICS)
CHUNK_DONATED = jax.jit(chunk_impl, static_argnames=STATICS,
                        donate_argnums=(1, 2, 17))
