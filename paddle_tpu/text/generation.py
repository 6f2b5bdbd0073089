"""Autoregressive generation with a static KV cache.

Reference pairing: PaddleNLP's GenerationMixin (model.generate: greedy /
sampling with top-k/top-p, eos early-exit) driving the reference's
incremental decode. TPU-native design: ONE jitted program — prefill runs
the model's normal forward over the prompt, then `lax.scan` decodes
max_new_tokens steps against a PREALLOCATED [layers, B, total_len, kv, hd]
cache (static shapes: no per-step recompilation, no concat growth), with
sampling and eos masking inside the scan.

The per-layer prefill/decode bodies (`_llama_prefill_layer`,
`_llama_decode_layer`, `_gpt_prefill_layer`, `_gpt_decode_layer`) are
module-level and parameterized on per-row cache/rotary positions: batch
``generate()``, beam search AND ``paddle_tpu.serving.Engine`` all trace
the same python, so there is exactly one lowering of the decode math to
keep conformant.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..tensor import Tensor
from .models.llama import _rope


def _stacked_weights(model):
    """Stack per-layer decoder weights of a LlamaForCausalLM into
    [L, ...] arrays (host-side, once per generate call)."""
    layers = model.llama.layers
    def st(get):
        return jnp.stack([get(l) for l in layers])
    w = {
        "wq": st(lambda l: l.self_attn.q_proj.weight._data),
        "wk": st(lambda l: l.self_attn.k_proj.weight._data),
        "wv": st(lambda l: l.self_attn.v_proj.weight._data),
        "wo": st(lambda l: l.self_attn.o_proj.weight._data),
        "wg": st(lambda l: l.mlp.gate_proj.weight._data),
        "wu": st(lambda l: l.mlp.up_proj.weight._data),
        "wd": st(lambda l: l.mlp.down_proj.weight._data),
        "ln1": st(lambda l: l.input_layernorm.weight._data),
        "ln2": st(lambda l: l.post_attention_layernorm.weight._data),
    }
    w["embed"] = model.llama.embed_tokens.weight._data
    w["norm"] = model.llama.norm.weight._data
    w["head"] = (model.llama.embed_tokens.weight._data.T if model.tie
                 else model.lm_head.weight._data)
    return w


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope_rows(q, k, pos, theta, dtype):
    """Rotary embedding for one-token-per-row decode: q, k [B, 1, H, D],
    pos [B] (each row may sit at a different position)."""
    d = q.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos[:, None].astype(jnp.float32) * inv_freq[None, :]  # [B, D/2]
    cos = jnp.cos(freqs)[:, None, None, :]
    sin = jnp.sin(freqs)[:, None, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(dtype)

    return rot(q), rot(k)


def _rotate(q, k, pos, lw, theta, dt, per_row=False):
    """Rotary embedding of q and k. ``pos`` is ``[L]`` for ``[B, L, H, D]``
    rows of one sequence, or with ``per_row`` ``[B]`` for one token a row.
    A layer that carries its own table (``lw["rope_inv"]`` ``[D/2]``, the
    angle a position, and ``lw["rope_scale"]``, the factor on cos and sin:
    a model whose layer kinds differ in their tables) is rotated by it;
    any other by ``theta``'s plain table."""
    if "rope_inv" not in lw:
        return (_rope_rows if per_row else _rope)(q, k, pos, theta, dt)
    freqs = pos[:, None].astype(jnp.float32) * lw["rope_inv"][None, :]
    cos = jnp.cos(freqs) * lw["rope_scale"]
    sin = jnp.sin(freqs) * lw["rope_scale"]
    if per_row:
        cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    else:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(dt)

    return rot(q), rot(k)


#: Keys beyond which :func:`_attend` walks the view in tiles of this many
#: with an online softmax. One pass over float32 scores ``[4, 8, 512,
#: 8192]`` (a 512 chunk against an 8192-line view) compiles to a softmax
#: fusion the chip's compiler has no schedule for: 46.9 ms for 537 MB (my
#: chip run, PR 27; its cost model gives no estimate either), where the
#: same fusion over 1552 keys takes 0.155 ms and up to 4096 keys is
#: estimated in proportion. Views up to 4096 keys stay on the one pass.
_ATTEND_TILE = 2048


def _score_scale(hd, scale):
    """What float32 scores are scaled by, as a function of them: over
    ``sqrt(hd)`` where a model states no ``scale`` (the plain attention's),
    else times it."""
    if scale is None:
        by = jnp.sqrt(jnp.float32(hd))
        return lambda s: s / by
    by = jnp.float32(scale)
    return lambda s: s * by


def _attend(qh, kh, vh, allowed, dt, scale=None):
    """Masked softmax attention of ``qh`` ``[B, H, Q, hd]`` over ``kh``
    ``[B, n_kv, T, hd]`` and ``vh`` ``[B, n_kv, T, vd]`` under ``allowed``
    (bool, broadcast to ``[B, H, Q, T]``) -> ``[B, H, Q, vd]``. Each KV
    head serves ``H / n_kv`` query heads, and the group contracts against
    its one head: no copy of K or V per query head is built. The scores
    are divided by ``sqrt(hd)``, or multiplied by ``scale`` where a model
    states its own. A view of more than two ``_ATTEND_TILE`` keys is
    walked tile by tile (:func:`_attend_tiled`)."""
    B, H, Q, hd = qh.shape
    n_kv, T = kh.shape[1], kh.shape[2]
    if T > 2 * _ATTEND_TILE:
        return _attend_tiled(qh, kh, vh, allowed, dt, scale)
    scaled = _score_scale(hd, scale)
    if n_kv == H:
        s = scaled(jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                              preferred_element_type=jnp.float32))
        p = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1).astype(dt)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    g = H // n_kv
    s = scaled(jnp.einsum(
        "bngqd,bnkd->bngqk", qh.reshape(B, n_kv, g, Q, hd), kh,
        preferred_element_type=jnp.float32).reshape(B, H, Q, -1))
    p = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1).astype(dt)
    return jnp.einsum("bngqk,bnkd->bngqd", p.reshape(B, n_kv, g, Q, -1),
                      vh).reshape(B, H, Q, vh.shape[-1])


def _attend_tiled(qh, kh, vh, allowed, dt, scale=None):
    """:func:`_attend` over ``T / _ATTEND_TILE`` tiles of keys, one after
    the other, carrying each row's running maximum, its sum of exponentials
    and its weighted values in float32 (the online softmax): the same sum,
    with scores never wider than a tile."""
    B, H, Q, hd = qh.shape
    n_kv, T, vd = kh.shape[1], kh.shape[2], vh.shape[-1]
    ok = jnp.broadcast_to(allowed, allowed.shape[:-2] + (Q, T))
    pad = -T % _ATTEND_TILE
    if pad:                  # whole tiles: keys that no row may see
        kh, vh = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                  for a in (kh, vh))
        ok = jnp.pad(ok, [(0, 0)] * (ok.ndim - 1) + [(0, pad)])
        T += pad
    g, nt = H // n_kv, T // _ATTEND_TILE
    scaled = _score_scale(hd, scale)
    qg = qh.reshape(B, n_kv, g, Q, hd)
    ok = jnp.moveaxis(ok.reshape(ok.shape[:-1] + (nt, _ATTEND_TILE)), -2, 0)
    if ok.ndim == 5:                      # [nt, B|1, H|1, Q, tile]
        ok = ok[:, :, :, None]            # over the group, beside n_kv

    def tiles(a):                         # [B, n, T, hd] -> [nt, B, n, t, hd]
        return jnp.moveaxis(
            a.reshape(B, n_kv, nt, _ATTEND_TILE, a.shape[-1]), 2, 0)

    def one(carry, tile):
        top, total, acc = carry
        k_t, v_t, ok_t = tile
        s = scaled(jnp.einsum("bngqd,bnkd->bngqk", qg, k_t,
                              preferred_element_type=jnp.float32))
        s = jnp.where(ok_t, s, -1e30)
        top2 = jnp.maximum(top, jnp.max(s, axis=-1))
        # a key that may not be seen weighs nothing, also in a tile that
        # holds no other (the one pass gives such a row equal weights)
        p = jnp.where(ok_t, jnp.exp(s - top2[..., None]), 0.0)
        shrink = jnp.exp(top - top2)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bngqk,bnkd->bngqd", p.astype(dt), v_t,
            preferred_element_type=jnp.float32)
        return (top2, total * shrink + jnp.sum(p, axis=-1), acc), None

    start = (jnp.full((B, n_kv, g, Q), -1e30, jnp.float32),
             jnp.zeros((B, n_kv, g, Q), jnp.float32),
             jnp.zeros((B, n_kv, g, Q, vd), jnp.float32))
    (_, total, acc), _ = jax.lax.scan(one, start,
                                      (tiles(kh), tiles(vh), ok))
    return (acc / jnp.maximum(total, 1e-30)[..., None]).astype(dt).reshape(
        B, H, Q, vd)


def _attend_rows(q, kview, vview, valid, dt, scale=None):
    """One query token a row: ``q`` ``[S, H, hd]`` over the row's view
    ``kview`` ``[S, T, n_kv, hd]``, ``vview`` ``[S, T, n_kv, vd]`` under
    ``valid`` ``[S, T]`` -> ``[S, H, vd]``; grouped and scaled as
    :func:`_attend`."""
    S, H, hd = q.shape
    n_kv = kview.shape[2]
    scaled = _score_scale(hd, scale)
    if n_kv == H:
        s = scaled(jnp.einsum("bhd,bthd->bht", q, kview,
                              preferred_element_type=jnp.float32))
        s = jnp.where(valid[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("bht,bthd->bhd", p, vview)
    g = H // n_kv
    s = scaled(jnp.einsum(
        "bngd,btnd->bngt", q.reshape(S, n_kv, g, hd), kview,
        preferred_element_type=jnp.float32).reshape(S, H, -1))
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bngt,btnd->bngd", p.reshape(S, n_kv, g, -1),
                      vview).reshape(S, H, vview.shape[-1])


def _window_blocks(window, rows, block_size):
    """How many blocks can hold a key that ``rows`` consecutive query
    positions may see through a window of ``window`` (each itself and the
    ``window - 1`` before it): the span of ``window - 1 + rows``
    positions, wherever it starts in its first block."""
    return (window + rows - 3) // block_size + 2


def _window_tables(tables, first_pos, rows, window, block_size):
    """Block tables ``[S, mb]`` cut to the blocks that can hold a key
    which ``rows`` consecutive query positions from ``first_pos`` ``[S]``
    on may see through ``window``: ``(tables [S, n], first line [S, 1])``,
    the view's first line being the first such block's. Where that is no
    fewer blocks than the whole table: the tables as they are, and 0."""
    n = _window_blocks(window, rows, block_size)
    if n >= tables.shape[1]:
        return tables, 0
    first = jnp.maximum(first_pos - (window - 1), 0) // block_size
    cols = jnp.minimum(first[:, None] + jnp.arange(n)[None, :],
                       tables.shape[1] - 1)
    return jnp.take_along_axis(tables, cols, axis=1), \
        (first * block_size)[:, None]


def _feed_forward(h2, lw, moe_k, valid, router=()):
    """The layer's second half on normed rows ``h2``: the dense SwiGLU of
    ``wg``/``wu``/``wd``, or, where the layer carries a router ``wr``
    and expert banks, the routed one (``nn/routed_ffn.py``: every pick on
    an expert held here computed, none dropped; ``valid`` marks the rows
    that are tokens; ``router``, pairs, are the model's further arguments
    of ``routed_ffn``, and ``lw["rb"]`` its selection bias), beside it
    the shared expert ``sg``/``su``/``sd`` where the layer has one.
    Returns ``(y, picks)``, ``picks`` ``[held]`` rows an expert or None."""
    def swiglu(wg, wu, wd):
        return (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd

    if "wr" not in lw:
        return swiglu(lw["wg"], lw["wu"], lw["wd"]), None
    from ..nn.routed_ffn import routed_ffn
    router = dict(router)
    if "rb" in lw:
        router["bias"] = lw["rb"]
    y, picks = routed_ffn(h2.reshape(-1, h2.shape[-1]), lw["wr"], lw["wg"],
                          lw["wu"], lw["wd"], moe_k, valid, **router)
    y = y.reshape(h2.shape)
    if "sg" in lw:
        y = y + swiglu(lw["sg"], lw["su"], lw["sd"])
    return y, picks


def _nucleus_filter(logits, top_p):
    """Top-p (nucleus) mask: keep exactly the smallest set of tokens
    whose cumulative probability reaches top_p (ties broken by sort
    order; the highest-prob token is always kept, even for top_p=0)."""
    order = jnp.argsort(-logits, axis=-1)          # descending
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = cum_excl < top_p
    keep_sorted = keep_sorted.at[..., 0].set(True)  # argmax survives
    inv = jnp.argsort(order, axis=-1)               # undo the sort
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, -jnp.inf)


def _filter_logits(logits, temperature, do_sample, top_k, top_p):
    """Temperature / top-k / top-p filtering shared by batch generate()
    and the serving engine. logits [B, V]; temperature scalar or
    per-row [B]."""
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    if t.ndim == 1:
        t = t[:, None]
    logits = logits.astype(jnp.float32) / t
    if do_sample and top_k:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if do_sample and top_p is not None and top_p < 1.0:
        logits = _nucleus_filter(logits, top_p)
    return logits


def _prompt_mask(ids, pad_token_id, attention_mask):
    """[B, L0] int32 prefix mask (1 = real token) for right-padded
    prompts. An explicit attention_mask wins; otherwise everything up to
    the last non-pad token is real (a pad_token_id occurring inside the
    prompt is kept as a real token)."""
    if attention_mask is not None:
        am = attention_mask._data if isinstance(attention_mask, Tensor) \
            else jnp.asarray(attention_mask)
        return am.astype(jnp.int32)
    if pad_token_id is None:
        return jnp.ones_like(ids)
    L0 = ids.shape[1]
    nonpad = ids != pad_token_id
    plen = jnp.max(jnp.where(nonpad, jnp.arange(1, L0 + 1)[None, :], 0),
                   axis=1)
    return (jnp.arange(L0)[None, :] < plen[:, None]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# shared per-layer bodies (Llama)
# ---------------------------------------------------------------------------

_LLAMA_STACK_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1", "ln2")
# what a layer may carry besides: a router over its (then batched) expert
# banks, and a rotary table of its own
_LLAMA_KIND_KEYS = ("wr", "rope_inv", "rope_scale")


def _llama_stack(w):
    """The per-layer leaves ``[L, ...]`` of a stacked weight tree."""
    return {k: w[k] for k in _LLAMA_STACK_KEYS + _LLAMA_KIND_KEYS if k in w}


def _llama_prefill_layer(x, lw, pos, *, n_heads, n_kv, eps, theta,
                         window=None, moe_k=0, valid=None):
    """One Llama decoder layer over a full [B, L] prompt (causal).
    Returns (x, (k, v)) with k/v [B, L, n_kv, hd] for the KV cache.

    The head size is the projection's width over the heads (a config may
    state one that is not ``h // n_heads``). ``window``: a row sees itself
    and the ``window - 1`` before it. A layer with a rotary table of its
    own is rotated by it (:func:`_rotate`), one with a router runs the
    routed feed-forward over ``moe_k`` experts a row
    (:func:`_feed_forward`) and returns ``(x, (k, v, picks))``."""
    B, L, h = x.shape
    hd = lw["wq"].shape[-1] // n_heads
    dt = x.dtype
    h1 = _rms(x, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(B, L, n_heads, hd)
    k = (h1 @ lw["wk"]).reshape(B, L, n_kv, hd)
    v = (h1 @ lw["wv"]).reshape(B, L, n_kv, hd)
    q, k = _rotate(q, k, pos, lw, theta, dt)
    cm = jnp.tril(jnp.ones((L, L), bool))
    if window is not None:
        cm = cm & ~jnp.tril(jnp.ones((L, L), bool), -window)
    o = _attend(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), cm, dt)
    o = jnp.swapaxes(o, 1, 2).reshape(B, L, n_heads * hd)
    x = x + o @ lw["wo"]
    y, picks = _feed_forward(_rms(x, lw["ln2"], eps), lw, moe_k, valid)
    return x + y, ((k, v) if picks is None else (k, v, picks))


def _llama_decode_layer(xt, lw, kc_l, vc_l, write_idx, rope_pos, key_mask,
                        *, n_heads, n_kv, eps, theta):
    """One Llama decoder layer advancing every row one token.

    xt [B, 1, h]; kc_l/vc_l [B, T, n_kv, hd]; write_idx [B] — the cache
    line each row's new K/V lands in; rope_pos [B] — each row's rotary
    position (differs from write_idx only for right-padded prompts);
    key_mask [B, T] bool or None — extra attendable-position mask on top
    of the causal ``<= write_idx`` bound (False = never attend; hides
    prompt padding lines).
    """
    B, T = kc_l.shape[0], kc_l.shape[1]
    hd = lw["wq"].shape[-1] // n_heads
    dt = xt.dtype
    h1 = _rms(xt, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(B, 1, n_heads, hd)
    k = (h1 @ lw["wk"]).reshape(B, 1, n_kv, hd)
    v = (h1 @ lw["wv"]).reshape(B, 1, n_kv, hd)
    q, k = _rope_rows(q, k, rope_pos, theta, dt)
    rows = jnp.arange(B)
    kc_l = kc_l.at[rows, write_idx].set(k[:, 0])
    vc_l = vc_l.at[rows, write_idx].set(v[:, 0])
    valid = jnp.arange(T)[None, :] <= write_idx[:, None]
    if key_mask is not None:
        valid = jnp.logical_and(valid, key_mask)
    o = _attend_rows(q[:, 0], kc_l, vc_l, valid, dt).reshape(
        B, 1, n_heads * hd)
    xt2 = xt + o @ lw["wo"]
    h2 = _rms(xt2, lw["ln2"], eps)
    xt2 = xt2 + (jax.nn.silu(h2 @ lw["wg"]) * (h2 @ lw["wu"])) @ lw["wd"]
    return xt2, kc_l, vc_l


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "max_new", "do_sample", "top_k",
    "eos_id", "top_p", "padded"))
def _generate_jit(w, input_ids, prompt_len_mask, key, *, n_heads, n_kv, eps,
                  theta, max_new, do_sample, top_k, eos_id, temperature,
                  top_p=None, padded=False):
    """input_ids: [B, L0] right-padded prompt; prompt_len_mask [B, L0]
    (1 = real token). With padded=True the right-padding semantics are
    active: per-row rotary positions continue from the prompt length and
    pad KV lines are masked out of decode attention. Returns
    [B, L0 + max_new]."""
    B, L0 = input_ids.shape
    h = w["embed"].shape[1]
    hd = w["wq"].shape[-1] // n_heads
    T = L0 + max_new
    nL = w["wq"].shape[0]
    dt = w["embed"].dtype

    # ---- prefill: full causal pass over the (padded) prompt ----
    x = jnp.take(w["embed"], input_ids, axis=0)
    pos = jnp.arange(L0)
    kcache = jnp.zeros((nL, B, T, n_kv, hd), dt)
    vcache = jnp.zeros((nL, B, T, n_kv, hd), dt)

    stack = {k: w[k] for k in _LLAMA_STACK_KEYS}

    def one_prefill(x, lw):
        return _llama_prefill_layer(x, lw, pos, n_heads=n_heads, n_kv=n_kv,
                                    eps=eps, theta=theta)

    x, kvs = jax.lax.scan(one_prefill, x, stack)
    kcache = kcache.at[:, :, :L0].set(kvs[0])
    vcache = vcache.at[:, :, :L0].set(kvs[1])

    # last real token index per row
    prompt_len = jnp.sum(prompt_len_mask, axis=1).astype(jnp.int32)
    last_idx = prompt_len - 1
    hidden = _rms(x, w["norm"], eps)
    logits0 = jnp.take_along_axis(
        hidden, last_idx[:, None, None].repeat(h, 2), axis=1)[:, 0] @ w["head"]

    def sample(logits, key):
        logits = _filter_logits(logits, temperature, do_sample, top_k, top_p)
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    key, sk = jax.random.split(key)
    tok0 = sample(logits0, sk)

    out = jnp.zeros((B, max_new), jnp.int32)
    out = out.at[:, 0].set(tok0)
    done0 = (tok0 == eos_id) if eos_id is not None else jnp.zeros(
        (B,), bool)

    # pad lines of the prompt must never be attended; generated lines
    # (>= L0) are gated by the causal <= write_idx bound alone
    key_mask = (jnp.concatenate(
        [prompt_len_mask.astype(bool), jnp.ones((B, max_new), bool)],
        axis=1) if padded else None)

    def decode_step(carry, i):
        tok, cur_pos, kcache, vcache, key, done = carry
        xt = jnp.take(w["embed"], tok, axis=0)[:, None]          # [B,1,h]
        write_idx = jnp.full((B,), cur_pos, jnp.int32)
        rope_pos = prompt_len + (i - 1) if padded else write_idx

        def one(cx, lw_kv):
            xt2, kc_l, vc_l = _llama_decode_layer(
                cx["x"], lw_kv, lw_kv["kc"], lw_kv["vc"], write_idx,
                rope_pos, key_mask, n_heads=n_heads, n_kv=n_kv, eps=eps,
                theta=theta)
            return {"x": xt2}, (kc_l, vc_l)

        lw_kv = dict(stack)
        lw_kv["kc"] = kcache
        lw_kv["vc"] = vcache
        cx, (kcache, vcache) = jax.lax.scan(one, {"x": xt}, lw_kv)
        hidden = _rms(cx["x"][:, 0], w["norm"], eps)
        logits = hidden @ w["head"]
        key, sk = jax.random.split(key)
        nxt = sample(logits, sk)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = jnp.logical_or(done, nxt == eos_id)
        return (nxt, cur_pos + 1, kcache, vcache, key, done), nxt

    if max_new > 1:
        carry = (tok0, jnp.int32(L0), kcache, vcache, key, done0)
        _, toks = jax.lax.scan(decode_step, carry,
                               jnp.arange(1, max_new))
        out = out.at[:, 1:].set(jnp.swapaxes(toks, 0, 1))
    return jnp.concatenate([input_ids, out], axis=1)


# ---------------------------------------------------------------------------
# paged-KV bodies (serving engine): decode attention reads K/V through
# per-slot block tables; prefill/chunk writes are block-aligned scatters
# into the shared pool (masked writes redirect to the reserved trash
# block). Module-level like the slot bodies: one lowering per shape.
# ---------------------------------------------------------------------------


def _paged_view(pool_l, tables, block_size):
    """Gather contiguous per-slot K or V views through block tables:
    pool_l [n_blocks, bs, kv, hd], tables [S, mb] -> [S, mb*bs, kv, hd]
    (view index == logical position; unused table entries point at the
    trash block and sit beyond the causal bound).

    The view of the chunk, verify and tensor-parallel bodies; the
    one-device decode reads the pool in place
    (:func:`_paged_decode_attention`). Two forms, chosen by the pool's KV
    heads, each measured (in decode, while decode gathered) where the
    other is worse. By whole blocks: at 32 heads a decode step of 16
    slots x 2048 lines spends 25.99 ms on the device this way and 31.06
    gathered by lines (448 against 393 tokens/s end to end; my chip run,
    PR 27). By lines of the flat ``[n_blocks*bs, kv, hd]`` form, the one
    the scatters write, under 8 heads: there a line fills under a tile,
    the chip's compiler wants a pool gathered by blocks in another
    layout and first copies all of it (537 MB a gather at 4 heads x 4
    layers x 8193 blocks, compiled for a described v5e:
    ``tests/test_tpu_compile.py``), while by lines the 16 x 8192 view
    takes 1.56 ms."""
    S, mb = tables.shape
    nb, bs, kv, hd = pool_l.shape
    if kv < 8:
        rows = (tables[:, :, None] * bs + jnp.arange(bs)).reshape(S, mb * bs)
        return pool_l.reshape(nb * bs, kv, hd)[rows]
    return pool_l[tables].reshape(S, mb * bs, kv, hd)


def _paged_decode_attention(q, kc_pool, vc_pool, tables, write_pos,
                            window=None):
    """One-token paged attention: q [S, H, hd] over the pool through
    block tables, each row seeing the positions ``<= write_pos`` (none
    where that is negative: a slot that does not decode) and, under a
    ``window``, only the last ``window`` of them. On a TPU one kernel
    reads the blocks that can hold such a key in place
    (``ops/pallas/paged_attention.py``; the window goes in as data, so a
    model of layer kinds compiles it once); elsewhere the same function
    computes the gathered form. The chunk and verify bodies keep their
    own view (``_paged_view``). Returns ``[S, H, hd]`` in q's type."""
    from ..ops.pallas import paged_attention as kernel

    return kernel.paged_attention(q, kc_pool, vc_pool, tables, write_pos,
                                  window or 0)


def _llama_decode_layer_paged(xt, lw, kc_pool, vc_pool, tables, dest,
                              write_pos, rope_pos, *, n_heads, n_kv, eps,
                              theta, block_size, window=None, moe_k=0,
                              valid=None):
    """One Llama decoder layer advancing every slot one token against
    the paged pool: the new K/V scatters to flat pool index ``dest``
    (trash-redirected for inactive rows), then attention reads the pool
    through each slot's block-table row
    (:func:`_paged_decode_attention`). kc_pool/vc_pool
    [n_blocks, bs, n_kv, hd] (one layer); tables [S, mb]; dest [S];
    write_pos/rope_pos [S]. ``window``, the layer's own rotary table and
    the routed feed-forward as in :func:`_llama_prefill_layer`; ``valid``
    [S] marks the rows that decode (the routed layer computes and counts
    no other), and a routed layer returns ``picks`` as a fourth value."""
    S = xt.shape[0]
    hd = lw["wq"].shape[-1] // n_heads
    dt = xt.dtype
    h1 = _rms(xt, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(S, 1, n_heads, hd)
    k = (h1 @ lw["wk"]).reshape(S, 1, n_kv, hd)
    v = (h1 @ lw["wv"]).reshape(S, 1, n_kv, hd)
    q, k = _rotate(q, k, rope_pos, lw, theta, dt, per_row=True)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, n_kv, hd).at[dest].set(
        k[:, 0]).reshape(nb, bs, n_kv, hd)
    vc_pool = vc_pool.reshape(nb * bs, n_kv, hd).at[dest].set(
        v[:, 0]).reshape(nb, bs, n_kv, hd)
    o = _paged_decode_attention(q[:, 0], kc_pool, vc_pool, tables,
                                write_pos, window).reshape(
                                    S, 1, n_heads * hd)
    xt2 = xt + o @ lw["wo"]
    y, picks = _feed_forward(_rms(xt2, lw["ln2"], eps), lw, moe_k, valid)
    xt2 = xt2 + y
    return (xt2, kc_pool, vc_pool) if picks is None \
        else (xt2, kc_pool, vc_pool, picks)


def _gpt_decode_layer_paged(xt, lw, kc_pool, vc_pool, tables, dest,
                            write_pos, *, n_heads, block_size):
    """GPT block, paged decode (learned positions enter at the
    embedding; only the pool write/gather differs from the slot body)."""
    S = xt.shape[0]
    h = xt.shape[-1]
    hd = h // n_heads
    hN = _ln(xt, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(S, 1, n_heads, hd)
    k = k.reshape(S, 1, n_heads, hd)
    v = v.reshape(S, 1, n_heads, hd)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, n_heads, hd).at[dest].set(
        k[:, 0]).reshape(nb, bs, n_heads, hd)
    vc_pool = vc_pool.reshape(nb * bs, n_heads, hd).at[dest].set(
        v[:, 0]).reshape(nb, bs, n_heads, hd)
    o = _paged_decode_attention(q[:, 0], kc_pool, vc_pool, tables,
                                write_pos).reshape(S, 1, h)
    xt2 = xt + o @ lw["wproj"] + lw["bproj"]
    h2 = _ln(xt2, lw["ln2w"], lw["ln2b"])
    xt2 = xt2 + jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"],
                            approximate=False) @ lw["wfc2"] + lw["bfc2"]
    return xt2, kc_pool, vc_pool


def _llama_chunk_layer(x, lw, kc_pool, vc_pool, table_row, gpos, wdest, *,
                       n_heads, n_kv, eps, theta, block_size, window=None,
                       moe_k=0, valid=None):
    """One Llama layer over one block-aligned prefill CHUNK of a single
    slot: x [1, C, h] at global positions ``gpos`` [C]; the chunk's K/V
    scatter to flat pool indices ``wdest`` [C] (shared-prefix / pad
    positions trash-redirected), then the chunk rows attend to the
    slot's full gathered view (earlier chunks + this one) under the
    causal bound ``view_pos <= gpos``. Under a ``window`` the view is
    the blocks that can hold a key one of the chunk's rows may see (97
    of 512 at a window of 1024 and a chunk of 512), from the first such
    block on. Rotary table, routed feed-forward, ``valid`` [C] and the
    fourth return value as in :func:`_llama_decode_layer_paged`."""
    B, C, h = x.shape
    hd = lw["wq"].shape[-1] // n_heads
    dt = x.dtype
    h1 = _rms(x, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(B, C, n_heads, hd)
    k = (h1 @ lw["wk"]).reshape(B, C, n_kv, hd)
    v = (h1 @ lw["wv"]).reshape(B, C, n_kv, hd)
    q, k = _rotate(q, k, gpos, lw, theta, dt)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, n_kv, hd).at[wdest].set(
        k[0]).reshape(nb, bs, n_kv, hd)
    vc_pool = vc_pool.reshape(nb * bs, n_kv, hd).at[wdest].set(
        v[0]).reshape(nb, bs, n_kv, hd)
    table, first = table_row[None], 0
    if window is not None:
        table, first = _window_tables(table, gpos[:1], C, window, block_size)
    kview = _paged_view(kc_pool, table, block_size)            # [1,T,kv,hd]
    vview = _paged_view(vc_pool, table, block_size)
    kpos = (first + jnp.arange(kview.shape[1])[None, :])[0]
    cm = kpos[None, :] <= gpos[:, None]                        # [C, T]
    if window is not None:
        cm = cm & (gpos[:, None] - kpos[None, :] < window)
    o = _attend(jnp.swapaxes(q, 1, 2), jnp.swapaxes(kview, 1, 2),
                jnp.swapaxes(vview, 1, 2), cm[None, None], dt)
    o = jnp.swapaxes(o, 1, 2).reshape(B, C, n_heads * hd)
    x = x + o @ lw["wo"]
    y, picks = _feed_forward(_rms(x, lw["ln2"], eps), lw, moe_k, valid)
    x = x + y
    return (x, kc_pool, vc_pool) if picks is None \
        else (x, kc_pool, vc_pool, picks)


def _gpt_chunk_layer(x, lw, kc_pool, vc_pool, table_row, gpos, wdest, *,
                     n_heads, block_size):
    """GPT block over one prefill chunk (positions via wpe upstream)."""
    B, C, h = x.shape
    hd = h // n_heads
    dt = x.dtype
    hN = _ln(x, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, C, n_heads, hd)
    k = k.reshape(B, C, n_heads, hd)
    v = v.reshape(B, C, n_heads, hd)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, n_heads, hd).at[wdest].set(
        k[0]).reshape(nb, bs, n_heads, hd)
    vc_pool = vc_pool.reshape(nb * bs, n_heads, hd).at[wdest].set(
        v[0]).reshape(nb, bs, n_heads, hd)
    kview = _paged_view(kc_pool, table_row[None], block_size)
    vview = _paged_view(vc_pool, table_row[None], block_size)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(kview, 1, 2)
    vh = jnp.swapaxes(vview, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    T = kview.shape[1]
    cm = jnp.arange(T)[None, :] <= gpos[:, None]
    s = jnp.where(cm[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, C, h)
    x = x + o @ lw["wproj"] + lw["bproj"]
    h2 = _ln(x, lw["ln2w"], lw["ln2b"])
    x = x + jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"],
                        approximate=False) @ lw["wfc2"] + lw["bfc2"]
    return x, kc_pool, vc_pool


def _llama_verify_layer(x, lw, kc_pool, vc_pool, table_row, gpos, wdest, *,
                        n_heads, n_kv, eps, theta, block_size):
    """One Llama layer over a speculative VERIFY chunk: the draft's k+1
    candidate tokens of one slot at decode positions ``gpos``, candidate
    K/V scattered through the slot's block table (``wdest`` trash-
    redirects positions past the effective draft width), attention over
    the slot's gathered view under the causal bound. Deliberately THE
    chunk-layer math — verification is a k-token chunk scoring k+1
    positions, so there is one body to keep conformant with prefill and
    one extra lowering total."""
    return _llama_chunk_layer(x, lw, kc_pool, vc_pool, table_row, gpos,
                              wdest, n_heads=n_heads, n_kv=n_kv, eps=eps,
                              theta=theta, block_size=block_size)


def _gpt_verify_layer(x, lw, kc_pool, vc_pool, table_row, gpos, wdest, *,
                      n_heads, block_size):
    """GPT block over a speculative verify chunk (see
    :func:`_llama_verify_layer`): shares the chunk-layer math."""
    return _gpt_chunk_layer(x, lw, kc_pool, vc_pool, table_row, gpos,
                            wdest, n_heads=n_heads, block_size=block_size)


# ---------------------------------------------------------------------------
# latent-attention bodies (MLA): low-rank q and kv projections with their
# norms, a decoupled rotary part that all heads share, and a cache whose
# line is the normed latent ``c`` beside the rotated ``k_pe``: one "KV
# head", no V. Prefill and the chunk expand the latents to per-head K and
# V (the plain form); decode absorbs ``kv_b`` into the query and the output
# and reads the lines as they lie. A layer's feed-forward is dense, or a
# routed share beside a shared expert (:func:`_feed_forward`).
# ---------------------------------------------------------------------------

_LATENT_KEYS = ("ln1", "wqa", "qln", "wqb", "wkva", "kvln", "wkvb", "wo",
                "ln2", "wg", "wu", "wd", "wr", "rb", "sg", "su", "sd",
                "rope_inv", "rope_scale")

#: Lines of the cached prefix that the chunk body expands and scores at a
#: time. It walks only the tiles that hold a key one of its rows may see
#: (a ``fori_loop`` to the tile of its last position), so a chunk at
#: position 5 k of a 16 k table expands 3 tiles, not 8.
_LATENT_TILE = 2048


def _latent_stack(w):
    """The per-layer leaves of a latent model's weight tree: tuples of the
    layers' own arrays, ``None`` where a layer has no such leaf."""
    return {k: w[k] for k in _LATENT_KEYS if k in w}


def _latent_project(h1, lw, pos, *, n_heads, eps, per_row=False):
    """The attention half's projections of normed rows ``h1`` ``[B, L,
    h]``: ``(q_nope [B, L, H, dn], q_pe [B, L, H, dr], c [B, L, r], k_pe
    [B, L, 1, dr])``, q through its low rank and norm, ``c`` the normed
    latent, the rotary parts rotated at ``pos`` (``[L]``, or ``[B]`` with
    ``per_row``) by the layer's own table. ``(c, k_pe)`` is the position's
    cache line; ``k_pe`` is one head that all ``H`` share."""
    B, L = h1.shape[:2]
    r = lw["kvln"].shape[0]
    q = (_rms(h1 @ lw["wqa"], lw["qln"], eps) @ lw["wqb"]).reshape(
        B, L, n_heads, -1)
    kva = h1 @ lw["wkva"]
    dr = kva.shape[-1] - r
    c = _rms(kva[..., :r], lw["kvln"], eps)
    q_pe, k_pe = _rotate(q[..., -dr:], kva[..., None, r:], pos, lw, 0.0,
                         h1.dtype, per_row=per_row)
    return q[..., :-dr], q_pe, c, k_pe


def _latent_line(c, k_pe, width):
    """The cache lines ``[..., 1, width]`` of ``c`` ``[..., r]`` and
    ``k_pe`` ``[..., 1, dr]``: ``c``, then ``k_pe``, then zeros up to the
    pool's width (whole lanes)."""
    pad = width - c.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c[..., None, :], k_pe,
         jnp.zeros(k_pe.shape[:-1] + (pad,), c.dtype)], axis=-1)


def _kv_b(lw, n_heads):
    """``kv_b_proj`` ``[r, H, dn + dv]``: head ``h``'s ``Wk_h`` and
    ``Wv_h`` side by side."""
    return lw["wkvb"].reshape(lw["wkvb"].shape[0], n_heads, -1)


def _latent_prefill_layer(x, lw, pos, *, n_heads, eps, attn_scale, moe_k=0,
                          valid=None, router=()):
    """One latent-attention layer over a full ``[B, L]`` prompt, the plain
    form: the latents expanded to per-head K (score width ``dn + dr``)
    and V (``dv``), causal. Returns ``(x, (lines,))``, the lines ``[B, L,
    1, r + dr]`` for the cache, and the picks after them where the layer
    routes."""
    B, L, _ = x.shape
    dt = x.dtype
    q_nope, q_pe, c, k_pe = _latent_project(
        _rms(x, lw["ln1"], eps), lw, pos, n_heads=n_heads, eps=eps)
    dn = q_nope.shape[-1]
    kv = jnp.einsum("blr,rhd->blhd", c, _kv_b(lw, n_heads))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)
    o = _attend(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(kv[..., dn:], 1, 2),
                jnp.tril(jnp.ones((L, L), bool)), dt, attn_scale)
    x = x + jnp.swapaxes(o, 1, 2).reshape(B, L, -1) @ lw["wo"]
    y, picks = _feed_forward(_rms(x, lw["ln2"], eps), lw, moe_k, valid,
                             router)
    line = _latent_line(c, k_pe, c.shape[-1] + k_pe.shape[-1])
    return x + y, ((line,) if picks is None else (line, picks))


def _latent_decode_layer_paged(xt, lw, pool, no_v, tables, dest, write_pos,
                               rope_pos, *, n_heads, eps, block_size,
                               attn_scale, moe_k=0, valid=None, router=()):
    """One latent-attention layer advancing every slot one token, the
    absorbed form: the new line scatters to flat pool index ``dest``, the
    query carries ``(q_nope Wk_h^T, q_pe)`` against the lines as they lie
    (one KV head under ``H`` query heads, the values the first ``r``
    numbers of the same line: ``ops/pallas/paged_attention.py`` reads the
    live blocks in place) and ``Wv_h`` multiplies the weighted latents
    after. ``pool`` ``[n_blocks, bs, 1, width]`` is the one pool there is
    (``no_v``, None, is the V pool that a latent cache has not)."""
    from ..ops.pallas import paged_attention as kernel

    S = xt.shape[0]
    q_nope, q_pe, c, k_pe = _latent_project(
        _rms(xt, lw["ln1"], eps), lw, rope_pos, n_heads=n_heads, eps=eps,
        per_row=True)
    nb, bs, _, width = pool.shape
    r, dn = c.shape[-1], q_nope.shape[-1]
    pool = pool.reshape(nb * bs, 1, width).at[dest].set(
        _latent_line(c, k_pe, width)[:, 0]).reshape(nb, bs, 1, width)
    wkv = _kv_b(lw, n_heads)
    q = _latent_line(jnp.einsum("shd,rhd->shr", q_nope[:, 0], wkv[..., :dn]),
                     q_pe[:, 0, :, None], width)[:, :, 0]
    o = kernel.paged_attention(q, pool, None, tables, write_pos,
                               scale=attn_scale, value_dim=r)
    o = jnp.einsum("shr,rhd->shd", o, wkv[..., dn:]).reshape(S, 1, -1)
    xt2 = xt + o @ lw["wo"]
    y, picks = _feed_forward(_rms(xt2, lw["ln2"], eps), lw, moe_k, valid,
                             router)
    xt2 = xt2 + y
    return (xt2, pool, no_v) if picks is None else (xt2, pool, no_v, picks)


def _latent_chunk_attention(q_nope, q_pe, pool, table_row, gpos, wkv, scale,
                            dt):
    """The chunk's rows against the slot's cached lines, the plain form a
    tile at a time: ``q_nope`` ``[C, H, dn]`` and ``q_pe`` ``[C, H, dr]``
    at positions ``gpos``; each tile of ``_LATENT_TILE`` lines is gathered
    through ``table_row``, expanded by ``wkv`` ``[r, H, dn + dv]`` and
    folded into a float32 online softmax under ``line <= gpos``
    (``ops/pallas/chunk_attention.py``: on a TPU one kernel a tile, the
    scores never an array). Tiles beyond the chunk's last position are not
    walked. -> ``[C, H, dv]``."""
    from ..ops.pallas import chunk_attention as kernel

    C, H, dn = q_nope.shape
    nb, bs, _, width = pool.shape
    dv = wkv.shape[-1] - dn
    blocks = max(min(_LATENT_TILE // bs, table_row.shape[0]), 1)
    tile = blocks * bs
    table = jnp.pad(table_row, (0, -table_row.shape[0] % blocks))
    flat = pool.reshape(nb * bs, width)
    q, q_pe = jnp.swapaxes(q_nope, 0, 1), jnp.swapaxes(q_pe, 0, 1)

    def one(t, carry):
        rows = (jax.lax.dynamic_slice_in_dim(table, t * blocks, blocks)[
            :, None] * bs + jnp.arange(bs)).reshape(tile)
        return kernel.chunk_attention(q, q_pe, flat[rows], wkv, gpos,
                                      t * tile, carry, scale=scale)

    _, total, acc = jax.lax.fori_loop(0, jnp.max(gpos) // tile + 1, one,
                                      kernel.start(H, C, dv))
    return jnp.swapaxes(acc / jnp.maximum(total, 1e-30)[..., None], 0,
                        1).astype(dt)


def _latent_chunk_layer(x, lw, pool, no_v, table_row, gpos, wdest, *, n_heads,
                        eps, block_size, attn_scale, moe_k=0, valid=None,
                        router=()):
    """One latent-attention layer over one block-aligned prefill chunk of
    a single slot: x ``[1, C, h]`` at global positions ``gpos``; the
    chunk's lines scatter to flat pool indices ``wdest``, then its rows
    attend to the slot's cached lines, earlier chunks' and its own
    (:func:`_latent_chunk_attention`). Operands and returns as
    :func:`_latent_decode_layer_paged`."""
    B, C, _h = x.shape
    q_nope, q_pe, c, k_pe = _latent_project(
        _rms(x, lw["ln1"], eps), lw, gpos, n_heads=n_heads, eps=eps)
    nb, bs, _, width = pool.shape
    pool = pool.reshape(nb * bs, 1, width).at[wdest].set(
        _latent_line(c, k_pe, width)[0]).reshape(nb, bs, 1, width)
    o = _latent_chunk_attention(q_nope[0], q_pe[0], pool, table_row, gpos,
                                _kv_b(lw, n_heads), attn_scale, x.dtype)
    x = x + o.reshape(B, C, -1) @ lw["wo"]
    y, picks = _feed_forward(_rms(x, lw["ln2"], eps), lw, moe_k, valid,
                             router)
    x = x + y
    return (x, pool, no_v) if picks is None else (x, pool, no_v, picks)


# ---------------------------------------------------------------------------
# tensor-parallel bodies (serving engine, paged KV): the SAME math as the
# single-device bodies above with the weights column-/row-parallel over a
# "tp" mesh axis — each device computes its head/column shard locally and
# the two row-parallel projections (o-proj, down-proj) reassemble the
# replicated activations through ppermute-pipelined collective-matmuls
# (distributed.collective_matmul), so no collective serializes after a
# dot. These run INSIDE shard_map: every weight leaf and the KV pool
# arrive as LOCAL shards; activations between layers stay replicated.
# ---------------------------------------------------------------------------

from ..distributed.collective_matmul import (matmul_allgather,  # noqa: E402
                                             ring_rowparallel_matmul)

_TP_AXIS = "tp"


def _llama_tp_specs():
    """PartitionSpec per stacked-Llama weight key over the ``tp`` axis:
    column-parallel QKV/gate-up (output dim sharded), row-parallel
    o-/down-proj (contraction dim sharded), vocab-sharded head; norms
    and the embedding table replicated."""
    from jax.sharding import PartitionSpec as P
    col, row = P(None, None, _TP_AXIS), P(None, _TP_AXIS, None)
    return {"wq": col, "wk": col, "wv": col, "wg": col, "wu": col,
            "wo": row, "wd": row, "ln1": P(), "ln2": P(),
            "embed": P(), "norm": P(), "head": P(None, _TP_AXIS)}


def _gpt_tp_specs():
    """GPT weight placement: the fused qkv columns are pre-permuted to
    device-major ``[q_d | k_d | v_d]`` order (see
    :func:`_gpt_qkv_tp_permutation`) so a contiguous tp shard carries
    one head-slice of each of q, k, v; row-parallel proj/fc2 biases stay
    replicated (added once, after the reduce)."""
    from jax.sharding import PartitionSpec as P
    col, row = P(None, None, _TP_AXIS), P(None, _TP_AXIS, None)
    return {"wqkv": col, "bqkv": P(None, _TP_AXIS),
            "wproj": row, "bproj": P(),
            "wfc1": col, "bfc1": P(None, _TP_AXIS),
            "wfc2": row, "bfc2": P(),
            "ln1w": P(), "ln1b": P(), "ln2w": P(), "ln2b": P(),
            "wte": P(), "wpe": P(), "lnfw": P(), "lnfb": P(),
            "head": P(None, _TP_AXIS)}


def _gpt_qkv_tp_permutation(h, tp):
    """Column permutation mapping the fused ``[q | k | v]`` qkv layout to
    device-major ``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]``: sharding the
    permuted last dim over ``tp`` then hands each device its own head
    slice of all three projections as one contiguous block."""
    import numpy as np
    hc = h // tp
    idx = []
    for d in range(tp):
        for blk in range(3):
            idx.append(np.arange(blk * h + d * hc, blk * h + (d + 1) * hc))
    return np.concatenate(idx)


def _llama_prefill_layer_tp(x, lw, pos, *, n_heads, n_kv, eps, theta, tp):
    """TP variant of :func:`_llama_prefill_layer`: local head shards for
    attention, collective-matmul for the o-projection and down-proj.
    Returns (x_replicated, (k_local, v_local)) — k/v carry this device's
    ``n_kv // tp`` head shard for the sharded KV pool."""
    B, L, h = x.shape
    hl, kvl = n_heads // tp, n_kv // tp
    hd = lw["wq"].shape[-1] // hl
    dt = x.dtype
    h1 = _rms(x, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(B, L, hl, hd)
    k = (h1 @ lw["wk"]).reshape(B, L, kvl, hd)
    v = (h1 @ lw["wv"]).reshape(B, L, kvl, hd)
    q, k = _rope(q, k, pos, theta, dt)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.repeat(jnp.swapaxes(k, 1, 2), n_heads // n_kv, axis=1)
    vh = jnp.repeat(jnp.swapaxes(v, 1, 2), n_heads // n_kv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    cm = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(cm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, L, hl * hd)
    x = x + ring_rowparallel_matmul(o, lw["wo"], _TP_AXIS, tp)
    h2 = _rms(x, lw["ln2"], eps)
    act = jax.nn.silu(h2 @ lw["wg"]) * (h2 @ lw["wu"])
    x = x + ring_rowparallel_matmul(act, lw["wd"], _TP_AXIS, tp)
    return x, (k, v)


def _llama_decode_layer_paged_tp(xt, lw, kc_pool, vc_pool, tables, dest,
                                 write_pos, rope_pos, *, n_heads, n_kv,
                                 eps, theta, block_size, tp):
    """TP variant of :func:`_llama_decode_layer_paged`: the pool shards
    hold this device's kv-head slice, attention runs over the local head
    group, and the o-/down-projections are overlapped collective-matmuls
    (the activations they produce are replicated for the next layer)."""
    S = xt.shape[0]
    h = xt.shape[-1]
    hl, kvl = n_heads // tp, n_kv // tp
    hd = lw["wq"].shape[-1] // hl
    dt = xt.dtype
    h1 = _rms(xt, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(S, 1, hl, hd)
    k = (h1 @ lw["wk"]).reshape(S, 1, kvl, hd)
    v = (h1 @ lw["wv"]).reshape(S, 1, kvl, hd)
    q, k = _rope_rows(q, k, rope_pos, theta, dt)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, kvl, hd).at[dest].set(
        k[:, 0]).reshape(nb, bs, kvl, hd)
    vc_pool = vc_pool.reshape(nb * bs, kvl, hd).at[dest].set(
        v[:, 0]).reshape(nb, bs, kvl, hd)
    kview = _paged_view(kc_pool, tables, block_size)   # [S, T, kvl, hd]
    vview = _paged_view(vc_pool, tables, block_size)
    kh = jnp.repeat(kview, n_heads // n_kv, axis=2)
    vh = jnp.repeat(vview, n_heads // n_kv, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0], kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    T = kview.shape[1]
    valid = jnp.arange(T)[None, :] <= write_pos[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bht,bthd->bhd", p, vh).reshape(S, 1, hl * hd)
    xt2 = xt + ring_rowparallel_matmul(o, lw["wo"], _TP_AXIS, tp)
    h2 = _rms(xt2, lw["ln2"], eps)
    act = jax.nn.silu(h2 @ lw["wg"]) * (h2 @ lw["wu"])
    xt2 = xt2 + ring_rowparallel_matmul(act, lw["wd"], _TP_AXIS, tp)
    return xt2, kc_pool, vc_pool


def _llama_chunk_layer_tp(x, lw, kc_pool, vc_pool, table_row, gpos, wdest,
                          *, n_heads, n_kv, eps, theta, block_size, tp):
    """TP variant of :func:`_llama_chunk_layer` (one prefill chunk of
    one slot against the sharded pool)."""
    B, C, h = x.shape
    hl, kvl = n_heads // tp, n_kv // tp
    hd = lw["wq"].shape[-1] // hl
    dt = x.dtype
    h1 = _rms(x, lw["ln1"], eps)
    q = (h1 @ lw["wq"]).reshape(B, C, hl, hd)
    k = (h1 @ lw["wk"]).reshape(B, C, kvl, hd)
    v = (h1 @ lw["wv"]).reshape(B, C, kvl, hd)
    q, k = _rope(q, k, gpos, theta, dt)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, kvl, hd).at[wdest].set(
        k[0]).reshape(nb, bs, kvl, hd)
    vc_pool = vc_pool.reshape(nb * bs, kvl, hd).at[wdest].set(
        v[0]).reshape(nb, bs, kvl, hd)
    kview = _paged_view(kc_pool, table_row[None], block_size)
    vview = _paged_view(vc_pool, table_row[None], block_size)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.repeat(jnp.swapaxes(kview, 1, 2), n_heads // n_kv, axis=1)
    vh = jnp.repeat(jnp.swapaxes(vview, 1, 2), n_heads // n_kv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    T = kview.shape[1]
    cm = jnp.arange(T)[None, :] <= gpos[:, None]
    s = jnp.where(cm[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, C, hl * hd)
    x = x + ring_rowparallel_matmul(o, lw["wo"], _TP_AXIS, tp)
    h2 = _rms(x, lw["ln2"], eps)
    act = jax.nn.silu(h2 @ lw["wg"]) * (h2 @ lw["wu"])
    x = x + ring_rowparallel_matmul(act, lw["wd"], _TP_AXIS, tp)
    return x, kc_pool, vc_pool


def _gpt_prefill_layer_tp(x, lw, *, n_heads, tp):
    """TP variant of :func:`_gpt_prefill_layer` (device-major permuted
    qkv shard; row-parallel proj/fc2 add their bias after the reduce)."""
    B, L, h = x.shape
    hd = h // n_heads
    hl = n_heads // tp
    dt = x.dtype
    hN = _ln(x, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, L, hl, hd)
    k = k.reshape(B, L, hl, hd)
    v = v.reshape(B, L, hl, hd)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    cm = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(cm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, L, h // tp)
    x = x + ring_rowparallel_matmul(o, lw["wproj"], _TP_AXIS, tp) \
        + lw["bproj"]
    h2 = _ln(x, lw["ln2w"], lw["ln2b"])
    act = jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"], approximate=False)
    x = x + ring_rowparallel_matmul(act, lw["wfc2"], _TP_AXIS, tp) \
        + lw["bfc2"]
    return x, (k, v)


def _gpt_decode_layer_paged_tp(xt, lw, kc_pool, vc_pool, tables, dest,
                               write_pos, *, n_heads, block_size, tp):
    """TP variant of :func:`_gpt_decode_layer_paged`."""
    S = xt.shape[0]
    h = xt.shape[-1]
    hd = h // n_heads
    hl = n_heads // tp
    dt = xt.dtype
    hN = _ln(xt, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(S, 1, hl, hd)
    k = k.reshape(S, 1, hl, hd)
    v = v.reshape(S, 1, hl, hd)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, hl, hd).at[dest].set(
        k[:, 0]).reshape(nb, bs, hl, hd)
    vc_pool = vc_pool.reshape(nb * bs, hl, hd).at[dest].set(
        v[:, 0]).reshape(nb, bs, hl, hd)
    kview = _paged_view(kc_pool, tables, block_size)
    vview = _paged_view(vc_pool, tables, block_size)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0], kview,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    T = kview.shape[1]
    valid = jnp.arange(T)[None, :] <= write_pos[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bht,bthd->bhd", p, vview).reshape(S, 1, h // tp)
    xt2 = xt + ring_rowparallel_matmul(o, lw["wproj"], _TP_AXIS, tp) \
        + lw["bproj"]
    h2 = _ln(xt2, lw["ln2w"], lw["ln2b"])
    act = jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"], approximate=False)
    xt2 = xt2 + ring_rowparallel_matmul(act, lw["wfc2"], _TP_AXIS, tp) \
        + lw["bfc2"]
    return xt2, kc_pool, vc_pool


def _gpt_chunk_layer_tp(x, lw, kc_pool, vc_pool, table_row, gpos, wdest,
                        *, n_heads, block_size, tp):
    """TP variant of :func:`_gpt_chunk_layer`."""
    B, C, h = x.shape
    hd = h // n_heads
    hl = n_heads // tp
    dt = x.dtype
    hN = _ln(x, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, C, hl, hd)
    k = k.reshape(B, C, hl, hd)
    v = v.reshape(B, C, hl, hd)
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    kc_pool = kc_pool.reshape(nb * bs, hl, hd).at[wdest].set(
        k[0]).reshape(nb, bs, hl, hd)
    vc_pool = vc_pool.reshape(nb * bs, hl, hd).at[wdest].set(
        v[0]).reshape(nb, bs, hl, hd)
    kview = _paged_view(kc_pool, table_row[None], block_size)
    vview = _paged_view(vc_pool, table_row[None], block_size)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(kview, 1, 2)
    vh = jnp.swapaxes(vview, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    T = kview.shape[1]
    cm = jnp.arange(T)[None, :] <= gpos[:, None]
    s = jnp.where(cm[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, C, h // tp)
    x = x + ring_rowparallel_matmul(o, lw["wproj"], _TP_AXIS, tp) \
        + lw["bproj"]
    h2 = _ln(x, lw["ln2w"], lw["ln2b"])
    act = jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"], approximate=False)
    x = x + ring_rowparallel_matmul(act, lw["wfc2"], _TP_AXIS, tp) \
        + lw["bfc2"]
    return x, kc_pool, vc_pool


# ---------------------------------------------------------------------------
# beam search (Llama decoder)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "max_new", "num_beams", "eos_id"))
def _beam_generate_jit(w, input_ids, *, n_heads, n_kv, eps, theta, max_new,
                       num_beams, eos_id, length_penalty):
    """Beam search with the same static cache design: beams fold into the
    batch dim; caches reorder by beam index each step (HF/PaddleNLP
    BeamSearchScorer semantics, length-penalized log-prob)."""
    B, L0 = input_ids.shape
    K = num_beams
    h = w["embed"].shape[1]
    hd = h // n_heads
    T = L0 + max_new
    nL = w["wq"].shape[0]
    dt = w["embed"].dtype
    NEG = jnp.float32(-1e9)

    # ---- prefill once per batch row, then tile to beams ----
    x = jnp.take(w["embed"], input_ids, axis=0)
    pos = jnp.arange(L0)
    stack = {k: w[k] for k in _LLAMA_STACK_KEYS}

    def one_prefill(x, lw):
        return _llama_prefill_layer(x, lw, pos, n_heads=n_heads, n_kv=n_kv,
                                    eps=eps, theta=theta)

    x, kvs = jax.lax.scan(one_prefill, x, stack)
    kcache = jnp.zeros((nL, B * K, T, n_kv, hd), dt)
    vcache = jnp.zeros_like(kcache)
    kcache = kcache.at[:, :, :L0].set(jnp.repeat(kvs[0], K, axis=1))
    vcache = vcache.at[:, :, :L0].set(jnp.repeat(kvs[1], K, axis=1))

    hidden = _rms(x[:, -1], w["norm"], eps)
    logp0 = jax.nn.log_softmax(
        (hidden @ w["head"]).astype(jnp.float32), axis=-1)   # [B, V]
    V = logp0.shape[-1]
    top0, tok0 = jax.lax.top_k(logp0, K)                     # [B, K]
    scores = top0                                            # [B, K]
    toks = jnp.zeros((B, K, max_new), jnp.int32).at[..., 0].set(tok0)
    done = (tok0 == eos_id) if eos_id is not None else jnp.zeros((B, K),
                                                                 bool)

    def decode_step(carry, i):
        toks, scores, cur_pos, kcache, vcache, done = carry
        tok = jax.lax.dynamic_index_in_dim(toks, i - 1, 2, False)  # [B,K]
        xt = jnp.take(w["embed"], tok.reshape(B * K), axis=0)[:, None]
        write_idx = jnp.full((B * K,), cur_pos, jnp.int32)

        def one(cx, lw_kv):
            xt2, kc_l, vc_l = _llama_decode_layer(
                cx["x"], lw_kv, lw_kv["kc"], lw_kv["vc"], write_idx,
                write_idx, None, n_heads=n_heads, n_kv=n_kv, eps=eps,
                theta=theta)
            return {"x": xt2}, (kc_l, vc_l)

        lw_kv = dict(stack)
        lw_kv["kc"] = kcache
        lw_kv["vc"] = vcache
        cx, (kcache, vcache) = jax.lax.scan(one, {"x": xt}, lw_kv)
        hidden = _rms(cx["x"][:, 0], w["norm"], eps)
        logp = jax.nn.log_softmax(
            (hidden @ w["head"]).astype(jnp.float32),
            axis=-1).reshape(B, K, V)
        if eos_id is not None:
            # finished beams may only extend with eos at unchanged score
            frozen = jnp.full((V,), NEG).at[eos_id].set(0.0)
            logp = jnp.where(done[..., None], frozen[None, None, :], logp)
        total = scores[..., None] + logp                      # [B, K, V]
        flat = total.reshape(B, K * V)
        new_scores, idx = jax.lax.top_k(flat, K)              # [B, K]
        beam_idx = idx // V
        new_tok = (idx % V).astype(jnp.int32)

        # reorder beam state
        gidx = (jnp.arange(B)[:, None] * K + beam_idx).reshape(B * K)
        kcache = kcache[:, gidx]
        vcache = vcache[:, gidx]
        toks = jnp.take_along_axis(toks, beam_idx[..., None], axis=1)
        done = jnp.take_along_axis(done, beam_idx, axis=1)
        toks = jax.lax.dynamic_update_index_in_dim(
            toks, new_tok, i, 2)
        if eos_id is not None:
            done = jnp.logical_or(done, new_tok == eos_id)
        return (toks, new_scores, cur_pos + 1, kcache, vcache, done), None

    if max_new > 1:
        carry = (toks, scores, jnp.int32(L0), kcache, vcache, done)
        carry, _ = jax.lax.scan(decode_step, carry,
                                jnp.arange(1, max_new))
        toks, scores, _, _, _, done = carry

    # length penalty on the final ranking (HF BeamSearchScorer)
    if eos_id is not None:
        lengths = jnp.argmax(
            jnp.concatenate([toks == eos_id,
                             jnp.ones((B, K, 1), bool)], axis=2),
            axis=2) + 1
    else:
        lengths = jnp.full((B, K), max_new)
    ranked = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(ranked, axis=1)
    best_toks = jnp.take_along_axis(
        toks, best[:, None, None].repeat(max_new, 2), axis=1)[:, 0]
    return jnp.concatenate([input_ids, best_toks], axis=1)


def beam_search_generate(model, input_ids, max_new_tokens: int = 32,
                         num_beams: int = 4,
                         eos_token_id: Optional[int] = None,
                         length_penalty: float = 1.0):
    """Beam search for LlamaForCausalLM (HF/PaddleNLP beam semantics)."""
    c = model.config
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(
        input_ids)
    w = _stacked_weights(model)
    out = _beam_generate_jit(
        w, ids.astype(jnp.int32), n_heads=c.num_attention_heads,
        n_kv=c.num_key_value_heads, eps=c.rms_norm_eps, theta=c.rope_theta,
        max_new=int(max_new_tokens), num_beams=int(num_beams),
        eos_id=eos_token_id, length_penalty=jnp.float32(length_penalty))
    return Tensor(out)


# ---------------------------------------------------------------------------
# GPT (pre-LN, learned positions, combined qkv)
# ---------------------------------------------------------------------------

def _gpt_stacked_weights(model):
    blocks = model.gpt.blocks

    def st(get):
        return jnp.stack([get(b) for b in blocks])

    w = {
        "wqkv": st(lambda b: b.qkv.weight._data),
        "bqkv": st(lambda b: b.qkv.bias._data),
        "wproj": st(lambda b: b.proj.weight._data),
        "bproj": st(lambda b: b.proj.bias._data),
        "ln1w": st(lambda b: b.ln_1.weight._data),
        "ln1b": st(lambda b: b.ln_1.bias._data),
        "ln2w": st(lambda b: b.ln_2.weight._data),
        "ln2b": st(lambda b: b.ln_2.bias._data),
        "wfc1": st(lambda b: b.fc1.weight._data),
        "bfc1": st(lambda b: b.fc1.bias._data),
        "wfc2": st(lambda b: b.fc2.weight._data),
        "bfc2": st(lambda b: b.fc2.bias._data),
    }
    w["wte"] = model.gpt.wte.weight._data
    w["wpe"] = model.gpt.wpe.weight._data
    w["lnfw"] = model.gpt.ln_f.weight._data
    w["lnfb"] = model.gpt.ln_f.bias._data
    w["head"] = model.lm_head.weight._data
    return w


def _ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - m) * jax.lax.rsqrt(v + eps)).astype(x.dtype) * w + b)


_GPT_STACK_KEYS = ("wqkv", "bqkv", "wproj", "bproj", "ln1w", "ln1b", "ln2w",
                   "ln2b", "wfc1", "bfc1", "wfc2", "bfc2")


def _gpt_prefill_layer(x, lw, *, n_heads):
    """One GPT block over a full [B, L] prompt (causal; positions enter
    via the wpe embedding). Returns (x, (k, v)), k/v [B, L, H, hd]."""
    B, L, h = x.shape
    hd = h // n_heads
    dt = x.dtype
    hN = _ln(x, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, L, n_heads, hd)
    k = k.reshape(B, L, n_heads, hd)
    v = v.reshape(B, L, n_heads, hd)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    cm = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(cm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    o = jnp.swapaxes(o, 1, 2).reshape(B, L, h)
    x = x + o @ lw["wproj"] + lw["bproj"]
    h2 = _ln(x, lw["ln2w"], lw["ln2b"])
    x = x + jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"],
                        approximate=False) @ lw["wfc2"] + lw["bfc2"]
    return x, (k, v)


def _gpt_decode_layer(xt, lw, kc_l, vc_l, write_idx, key_mask, *, n_heads):
    """One GPT block advancing every row one token (learned positions are
    applied at the embedding, so only the cache line index matters here).
    kc_l/vc_l [B, T, H, hd]; write_idx [B]; key_mask as in the Llama
    decode layer."""
    B, T = kc_l.shape[0], kc_l.shape[1]
    h = xt.shape[-1]
    hd = h // n_heads
    dt = xt.dtype
    hN = _ln(xt, lw["ln1w"], lw["ln1b"])
    qkv = hN @ lw["wqkv"] + lw["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, 1, n_heads, hd)
    k = k.reshape(B, 1, n_heads, hd)
    v = v.reshape(B, 1, n_heads, hd)
    rows = jnp.arange(B)
    kc_l = kc_l.at[rows, write_idx].set(k[:, 0])
    vc_l = vc_l.at[rows, write_idx].set(v[:, 0])
    s = jnp.einsum("bhd,bthd->bht", q[:, 0], kc_l,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(hd))
    valid = jnp.arange(T)[None, :] <= write_idx[:, None]
    if key_mask is not None:
        valid = jnp.logical_and(valid, key_mask)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bht,bthd->bhd", p, vc_l).reshape(B, 1, h)
    xt2 = xt + o @ lw["wproj"] + lw["bproj"]
    h2 = _ln(xt2, lw["ln2w"], lw["ln2b"])
    xt2 = xt2 + jax.nn.gelu(h2 @ lw["wfc1"] + lw["bfc1"],
                            approximate=False) @ lw["wfc2"] + lw["bfc2"]
    return xt2, kc_l, vc_l


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "max_new", "do_sample", "top_k", "eos_id", "top_p",
    "padded"))
def _gpt_generate_jit(w, input_ids, prompt_len_mask, key, *, n_heads,
                      max_new, do_sample, top_k, eos_id, temperature,
                      top_p=None, padded=False):
    B, L0 = input_ids.shape
    h = w["wte"].shape[1]
    hd = h // n_heads
    T = L0 + max_new
    dt = w["wte"].dtype

    pos = jnp.arange(L0)
    x = jnp.take(w["wte"], input_ids, axis=0) + w["wpe"][pos][None]
    kcache = jnp.zeros((w["wqkv"].shape[0], B, T, n_heads, hd), dt)
    vcache = jnp.zeros_like(kcache)

    stack = {k: w[k] for k in _GPT_STACK_KEYS}

    def one_prefill(x, lw):
        return _gpt_prefill_layer(x, lw, n_heads=n_heads)

    x, kvs = jax.lax.scan(one_prefill, x, stack)
    kcache = kcache.at[:, :, :L0].set(kvs[0])
    vcache = vcache.at[:, :, :L0].set(kvs[1])

    prompt_len = jnp.sum(prompt_len_mask, axis=1).astype(jnp.int32)
    last_idx = prompt_len - 1
    xlast = jnp.take_along_axis(
        x, last_idx[:, None, None].repeat(h, 2), axis=1)[:, 0]
    logits0 = _ln(xlast, w["lnfw"], w["lnfb"]) @ w["head"]

    def sample(logits, key):
        logits = _filter_logits(logits, temperature, do_sample, top_k, top_p)
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    key, sk = jax.random.split(key)
    tok0 = sample(logits0, sk)
    out = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(tok0)
    done0 = (tok0 == eos_id) if eos_id is not None else jnp.zeros((B,), bool)

    key_mask = (jnp.concatenate(
        [prompt_len_mask.astype(bool), jnp.ones((B, max_new), bool)],
        axis=1) if padded else None)

    def decode_step(carry, i):
        tok, cur_pos, kcache, vcache, key, done = carry
        write_idx = jnp.full((B,), cur_pos, jnp.int32)
        rope_pos = prompt_len + (i - 1) if padded else write_idx
        xt = (jnp.take(w["wte"], tok, axis=0)
              + jnp.take(w["wpe"], rope_pos, axis=0))[:, None]

        def one(cx, lw_kv):
            xt2, kc_l, vc_l = _gpt_decode_layer(
                cx["x"], lw_kv, lw_kv["kc"], lw_kv["vc"], write_idx,
                key_mask, n_heads=n_heads)
            return {"x": xt2}, (kc_l, vc_l)

        lw_kv = dict(stack)
        lw_kv["kc"] = kcache
        lw_kv["vc"] = vcache
        cx, (kcache, vcache) = jax.lax.scan(one, {"x": xt}, lw_kv)
        logits = _ln(cx["x"][:, 0], w["lnfw"], w["lnfb"]) @ w["head"]
        key, sk = jax.random.split(key)
        nxt = sample(logits, sk)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = jnp.logical_or(done, nxt == eos_id)
        return (nxt, cur_pos + 1, kcache, vcache, key, done), nxt

    if max_new > 1:
        carry = (tok0, jnp.int32(L0), kcache, vcache, key, done0)
        _, toks = jax.lax.scan(decode_step, carry, jnp.arange(1, max_new))
        out = out.at[:, 1:].set(jnp.swapaxes(toks, 0, 1))
    return jnp.concatenate([input_ids, out], axis=1)


def gpt_generate(model, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, top_k: int = 0,
                 temperature: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 top_p: Optional[float] = None,
                 pad_token_id: Optional[int] = None, attention_mask=None):
    """Greedy / top-k generation for GPTForCausalLM (same static-cache
    design as the Llama path). Right-padded prompts are supported via
    pad_token_id and/or an explicit attention_mask, as in generate()."""
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(
        input_ids)
    ids = ids.astype(jnp.int32)
    mask = _prompt_mask(ids, pad_token_id, attention_mask)
    padded = pad_token_id is not None or attention_mask is not None
    w = _gpt_stacked_weights(model)
    out = _gpt_generate_jit(
        w, ids, mask, jax.random.PRNGKey(seed),
        n_heads=model.config.num_attention_heads,
        max_new=int(max_new_tokens), do_sample=bool(do_sample),
        top_k=int(top_k), eos_id=eos_token_id,
        temperature=jnp.float32(temperature),
        top_p=None if top_p is None else float(top_p), padded=padded)
    return Tensor(out)


def generate(model, input_ids, max_new_tokens: int = 32,
             do_sample: bool = False, top_k: int = 0,
             temperature: float = 1.0,
             eos_token_id: Optional[int] = None, seed: int = 0,
             top_p: Optional[float] = None,
             pad_token_id: Optional[int] = None, attention_mask=None):
    """Greedy / top-k sampled generation for LlamaForCausalLM.

    input_ids: Tensor [B, L0]. Right-padded prompts are supported: pass
    pad_token_id (mask derived from trailing pad tokens) and/or an
    explicit attention_mask [B, L0]; pad positions are excluded from
    attention and each row's generated tokens take rotary positions
    continuing from its own prompt length. Without either, every token
    is treated as real context. Returns Tensor [B, L0 + max_new_tokens].
    """
    c = model.config
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(
        input_ids)
    ids = ids.astype(jnp.int32)
    mask = _prompt_mask(ids, pad_token_id, attention_mask)
    padded = pad_token_id is not None or attention_mask is not None
    w = _stacked_weights(model)
    key = jax.random.PRNGKey(seed)
    out = _generate_jit(
        w, ids, mask, key, n_heads=c.num_attention_heads,
        n_kv=c.num_key_value_heads, eps=c.rms_norm_eps, theta=c.rope_theta,
        max_new=int(max_new_tokens), do_sample=bool(do_sample),
        top_k=int(top_k), eos_id=eos_token_id,
        temperature=jnp.float32(temperature),
        top_p=None if top_p is None else float(top_p), padded=padded)
    return Tensor(out)
