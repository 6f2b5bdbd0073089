"""The plain reference of the SambaY decoder-hybrid-decoder (the
``phi4flash`` family: Phi-4-mini-flash-reasoning, arXiv:2507.06607) in
``jax.numpy`` float32: no kernel, no cache, no batching, and no import
from the program under test.

Every layer is ``x += Mixer_i(LN(x)); x += W_down(silu(g) * u)`` with
``[g, u] = W_gate_up LN'(x)``; ``LayerNorm`` has weight and bias; the head
is the embedding (tied); there is no position encoding. With
``mb_per_layer`` 2 and depth ``L`` the mixers are (``layer_kinds``):

* even layers up to ``L / 2``: **Mamba-1**. ``[u, z] = W_in x``; ``u =
  silu(causal_conv1d(u))`` (depthwise, width ``d_conv``, with bias);
  ``[dt, B, C] = W_x u``; ``dt = softplus(W_dt dt + b_dt)``; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t B_t u_t`` with ``A = -exp(A_log)``; ``m_t =
  C_t . h_t + D u_t``; out ``W_out(m * silu(z))``. The recurrence is a
  ``lax.scan`` a token at a time. Layer ``L / 2``'s ``m`` is also the
  memory of the gated units;
* odd layers under ``L / 2``: **differential attention** through a window
  of ``sliding_window`` (a row sees itself and the ``window - 1`` before
  it); layer ``L / 2 + 1``: the same over the whole context. Heads pair
  up, ``q = (q1, q2)``, ``k = (k1, k2)``, ``v = [v1 | v2]``: ``a_j =
  softmax(q_j k_j^T / sqrt(hd)) v``; ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` for layer
  ``i``; out ``W_o((1 - lambda_init) RMSNorm(a1 - lambda a2))``, the norm
  over the ``2 hd`` numbers of a pair with a weight; query pair ``p`` reads
  KV pair ``p // 2``;
* later even layers: **gated memory unit** ``W_out(silu(W_in LN(x)) * m)``;
* later odd layers: differential attention with queries of their own over
  layer ``L / 2 + 1``'s keys and values.

Departures from the published description, each of them exact: a weight
matrix is stored ``[in, out]`` and the convolution's ``[d_conv, d_inner]``
(the program's layouts); weights arrive in the served type and are
widened to float32 a layer at a time; scores are computed ``QUERY_BLOCK``
rows at a time; the head's product runs over ``HEAD_BLOCKS`` slices of the
vocabulary. Every product runs under
``jax.default_matmul_precision("highest")``.

Weights are keyed as ``named_parameters()`` gives them:
``model.embed_tokens.weight``, ``model.layers.<i>.{input_layernorm,
post_attention_layernorm}.{weight,bias}``, ``...mlp.{gate_up_proj,
down_proj}.weight``, a Mamba layer's ``mixer.{in_proj.weight, conv1d.weight,
conv1d.bias, x_proj.weight, dt_proj.weight, dt_proj.bias, A_log, D,
out_proj.weight}``, an attention layer's ``mixer.{Wqkv.weight | q_proj.weight,
out_proj.weight, lambda_q1, lambda_k1, lambda_q2, lambda_k2, subln}``, a
gated unit's ``mixer.{in_proj,out_proj}.weight``,
``model.final_layernorm.{weight,bias}``.

What the drivers call: ``logits``, ``engine_copies``, ``LOGIT_TOL_ULPS``,
``bf16_step``. Serving only: no loss, no gradients.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# LOGIT_TOL_ULPS (4 bf16 steps at the size of the largest logit), for the
# dense reference's reasons: the same bf16 program types under the same
# float32 comparison. Here the head is the embedding, drawn N(0, 0.02)
# (at N(0, 1) a position's own token would outscore all others whatever
# the layers do, and no fault could move the largest logit), so logits
# have a deviation near 1 and the best two of 2e5 lie a few steps apart;
# 32 layers of bf16 roundings, a float32 recurrence between them,
# displace a logit by a step or two. The two readings the limit lies
# between, on the chip at the cell's own sizes over 4 x 32 rows (PERF.md
# section 6, PR 35): the sound program 0.06-2.54 in 17 runs | 4 | the
# program lowered to 8-bit activations 5.86 and 8.69
# (``controls_hybrid.py``).
from benchmarks.reference import LOGIT_TOL_ULPS, bf16_step  # noqa: F401

QUERY_BLOCK = 512
HEAD_BLOCKS = 8
SUBLN_EPS = 1e-5


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def engine_copies(name):
    """Whether the serving engine stacks a copy of its own of this named
    weight. It stacks none: its layer loop takes every layer's leaf as the
    array the model holds, so nothing goes to the host."""
    return False


def layer_kinds(config):
    half, mb = config["num_hidden_layers"] // 2, config["mb_per_layer"]
    kinds = []
    for i in range(config["num_hidden_layers"]):
        if i <= half:
            kinds.append("mamba" if i % mb == 0 else "window")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if i % mb == 0 else "cross")
    return kinds


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def mamba(x, w):
    """``x`` ``[T, h]`` normed -> ``(W_out(m * silu(z)), m [T, d_inner])``."""
    T = x.shape[0]
    u, z = jnp.split(x @ w["in_proj.weight"], 2, axis=-1)
    taps = w["conv1d.weight"]                               # [d_conv, di]
    padded = jnp.pad(u, ((taps.shape[0] - 1, 0), (0, 0)))
    u = sum(padded[j:j + T] * taps[j] for j in range(taps.shape[0])) \
        + w["conv1d.bias"]
    u = jax.nn.silu(u)
    rank, states = w["dt_proj.weight"].shape[0], w["A_log"].shape[1]
    dbc = u @ w["x_proj.weight"]
    dt = jax.nn.softplus(dbc[:, :rank] @ w["dt_proj.weight"]
                         + w["dt_proj.bias"])
    B, C = dbc[:, rank:rank + states], dbc[:, rank + states:]
    A = -jnp.exp(w["A_log"])                                # [di, states]

    def token(h, t):
        u_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t[:, None] * A) * h \
            + (dt_t * u_t)[:, None] * B_t[None, :]
        return h, h @ C_t

    _, y = jax.lax.scan(token, jnp.zeros_like(A), (u, dt, B, C))
    m = y + w["D"] * u
    return (m * jax.nn.silu(z)) @ w["out_proj.weight"], m


def _blocks(f, x, size):
    """``f`` over ``x``'s rows, ``size`` at a time, put together again."""
    T = x.shape[0]
    if T <= size:
        return f(x, 0)
    pad = -T % size
    xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (-1, size) + x.shape[1:])
    out = jax.lax.map(lambda a: f(a[0], a[1]),
                      (xs, jnp.arange(xs.shape[0]) * size))
    return out.reshape((-1,) + out.shape[2:])[:T]


def differential_attention(x, w, kv, layer, config, window=None):
    """``x`` ``[T, h]`` normed -> ``(W_o(...), (k, v))``; ``kv``: the keys
    and values ``[T, n_kv, hd]`` of the layer that keeps them, for a layer
    with queries alone."""
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // nq
    T = x.shape[0]
    if kv is None:
        qkv = x @ w["Wqkv.weight"]
        q = qkv[:, :nq * hd]
        kept = (qkv[:, nq * hd:(nq + nkv) * hd].reshape(T, nkv, hd),
                qkv[:, (nq + nkv) * hd:].reshape(T, nkv, hd))
        k, v = kept
    else:
        q, (k, v), kept = x @ w["q_proj.weight"], kv, None
    q = q.reshape(T, nq // 2, 2, hd)
    # KV pair g: keys (k1, k2) = heads (2g, 2g + 1), values the two
    # heads' side by side; query pair p reads pair p // (pairs a KV pair)
    per = (nq // 2) // (nkv // 2)
    k = jnp.repeat(k.reshape(T, nkv // 2, 2, hd), per, axis=1)
    v = jnp.repeat(v.reshape(T, nkv // 2, 2 * hd), per, axis=1)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init
    keys = jnp.arange(T)

    def rows(qb, first):
        at = first + jnp.arange(qb.shape[0])
        ok = keys[None, :] <= at[:, None]
        if window is not None:
            ok = ok & (at[:, None] - keys[None, :] < window)
        s = jnp.einsum("qpjd,kpjd->pjqk", qb, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        a = jnp.einsum("pjqk,kpd->qpjd", p, v)              # [Q, pairs, 2, 2hd]
        d = a[:, :, 0] - lam * a[:, :, 1]
        d = d / jnp.sqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                         + SUBLN_EPS) * w["subln"]
        return ((1.0 - lam_init) * d).reshape(qb.shape[0], -1)

    return _blocks(rows, q, QUERY_BLOCK) @ w["out_proj.weight"], kept


def mlp(x, w):
    g, u = jnp.split(x @ w["gate_up_proj.weight"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w["down_proj.weight"]


@functools.partial(jax.jit, static_argnames=("kind", "layer", "sizes"))
def _layer(x, w, m, kv, *, kind, layer, sizes):
    """One layer over one sequence ``x`` ``[T, h]``; ``m`` and ``kv`` are
    what the layers above left for the cross-decoder (None before)."""
    config = dict(sizes)
    w = jax.tree.map(_f32, w)
    eps = config["layer_norm_eps"]
    mix = {k[len("mixer."):]: a for k, a in w.items()
           if k.startswith("mixer.")}
    h1 = layer_norm(x, w["input_layernorm.weight"],
                    w["input_layernorm.bias"], eps)
    if kind == "mamba":
        y, m = mamba(h1, mix)
    elif kind == "gmu":
        y = (jax.nn.silu(h1 @ mix["in_proj.weight"]) * m) \
            @ mix["out_proj.weight"]
    else:
        y, new = differential_attention(
            h1, mix, kv if kind == "cross" else None, layer, config,
            window=config["sliding_window"] if kind == "window" else None)
        if kind == "full":
            kv = new
    x = x + y
    h2 = layer_norm(x, w["post_attention_layernorm.weight"],
                    w["post_attention_layernorm.bias"], eps)
    x = x + mlp(h2, {k[len("mlp."):]: a for k, a in w.items()
                     if k.startswith("mlp.")})
    return x, m, kv


SIZES = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "hidden_size", "mb_per_layer", "sliding_window", "layer_norm_eps")


def _layer_weights(weights, i):
    prefix = f"model.layers.{i}."
    return {k[len(prefix):]: a for k, a in weights.items()
            if k.startswith(prefix)}


def hidden_states(weights, config, ids):
    """float32 hidden states ``[B, L, h]`` after the final norm, a
    sequence at a time (a right-padded batch: everything here is causal,
    so the padding changes nothing before it)."""
    sizes = tuple((k, config[k]) for k in SIZES)
    kinds = layer_kinds(config)
    embed = weights["model.embed_tokens.weight"]
    out = []
    for row in jnp.asarray(ids, jnp.int32):
        x, m, kv = _f32(jnp.take(embed, row, axis=0)), None, None
        for i, kind in enumerate(kinds):
            x, m, kv = _layer(x, _layer_weights(weights, i), m, kv,
                              kind=kind, layer=i, sizes=sizes)
        out.append(layer_norm(
            x, _f32(weights["model.final_layernorm.weight"]),
            _f32(weights["model.final_layernorm.bias"]),
            config["layer_norm_eps"]))
    return jnp.stack(out)


@jax.jit
def _head_block(x, block):
    return x @ _f32(block).T


def logits(weights, config, ids, rows=None):
    """float32 logits ``[B, L, vocab]`` of token ids ``[B, L]``; with
    ``rows`` (positions ``[B, R]``, each sequence's own), only those rows
    of L: ``[B, R, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, config, ids)
        if rows is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(rows, jnp.int32)[..., None], axis=1)
        embed = weights["model.embed_tokens.weight"]
        edges = [embed.shape[0] * i // HEAD_BLOCKS
                 for i in range(HEAD_BLOCKS + 1)]
        return jnp.concatenate(
            [_head_block(x, embed[a:b]) for a, b in zip(edges, edges[1:])
             if b > a], axis=-1)
