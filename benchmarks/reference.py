"""The plain reference: a dense pre-norm decoder in ``jax.numpy`` float32.

RMSNorm, rotary position embedding (the half-split rotation of the
published Hugging Face models), grouped-query causal attention, SwiGLU,
an untied output head, and the mean next-token cross-entropy. No kernel,
no cache, no batching tricks, and no import from the program under test.

Departures from the published description, each of them exact:

* a weight matrix is stored ``[in, out]`` (the program's layout), so a
  projection is ``x @ w`` where the published code writes ``x @ w.T``;
* weights arrive in the type the model is served in (bf16) and are widened
  to float32 layer by layer, which changes no value, so that a float32
  copy of the whole model never has to fit beside the system under test;
* attention scores are computed for ``QUERY_BLOCK`` query rows at a time
  against all keys: the same numbers, without the ``[L, L]`` matrix of a
  long sequence held whole.

Every matrix product runs under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 product otherwise rounds its operands to bf16.

A configuration file names its reference module under
``program.reference``; the drivers call what it names and nothing of this
file by name. What a reference module gives: ``loss_and_gradients``,
``logits``, ``engine_copies``, and the tolerances ``LOSS_TOL``,
``SIGN_TOL``, ``SIZE_TOL``, ``LOGIT_TOL_ULPS`` with ``update_agreement``
and ``bf16_step`` (a module for another architecture imports those from
here unless its own differ).

Weights are a dict keyed by the names ``named_parameters()`` gives:
``llama.embed_tokens.weight``, ``llama.layers.<i>.{input_layernorm,
post_attention_layernorm}.weight``, ``llama.layers.<i>.self_attn.{q,k,v,o}
_proj.weight``, ``llama.layers.<i>.mlp.{gate,up,down}_proj.weight``,
``llama.norm.weight``, ``lm_head.weight``. The configuration is the dict of
the configuration file (the published ``config.json`` keys).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

# --- tolerances, and why ---------------------------------------------------
#
# LOSS_TOL: the compiled step computes the same loss with bf16 activations
# (8 bits of mantissa, a relative 2**-8 = 0.4 % per rounding) and a float32
# cross-entropy over bf16 logits; the reference keeps float32 throughout.
# The loss is a mean over thousands of positions, so the roundings average
# out: on the chip the two differed by 7.6e-6 to 4.7e-5 in every run
# (PR 23). 1e-3 is twenty times the largest. The loss at initialisation is
# ln(vocab) + var(logits)/2 = 10.40 + 0.11, so a fault that changes the
# logits' variance by a hundredth (a dropped layer, a mis-scaled attention
# or residual) moves it by more.
LOSS_TOL = 1e-3

# SIGN_TOL, SIZE_TOL: the loss says nothing about the backward pass and
# the update (on fresh random tokens it stays near ln(vocab) whatever the
# gradients are), so the first step's change of the weights is held to the
# reference's gradient. AdamW's first update of a weight is -lr * (g / (|g|
# + eps) + decay * w): lr against the gradient's sign, the decay a hundred
# times smaller. Over the larger half of the reference's gradients in the
# first ``SLAB`` elements (whole rows) of each checked tensor, the share
# of weights that moved against the gradient must be at least SIGN_TOL,
# and the median size of the move within SIZE_TOL of lr. A weight of 0.02
# in bf16 has steps of 1.2e-4, so a move of 3e-4 shows as two or three
# steps; a bf16 backward pass flips the sign of few of the larger
# gradients (on the chip the shares were 0.999998 to 1.0, and the sizes
# 1.00 to 1.02, 0.81 in the embedding; PR 23). Wrong gradients agree in
# half the weights, no update in none, and a wrong rate shows in the size.
SIGN_TOL = 0.95
SIZE_TOL = 0.5
SLAB = 1 << 22

# LOGIT_TOL_ULPS: the engine picks the largest of ~1e5 bf16 logits; the
# reference scores the same sequence in float32. Where the engine's token
# is not the reference's best, the reference's score of it may lie below
# the best by no more than this many bf16 steps at the size of the largest
# logit. Eight layers of bf16 roundings displace a logit by a few steps (a
# step is 2**-8 relative), and the best two of 1e5 random logits lie about
# 0.2 standard deviations apart where the largest is 4.5: ~1 % of it, two
# or three steps. A token read through a wrong cache line or block table
# scores like a random token: hundreds of steps below the best.
LOGIT_TOL_ULPS = 4


def bf16_step(magnitude):
    """The distance between neighbouring bf16 values at ``magnitude``."""
    return 2.0 ** (math.floor(math.log2(max(float(magnitude), 1e-30))) - 7)


def engine_copies(name):
    """Whether the serving engine stacks a copy of its own of this named
    weight (the layers' weights; embedding, final norm and head are
    shared with the model): the model's copy of those goes to host
    memory, where the reference reads it."""
    return ".layers." in name


def checked(config):
    """The tensors whose first update is held to the reference's
    gradient: the embedding's scatter, a projection behind rotary
    attention in the first layer (every later layer's backward pass lies
    before it), the last layer's MLP, and the head."""
    last = config["num_hidden_layers"] - 1
    return ("llama.embed_tokens.weight",
            "llama.layers.0.self_attn.q_proj.weight",
            f"llama.layers.{last}.mlp.down_proj.weight",
            "lm_head.weight")


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, theta):
    """``x`` ``[B, L, H, D]`` rotated by its position ``0..L-1``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal softmax attention; ``q`` ``[B, L, H, D]``, ``k`` and ``v``
    ``[B, L, Hkv, D]``, each key head serving ``H / Hkv`` query heads."""
    b, l, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    cols = jnp.arange(l)

    @jax.checkpoint  # a backward pass then holds one block's scores
    def block(qb, k, v, rows):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    return jnp.concatenate(
        [block(q[:, s:s + QUERY_BLOCK], k, v,
               jnp.arange(s, min(s + QUERY_BLOCK, l)))
         for s in range(0, l, QUERY_BLOCK)], axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def _layer(x, w, *, heads, kv_heads, eps, theta):
    b, l, hidden = x.shape
    d = hidden // heads
    y = rms_norm(x, w["input_layernorm.weight"], eps)
    q = (y @ _f32(w["self_attn.q_proj.weight"])).reshape(b, l, heads, d)
    k = (y @ _f32(w["self_attn.k_proj.weight"])).reshape(b, l, kv_heads, d)
    v = (y @ _f32(w["self_attn.v_proj.weight"])).reshape(b, l, kv_heads, d)
    a = attention(rope(q, theta), rope(k, theta), v)
    x = x + a.reshape(b, l, hidden) @ _f32(w["self_attn.o_proj.weight"])
    y = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    gate = jax.nn.silu(y @ _f32(w["mlp.gate_proj.weight"]))
    return x + (gate * (y @ _f32(w["mlp.up_proj.weight"]))) \
        @ _f32(w["mlp.down_proj.weight"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    return rms_norm(x, norm, eps) @ _f32(head)


@jax.jit
def _mean_ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def hidden_states(weights, config, ids):
    """The last layer's output ``[B, L, hidden]``, before the final norm."""
    x = _f32(jnp.take(weights["llama.embed_tokens.weight"], ids, axis=0))
    # checkpointed: a backward pass keeps a layer's input, not its insides
    layer = jax.checkpoint(functools.partial(
        _layer, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"])))
    for i in range(config["num_hidden_layers"]):
        prefix = f"llama.layers.{i}."
        x = layer(x, {k[len(prefix):]: a for k, a in weights.items()
                      if k.startswith(prefix)})
    return x


def logits(weights, config, ids, rows=None):
    """float32 logits ``[B, L, vocab]`` of token ids ``[B, L]``; with
    ``rows`` (positions ``[B, R]``, each sequence's own), only those rows
    of L: ``[B, R, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, config, jnp.asarray(ids, jnp.int32))
        if rows is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(rows, jnp.int32)[..., None], axis=1)
        return _head(x, weights["llama.norm.weight"],
                     weights["lm_head.weight"], eps=config["rms_norm_eps"])


# the keys of a configuration that this file reads
SIZES = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "rms_norm_eps", "rope_theta")


# the batch is an argument, not a constant of the program: as a constant it
# would make every seed's program another one, compiled anew
@functools.partial(jax.jit, static_argnames=("sizes",))
def _loss_and_gradients(slabs, weights, ids, labels, *, sizes):
    def f(slabs):
        w = dict(weights)
        for name, d in slabs.items():
            w[name] = _f32(w[name]).at[:d.shape[0]].add(d)
        return _mean_ce(logits(w, dict(sizes), ids)[:, :-1], labels[:, 1:])

    return jax.value_and_grad(f)(slabs)


def loss_and_gradients(weights, config, ids, labels):
    """The mean cross-entropy of position ``t``'s logits against
    ``labels[t + 1]`` over every sequence of the batch, and its float32
    gradient with respect to the first rows (``SLAB`` elements) of each
    ``checked`` tensor: ``(loss, {name: gradient})``."""
    zeros = {}
    for name in checked(config):
        rows, *rest = weights[name].shape
        zeros[name] = jnp.zeros(
            (min(rows, max(SLAB // math.prod(rest), 1)), *rest), jnp.float32)
    value, grads = _loss_and_gradients(
        zeros, weights, jnp.asarray(ids, jnp.int32),
        jnp.asarray(labels, jnp.int32),
        sizes=tuple((k, config[k]) for k in SIZES))
    return float(value), grads


def update_agreement(before, after, grads, lr):
    """How the program's first update agrees with the reference's
    gradient: ``{name: (share, size)}``, ``share`` of the weights having
    moved against their gradient and ``size`` the median move over
    ``lr``. Counted are the weights with the larger half of the gradients
    that are not zero, and of those only the ones stored finely enough
    for a move of ``lr`` to show (an embedding drawn from N(0, 1) and
    kept in bf16 has steps of 2**-8 to 2**-7 at most of its weights, so
    3e-4 shows in one of twenty). ``before`` and ``after`` hold the rows
    of each tensor that ``grads`` covers."""
    out = {}
    for name, g in grads.items():
        g = np.asarray(g)
        w = np.asarray(before[name], np.float32)
        d = np.asarray(after[name], np.float32) - w
        step = float(jnp.finfo(before[name].dtype).eps) * 2.0 ** np.floor(
            np.log2(np.maximum(np.abs(w), 1e-30)))
        counted = (np.abs(g) >= np.median(np.abs(g[g != 0]))) & (step <= lr)
        out[name] = (float(np.mean(np.sign(d[counted])
                                   == -np.sign(g[counted]))),
                     float(np.median(np.abs(d[counted]))) / lr)
    return out
