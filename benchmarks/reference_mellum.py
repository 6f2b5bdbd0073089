"""The plain reference of a decoder whose layers differ in kind and route
their feed-forward, in ``jax.numpy`` float32: RMSNorm, grouped-query
attention that is full or windowed by the layer's kind, a rotary table by
the layer's kind (plain, or static YaRN with a factor on cos and sin), a
routed SwiGLU feed-forward (float32 softmax over all experts, the ``k``
largest, renormalised, every pick computed), an untied head, and the mean
next-token cross-entropy. No kernel, no cache, no sorting of rows by
expert, and no import from the program under test. Every size comes from
the configuration's dict (the published ``config.json`` keys).

Per layer ``l`` of kind ``layer_types[l]``, pre-norm, no biases::

    a  = RMSNorm(x; w_in)
    q  = a Wq -> [T, H, d];  k = a Wk -> [T, Hkv, d];  v = a Wv
    q, k = rope_kind(q, k, pos)            # half-split rotation
    s[i, j] = q_i . k_j / sqrt(d), query head h reads KV head h // (H / Hkv)
    allowed(i, j) = j <= i                               (full_attention)
                  = j <= i and i - j < sliding_window    (sliding_attention)
    x  = x + softmax(s | allowed) v Wo
    m  = RMSNorm(x; w_post)
    g  = softmax(m Wr);  S = the k largest of g;  c_e = g_e / sum_S g
    x  = x + sum_{e in S} c_e (silu(m Wg_e) * (m Wu_e)) Wd_e

Departures from the published description, each of them exact:

* a weight matrix is stored ``[in, out]`` and an expert bank ``[E, in,
  out]`` (the program's layout): ``x @ w`` for the published ``x @ w.T``;
* weights arrive in the served type (bf16) and are widened to float32
  layer by layer, and an expert's three matrices expert by expert;
* the routed feed-forward is a loop over ALL experts, each applied to
  every row and weighted by ``c_e`` (zero where the row did not pick it):
  the same sum, without gathering rows;
* attention scores are computed ``QUERY_BLOCK`` query rows at a time.

Left out because the published config has no key for it (the
configuration file lists these under ``assumed``): normalisation of q or
k, a shared expert, a router bias or score correction, a multi-token
prediction head, a load-balancing loss.

**Router ties.** A bf16 program and this float32 reference can pick a
different ``k``-th expert where a row's ``k``-th and ``k+1``-th router
probabilities lie close: on the chip the program's router logits differ
from these by about a hundredth (its hidden states are rounded to bf16
layer after layer), and the row's logits then differ by a whole (small)
expert's output: up to ten bf16 steps at the published widths, which no
rounding tolerance is meant to cover. ``routing`` therefore also returns
each row's relative gap ``(p_k - p_{k+1}) / p_k``, and takes a ``flip``:
a flipped row's ``k``-th pick is its ``k+1``-th largest instead.
``logits(..., rows=...)``, which is the call the serving driver's
comparison makes, first scores every row under this reference's own
routing. Only a row whose token then lies more than ``LOGIT_TOL_ULPS``
under the best, and which has layers with a gap under ``TIE_GAP``, is
scored again under each way of settling those layers (one more forward
pass a combination, all such rows at once; a row's flip moves other rows
by one key in thousands), and gets the logits of the way that suits its
token best: the token still has to be within the tolerance of the best
under ONE consistent routing. A failing row with more than ``TIE_LAYERS``
such layers is not compared (its logits come back flat, so the caller
finds no gap there); a ``router_ties`` line says which rows were settled
by a flip, through how many layers and up to what gap, and how many rows
were not compared; more than ``TIE_SHARE`` of the rows, and every row is
made to fail. ``LOGIT_TOL_ULPS`` is not widened, and a row that routed or
masked wrongly anywhere else still fails: ``benchmarks/controls.py``
plants such faults in the program at the cell's own widths and runs this
comparison on them.

Weights are a dict keyed by the names ``named_parameters()`` gives:
``mellum.embed_tokens.weight``, ``mellum.layers.<i>.{input_layernorm,
post_attention_layernorm}.weight``, ``mellum.layers.<i>.self_attn.{q,k,v,
o}_proj.weight``, ``mellum.layers.<i>.mlp.gate.weight`` (the router),
``mellum.layers.<i>.mlp.experts.{gate,up,down}_proj`` (the banks),
``mellum.norm.weight``, ``lm_head.weight``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the tolerances are the dense reference's own, for its reasons: the same
# bf16 program types, the same float32 comparison (the loss averages its
# roundings out; AdamW's first update follows the gradient's sign; the
# best of ~1e5 logits is a few bf16 steps from the next)
from benchmarks.reference import (LOGIT_TOL_ULPS, LOSS_TOL,  # noqa: F401
                                  QUERY_BLOCK, SIGN_TOL, SIZE_TOL, SLAB,
                                  bf16_step, rms_norm, update_agreement)

SLIDING, FULL = "sliding_attention", "full_attention"

# The three limits of the routing comparison (module docstring), each
# between two readings (my chip runs, PR 27, at the published widths and 8
# layers; PERF.md section 6 has the runs).
#
# TIE_GAP: a layer in which a row's k-th and (k+1)-th router probabilities
# differ by less than this share of the k-th is one the program may have
# settled the other way. From below: the largest gap at which the bf16
# program was seen to route the other way, in a row that failed until the
# layer was flipped, is 0.0148 (rows at 0.0026 and 0.0148: 7.1 and 6.3 bf16
# steps before, 0 after; the program's router logits are off by about a
# hundredth), so 2**-5 is twice that. From above: a quarter of the rows
# have a gap under 2**-5 in a given layer and nearly half under 2**-4,
# where a row of 8 layers has more than TIE_LAYERS such layers with
# probability 0.09-0.38 (``share_over_the_layers_at_twice_the_gap`` on the
# line, 12 runs): a failing row would then be excused one time in four, or
# given 2**5 and more ways.
TIE_GAP = 2.0 ** -5
# TIE_LAYERS: near-tie layers a failing row may have and still be scored
# (2**4 - 1 more passes at most, 1.7 s each on the chip; none where every
# row passes under the reference's own routing: 7 runs of 8). From
# below: no row needed more than 2 layers settled the other way. From
# above: the passes double with each, and so do a faulty program's ways
# out (the controls' worst rows stayed at 5.6 to 50 steps under all 16).
# TIE_SHARE: the share of the rows that may go uncompared, for failing
# with more near-tie layers than that. From below: 1 row of 32 in one run
# of eight, none in the other seven (a row that fails is a row misrouted,
# and those are the rows with many near ties: 0 to 3 of 32 rows have more
# than four). From above: each row excused is one a faulty program need
# not pass; the weakest control (a dropped pick) still leaves three rows
# over the tolerance under every way. Two rows in 32.
TIE_LAYERS = 4
TIE_SHARE = 2 / 32


def _f32(x):
    return x.astype(jnp.float32)


def engine_copies(name):
    """Whether the serving engine stacks a copy of its own of this named
    weight, so that the model's copy goes to host memory for this
    reference: a layer's projections, norms and router. The expert banks
    (nine tenths of a layer) are shared between the model and the engine
    like the embedding, the final norm and the head: the engine's layer
    loop takes each layer's bank as the array it is."""
    return ".layers." in name and ".experts." not in name


def rope_table(params, head_dim):
    """``(inv_freq [head_dim / 2], factor)`` of one kind's entry of
    ``rope_parameters``. ``default``: ``theta ** (-2i / d)``, factor 1.
    ``yarn``, static: with ``turn(n) = d ln(original / (2 pi n)) / (2 ln
    theta)`` the pair index that turns ``n`` times within the original
    length, ``low = floor(turn(beta_fast))``, ``high =
    ceil(turn(beta_slow))``, ``ramp_i = clip((i - low) / (high - low), 0,
    1)``, ``inv_freq_i = (1 - ramp_i) base_i + ramp_i base_i / factor``;
    cos and sin are multiplied by ``attention_factor`` (``0.1 ln factor +
    1`` where the entry does not state it)."""
    d = head_dim
    base = float(params["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    if params.get("rope_type", "default") == "default":
        return base.astype(np.float32), 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"no reference for rope_type "
                         f"{params['rope_type']!r}")

    def turn(n):
        return d * math.log(params["original_max_position_embeddings"]
                            / (2 * math.pi * n)) \
            / (2 * math.log(params["rope_theta"]))

    low = max(math.floor(turn(params["beta_fast"])), 0)
    high = min(math.ceil(turn(params["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    factor = params.get("attention_factor") \
        or 0.1 * math.log(params["factor"]) + 1.0
    return ((1 - ramp) * base + ramp * base / params["factor"]).astype(
        np.float32), float(factor)


def rope(x, inv_freq, factor):
    """``x`` ``[B, L, H, D]`` rotated by its position ``0..L-1``."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def allowed(rows, cols, window):
    """``[len(rows), len(cols)]`` bool: may query position ``rows[i]``
    see key position ``cols[j]``: no later one, and under a ``window``
    itself and the ``window - 1`` before it."""
    ok = cols[None, :] <= rows[:, None]
    if window is not None:
        ok = ok & (rows[:, None] - cols[None, :] < window)
    return ok


def attention(q, k, v, window=None):
    """Causal softmax attention, windowed where ``window`` is given;
    ``q`` ``[B, L, H, D]``, ``k`` and ``v`` ``[B, L, Hkv, D]``, each key
    head serving ``H / Hkv`` query heads."""
    b, l, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    cols = jnp.arange(l)

    @jax.checkpoint  # a backward pass then holds one block's scores
    def block(qb, k, v, rows):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        scores = jnp.where(allowed(rows, cols, window), scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    return jnp.concatenate(
        [block(q[:, s:s + QUERY_BLOCK], k, v,
               jnp.arange(s, min(s + QUERY_BLOCK, l)))
         for s in range(0, l, QUERY_BLOCK)], axis=1)


def routing(m, router, k, flip=None):
    """``(c [..., E], gap [...])``: each row's weight on every expert
    (the ``k`` largest of a softmax over all of them, renormalised; zero
    elsewhere) and the relative gap between its ``k``-th and ``k+1``-th
    probability. Where ``flip`` (bool ``[...]``) is set the row's last
    pick is its ``k+1``-th largest instead of its ``k``-th."""
    g = jax.nn.softmax(m @ _f32(router), axis=-1)
    top = jax.lax.top_k(g, k + 1)[0]
    kth, after = top[..., k - 1, None], top[..., k, None]
    picked = g >= kth
    if flip is not None:
        picked = jnp.where(flip[..., None], (g > kth) | (g == after), picked)
    c = jnp.where(picked, g, 0.0)
    return c / jnp.sum(c, axis=-1, keepdims=True), \
        ((kth - after) / kth)[..., 0]


def routed(m, router, gate, up, down, k, flip=None):
    """The routed feed-forward of rows ``m`` ``[..., h]`` as a loop over
    all experts, each weighted by the rows' ``c_e``; the rows' gap; and
    how many rows picked each expert ``[E]``."""
    c, gap = routing(m, router, k, flip)

    def expert(y, w):
        wg, wu, wd, ce = w
        out = (jax.nn.silu(m @ _f32(wg)) * (m @ _f32(wu))) @ _f32(wd)
        return y + ce[..., None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (gate, up, down, jnp.moveaxis(c, -1, 0)))
    return y, gap, jnp.sum(c > 0, axis=tuple(range(c.ndim - 1)))


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "window", "k"))
def _layer(x, w, inv_freq, factor, flip, *, heads, kv_heads, head_dim, eps,
           window, k):
    b, l, _ = x.shape
    y = rms_norm(x, w["input_layernorm.weight"], eps)
    q = (y @ _f32(w["self_attn.q_proj.weight"])).reshape(
        b, l, heads, head_dim)
    kk = (y @ _f32(w["self_attn.k_proj.weight"])).reshape(
        b, l, kv_heads, head_dim)
    v = (y @ _f32(w["self_attn.v_proj.weight"])).reshape(
        b, l, kv_heads, head_dim)
    a = attention(rope(q, inv_freq, factor), rope(kk, inv_freq, factor), v,
                  window)
    x = x + a.reshape(b, l, heads * head_dim) \
        @ _f32(w["self_attn.o_proj.weight"])
    y = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    out, gap, picks = routed(
        y, w["mlp.gate.weight"], w["mlp.experts.gate_proj"],
        w["mlp.experts.up_proj"], w["mlp.experts.down_proj"], k, flip)
    return x + out, gap, picks


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    return rms_norm(x, norm, eps) @ _f32(head)


@jax.jit
def _mean_ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def hidden_states(weights, config, ids, flips=None):
    """``(x [B, L, hidden], gaps [layers, B, L], picks [layers, E])``: the
    last layer's output before the final norm, each row's routing gap in
    each layer, and the rows that picked each expert. ``flips`` (bool
    ``[layers, B, L]``) settles the marked rows' last pick the other way
    (``routing``)."""
    x = _f32(jnp.take(weights["mellum.embed_tokens.weight"], ids, axis=0))
    tables = {kind: rope_table(p, config["head_dim"])
              for kind, p in config["rope_parameters"].items()}
    gaps, picks = [], []
    for i, kind in enumerate(
            config["layer_types"][:config["num_hidden_layers"]]):
        # checkpointed: a backward pass keeps a layer's input, not its
        # insides
        layer = jax.checkpoint(functools.partial(
            _layer, heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], eps=config["rms_norm_eps"],
            window=config["sliding_window"] if kind == SLIDING else None,
            k=config["num_experts_per_tok"]))
        prefix = f"mellum.layers.{i}."
        inv_freq, factor = tables[kind]
        x, gap, n = layer(
            x, {k[len(prefix):]: a for k, a in weights.items()
                if k.startswith(prefix)}, jnp.asarray(inv_freq),
            jnp.float32(factor), jnp.zeros(ids.shape, bool)
            if flips is None else jnp.asarray(flips[i]))
        gaps.append(gap)
        picks.append(n)
    return x, jnp.stack(gaps), jnp.stack(picks)


def _forward(weights, config, ids, rows, flips=None):
    """``(logits, gaps [layers, ...])`` of all positions, or of ``rows``."""
    with jax.default_matmul_precision("highest"):
        x, gaps, _ = hidden_states(weights, config,
                                   jnp.asarray(ids, jnp.int32), flips)
        if rows is not None:
            rows = jnp.asarray(rows, jnp.int32)
            x = jnp.take_along_axis(x, rows[..., None], axis=1)
            gaps = jnp.take_along_axis(
                gaps, jnp.broadcast_to(rows, gaps.shape[:1] + rows.shape), 2)
        return _head(x, weights["mellum.norm.weight"],
                     weights["lm_head.weight"],
                     eps=config["rms_norm_eps"]), gaps


def logits_and_gaps(weights, config, ids, rows=None):
    """float32 logits ``[B, L, vocab]`` of token ids ``[B, L]`` and each
    row's smallest routing gap over the layers ``[B, L]``; with ``rows``
    (positions ``[B, R]``, each sequence's own) only those rows of L."""
    z, gaps = _forward(weights, config, ids, rows)
    return z, jnp.min(gaps, axis=0)


def _steps(zt, token):
    """How many bf16 steps ``token``'s logit lies under the best."""
    return float(zt.max() - zt[token]) / bf16_step(np.abs(zt).max())


def logits(weights, config, ids, rows=None):
    """The logits of :func:`logits_and_gaps`. With ``rows`` this is the
    serving comparison's call (the sequences are prompts followed by the
    program's own tokens, ``rows`` the positions that predicted each of
    them): a row that fails under this reference's own routing and has
    near-tie layers is scored under each way of settling them and given
    the logits that suit the program's token best, as the module's
    docstring says."""
    z, gaps = _forward(weights, config, ids, rows)
    if rows is None:
        return z
    ids, rows, gaps = np.asarray(ids), np.asarray(rows), np.asarray(gaps)
    z, near = np.array(z), gaps < TIE_GAP                  # [layers, B, R]
    # the token the program chose at a row is the next one of its sequence
    chosen = np.take_along_axis(ids, rows + 1, 1)
    where = list(np.ndindex(rows.shape))
    first = np.array([_steps(z[b, r], chosen[b, r]) for b, r in where])
    failing = (first > LOGIT_TOL_ULPS).reshape(rows.shape)
    left_out = failing & (near.sum(0) > TIE_LAYERS)
    # a failing row's ways: the non-empty subsets of its near-tie layers,
    # in the order of a binary counter; pass j settles every such row's
    # j-th way
    settle = failing & ~left_out
    layers = [np.flatnonzero(near[:, b, r]) if settle[b, r] else ()
              for b, r in where]
    best, way = first.copy(), [()] * len(where)
    passes = 1 << max(map(len, layers), default=0)
    for j in range(1, passes):
        flips = np.zeros(near.shape[:1] + ids.shape, bool)
        mine = []
        for i, (b, r) in enumerate(where):
            if j < 1 << len(layers[i]):
                on = [n for bit, n in enumerate(layers[i]) if j >> bit & 1]
                flips[on, b, rows[b, r]] = True
                mine.append((i, b, r, on))
        zj = np.asarray(_forward(weights, config, ids, rows, flips)[0])
        for i, b, r, on in mine:
            steps = _steps(zj[b, r], chosen[b, r])
            if steps < best[i]:
                best[i], way[i], z[b, r] = steps, on, zj[b, r]
    too_many = bool(left_out.mean() > TIE_SHARE)
    print(json.dumps({
        "info": "router_ties", "rows": int(rows.size), "tie_gap": TIE_GAP,
        "tie_layers": TIE_LAYERS, "passes": passes,
        "rows_with_near_tie_layers": int((near.sum(0) > 0).sum()),
        "rows_failing_at_first": int(failing.sum()),
        # of those, the rows scored better under another way: bf16 steps
        # before and after, the layers settled the other way and the
        # largest of their gaps
        "settled_by_a_flip": [
            [round(float(first[i]), 3), round(float(best[i]), 3),
             len(way[i]), float(gaps[way[i], b, r].max())]
            for i, (b, r) in enumerate(where) if way[i]],
        "not_compared": int(left_out.sum()),
        "compared": int(rows.size - left_out.sum()),
        "max_share_not_compared": TIE_SHARE, "fails_on_share": too_many,
        "share_over_the_layers": float(
            (near.sum(0) > TIE_LAYERS).mean()),
        "share_over_the_layers_at_twice_the_gap": float(
            ((gaps < 2 * TIE_GAP).sum(0) > TIE_LAYERS).mean()),
        "smallest_gap": float(gaps.min())}), flush=True)
    if too_many:
        # no row may pass: the least likely token scores far above all
        worst = z.argmin(-1)[..., None] == np.arange(z.shape[-1])
        return np.where(worst, 1e4, 0.0).astype(np.float32)
    # a row that is not compared comes back flat: the caller, which scores
    # every row it is given, finds no gap there; ``compared`` above is the
    # number of rows that the tolerance was held against
    return np.where(left_out[..., None], 0.0, z)


def expert_picks(weights, config, ids):
    """``[layers, E]``: how many of the rows of ``ids`` ``[B, L]`` picked
    each expert (what the program's own load counters must show for the
    same rows)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden_states(
            weights, config, jnp.asarray(ids, jnp.int32))[2])


def checked(config):
    """The tensors whose first update is held to the reference's
    gradient: the embedding's scatter, a projection behind rotary
    attention and the router in the first layer, the last layer's
    expert bank, and the head."""
    last = config["num_hidden_layers"] - 1
    return ("mellum.embed_tokens.weight",
            "mellum.layers.0.self_attn.q_proj.weight",
            "mellum.layers.0.mlp.gate.weight",
            f"mellum.layers.{last}.mlp.experts.down_proj",
            "lm_head.weight")


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
    ids, labels = jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32)

    def f(slabs):
        w = dict(weights)
        for name, d in slabs.items():
            w[name] = _f32(w[name]).at[:d.shape[0]].add(d)
        return _mean_ce(logits_and_gaps(w, config, ids)[0][:, :-1],
                        labels[:, 1:])

    value, grads = jax.value_and_grad(f)(zeros)
    return float(value), grads
