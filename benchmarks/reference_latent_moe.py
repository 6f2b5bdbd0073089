"""The plain reference of a decoder with latent attention (MLA) and a
routed feed-forward beside a shared expert (the ``kimi_k2`` / DeepSeek-V3
block), in ``jax.numpy`` float32. The PLAIN form only: every position's
latent is expanded to per-head keys and values and attended causally; no
absorption of ``kv_b`` into the query, no cache, no kernel, no batching,
and no import from the program under test. Every size comes from the
configuration's dict (the published ``config.json`` keys, and the three
that state the share: ``n_routed_experts`` held of ``n_router_experts``
scored, from ``first_routed_expert`` on).

Per layer ``l``, pre-norm, no biases, ``H`` heads::

    a    = RMSNorm(x; w_in)
    cq   = RMSNorm(a Wqa; w_qa);   q = cq Wqb -> [T, H, dn + dr] = (q_nope, q_pe)
    kva  = a Wkva -> [T, r + dr] = (ckv, k_pe)        # k_pe: ONE head for all H
    c    = RMSNorm(ckv; w_kva)
    q_pe, k_pe = rope_yarn(q_pe, k_pe, pos)           # half-split rotation
    kv   = c Wkvb -> [T, H, dn + dv] = (k_nope, v)
    s[h, i, j] = (q_nope[i,h] . k_nope[j,h] + q_pe[i,h] . k_pe[j]) * scale,  j <= i
    x    = x + concat_h(softmax(s[h]) v[:, h]) Wo
    m    = RMSNorm(x; w_post)
    l < first_k_dense_replace:  x = x + (silu(m Wg) * (m Wu)) Wd
    else:  g = sigmoid(m Wr);  S = the k largest of (g + b)
           c_e = routed_scaling_factor * g_e / (sum_{e' in S} g_e' + 1e-20)
           x = x + sum_{e in S, e held} c_e (silu(m Wg_e) * (m Wu_e)) Wd_e
                 + (silu(m Wg_s) * (m Wu_s)) Wd_s

``scale = (dn + dr) ** -0.5 * m(mscale_all_dim) ** 2`` with ``m(k) = 0.1 k
ln(factor) + 1``; cos and sin are multiplied by ``m(mscale) /
m(mscale_all_dim)``. The sum over ``S`` runs over the experts HELD here:
what a row's picks on absent experts would add is left out, in the program
and here alike, and the weights ``c_e`` are normalised over all ``k`` picks
all the same.

Departures from the published description, each of them exact: weight
matrices are stored ``[in, out]`` and banks ``[held, in, out]`` (the
program's layout); weights arrive in the served type and are widened to
float32 where they are used; the routed feed-forward is a loop over the
held experts, each applied to every row and weighted by ``c_e`` (zero where
the row did not pick it); one sequence is computed at a time, its
feed-forward ``ROWS`` positions at a time and its scores ``QUERY_BLOCK``
query rows at a time, so that four sequences of 8-12 k fit beside the
engine. Assumptions (the order of operations, the 1e-20, the bias in the
selection alone, the ``mscale`` rule) are listed in the configuration file
under ``assumed``.

**Router ties** are treated as ``reference_mellum.py`` treats them (its
docstring has the argument): a bf16 program and this reference can settle
a row's ``k``-th pick differently where the ``k``-th and ``k+1``-th of ``g
+ b`` lie close, and the row's logits then differ by a whole expert's
output, which no rounding tolerance is meant to cover. Two differences.
The gap is of sigmoid scores: a router logit off by ``d`` moves ``g`` by
``d g (1 - g)``, a twentieth of what it moves a softmax probability at the
scores that win here (``g`` about 0.94), so ``TIE_GAP`` is smaller than
Mellum2's. And a tie counts only where one of the two experts is HELD: the
other way of settling a tie between two absent experts changes the held
experts' weights by the gap over ``k``, under any rounding.

Weights are a dict keyed by the names ``named_parameters()`` gives:
``model.embed_tokens.weight``, ``model.layers.<i>.{input_layernorm,
post_attention_layernorm}.weight``, ``model.layers.<i>.self_attn.{q_a_proj,
q_b_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj}.weight``,
``...self_attn.{q_a_layernorm, kv_a_layernorm}.weight``, a dense layer's
``mlp.{gate,up,down}_proj.weight``, a routed layer's ``mlp.gate.weight``,
``mlp.gate.e_score_correction_bias``, ``mlp.experts.{gate,up,down}_proj``
and ``mlp.shared_experts.{gate,up,down}_proj.weight``,
``model.norm.weight``, ``lm_head.weight``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the dense reference's tolerance, for its reasons: the same bf16 program
# types, the same float32 comparison, the best of ~2e4 logits a few bf16
# steps from the next
from benchmarks.reference import (LOGIT_TOL_ULPS, bf16_step,  # noqa: F401
                                  rms_norm)

#: positions whose feed-forward is computed together, and query rows whose
#: scores are (64 heads x 256 rows x 12 k keys of float32 are 0.8 GB, and
#: the softmax holds them twice: beside 10.4 GB of engine that is the room)
ROWS = 2048
QUERY_BLOCK = 256

# The limits of the comparison, each between two readings of this cell on
# the chip (PERF.md section 6 has the runs): the sound
# program from below, and from above the program lowered to 8-bit
# activations (``controls_latent.py --fault eight_bit_activations``), which
# has to fail, or a fault planted in it.
#
# The tolerance (``LOGIT_TOL_ULPS``, 4 bf16 steps, the dense reference's)
# picks the rows that tie settling rescores. Over 4 x 8 rows the control's
# worst row read 5.10, 6.22 and 2.93: one seed of three passed, so the
# traffic file asks for 32 new tokens a request, 128 rows.
#
# Over 128 rows the worst row does not separate the two: it is one row's
# draw, 8-bit from 5.91, and sound up to 13.06 (a tied row that settling
# its own position brought down from 19.6 only). The count of rows over the tolerance does: ROWS_PER_ROW_OVER lets
# one row in that many lie over it (2 of the check's 128; none of a 12-row
# toy's). Reading below | limit | above, after tie settling: sound runs at
# most 1 row of 128 (2 runs of 57) | 2 | 8-bit 3, 3, 4, 4, 7, 10: the one
# count with room on both sides.
# CEILING_ULPS then holds every row, those let over included: sound 13.06
# (19.6 before its tie was settled) | 64 | a token altered where it is
# produced (197 and 298: one row that scores like a random token, which the
# count lets through).
ROWS_PER_ROW_OVER = 64
CEILING_ULPS = 16 * LOGIT_TOL_ULPS
#
# TIE_GAP: a routed layer in which a row's k-th and (k+1)-th selection
# scores differ by less than this share of the k-th, and one of the two
# experts is held, is one the program may have settled the other way. From
# below: the one row a sound run settled the other way did so at a gap of
# 6.9e-4 (the program's router logits are off by about a hundredth, which
# moves a sigmoid score of 0.94 by 6e-4 of itself). From above this limit
# has no reading of its own: the 8-bit program's three flipped rows had
# gaps of 1.04e-3 to 1.59e-3, inside it, were settled like a sound run's,
# and the control failed all the same by its other rows; at 2**-7 a fifth
# of the rows would have a near-tie layer and a way out. A sound flip at a
# gap over the limit refuses a run, so the limit keeps three times of room
# over the one reading from below.
TIE_GAP = 2.0 ** -9
# TIE_LAYERS: a failing row is scored under every way of settling up to
# this many near-tie layers (the passes double with each) and is not
# compared beyond. From below: no failing row of a sound or an 8-bit run had
# more than 1 near-tie layer, and no row at all more than 2 of its four
# routed layers. From above: 4 is every routed layer, so no row could ever
# be left out.
# TIE_SHARE: the share of the rows that may be left out. From below: no
# row of any run was. From above: the 8-bit control fails by 6 and by 10 of
# its 128 rows, so a share of 6/128 could pass it.
TIE_LAYERS = 2
TIE_SHARE = 2 / 128


def _f32(x):
    return x.astype(jnp.float32)


def engine_copies(name):
    """Whether the serving engine stacks a copy of its own of this named
    weight. It stacks none: its layer loop takes every layer's leaf as the
    array the model holds, so nothing goes to the host."""
    return False


def yarn(config):
    """``(inv_freq [dr / 2], factor on cos and sin, softmax scale)``. With
    ``turn(n) = dr ln(original / (2 pi n)) / (2 ln theta)`` the pair that
    turns ``n`` times within the original length: ``low =
    floor(turn(beta_fast))``, ``high = ceil(turn(beta_slow))``, ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = (1 - ramp_i)
    base_i + ramp_i base_i / factor``; static, at every position."""
    s, d = config["rope_scaling"], config["qk_rope_head_dim"]
    if s.get("type") != "yarn":
        raise ValueError(f"no reference for rope_scaling {s.get('type')!r}")
    theta = config["rope_theta"]
    base = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def turn(n):
        return d * math.log(s["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    def m(k):
        return 0.1 * k * math.log(s["factor"]) + 1.0

    low = max(math.floor(turn(s["beta_fast"])), 0)
    high = min(math.ceil(turn(s["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 - ramp) * base + ramp * base / s["factor"]
    scale = (config["qk_nope_head_dim"] + d) ** -0.5 \
        * m(s["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), \
        m(s["mscale"]) / m(s["mscale_all_dim"]), scale


def rope(x, inv_freq, factor):
    """``x`` ``[L, H, D]`` rotated by its position ``0..L-1``."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(f, x, size):
    """``f`` over blocks of ``size`` rows of ``x`` (and of every other
    leaf of ``x``), one after the other; the rows come back in order."""
    n = jax.tree.leaves(x)[0].shape[0]
    size = min(size, n)
    pad = -n % size
    cut = jax.tree.map(lambda a: jnp.pad(
        a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (-1, size) + a.shape[1:]), x)
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:])[:n],
                        jax.lax.map(f, cut))


def latents(a, w, config):
    """``(q [L, H, dn + dr], c [L, r], k_pe [L, dr])`` of normed rows
    ``a``, rotary parts rotated: what attention reads, and ``(c, k_pe)``
    all that a cache would keep."""
    eps, r = config["rms_norm_eps"], config["kv_lora_rank"]
    dr = config["qk_rope_head_dim"]
    inv_freq, factor, _ = yarn(config)
    q = (rms_norm(a @ _f32(w["self_attn.q_a_proj.weight"]),
                  w["self_attn.q_a_layernorm.weight"], eps)
         @ _f32(w["self_attn.q_b_proj.weight"])).reshape(
             a.shape[0], config["num_attention_heads"], -1)
    kva = a @ _f32(w["self_attn.kv_a_proj_with_mqa.weight"])
    c = rms_norm(kva[:, :r], w["self_attn.kv_a_layernorm.weight"], eps)
    q = jnp.concatenate(
        [q[..., :-dr], rope(q[..., -dr:], inv_freq, factor)], axis=-1)
    return q, c, rope(kva[:, None, r:], inv_freq, factor)[:, 0]


def attention(q, c, k_pe, kv_b, config):
    """Causal softmax attention in the plain form: ``c`` ``[L, r]``
    expanded by ``kv_b`` to every head's ``k_nope`` and ``v``, the one
    ``k_pe`` ``[L, dr]`` beside each head's key; ``q`` ``[L, H, dn +
    dr]``. -> ``[L, H * dv]``."""
    L, H, _ = q.shape
    dn = config["qk_nope_head_dim"]
    scale = yarn(config)[2]
    kv = (c @ _f32(kv_b)).reshape(L, H, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    cols = jnp.arange(L)

    def block(qr):
        qb, rows = qr
        s = (jnp.einsum("qhd,khd->hqk", qb[..., :dn], k_nope)
             + jnp.einsum("qhd,kd->hqk", qb[..., dn:], k_pe)) * scale
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return _blocks(block, (q, cols), QUERY_BLOCK).reshape(L, -1)


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ _f32(gate)) * (m @ _f32(up))) @ _f32(down)


def routing(m, router, bias, config, flip=None):
    """``(c [T, E], gap [T])``: each row's weight on every one of the
    router's ``E`` experts (zero on those it did not pick) and the
    relative gap between its ``k``-th and ``k+1``-th selection score,
    where one of those two experts is held (1 elsewhere: nothing to
    settle). Where ``flip`` (bool ``[T]``) is set, the row's last pick is
    its ``k+1``-th largest instead of its ``k``-th."""
    k = config["num_experts_per_tok"]
    z = m @ _f32(router)
    g = jax.nn.sigmoid(z) if config["scoring_func"] == "sigmoid" \
        else jax.nn.softmax(z, axis=-1)
    score = g + _f32(bias)
    top, which = jax.lax.top_k(score, k + 1)
    kth, after = top[:, k - 1, None], top[:, k, None]
    picked = score >= kth
    if flip is not None:
        picked = jnp.where(flip[:, None], (score > kth) | (score == after),
                           picked)
    c = jnp.where(picked, g, 0.0)
    c = config["routed_scaling_factor"] * c \
        / (jnp.sum(c, axis=-1, keepdims=True) + 1e-20)
    first = config.get("first_routed_expert", 0)
    held = (which[:, k - 1:] >= first) \
        & (which[:, k - 1:] < first + config["n_routed_experts"])
    return c, jnp.where(held.any(-1), ((kth - after) / kth)[:, 0], 1.0)


def routed(m, w, config, flip=None):
    """``(y [T, h], gap [T], picked [T, held])``: the part of the routed
    feed-forward that the experts held here give to rows ``m``, as a loop
    over them, each weighted by the rows' ``c_e``; the rows' gap; and
    which rows picked each held expert (0 or 1)."""
    c, gap = routing(m, w["mlp.gate.weight"],
                     w["mlp.gate.e_score_correction_bias"], config, flip)
    first = config.get("first_routed_expert", 0)
    c = c[:, first:first + config["n_routed_experts"]]

    def expert(y, e):
        wg, wu, wd, ce = e
        return y + ce[:, None] * swiglu(m, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], c.T))
    return y, gap, (c > 0).astype(jnp.int32)


def shared(m, w):
    """The shared expert's part: every row passes it."""
    return swiglu(m, *(w[f"mlp.shared_experts.{p}_proj.weight"]
                       for p in ("gate", "up", "down")))


class _Config(dict):
    """The configuration's dict as a static argument of a jitted
    function: hashed by identity, compared by ``is``."""
    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other


@functools.partial(jax.jit, static_argnames=("config",))
def _attention_half(x, w, *, config):
    a = rms_norm(x, w["input_layernorm.weight"], config["rms_norm_eps"])
    q, c, k_pe = latents(a, w, config)
    return x + attention(q, c, k_pe, w["self_attn.kv_b_proj.weight"],
                         config) @ _f32(w["self_attn.o_proj.weight"])


@functools.partial(jax.jit, static_argnames=("config",))
def _feed_forward_half(x, w, flip, *, config):
    """``(x, gap [L], picks [held])``; a dense layer has no gap to report
    (1) and no picks (zeros)."""
    def rows(xf):
        xb, fb = xf
        m = rms_norm(xb, w["post_attention_layernorm.weight"],
                     config["rms_norm_eps"])
        if "mlp.gate.weight" not in w:
            y = swiglu(m, *(w[f"mlp.{p}_proj.weight"]
                            for p in ("gate", "up", "down")))
            return xb + y, jnp.ones(xb.shape[0]), jnp.zeros(
                (xb.shape[0], config["n_routed_experts"]), jnp.int32)
        y, gap, picked = routed(m, w, config, fb)
        return xb + y + shared(m, w), gap, picked

    x, gap, picked = _blocks(rows, (x, flip), ROWS)
    return x, gap, picked


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    return rms_norm(x, norm, eps) @ _f32(head)


def _layer_weights(weights, i):
    prefix = f"model.layers.{i}."
    return {k[len(prefix):]: a for k, a in weights.items()
            if k.startswith(prefix)}


def hidden_states(weights, config, ids, flips=None, valid=None):
    """``(x [B, L, hidden], gaps [layers, B, L], picks [routed layers,
    held])``: the last layer's output before the final norm, each row's
    routing gap in each layer (1 in a dense layer), and the rows (of
    ``valid`` ``[B, L]`` bool; all of them without it) that picked each
    held expert. ``flips`` (bool ``[layers, B, L]``) settles the marked
    rows' last pick the other way. One sequence at a time."""
    config = config if isinstance(config, _Config) else _Config(config)
    ids = jnp.asarray(ids, jnp.int32)
    B, L = ids.shape
    layers = config["num_hidden_layers"]
    xs, gaps, picks = [], [], 0
    for b in range(B):
        x = _f32(jnp.take(weights["model.embed_tokens.weight"], ids[b],
                          axis=0))
        gaps.append([])
        mine = []
        for i in range(layers):
            w = _layer_weights(weights, i)
            x = _attention_half(x, w, config=config)
            x, gap, picked = _feed_forward_half(
                x, w, jnp.zeros((L,), bool) if flips is None
                else jnp.asarray(flips[i][b]), config=config)
            gaps[-1].append(gap)
            if "mlp.gate.weight" in w:
                if valid is not None:
                    picked = picked * jnp.asarray(valid[b])[:, None]
                mine.append(jnp.sum(picked, axis=0))
        xs.append(x)
        picks = picks + jnp.stack(mine)
    return jnp.stack(xs), jnp.stack(
        [jnp.stack(g) for g in gaps]).transpose(1, 0, 2), picks


def _forward(weights, config, ids, rows, flips=None):
    """``(logits, gaps [layers, ...])`` of all positions, or of ``rows``."""
    with jax.default_matmul_precision("highest"):
        x, gaps, _ = hidden_states(weights, config, ids, flips)
        if rows is not None:
            rows = jnp.asarray(rows, jnp.int32)
            x = jnp.take_along_axis(x, rows[..., None], axis=1)
            gaps = jnp.take_along_axis(
                gaps, jnp.broadcast_to(rows, gaps.shape[:1] + rows.shape), 2)
        return _head(x, weights["model.norm.weight"],
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
    the logits that suit the program's token best (``reference_mellum``'s
    procedure, line for line; the module's docstring says what differs)."""
    config = _Config(config)
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
        "most_near_tie_layers_in_a_row": int(near.sum(0).max()),
        "rows_failing_at_first": int(failing.sum()),
        # what each limit's next reading needs: a failing row's near-tie
        # layers (TIE_LAYERS) and its smallest gap (TIE_GAP: a row whose
        # smallest lies over it is given no way out)
        "most_near_tie_layers_in_a_failing_row": int(
            (near.sum(0) * failing).max()),
        "smallest_gap_in_a_failing_row": float(
            np.where(failing, gaps.min(0), np.inf).min())
        if failing.any() else None,
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
        "worst_gap_bf16_steps_at_first": float(first.max()),
        # every row's gap, a request a list, before and after the flips:
        # what a limit over more or fewer rows would have read
        "gaps_at_first": np.round(first, 2).reshape(rows.shape).tolist(),
        "gaps_at_best": np.round(best, 2).reshape(rows.shape).tolist(),
        "share_of_layers_near_a_tie": float(near.mean()),
        "share_of_layers_at_four_times_the_gap": float(
            (gaps < 4 * TIE_GAP).mean()),
        "smallest_gap": float(gaps.min())}), flush=True)
    if too_many:
        # no row may pass: the least likely token scores far above all
        worst = z.argmin(-1)[..., None] == np.arange(z.shape[-1])
        return np.where(worst, 1e4, 0.0).astype(np.float32)
    # a row that is not compared comes back flat: the caller, which scores
    # every row it is given, finds no gap there; ``compared`` above is the
    # number of rows that the tolerance was held against
    return np.where(left_out[..., None], 0.0, z)


def expert_picks(weights, config, ids, valid=None):
    """``[routed layers, held]``: how many of the rows of ``ids`` ``[B,
    L]`` (those of ``valid``, where given) picked each held expert: what
    the program's own load counters must show for the same rows."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden_states(weights, config, ids,
                                        valid=valid)[2])
