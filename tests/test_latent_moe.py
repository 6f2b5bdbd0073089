"""A decoder with latent attention, a routed share beside a shared expert
and a leading dense layer (``text/models/kimi_k2.py``) against its plain
float32 reference (``benchmarks/reference_latent_moe.py``), tiny and
seeded, on the CPU: hidden 64, 4 heads, ranks 24 / 16, a rotary part of 8
under YaRN over an original 16, 16 experts top 4, 1 + 2 layers. The
model's ``forward``; the absorbed decode form against the plain one on the
same latents; ``serving.Engine``'s prefill, chunked prefill and decode
through the latent pool, logit-level and teacher-forced as the serving
driver of the benchmark compares them; the share tied to the model; the
refusals and the counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks import reference_latent_moe as ref
from paddle_tpu.nn import routed_ffn as R
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import Engine
from paddle_tpu.serving.speculative import SpecConfig
from paddle_tpu.text import generation as G
from paddle_tpu.text.models.kimi_k2 import (KIMI_K2_TINY, KimiK2Config,
                                            KimiK2ForCausalLM)

# (the selection bias drawn wide, so that it is live among 16 experts)
CFG = dataclasses.replace(KIMI_K2_TINY, initializer_range=0.3)
# the same model as one chip of four that share each layer: experts 4-7
SHARE = dataclasses.replace(CFG, n_routed_experts=4, n_router_experts=16,
                            first_routed_expert=4)


def _model(cfg, seed=7):
    paddle.seed(seed)
    m = KimiK2ForCausalLM(cfg)
    m.eval()
    return m, {name: p._data for name, p in m.named_parameters()}


@pytest.fixture(scope="module", params=[CFG, SHARE], ids=["whole", "share"])
def built(request):
    return (request.param,) + _model(request.param)


def _ids(n, seed=0, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def test_the_published_rotary_table_and_scale():
    inv, factor, scale = KimiK2Config().rope()
    want_inv, want_factor, want_scale = ref.yarn(
        dataclasses.asdict(KimiK2Config()))
    np.testing.assert_array_equal(inv, want_inv)
    assert factor == want_factor == 1.0
    assert abs(scale - 0.144680) < 1e-6 and scale == want_scale
    base = 50000.0 ** (-np.arange(32) / 32)
    # pairs 0-8 keep their frequency, pairs 20-31 have it divided by 64
    np.testing.assert_allclose(inv[:9], base[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], base[20:] / 64, rtol=1e-6)


def test_forward_logits_match_the_reference(built):
    cfg, model, weights = built
    ids = _ids(40, batch=2)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want, gaps = ref.logits_and_gaps(weights, dataclasses.asdict(cfg), ids)
    assert got.shape == (2, 40, cfg.vocab_size)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    assert np.all(np.asarray(gaps) > 0)


def test_it_trains_with_every_expert_held():
    paddle.seed(11)
    m = KimiK2ForCausalLM(CFG)
    m.train()
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=m.parameters())
    ids = paddle.to_tensor(_ids(24, seed=3, batch=2))
    losses = []
    for _ in range(6):
        loss = m(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05
    bank = dict(m.named_parameters())["model.layers.1.mlp.experts.up_proj"]
    assert bank.shape == [16, 64, 32]


# -- the router and the share -------------------------------------------------

def _todays_softmax_route(m, wr, k):
    g = jax.nn.softmax(jnp.dot(m, wr, preferred_element_type=jnp.float32),
                       axis=-1)
    vals, experts = jax.lax.top_k(g, k)
    return experts.astype(jnp.int32), vals / jnp.sum(vals, -1, keepdims=True)


def _rows_and_router(seed=0, T=24, h=64, E=16):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (T, h), jnp.float32),
            jax.random.normal(k2, (h, E), jnp.float32) * 0.3,
            jax.random.normal(k3, (E,), jnp.float32) * 0.2)


def test_softmax_routing_is_bitwise_what_it_was():
    m, wr, _ = _rows_and_router()
    for k in (2, 4):
        got, want = R.route(m, wr, k), _todays_softmax_route(m, wr, k)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


def test_the_bias_picks_and_never_weighs():
    m, wr, bias = _rows_and_router()
    g = np.asarray(jax.nn.sigmoid(m @ wr))
    experts, weights = R.route(m, wr, 4, scoring="sigmoid", bias=bias,
                               scale=2.5)
    experts, weights = np.asarray(experts), np.asarray(weights)
    want = np.argsort(-(g + np.asarray(bias)), axis=-1)[:, :4]
    assert (np.sort(experts, -1) == np.sort(want, -1)).all()
    assert (np.sort(experts, -1) != np.sort(
        np.argsort(-g, axis=-1)[:, :4], -1)).any()      # the bias is live
    picked = np.take_along_axis(g, experts, -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)


def _banks(seed, E, h=64, f=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (E, h, f)) * 0.1,
            jax.random.normal(ks[1], (E, h, f)) * 0.1,
            jax.random.normal(ks[2], (E, f, h)) * 0.1)


@pytest.mark.parametrize("target,held", [(5, True), (12, False)])
def test_every_token_to_one_expert_held_or_absent(target, held):
    """A bias that sends every row's first pick to one expert: held here,
    it is computed for every row; absent, the routed part of a row that
    picked nothing held is zero, and the layer's output is the shared
    expert's."""
    m, wr, _ = _rows_and_router()
    bias = jnp.zeros(16).at[target].set(10.0)
    wg, wu, wd = _banks(1, 16)
    first, n = 4, 4                                   # experts 4-7 held
    y, picks = R.routed_ffn(m, wr, wg[4:8], wu[4:8], wd[4:8], 1,
                            first=first, scoring="sigmoid", bias=bias)
    assert (np.asarray(picks) == ([0, 24, 0, 0] if held else [0] * n)).all()
    if held:
        assert np.abs(np.asarray(y)).min(axis=-1).max() > 0
    else:
        assert not np.asarray(y).any()
        sg, su, sd = (b[0] for b in _banks(2, 1))
        lw = {"wr": wr, "rb": bias, "wg": wg[4:8], "wu": wu[4:8],
              "wd": wd[4:8], "sg": sg, "su": su, "sd": sd}
        out, _ = G._feed_forward(m, lw, 1, None, (("first", 4),
                                                  ("scoring", "sigmoid")))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray((jax.nn.silu(m @ sg) * (m @ su))
                                        @ sd), rtol=1e-6)
        assert np.asarray(out).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed parts that all 4 shares of
    4 experts give, and the shared expert counted once, add up to what the
    uncut reference gives for the whole layer; each share's picks are the
    uncut layer's picks on its experts."""
    _, weights = _model(CFG)
    layer = ref._layer_weights(weights, 2)
    config = dataclasses.asdict(CFG)
    m = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, whole_picked = ref.routed(m, layer, config)
        want = whole + ref.shared(m, layer)
    router = dict(scoring="sigmoid", scale=CFG.routed_scaling_factor,
                  bias=layer["mlp.gate.e_score_correction_bias"])
    banks = [layer[f"mlp.experts.{p}_proj"] for p in ("gate", "up", "down")]
    total, picks = 0.0, []
    for first in (0, 4, 8, 12):
        y, n = R.routed_ffn(m, layer["mlp.gate.weight"],
                            *(b[first:first + 4] for b in banks), 4,
                            first=first, **router)
        total, picks = total + y, picks + list(np.asarray(n))
        # ... and the reference given the same share computes the same part
        part, _, _ = ref.routed(m, dict(layer, **{
            f"mlp.experts.{p}_proj": b[first:first + 4]
            for p, b in zip(("gate", "up", "down"), banks)}), dict(
                config, n_routed_experts=4, n_router_experts=16,
                first_routed_expert=first))
        np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                   atol=2e-5)
    shared = ref.shared(m, layer)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5)
    assert picks == list(np.asarray(whole_picked.sum(0)))
    assert sum(picks) == 40 * 4


# -- the two attention forms ----------------------------------------------------

def _latents(S=3, H=4, r=128, dr=64, dn=16, dv=16, bs=8, mb=6, seed=0,
             dtype=jnp.float32):
    """A latent pool of lane-wide lines with every slot's context written
    through its block table, queries, and ``kv_b``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = -(-(r + dr) // 128) * 128
    nb = 1 + S * mb
    lines = jax.random.normal(ks[0], (nb, bs, 1, r + dr), dtype)
    pool = jnp.pad(lines, [(0, 0)] * 3 + [(0, width - r - dr)]).at[0].set(0)
    tables = (1 + np.arange(S * mb).reshape(S, mb)).astype(np.int32)
    tables = np.random.default_rng(seed).permutation(
        tables.ravel()).reshape(S, mb)
    wkv = jax.random.normal(ks[1], (r, H, dn + dv), dtype) * 0.1
    q_nope = jax.random.normal(ks[2], (S, H, dn), dtype)
    q_pe = jax.random.normal(ks[3], (S, H, dr), dtype)
    write_pos = jnp.asarray([mb * bs - 1, 13, -1][:S], jnp.int32)
    return pool, jnp.asarray(tables), wkv, q_nope, q_pe, write_pos


def _plain(pool, tables, wkv, q_nope, q_pe, write_pos, scale):
    """Every cached latent expanded to per-head keys and values."""
    S, H, dn = q_nope.shape
    r, dr = wkv.shape[0], q_pe.shape[-1]
    view = np.asarray(G._paged_view(pool, tables, pool.shape[1]))[:, :, 0]
    out = np.zeros((S, H, wkv.shape[-1] - dn), np.float32)
    for s in range(S):
        n = int(write_pos[s]) + 1
        if n <= 0:
            continue
        c, k_pe = view[s, :n, :r], view[s, :n, r:r + dr]
        kv = np.einsum("kr,rhd->khd", c, np.asarray(wkv))
        sc = (np.einsum("hd,khd->hk", np.asarray(q_nope[s]), kv[..., :dn])
              + np.einsum("hd,kd->hk", np.asarray(q_pe[s]), k_pe)) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[s] = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True),
                           kv[..., dn:])
    return out


@pytest.mark.parametrize("how", ["gathered", "kernel"])
def test_absorbed_decode_is_plain_attention_on_the_same_latents(how):
    """``q_nope Wk_h^T`` against the latent as it lies, ``Wv_h`` after:
    the same numbers as expanding every line. The kernel (through the
    interpreter) reads one pool, its values the first 128 numbers of a
    line; a slot that does not decode reads nothing and returns zeros."""
    pool, tables, wkv, q_nope, q_pe, write_pos = _latents()
    dn, r, scale = q_nope.shape[-1], wkv.shape[0], 0.3
    q = G._latent_line(jnp.einsum("shd,rhd->shr", q_nope, wkv[..., :dn]),
                       q_pe[:, :, None], pool.shape[-1])[:, :, 0]
    o = pa.paged_attention(q, pool, None, tables, write_pos, scale=scale,
                           value_dim=r, interpret=how == "kernel")
    assert o.shape == (3, 4, r)
    got = np.asarray(jnp.einsum("shr,rhd->shd", o, wkv[..., dn:]))
    want = _plain(pool, tables, wkv, q_nope, q_pe, write_pos, scale)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5, rtol=2e-5)
    if how == "kernel":
        assert not got[2].any()


def test_a_pool_without_v_says_how_wide_its_values_are():
    pool, tables, wkv, q_nope, q_pe, write_pos = _latents()
    q = jnp.zeros((3, 4, pool.shape[-1]))
    with pytest.raises(ValueError, match="value_dim"):
        pa.paged_attention(q, pool, None, tables, write_pos)
    with pytest.raises(ValueError, match="value_dim"):
        pa.paged_attention(q, pool, pool, tables, write_pos, value_dim=128)


# -- the engine through the latent pool ---------------------------------------

def _worst_gap(weights, config, sample, handles):
    """The benchmark's comparison (``benchmarks/drivers/serve.py``)."""
    worst = 0.0
    for (prompt, n), h in zip(sample, handles):
        assert h.finish_reason == "length" and len(h.tokens) == n
        seq = np.concatenate([prompt, np.asarray(h.tokens, np.int32)])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)[None]
        z = np.asarray(ref.logits_and_gaps(weights, config, seq[None],
                                           rows)[0])[0]
        for zt, tok in zip(z, h.tokens):
            worst = max(worst, float(zt.max() - zt[tok])
                        / ref.bf16_step(np.abs(zt).max()))
    return worst


# a bucket prefill (9 < chunk), a chunked one (20), and one whose chunks
# and decode steps run past the YaRN original length (16): 45 + 12
SAMPLE = [(9, 6), (20, 6), (45, 12)]


@pytest.fixture(scope="module", params=[(4, 4, None), (3, 8, 16)],
                ids=["blocks_of_4", "blocks_of_8_tiles_of_16"])
def served(built, request):
    """The sample through engines of two geometries; the second walks the
    chunk's cached prefix in tiles of 16 lines, so the 45-token prompt's
    last chunk folds three tiles."""
    cfg, model, weights = built
    n_slots, block_size, tile = request.param
    mp = pytest.MonkeyPatch()
    if tile:
        mp.setattr(G, "_LATENT_TILE", tile)
    from paddle_tpu.serving import engine as E
    for f in (E._PAGED_CHUNK, E._PAGED_CHUNK_DONATED):
        f.clear_cache()
    try:
        eng = Engine(model, n_slots=n_slots, max_len=64,
                     block_size=block_size, prefill_chunk=16,
                     prefix_sharing=False)
        sample = [(_ids(n, seed=20 + n), new) for n, new in SAMPLE]
        handles = [eng.submit(p, max_new_tokens=new) for p, new in sample]
        eng.drain()
    finally:
        mp.undo()
        for f in (E._PAGED_CHUNK, E._PAGED_CHUNK_DONATED):
            f.clear_cache()
    return cfg, weights, eng, sample, handles


def test_engine_prefill_chunk_and_decode_match_the_reference(served):
    cfg, weights, eng, sample, handles = served
    st = eng.stats()
    assert st["chunk_program"] and st["prefill_buckets"] == [16]
    assert st["chunk_steps"] == 2 + 3
    assert _worst_gap(weights, dataclasses.asdict(cfg), sample,
                      handles) < 0.05


def test_the_cache_holds_one_pool_of_lines_and_counts_them(served):
    cfg, weights, eng, sample, handles = served
    assert eng.cache.vc is None and not eng.cache.values
    # 16 + 8 numbers a line, padded to whole lanes
    assert eng.cache.kc.shape[3:] == (1, 128) == eng.cache.line
    st = eng.stats()
    assert st["kv_cache_bytes"] == eng.cache.kc.size * 4
    assert st["kv_cache_bytes"] == 3 * eng.cache.pool.n_blocks \
        * eng.block_size * 128 * 4
    assert st["decode_lines_seen"] == {"calls": 0, "lines": 0,
                                       "in_window": 0}
    # the probes of the program set mirror the live calls: no V pool
    probes = {kind: args for kind, _, _, args, _, _ in
              eng._aot_probe_specs(buckets=[16])}
    assert set(probes) == {"prefill", "decode", "chunk"}
    assert all(args[2] is None and args[1].shape == eng.cache.kc.shape
               for args in probes.values())
    latent = st["latent"]
    assert latent["line_bytes"] == (16 + 8) * 4
    assert latent["decode_calls"] == st["decode_steps"] > 0
    # every decode call's active rows see their context and the new line
    assert latent["lines"] == sum(
        len(p) + i + 1 for p, n in sample for i in range(n - 1))
    # every call of the chunk program: the lines up to its last position
    # (prompts of 20 and 45 in chunks of 16, nothing shared)
    assert latent["chunk_calls"] == st["chunk_steps"] == 2 + 3
    assert latent["chunk_lines"] == (16 + 20) + (16 + 32 + 45)


def test_the_counters_are_the_references_picks(served):
    cfg, weights, eng, sample, handles = served
    moe = eng.stats()["moe"]
    fed = [np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
           for (p, _), h in zip(sample, handles)]
    want = sum(ref.expert_picks(weights, dataclasses.asdict(cfg), f[None])
               for f in fed)
    assert np.asarray(moe["expert_tokens"]).shape == (
        2, cfg.n_routed_experts)
    np.testing.assert_array_equal(moe["expert_tokens"], want)
    assert moe["decode_calls"] == eng.stats()["decode_steps"]
    if cfg is SHARE:
        assert moe["picks"] == 2 * 4 * sum(map(len, fed))
        assert moe["picks_held"] == int(want.sum())
        assert 0.1 < moe["picks_held"] / moe["picks"] < 0.45   # 4 of 16
    else:
        assert "picks" not in moe


def test_a_prefix_hit_gives_the_tokens_of_no_hit(built):
    cfg, model, _ = built
    system = _ids(32, seed=90)
    prompts = [np.concatenate([system, _ids(n, seed=91 + n)])
               for n in (7, 18, 11)]

    def serve(sharing):
        eng = Engine(model, n_slots=2, max_len=64, block_size=4,
                     prefill_chunk=16, prefix_sharing=sharing)
        first = eng.submit(prompts[0], max_new_tokens=5)
        eng.drain()                  # the producer's prefix is committed
        rest = [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
        eng.drain()
        assert eng.cache.check_refcounts()
        return eng.stats(), [h.tokens for h in [first] + rest]

    shared, got = serve(True)
    alone, want = serve(False)
    assert shared["prefix_hit_tokens"] >= 2 * 32
    assert alone["prefix_hit_tokens"] == 0
    assert got == want


def test_engine_decodes_through_the_kernel_as_through_the_gathered_form(
        monkeypatch):
    """On a TPU the latent decode program holds the kernel once a layer;
    here through the interpreter, at a latent of whole lanes (128 beside a
    rotary part of 64: a line of 256), the tokens are the gathered form's
    and as close to the reference."""
    from paddle_tpu.serving import engine as E

    cfg = dataclasses.replace(CFG, kv_lora_rank=128, qk_rope_head_dim=64)
    model, weights = _model(cfg, seed=9)
    sample = [(_ids(n, seed=20 + n), new) for n, new in SAMPLE]

    def serve():
        eng = Engine(model, n_slots=3, max_len=64, block_size=8,
                     prefill_chunk=16, prefix_sharing=False)
        handles = [eng.submit(p, max_new_tokens=new) for p, new in sample]
        eng.drain()
        return handles

    plain = serve()
    traced, kernel = [], pa.paged_attention

    def interpreted(q, pool, vc, *args, **how):
        traced.append((q.shape, pool.shape, vc, how["value_dim"]))
        return kernel(q, pool, vc, *args, interpret=True, **how)

    def forget():
        E._PAGED_DECODE_DONATED.clear_cache()
        E._PAGED_DECODE.clear_cache()

    forget()
    monkeypatch.setattr(pa, "paged_attention", interpreted)
    try:
        handles = serve()
    finally:
        forget()
    assert traced == [((3, 4, 256), (3 * 25, 8, 1, 256), None, 128)] * 3
    assert [h.tokens for h in handles] == [h.tokens for h in plain]
    assert _worst_gap(weights, dataclasses.asdict(cfg), sample,
                      handles) < 0.05


def test_engine_chunks_through_the_kernel_as_through_the_plain_form(
        built, monkeypatch):
    """On a TPU the latent chunk program folds each tile of the cached
    prefix through the chunk kernel; here through the interpreter, tiles
    of 16 lines, the tokens are the plain form's, a prompt whose first 32
    tokens come from the radix index among them, and as close to the
    reference."""
    from paddle_tpu.ops.pallas import chunk_attention as ca
    from paddle_tpu.serving import engine as E

    cfg, model, weights = built
    system = _ids(32, seed=90)
    sample = [(np.concatenate([system, _ids(n, seed=91 + n)]), new)
              for n, new in ((7, 5), (18, 5), (29, 5))]

    def forget():
        for f in (E._PAGED_CHUNK, E._PAGED_CHUNK_DONATED):
            f.clear_cache()

    def serve():
        forget()
        eng = Engine(model, n_slots=2, max_len=96, block_size=4,
                     prefill_chunk=16)
        first = eng.submit(sample[0][0], max_new_tokens=sample[0][1])
        eng.drain()                  # the producer's prefix is committed
        rest = [eng.submit(p, max_new_tokens=n) for p, n in sample[1:]]
        eng.drain()
        return eng.stats(), [first] + rest

    monkeypatch.setattr(G, "_LATENT_TILE", 16)
    traced, fold = [], ca.chunk_attention

    def interpreted(q, q_shared, lines, *args, **how):
        traced.append((q.shape, q_shared.shape, lines.shape))
        return fold(q, q_shared, lines, *args, interpret=True, **how)

    try:
        plain_stats, plain = serve()
        monkeypatch.setattr(ca, "chunk_attention", interpreted)
        stats, handles = serve()
    finally:
        forget()
    # one trace a layer: 4 heads x 16 rows, 16 + 8 numbers against a tile
    # of 16 lines padded to whole lanes
    assert traced == [((4, 16, 16), (4, 16, 8), (16, 128))] * 3
    assert stats["prefix_hit_tokens"] == plain_stats["prefix_hit_tokens"] \
        >= 2 * 32
    assert [h.tokens for h in handles] == [h.tokens for h in plain]
    assert _worst_gap(weights, dataclasses.asdict(cfg), sample,
                      handles) < 0.05
    # the chunk program's calls and the lines their last rows could see
    assert stats["latent"]["chunk_calls"] == stats["chunk_steps"] > 0
    assert stats["latent"] == plain_stats["latent"]


@pytest.mark.parametrize("fault", ["no_rotary_score", "no_mscale",
                                   "no_selection_bias", "no_routed_scale",
                                   "no_shared_expert", "no_latent_norm"])
def test_the_comparison_fails_a_reference_with_one_fault(served, fault,
                                                         monkeypatch):
    """The comparison is symmetric in who is at fault: a reference that
    lacks one piece of the mathematics disagrees with the engine by more
    than the benchmark's tolerance (``LOGIT_TOL_ULPS`` bf16 steps)."""
    cfg, weights, _, sample, handles = served
    config = dataclasses.asdict(cfg)
    if fault == "no_mscale":
        config["rope_scaling"] = dict(config["rope_scaling"],
                                      mscale_all_dim=0, mscale=0)
    elif fault == "no_routed_scale":
        config["routed_scaling_factor"] = 1.0
    elif fault == "no_selection_bias":
        weights = {k: jnp.zeros_like(a) if k.endswith(
            "e_score_correction_bias") else a for k, a in weights.items()}
    elif fault == "no_shared_expert":
        weights = {k: jnp.zeros_like(a) if "shared_experts.down" in k else a
                   for k, a in weights.items()}
    elif fault == "no_latent_norm":
        weights = {k: jnp.ones_like(a) * 3.0 if k.endswith(
            "kv_a_layernorm.weight") else a for k, a in weights.items()}
    else:
        # jax keys its traces by the function: a patched rope needs a
        # function of its own to be traced at all
        plain = ref._attention_half.__wrapped__
        monkeypatch.setattr(ref, "rope",
                            lambda x, inv, factor: jnp.zeros_like(x))
        monkeypatch.setattr(ref, "_attention_half", jax.jit(
            lambda *a, **kw: plain(*a, **kw), static_argnames=("config",)))
    assert _worst_gap(weights, config, sample, handles) > ref.LOGIT_TOL_ULPS


@pytest.mark.parametrize("asked,named", [
    (dict(tp=2), "tp > 1"),
    (dict(speculative=SpecConfig(k=2, draft="ngram")), "speculative")])
def test_what_the_engine_cannot_do_with_this_model_it_refuses_by_name(
        built, asked, named):
    _, model, _ = built
    with pytest.raises(ValueError, match="cannot serve KimiK2ForCausalLM") \
            as e:
        Engine(model, n_slots=2, max_len=32, block_size=4, **asked)
    assert named in str(e.value) and "latent" in str(e.value)


@pytest.mark.parametrize("change,named", [
    (dict(n_group=8, topk_group=4), "group-limited"),
    (dict(num_nextn_predict_layers=1), "multi-token"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(n_routed_experts=4, n_router_experts=16, first_routed_expert=13),
     "are held")])
def test_a_config_asks_for_nothing_that_is_not_implemented(change, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(CFG, **change)
