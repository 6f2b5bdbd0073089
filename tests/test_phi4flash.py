"""A SambaY decoder (``text/models/phi4flash.py``: recurrent, window, one
full and query-only layers, gated memory units) against its plain float32
reference (``benchmarks/reference_sambay.py``), tiny and seeded, on the
CPU: hidden 64, 8 query / 4 KV heads of 8, window 8, state 4 x 128, 8
layers (every kind present). The model's ``forward``; ``serving.Engine``'s
prefill, chunks and decode through the three kinds of state, logit-level
and teacher-forced as the serving driver of the benchmark compares them;
what a slot keeps and forgets; the refusals and the counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks import arithmetic_hybrid
from benchmarks import reference_sambay as ref
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import Engine
from paddle_tpu.serving.speculative import SpecConfig
from paddle_tpu.text import sambay as S
from paddle_tpu.text.models.phi4flash import (PHI4FLASH_TINY,
                                              Phi4FlashConfig,
                                              Phi4FlashForCausalLM)

CFG = PHI4FLASH_TINY
ENGINE = dict(n_slots=4, max_len=64, block_size=4, prefill_chunk=16,
              prefix_sharing=False)
# prompts through the bucket prefill (5, 9, 16), through two and three
# chunks (20, 45: past the window of 8 and a chunk boundary), 12 new tokens
# each: every one decodes past the window too
SAMPLE = [(5, 12), (9, 12), (16, 12), (20, 12), (45, 12)]


def _model(cfg=CFG, seed=7):
    paddle.seed(seed)
    m = Phi4FlashForCausalLM(cfg)
    m.eval()
    return m, {name: p._data for name, p in m.named_parameters()}


@pytest.fixture(scope="module")
def built():
    return _model()


def _ids(n, seed=0, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def _sample():
    return [(_ids(n, seed=30 + n), new) for n, new in SAMPLE]


def _serve(model, sample, **how):
    eng = Engine(model, **dict(ENGINE, **how))
    handles = [eng.submit(p, max_new_tokens=new) for p, new in sample]
    eng.drain()
    return eng, handles


def _worst_gap(weights, cfg, sample, handles):
    """The serving driver's comparison: the reference scores prompt + the
    engine's tokens in one forward; at each generated position, how far
    its logit of the engine's token lies under its best, in bf16 steps."""
    worst = 0.0
    for (p, new), h in zip(sample, handles):
        seq = np.concatenate([p, np.asarray(h.tokens, np.int32)])
        rows = np.arange(len(p) - 1, len(p) - 1 + new)[None]
        z = np.asarray(ref.logits(weights, cfg, seq[None], rows=rows))[0]
        for zt, tok in zip(z, h.tokens):
            worst = max(worst, float(zt.max() - zt[tok])
                        / ref.bf16_step(np.abs(zt).max()))
    return worst


@pytest.fixture(scope="module")
def served(built):
    model, weights = built
    sample = _sample()
    eng, handles = _serve(model, sample)
    return eng, sample, handles


def test_the_layer_table_of_the_published_depth():
    kinds = Phi4FlashConfig().layer_kinds()
    assert kinds[:16:2] == ("mamba",) * 8
    assert kinds[1:16:2] == ("sliding_attention",) * 8
    assert kinds[16:18] == ("mamba", "full_attention")
    assert kinds[18::2] == ("gmu",) * 7
    assert kinds[19::2] == ("cross_attention",) * 7
    assert tuple(k.replace("sliding_attention", "window").replace(
        "full_attention", "full").replace("cross_attention", "cross")
        for k in kinds) == tuple(ref.layer_kinds(
            dataclasses.asdict(Phi4FlashConfig())))
    assert Phi4FlashConfig().mamba_dt_rank == 160
    n = arithmetic_hybrid.layer_counts(dataclasses.asdict(Phi4FlashConfig()))
    assert n == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}


def test_forward_logits_match_the_reference(built):
    model, weights = built
    ids = _ids(40, batch=2)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(ref.logits(weights, dataclasses.asdict(CFG), ids))
    assert got.shape == (2, 40, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4)


def test_the_published_initialisers_are_live(built):
    _, w = built
    mix = "model.layers.0.mixer."
    np.testing.assert_allclose(
        np.asarray(w[mix + "A_log"])[3],
        np.log(np.arange(1, CFG.mamba_d_state + 1)), rtol=1e-6)
    assert np.all(np.asarray(w[mix + "D"]) == 1.0)
    dt = np.log1p(np.exp(np.asarray(w[mix + "dt_proj.bias"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    lam = np.asarray(w["model.layers.1.mixer.lambda_q1"])
    assert 0.02 < lam.std() < 0.3


def test_engine_prefill_chunks_and_decode_match_the_reference(built, served):
    """Bucket prefill, two and three chunks with the state carried, decode
    past the window: at every generated position the reference's best
    token is the engine's (float32 on both sides)."""
    _, weights = built
    eng, sample, handles = served
    assert all(h.finish_reason == "length" for h in handles)
    assert _worst_gap(weights, dataclasses.asdict(CFG), sample,
                      handles) < 0.05
    assert eng.stats()["chunk_program"]
    assert eng.stats()["prefill_buckets"] == [8, 16]


def test_a_window_layer_keeps_its_window_and_nothing_else(built, served):
    """Memory held by a window layer is ``window`` lines a slot however
    long the sequence; the ring's lines after a run are the last
    ``window`` positions' keys."""
    eng = served[0]
    wk = eng.cache.state["wk"]
    n_window = CFG.layer_kinds().count("sliding_attention")
    per_slot = CFG.sliding_window // ENGINE["block_size"]
    assert wk.shape == (n_window, 1 + ENGINE["n_slots"] * per_slot,
                        ENGINE["block_size"] * CFG.num_key_value_heads // 2,
                        2 * CFG.hidden_size // CFG.num_attention_heads)
    assert eng.cache.kc.shape[0] == 1       # the pool: ONE layer


def test_ring_write_leaves_the_newest_lines():
    window, bs, n, w = 8, 4, 2, 4
    pool = jnp.zeros((1 + 2 * 2, bs * n, w))
    row = S.ring_tables(2, window, bs)[1]
    lines = jnp.arange(1, 14, dtype=jnp.float32)[:, None, None] \
        * jnp.ones((13, n, w))
    # positions 5..17, of which the first 11 (5..15) are tokens
    got = S.ring_write(pool, row, lines, 5, 11, window=window)
    ring = np.asarray(got[np.asarray(row)]).reshape(window, n, w)[:, 0, 0]
    # ring line p % 8 holds position p for p in 8..15: value p - 4
    np.testing.assert_array_equal(ring, [4, 5, 6, 7, 8, 9, 10, 11])
    assert np.all(np.asarray(got[1:3]) == 0)        # the other slot's


def _window_scatter(pool, dest, lines):
    """The writes' earlier form: slab ``j`` of ``lines`` ``[R, n, w]`` as
    one scatter window at rows ``dest[j] * n`` of the flat pool."""
    nb, rows, w = pool.shape
    return jax.lax.scatter(
        pool.reshape(nb * rows, w), (dest * lines.shape[1])[:, None], lines,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(0,)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS).reshape(pool.shape)


def _window_blocks(pool, blocks, lines):
    return _window_scatter(pool, blocks,
                           lines.reshape((-1,) + pool.shape[1:]))


_BS, _N, _W, _WINDOW = 4, 3, 8, 8


def _random_pool(blocks, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (blocks, _BS * _N, _W)).astype(jnp.bfloat16)


def _random_lines(rows, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (rows, _N, _W)).astype(jnp.bfloat16)


def _decode_lines(pool_blocks, per, n_slots, inactive):
    """64 lines a line a slot, as the decode program places them: an
    active slot at its own block's line, an inactive one in the trash
    block, at the line its position names."""
    pos = jnp.arange(n_slots) * 7 % (per * _BS)
    owned = (1 + jnp.arange(n_slots) * per + pos // _BS) * _BS + pos % _BS
    active = jnp.arange(n_slots) % inactive != 0
    dest = jnp.where(active, owned, pos % _BS)
    pool = _random_pool(pool_blocks, 1)
    lines = _random_lines(n_slots, 2)
    return pool, dest, lines


def _decode_k(pool, dest, lines):
    """K's pool after decode's write of K and V lines."""
    return S._scatter(pool, -pool, dest, lines, -lines)[0]


def _decode_v(pool, dest, lines):
    """V's pool after decode's write of K and V lines."""
    return S._scatter(-pool, pool, dest, -lines, lines)[1]


def _window_pair(kc, vc, dest, k, v):
    return _window_scatter(kc, dest, k), _window_scatter(vc, dest, v)


def _write_cases():
    """``(name, write, args, per)``: a write of ``text/sambay.py`` and its
    arguments; ``per`` blocks to a run that begins with a trash block."""
    table = 1 + jnp.arange(4, dtype=jnp.int32)
    rings = S.ring_tables(3, _WINDOW, _BS)
    nbw = 1 + 3 * _WINDOW // _BS
    yield ("decode_64_lines_some_inactive", _decode_k,
           _decode_lines(1 + 64 * 4, 4, 64, 5), 1 + 64 * 4)
    yield ("decode_into_the_second_ring", _decode_v,
           (_random_pool(2 * nbw, 3),
            nbw * _BS + jnp.array([0, 9, 13, 17, 2, 1]),
            _random_lines(6, 4)), nbw)
    yield ("blocks", S._put_blocks,
           (_random_pool(9, 5), jnp.array([3, 0, 7, 1, 0]),
            _random_lines(5 * _BS, 6)), 9)
    # a prompt shorter than a block, at position 0
    yield ("prompt_shorter_than_a_block",
           lambda p, r, x: S.pool_write(p, r, x, 0, 3, _BS),
           (_random_pool(5, 7), table, _random_lines(3, 8)), 5)
    # 11 tokens of a 16-row chunk from position 4: blocks past them to trash
    yield ("chunk_with_blocks_past_the_prompt",
           lambda p, r, x: S.pool_write(p, r, x, 4, 11, _BS),
           (_random_pool(5, 9), table, _random_lines(16, 10)), 5)
    # positions 5..17 into a ring of 8: wrapped past its window
    yield ("ring_wrapped_past_its_window",
           lambda p, r, x: S.ring_write(p, r, x, 5, 13, window=_WINDOW,
                                        old=_random_lines(_WINDOW, 11)),
           (_random_pool(nbw, 12), rings[1], _random_lines(13, 13)), nbw)
    yield ("ring_first_rows",
           lambda p, r, x: S.ring_write(p, r, x, 0, 5, window=_WINDOW),
           (_random_pool(nbw, 14), rings[2], _random_lines(6, 15)), nbw)


@pytest.mark.parametrize("case", list(_write_cases()),
                         ids=lambda case: case[0])
def test_pool_writes_are_the_window_forms_bytes(case, monkeypatch):
    """The writes into a folded pool (decode's lines, whole blocks by an
    inserted block index) leave the same bytes as one scatter window a
    slab did, but in the trash blocks: inactive rows and blocks past a
    prompt all write there, so their indices repeat."""
    _, write, args, per = case
    got = np.asarray(jax.jit(write)(*args))
    monkeypatch.setattr(S, "_scatter", _window_pair)
    monkeypatch.setattr(S, "_put_blocks", _window_blocks)
    want = np.asarray(jax.jit(write)(*args))
    keep = np.arange(got.shape[0]) % per != 0
    np.testing.assert_array_equal(got[keep], want[keep])
    assert not np.array_equal(got[keep], np.asarray(args[0])[keep])


def test_a_scan_in_two_chunks_is_the_scan_in_one(built):
    model, _ = built
    w = model.stacked_weights()
    lw = {k: a._data if hasattr(a, "_data") else a
          for k, a in S.layer_of(S.stack_of(w), 0).items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, CFG.hidden_size))
    keep = jnp.ones((24,), bool)
    whole = S.mamba_prefill(x, lw, keep, 24, eps=1e-5)
    a = S.mamba_prefill(x[:, :16], lw, keep[:16], 16, eps=1e-5)
    # the second half padded to 16 rows of which 8 are tokens
    xb = jnp.concatenate([x[:, 16:], jnp.zeros((1, 8, CFG.hidden_size))], 1)
    b = S.mamba_chunk(xb, lw, a[1], a[2], jnp.arange(16) < 8, 8, eps=1e-5)
    np.testing.assert_allclose(b[0][0, :8], whole[0][0, 16:], atol=1e-5)
    np.testing.assert_allclose(b[1], whole[1], atol=1e-5)     # the state
    np.testing.assert_allclose(b[2], whole[2], atol=1e-6)     # conv inputs


def test_chunks_give_the_tokens_of_one_prefill(built, served):
    model, _ = built
    _, sample, handles = served
    _, whole = _serve(model, sample, prefill_chunk=None)
    assert [h.tokens for h in whole] == [h.tokens for h in handles]


def test_preemption_and_replay_give_the_same_tokens(built, served):
    """A pool too small for all four: a request is pre-empted, re-queued
    and its state rebuilt from ``prompt + tokens``."""
    model, _ = built
    _, sample, handles = served
    eng, tight = _serve(model, sample, n_blocks=24)
    assert eng.stats()["preemptions"] > 0
    assert eng.stats()["recurrent"]["state_replays"] > 0
    assert [h.tokens for h in tight] == [h.tokens for h in handles]
    assert eng.cache.check_refcounts()


def test_a_reused_slot_sees_nothing_of_its_previous_tenant(built, served):
    model, _ = built
    _, sample, handles = served
    eng = Engine(model, **dict(ENGINE, n_slots=1))
    got = []
    for p, new in sample:           # one slot: every request reuses it
        h = eng.submit(p, max_new_tokens=new)
        eng.drain()
        got.append(h.tokens)
    assert got == [h.tokens for h in handles]
    assert eng.stats()["recurrent"]["state_resets"] == len(sample)


def test_adopt_replays_into_another_engine(built, served):
    model, _ = built
    _, sample, handles = served
    p, new = sample[4]
    a = Engine(model, **ENGINE)
    h = a.submit(p, max_new_tokens=new)
    for _ in range(6):
        a.step()
    assert 0 < len(h.tokens) < new
    b = Engine(model, **ENGINE)
    b.adopt(h)
    b.drain()
    assert h.tokens == handles[4].tokens
    assert b.stats()["recurrent"]["state_replays"] == 1


@pytest.mark.parametrize("asked,named", [
    (dict(tp=2), "tp > 1"),
    (dict(speculative=SpecConfig(k=2, draft="ngram")), "speculative"),
    (dict(prefix_sharing=True), "prefix_sharing=True")])
def test_what_the_engine_cannot_do_with_this_model_it_refuses_by_name(
        built, asked, named):
    model, _ = built
    how = dict(ENGINE, **asked)
    with pytest.raises(ValueError,
                       match="cannot serve Phi4FlashForCausalLM") as e:
        Engine(model, **how)
    assert named in str(e.value)


def test_prefix_sharing_is_refused_by_default(built):
    with pytest.raises(ValueError, match="prefix_sharing=True"):
        Engine(built[0], n_slots=2, max_len=32, block_size=4)


def test_the_counters_count_what_was_run(built, served):
    eng, sample, handles = served
    r = eng.stats()["recurrent"]
    assert r["scan_tokens"] == sum(len(p) for p, _ in sample)
    assert r["state_resets"] == len(sample) and r["state_replays"] == 0
    assert r["shared_kv_readers"] == 2      # the full layer and one reader
    assert r["decode_calls"] == eng.metrics.decode_steps
    # a decode call of a row at position p sees p + 1 lines
    want = sum(len(p) + k + 1 for p, new in sample for k in range(new - 1))
    assert r["decode_lines_seen"] == want
    assert r["decode_lines_in_window"] == sum(
        min(len(p) + k + 1, CFG.sliding_window)
        for p, new in sample for k in range(new - 1))
    # the generic count is a llama's: silent here, as for a latent cache
    assert eng.stats()["decode_lines_seen"]["calls"] == 0
    programs = {rec.program for rec in eng.metrics.launches}
    assert programs == {"prefill:L8", "prefill:L16", "chunk", "decode"}


def test_bytes_by_kind_are_the_geometrys_arithmetic(served):
    eng = served[0]
    cfg = dict(dataclasses.asdict(CFG), torch_dtype="float32")
    want = arithmetic_hybrid.cache_bytes(cfg, ENGINE["n_slots"],
                                         ENGINE["max_len"])
    got = eng.cache.bytes_by_kind()
    line = arithmetic_hybrid.line_bytes(cfg)
    bs = ENGINE["block_size"]
    # beside the arithmetic's: one trash block a pooled layer
    assert got["pool_bytes"] == want["pool_bytes"] + bs * line
    assert got["window_bytes"] == want["window_bytes"] + 2 * bs * line
    assert got["state_bytes"] == want["state_bytes"]
    assert eng.stats()["kv_cache_bytes"] == sum(got.values())
    r = eng.stats()["recurrent"]
    assert {k: r[k] for k in got} == got
    assert eng.stats()["window_bytes"] == got["window_bytes"]


def test_the_published_sizes_add_up():
    cfg = dict(dataclasses.asdict(Phi4FlashConfig()),
               torch_dtype="bfloat16")
    assert abs(arithmetic_hybrid.total_params(cfg) / 1e9 - 3.85) < 0.01
    got = arithmetic_hybrid.cache_bytes(cfg, 64, 8192)
    assert arithmetic_hybrid.line_bytes(cfg) == 5120
    assert round(got["pool_bytes"] / 1e9, 2) == 2.68
    assert round(got["window_bytes"] / 1e9, 2) == 1.34
    assert round(got["state_bytes"] / 1e9, 2) == 0.21


def test_a_vocab_mask_is_a_row_on_the_device(built, served):
    """The decode program's masks live on the device and take a row when
    a slot's request changes it: a constrained request samples inside its
    mask, its unconstrained successor in the same slot as if alone."""
    model, _ = built
    _, sample, handles = served
    allowed = np.zeros(CFG.vocab_size, bool)
    allowed[100:140] = True
    eng = Engine(model, **dict(ENGINE, n_slots=1))
    first = eng.submit(sample[0][0], max_new_tokens=4)
    eng.drain()                       # the device copy exists from here on
    masked = eng.submit(sample[1][0], max_new_tokens=6, logit_mask=allowed)
    eng.drain()
    plain = eng.submit(sample[1][0], max_new_tokens=12)
    eng.drain()
    assert all(100 <= t < 140 for t in masked.tokens)
    assert first.tokens == handles[0].tokens[:4]
    assert plain.tokens == handles[1].tokens
    assert np.all(np.asarray(eng._vmask_dev) == 1.0)


def test_engine_decodes_through_the_kernel_as_through_the_gathered_form(
        monkeypatch):
    """On a TPU every attention layer's decode is the paged kernel over a
    pool of KV-pair lines (128 lanes at heads of 64); here through the
    interpreter the tokens are the gathered form's."""
    from paddle_tpu.serving import sambay_programs as sp

    cfg = dataclasses.replace(CFG, hidden_size=512, intermediate_size=256,
                              mamba_d_state=4, sliding_window=16)
    model, weights = _model(cfg, seed=9)
    sample = [(_ids(n, seed=50 + n), 6) for n in (7, 21)]
    how = dict(n_slots=2, max_len=48, block_size=8, prefill_chunk=16)

    plain = _serve(model, sample, **how)[1]
    traced, kernel = [], pa.paged_attention

    def interpreted(q, kc, vc, *args, **kw):
        traced.append((q.shape, kc.shape))
        return kernel(q, kc, vc, *args, interpret=True, **kw)

    sp.DECODE.clear_cache()
    monkeypatch.setattr(pa, "paged_attention", interpreted)
    try:
        handles = _serve(model, sample, **how)[1]
    finally:
        sp.DECODE.clear_cache()
    # two window layers over the rings, the full layer and its one reader
    # over the pool: eight query rows of 128 against two KV pairs
    rings, pool = (2 * (1 + 2 * 2), 8, 2, 128), (2 * 6 + 1, 8, 2, 128)
    assert traced == [((2, 8, 128), rings)] * 2 + [((2, 8, 128), pool)] * 2
    assert [h.tokens for h in handles] == [h.tokens for h in plain]
    assert _worst_gap(weights, dataclasses.asdict(cfg), sample,
                      handles) < 0.05


def test_engine_writes_lines_through_the_kernel_as_through_the_scatter(
        monkeypatch):
    """On a TPU decode writes each attention layer's K and V lines through
    one kernel call (``ops/pallas/line_write.py``); here through the
    interpreter the tokens are the XLA scatter's and every call wrote K
    and V of a line a slot into blocks of whole tiles."""
    from paddle_tpu.ops.pallas import line_write as L
    from paddle_tpu.serving import sambay_programs as sp

    cfg = dataclasses.replace(CFG, hidden_size=512, intermediate_size=256,
                              mamba_d_state=4, sliding_window=16)
    model, weights = _model(cfg, seed=9)
    sample = [(_ids(n, seed=50 + n), 6) for n in (7, 21)]
    how = dict(n_slots=2, max_len=48, block_size=8, prefill_chunk=16)

    plain = _serve(model, sample, **how)[1]
    traced, kernel = [], L.write_lines

    def interpreted(pools, dest, lines):
        traced.append(tuple(p.shape for p in pools))
        return kernel(pools, dest, lines, interpret=True)

    sp.DECODE.clear_cache()
    monkeypatch.setattr(L, "write_lines", interpreted)
    try:
        handles = _serve(model, sample, **how)[1]
    finally:
        sp.DECODE.clear_cache()
    # the two window layers' rings and the full layer's pool, K and V a
    # call: blocks of 8 lines of 2 KV pairs, 16 rows of 128 lanes
    assert len(traced) == 3
    assert all(len(t) == 2 and t[0][1:] == (16, 128) for t in traced)
    assert [h.tokens for h in handles] == [h.tokens for h in plain]
    assert _worst_gap(weights, dataclasses.asdict(cfg), sample,
                      handles) < 0.05


@pytest.mark.parametrize("change,named", [
    (dict(tie_word_embeddings=False), "untied head"),
    (dict(mb_per_layer=1), "mb_per_layer"),
    (dict(num_hidden_layers=6), "depth"),
    (dict(num_key_value_heads=8), "pair up"),
    (dict(mlp_bias=True), "mlp_bias")])
def test_a_config_asks_for_nothing_that_is_not_implemented(change, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(CFG, **change)
