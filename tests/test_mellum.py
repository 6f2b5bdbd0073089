"""A decoder of layer kinds with routed experts (``text/models/mellum.py``)
against its plain float32 reference (``benchmarks/reference_mellum.py``),
tiny and seeded, on the CPU: the model's ``forward``, one step's loss and
gradients, and ``serving.Engine``'s prefill, chunked prefill and decode
through the paged cache, past the window and past the YaRN table's
original length, logit-level and teacher-forced as the serving driver of
the benchmark compares them. The comparison is tight enough that a
missing window, a plain table on a full layer, top-k weights that are not
renormalised and a dropped pick each fail it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks import reference_mellum as ref
from paddle_tpu.nn.routed_ffn import routed_ffn
from paddle_tpu.serving import Engine
from paddle_tpu.serving.speculative import SpecConfig
from paddle_tpu.text.models import LLAMA_TINY, LlamaForCausalLM
from paddle_tpu.text.models.mellum import (FULL, MELLUM_TINY, SLIDING,
                                           MellumConfig, MellumForCausalLM,
                                           rope_table)

# pattern S,S,S,F; 8 experts, top 2; window 8; YaRN over an original 16
CFG = MELLUM_TINY
PUBLISHED_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _config_dict(cfg=CFG):
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = MellumForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def weights(model):
    return {name: p._data for name, p in model.named_parameters()}


def _ids(n, seed=0, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape).astype(np.int32)


def test_forward_logits_match_the_reference(model, weights):
    ids = _ids(40, batch=2)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want, gaps = ref.logits_and_gaps(weights, _config_dict(), ids)
    assert got.shape == (2, 40, CFG.vocab_size)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    assert np.all(np.asarray(gaps) > 0)


def test_one_train_step_loss_and_gradients_match_the_reference():
    paddle.seed(11)
    m = MellumForCausalLM(CFG)
    m.train()
    ids = _ids(24, seed=3, batch=2)
    before = {n: p._data for n, p in m.named_parameters()}
    loss = m(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    loss.backward()
    want_loss, want = ref.loss_and_gradients(before, _config_dict(), ids,
                                             ids)
    assert abs(float(loss) - want_loss) < 1e-5
    params = dict(m.named_parameters())
    assert set(want) == set(ref.checked(_config_dict()))
    for name, g in want.items():
        got = np.asarray(params[name].grad._data)[:g.shape[0]]
        assert np.abs(np.asarray(g)).max() > 0, name
        np.testing.assert_allclose(got, np.asarray(g), atol=2e-5,
                                   rtol=2e-3, err_msg=name)
    # and the step trains: plain SGD on these gradients lowers the loss
    opt = paddle.optimizer.SGD(learning_rate=0.5, parameters=m.parameters())
    opt.step()
    after = m(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    assert float(after) < float(loss)


def _worst_gap(weights, config, sample, handles, reference=ref):
    """The serving driver's comparison: the reference scores each prompt
    followed by the engine's own tokens, and at every generated position
    its logit of the engine's token may lie below its largest by so many
    bf16 steps (here: float32 logits, so next to none)."""
    worst = 0.0
    for (prompt, n), h in zip(sample, handles):
        assert h.finish_reason == "length" and len(h.tokens) == n
        seq = np.concatenate([prompt, np.asarray(h.tokens, np.int32)])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)[None]
        z = np.asarray(reference.logits_and_gaps(
            weights, config, seq[None], rows)[0])[0]
        for zt, tok in zip(z, h.tokens):
            worst = max(worst, float(zt.max() - zt[tok])
                        / ref.bf16_step(np.abs(zt).max()))
    return worst


# a bucket prefill (9 < chunk), a chunked one inside the window's reach
# (20), and one whose chunks and decode steps run past the window (8) and
# past the YaRN original length (16): 45 + 12 positions
SAMPLE = [(9, 6), (20, 6), (45, 12)]


@pytest.fixture(scope="module", params=[(4, 4), (3, 8)],
                ids=["4_slots_blocks_of_4", "3_slots_blocks_of_8"])
def served(model, request):
    """The sample through engines of two geometries: blocks of 4 lines
    (a window layer gathers 4 of a slot's 16 blocks in decode) and blocks
    as long as the window (2 of 8)."""
    n_slots, block_size = request.param
    eng = Engine(model, n_slots=n_slots, max_len=64, block_size=block_size,
                 prefill_chunk=16, prefix_sharing=False)
    sample = [(_ids(n, seed=20 + n), new) for n, new in SAMPLE]
    handles = [eng.submit(p, max_new_tokens=new) for p, new in sample]
    eng.drain()
    return eng, sample, handles


def test_engine_prefill_chunk_and_decode_match_the_reference(served,
                                                             weights):
    eng, sample, handles = served
    st = eng.stats()
    assert st["chunk_program"] and st["prefill_buckets"] == [16]
    assert st["chunk_steps"] == 2 + 3
    assert _worst_gap(weights, _config_dict(), sample, handles) < 0.05


def test_engine_decodes_through_the_kernel_as_through_the_gathered_form(
        model, weights, monkeypatch):
    """On a TPU the decode program of a model of layer kinds holds the
    paged-attention kernel in every layer, its window an operand: here
    through the interpreter, the tokens are the gathered form's and as
    close to the reference, past the window and the YaRN table's
    original length."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import engine as E

    sample = [(_ids(n, seed=20 + n), new) for n, new in SAMPLE]

    def serve():
        eng = Engine(model, n_slots=3, max_len=64, block_size=4,
                     prefill_chunk=16, prefix_sharing=False)
        handles = [eng.submit(p, max_new_tokens=new) for p, new in sample]
        eng.drain()
        return handles

    plain = serve()
    windows, kernel = [], pa.paged_attention

    def interpreted(q, kc, vc, tables, write_pos, window):
        windows.append(window)
        return kernel(q, kc, vc, tables, write_pos, window, interpret=True)

    E._PAGED_DECODE_DONATED.clear_cache()
    E._PAGED_DECODE.clear_cache()
    monkeypatch.setattr(pa, "paged_attention", interpreted)
    try:
        handles = serve()
    finally:
        E._PAGED_DECODE_DONATED.clear_cache()
        E._PAGED_DECODE.clear_cache()
    assert windows == [CFG.sliding_window if kind == SLIDING else 0
                       for kind in CFG.layer_types]
    assert [h.tokens for h in handles] == [h.tokens for h in plain]
    assert _worst_gap(weights, _config_dict(), sample, handles) < 0.05


def _no_window(config, reference):
    return dict(config, sliding_window=CFG.max_position_embeddings)


def _plain_table_on_full_layers(config, reference):
    rp = dict(config["rope_parameters"])
    rp[FULL] = rp[SLIDING]
    return dict(config, rope_parameters=rp)


def _unnormalised(monkeypatch):
    def routing(m, router, k, flip=None):
        g = jax.nn.softmax(m @ router.astype(jnp.float32), axis=-1)
        kth = jax.lax.top_k(g, k)[0][..., k - 1]
        return jnp.where(g >= kth[..., None], g, 0.0), jnp.ones(g.shape[:-1])
    monkeypatch.setattr(ref, "routing", routing)


def _dropped_pick(monkeypatch):
    true = ref.routing

    def routing(m, router, k, flip=None):
        c, gap = true(m, router, k)
        # the smallest of a row's picks is dropped, nothing renormalised
        kth = jnp.min(jnp.where(c > 0, c, jnp.inf), axis=-1, keepdims=True)
        return jnp.where(c > kth, c, 0.0), gap
    monkeypatch.setattr(ref, "routing", routing)


@pytest.mark.parametrize("fault", ["no_window", "plain_table_on_full_layers",
                                   "unnormalised_top_k", "dropped_pick"])
def test_the_comparison_fails_a_reference_with_one_fault(
        served, weights, monkeypatch, fault):
    """The comparison is symmetric in who is at fault: a reference that
    lacks one piece of the mathematics disagrees with the engine by more
    than the benchmark's tolerance (``LOGIT_TOL_ULPS`` bf16 steps)."""
    _, sample, handles = served
    config = _config_dict()
    if fault == "no_window":
        config = _no_window(config, ref)
    elif fault == "plain_table_on_full_layers":
        config = _plain_table_on_full_layers(config, ref)
    else:
        # _layer is jitted, and jax keys its traces by the function: a
        # patched routing needs a function of its own to be traced at all
        plain = ref._layer.__wrapped__
        monkeypatch.setattr(ref, "_layer", jax.jit(
            lambda *a, **kw: plain(*a, **kw), static_argnames=(
                "heads", "kv_heads", "head_dim", "eps", "window", "k")))
        {"unnormalised_top_k": _unnormalised,
         "dropped_pick": _dropped_pick}[fault](monkeypatch)
    assert _worst_gap(weights, config, sample, handles) > ref.LOGIT_TOL_ULPS


def test_engine_counts_the_picks_the_reference_makes(served, weights):
    eng, sample, handles = served
    moe = eng.stats()["moe"]
    want = np.zeros((CFG.num_hidden_layers, CFG.num_experts), np.int64)
    for (prompt, n), h in zip(sample, handles):
        # the last token is returned, never fed
        seq = np.concatenate([prompt, np.asarray(h.tokens[:-1], np.int32)])
        want += ref.expert_picks(weights, _config_dict(), seq[None])
    assert np.array_equal(np.asarray(moe["expert_tokens"]), want)
    assert want.sum(1).tolist() == [
        CFG.num_experts_per_tok * sum(p + n - 1 for p, n in SAMPLE)
    ] * CFG.num_hidden_layers
    assert moe["decode_calls"] == eng.stats()["decode_steps"] > 0
    hit = np.asarray(moe["experts_hit"])
    assert np.all(hit >= moe["decode_calls"] * CFG.num_experts_per_tok)
    assert np.all(hit <= moe["decode_calls"] * CFG.num_experts)


def test_a_dense_engine_reports_no_moe():
    paddle.seed(0)
    m = LlamaForCausalLM(dataclasses.replace(LLAMA_TINY, dtype="float32",
                                             num_hidden_layers=1))
    m.eval()
    eng = Engine(m, n_slots=2, max_len=32, block_size=4)
    eng.generate_all([_ids(5) % LLAMA_TINY.vocab_size], max_new_tokens=2)
    assert "moe" not in eng.stats()


@pytest.mark.parametrize("kwargs,names", [
    (dict(tp=2), "tp > 1"),
    (dict(speculative=SpecConfig(k=2)), "speculative"),
])
def test_engine_refuses_what_this_model_cannot_have(model, kwargs, names):
    with pytest.raises(ValueError, match="cannot serve MellumForCausalLM"
                                         ".*" + names.replace("'", ".")):
        Engine(model, n_slots=2, max_len=32, **kwargs)


@pytest.mark.parametrize("table", [rope_table, ref.rope_table],
                         ids=["program", "reference"])
def test_yarn_table_is_the_closed_form(table):
    inv, factor = table(PUBLISHED_YARN, 128)
    i = np.arange(64)
    base = 500000.0 ** (-2 * i / 128)
    # low = floor(d(32)) = 18, high = ceil(d(1)) = 35
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = (1 - ramp) * base + ramp * base / 16
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert np.array_equal(inv[:19], base[:19].astype(np.float32))
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    assert factor == pytest.approx(0.1 * np.log(16) + 1)
    plain, one = table({"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(plain, base, rtol=1e-6)
    assert one == 1.0


def test_nothing_is_dropped_when_every_row_picks_one_expert():
    """Rows that all route to experts 0 and 1: both take the whole batch,
    every row computed; the dense sum over the two is the answer."""
    T, h, f, E, k = 48, 16, 8, 8, 2
    rng = np.random.default_rng(5)
    m = jnp.asarray(np.abs(rng.standard_normal((T, h))), jnp.float32)
    wr = np.zeros((h, E), np.float32)
    wr[:, 0], wr[:, 1] = 2.0, 1.0
    wg, wu = (jnp.asarray(rng.standard_normal((E, h, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, f, h)), jnp.float32)
    g = jax.nn.softmax(m @ wr, axis=-1)[:, :2]
    c = g / g.sum(-1, keepdims=True)
    want = sum(c[:, e:e + 1] * ((jax.nn.silu(m @ wg[e]) * (m @ wu[e]))
                                @ wd[e]) for e in range(2))
    valid = jnp.arange(T) % 3 != 0
    y, picks = jax.jit(routed_ffn, static_argnums=5)(
        m, jnp.asarray(wr), wg, wu, wd, k)
    assert picks.tolist() == [T, T] + [0] * (E - 2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # rows marked as no tokens count for no expert and come back zero
    y2, picks2 = routed_ffn(m, jnp.asarray(wr), wg, wu, wd, k, valid)
    assert picks2.tolist() == [int(valid.sum())] * 2 + [0] * (E - 2)
    assert np.all(np.asarray(y2)[~np.asarray(valid)] == 0)
    np.testing.assert_allclose(np.asarray(y2)[np.asarray(valid)],
                               np.asarray(y)[np.asarray(valid)], rtol=1e-6)


@pytest.mark.parametrize("window", [None, 5])
def test_attention_in_key_tiles_is_the_one_pass(monkeypatch, window):
    """A long view is walked in tiles with an online softmax
    (``generation._attend_tiled``): the same numbers as the one pass."""
    from paddle_tpu.text import generation as G

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 8, 6, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 40, 16)), jnp.float32)
            for _ in range(2))
    gpos, kpos = jnp.arange(30, 36), jnp.arange(40)
    cm = kpos[None, :] <= gpos[:, None]
    if window:
        cm = cm & (gpos[:, None] - kpos[None, :] < window)
    want = G._attend(q, k, v, cm[None, None], jnp.float32)
    for tile in (8, 12):          # 40 keys: five tiles, or 3 1/3 padded
        monkeypatch.setattr(G, "_ATTEND_TILE", tile)
        got = G._attend(q, k, v, cm[None, None], jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
    # and with the mask as the prefill body gives it, [Q, T]
    sq = jnp.tril(jnp.ones((40, 40), bool))
    q40 = jnp.asarray(rng.standard_normal((1, 8, 40, 16)), jnp.float32)
    monkeypatch.setattr(G, "_ATTEND_TILE", 2048)
    want = G._attend(q40, k, v, sq, jnp.float32)
    monkeypatch.setattr(G, "_ATTEND_TILE", 8)
    np.testing.assert_allclose(
        np.asarray(G._attend(q40, k, v, sq, jnp.float32)), np.asarray(want),
        rtol=2e-5, atol=2e-6)


def test_config_refuses_what_is_not_implemented():
    for bad in (dict(attention_bias=True), dict(norm_topk_prob=False),
                dict(mlp_layer_types=["dense"] * 4),
                dict(layer_types=[SLIDING] * 3)):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **bad)
    assert MellumConfig().layer_types.count(FULL) == 7
