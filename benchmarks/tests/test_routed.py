"""The routed configuration's pieces of the benchmark: its plain reference
(window mask, YaRN table, top-k renormalisation, the tie gap and what the
serving comparison leaves out), the byte count, the reader (fed a recorded
fragment of operations and counters; silent on a dense cell), and one
end-to-end run of the tiny routed cell that exists only as data."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arithmetic_moe, models, reference_mellum as ref
from benchmarks.readers import routed

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


TINY = _load(HERE, "data", "configs", "tiny-mellum.json")
DENSE = _load(HERE, "data", "configs", "tiny-gqa.json")
PUBLISHED = _load(ROOT, "benchmarks", "configs", "mellum2-12b-a2.5b.json")


# --- the reference ----------------------------------------------------------

def test_window_mask():
    rows, cols = jnp.arange(4, 8), jnp.arange(8)
    full = np.asarray(ref.allowed(rows, cols, None))
    assert np.array_equal(full, np.tril(np.ones((8, 8), bool))[4:])
    win = np.asarray(ref.allowed(rows, cols, 3))
    # itself and the two before it
    assert [np.flatnonzero(r).tolist() for r in win] == [
        [2, 3, 4], [3, 4, 5], [4, 5, 6], [5, 6, 7]]


def test_windowed_attention_forgets_what_lies_behind_the_window():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 12, h, 8)), jnp.float32)
               for h in (4, 2, 2))
    out = np.asarray(ref.attention(q, k, v, window=4))
    k2, v2 = k.at[:, :5].set(9.0), v.at[:, :5].set(-9.0)
    moved = np.asarray(ref.attention(q, k2, v2, window=4))
    # rows 8.. see keys 5.. alone; rows before see a changed key
    np.testing.assert_allclose(moved[:, 8:], out[:, 8:], atol=1e-6)
    assert np.abs(moved[:, :8] - out[:, :8]).max() > 0.1
    assert np.abs(np.asarray(ref.attention(q, k2, v2))[:, 8:]
                  - out[:, 8:]).max() > 0.1


def test_yarn_table_at_the_published_numbers():
    inv, factor = ref.rope_table(PUBLISHED["rope_parameters"]
                                 ["full_attention"], PUBLISHED["head_dim"])
    i = np.arange(64)
    base = 500000.0 ** (-2 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)      # low 18, high 35
    np.testing.assert_allclose(inv, (1 - ramp) * base + ramp * base / 16,
                               rtol=1e-6)
    assert factor == 1.2772588722239782
    plain, one = ref.rope_table(PUBLISHED["rope_parameters"]
                                ["sliding_attention"], 128)
    np.testing.assert_allclose(plain, base, rtol=1e-6)
    assert one == 1.0


def test_routing_keeps_the_k_largest_renormalised_and_reports_the_gap():
    router = jnp.eye(4, 6, dtype=jnp.float32)
    m = jnp.asarray([[3.0, 2.0, 1.0, 0.0], [0.0, 0.0, 1.0, 1.001]])
    c, gap = ref.routing(m, router, 2)
    g = np.exp(np.asarray(m @ router))
    g /= g.sum(-1, keepdims=True)
    assert np.flatnonzero(np.asarray(c[0])).tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(c[0])[:2], g[0, :2] / g[0, :2].sum(),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(c).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gap)[0], 1 - g[0, 2] / g[0, 1],
                               rtol=1e-5)
    # the second row's second and third probabilities: e**0 against e**0
    top = np.sort(g[1])[::-1]
    np.testing.assert_allclose(np.asarray(gap)[1], 1 - top[2] / top[1],
                               atol=1e-6)


@pytest.fixture(scope="module")
def tiny():
    model = models.build(TINY, 5)
    model.eval()
    return model, models.weights(model)


def test_reference_agrees_with_the_program(tiny):
    import paddle_tpu as paddle

    model, weights = tiny
    ids = np.random.default_rng(1).integers(
        0, TINY["vocab_size"], (2, 40)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want, gaps = ref.logits_and_gaps(weights, TINY, ids)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    picks = ref.expert_picks(weights, TINY, ids)
    assert picks.shape == (4, 8) and picks.sum(1).tolist() == [160] * 4
    assert np.asarray(gaps).shape == (2, 40)


def test_a_flipped_row_takes_the_next_expert_instead_of_its_last():
    router = jnp.eye(4, 6, dtype=jnp.float32)
    m = jnp.asarray([[3.0, 2.0, 1.0, 0.0], [3.0, 2.0, 1.0, 0.0]])
    c, _ = ref.routing(m, router, 2, jnp.asarray([False, True]))
    assert np.flatnonzero(np.asarray(c[0])).tolist() == [0, 1]
    assert np.flatnonzero(np.asarray(c[1])).tolist() == [0, 2]
    np.testing.assert_allclose(np.asarray(c).sum(-1), 1.0, rtol=1e-6)


def _said(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_comparison_settles_near_ties_both_ways_and_says_so(
        tiny, monkeypatch, capsys):
    _, weights = tiny
    ids = np.random.default_rng(2).integers(
        0, TINY["vocab_size"], (2, 30)).astype(np.int32)
    rows = np.array([[10, 12, 14, 16], [20, 22, 24, 26]])
    z, per_layer = (np.asarray(a) for a in
                    ref._forward(weights, TINY, ids, rows))
    assert per_layer.shape == (4, 2, 4)
    np.testing.assert_array_equal(
        np.asarray(ref.logits_and_gaps(weights, TINY, ids, rows)[1]),
        per_layer.min(0))
    # no layer of any row under the gap: one pass, the logits as they are
    monkeypatch.setattr(ref, "TIE_GAP", 0.0)
    np.testing.assert_array_equal(ref.logits(weights, TINY, ids, rows), z)
    said = _said(capsys)
    assert said["info"] == "router_ties" and said["rows"] == 8
    assert said["passes"] == 1 and said["rows_with_near_tie_layers"] == 0
    # the gap set so that exactly one layer of one row is a near tie, and
    # the program's token (the next id) made the reference's least likely:
    # whichever way suits it better is the one that comes back
    layer, b, r = (int(i) for i in np.unravel_index(per_layer.argmin(),
                                                     per_layer.shape))
    monkeypatch.setattr(ref, "TIE_GAP", float(np.sort(per_layer.ravel())[:2]
                                              .mean()))
    flips = np.zeros((4,) + ids.shape, bool)
    flips[layer, b, rows[b, r]] = True
    other = np.asarray(ref._forward(weights, TINY, ids, rows, flips)[0])
    # the flip moved that row and, by its one key, hardly any other
    assert np.abs(other[b, r] - z[b, r]).max() > 1e-3
    token = ids[b, rows[b, r] + 1]
    flip_wins = ref._steps(other[b, r], token) < ref._steps(z[b, r], token)
    out = ref.logits(weights, TINY, ids, rows)
    np.testing.assert_array_equal(out[b, r],
                                  (other if flip_wins else z)[b, r])
    keep = np.ones((2, 4), bool)
    keep[b, r] = False
    np.testing.assert_array_equal(out[keep], z[keep])
    said = _said(capsys)
    assert said["passes"] == 2 and said["rows_with_near_tie_layers"] == 1
    # (the tokens are random ids: every row fails at first)
    assert said["rows_failing_at_first"] == 8
    assert len(said["settled_by_a_flip"]) == int(flip_wins)
    for before, after, flipped, gap in said["settled_by_a_flip"]:
        assert after < before and flipped == 1
        assert gap == pytest.approx(float(per_layer.min()))
    assert said["not_compared"] == 0 and said["compared"] == 8
    assert not said["fails_on_share"]
    # every layer of every row a near tie, more than a row may have: no
    # row is compared, and then none can pass, whatever token was chosen
    monkeypatch.setattr(ref, "TIE_GAP", 2.0)
    monkeypatch.setattr(ref, "TIE_LAYERS", 3)
    out = ref.logits(weights, TINY, ids, rows)
    for zt in out.reshape(-1, out.shape[-1]):
        others = np.delete(zt, zt.argmax())
        assert (zt.max() - others.max()) / ref.bf16_step(
            np.abs(zt).max()) > ref.LOGIT_TOL_ULPS
    said = _said(capsys)
    assert said["not_compared"] == 8 and said["compared"] == 0
    assert said["fails_on_share"]
    assert said["share_over_the_layers_at_twice_the_gap"] == 1.0
    # under the share they come back flat: the caller finds no gap there
    monkeypatch.setattr(ref, "TIE_SHARE", 1.0)
    assert np.all(ref.logits(weights, TINY, ids, rows) == 0)
    # a row that passes under the reference's own routing is never scored
    # another way, whatever its ties: one pass, the logits as they are
    capsys.readouterr()
    monkeypatch.setattr(ref, "LOGIT_TOL_ULPS", 1e9)
    np.testing.assert_array_equal(ref.logits(weights, TINY, ids, rows), z)
    said = _said(capsys)
    assert said["passes"] == 1 and said["rows_failing_at_first"] == 0
    assert said["not_compared"] == 0 and said["share_over_the_layers"] == 1.0


# --- the byte count -----------------------------------------------------------

def test_bytes_of_a_decode_step_at_the_published_sizes():
    assert arithmetic_moe.expert_bytes(PUBLISHED) == 3 * 2304 * 896 * 2
    assert arithmetic_moe.router_bytes(PUBLISHED) == 2304 * 64 * 2
    hit = arithmetic_moe.expected_experts_hit(PUBLISHED, 16)
    assert 56.0 < hit < 56.5              # 64 (1 - (56/64)**16)
    layers = PUBLISHED["num_hidden_layers"]
    got = arithmetic_moe.routed_decode_bytes(PUBLISHED, layers * 56)
    assert got == layers * (56 * 12386304 + 294912)
    # ~0.69 GB a layer
    assert 0.69e9 < got / layers < 0.70e9


# --- the reader ---------------------------------------------------------------

class _Run:
    def __init__(self, config, stats=None):
        self.config, self.samples = config, {"engine_stats": stats or {}}
        self.trace, self.said = None, []

    def info(self, kind, **values):
        self.said.append((kind, values))


# operations of two decode-only steps and one admit step as the trace names
# them (instruction, result type, the start of the operands), in ns
_OPS = [
    ("while.3 = (s32[], bf16[16,1,64]) while(...)", 0, 1000),
    ("fusion.12 = bf16[16,64] fusion(bf16[16,1,64] %x)", 10, 110),
    ("fusion.40 = bf16[8,16,32] fusion(bf16[16,64] %m, bf16[8,64,32] %wg)",
     120, 130),
    ("fusion.41 = f32[16,64] fusion(bf16[8,16,32] %g, bf16[16,64] %m, "
     "bf16[8,64,32] %wu, bf16[8,32,64] %wd, f32[16,8] %c)", 130, 330),
    ("fusion.7 = f32[16,8] fusion(bf16[16,64] %m, bf16[64,8] %wr)", 340, 360),
    ("copy.4 = bf16[8,32,64] copy(bf16[8,32,64] %wd)", 400, 450),
    ("fusion.30 = bf16[16,512] fusion(bf16[16,64] %h)", 500, 900),
    # the second decode-only step
    ("fusion.41 = f32[16,64] fusion(bf16[8,16,32] %g, bf16[16,64] %m, "
     "bf16[8,64,32] %wu, bf16[8,32,64] %wd, f32[16,8] %c)", 2100, 2400),
    ("fusion.30 = bf16[16,512] fusion(bf16[16,64] %h)", 2500, 2700),
    # an admit step: not counted
    ("fusion.90 = bf16[8,512,32] fusion(bf16[512,64] %m, bf16[8,64,32] %wg)",
     4000, 4900),
]


def test_routed_operations_are_found_by_what_they_are():
    texts = routed.marks(TINY)
    assert texts == ("[8,64,32]", "[8,32,64]", "[64,8]")
    from benchmarks import trace_reduce
    ops = trace_reduce.self_times(_OPS)
    got, rest, by_name = routed.split_steps(
        ops, [(0, 1000), (2000, 3000)], texts)
    # the banks' fusions 10 + 200 + 300, the router's 20, a bank's copy 50
    assert got == (10 + 200 + 20 + 50 + 300) / 2
    # the while's own time (1000 less what is nested in it), two fusions
    assert rest == ((1000 - 780) + 100 + 400 + 200) / 2
    assert set(by_name) == {"fusion", "copy"}
    assert routed.imbalance([[4, 4, 4, 4], [10, 2, 2, 2], [0, 0, 0, 0]]) \
        == 2.5
    assert routed.imbalance([[0, 0]]) is None


def test_longest_steps_names_the_step_its_phase_and_its_launches():
    from paddle_tpu.serving.metrics import LaunchRecord, StepRecord
    steps = [StepRecord(0, "decode", 10.0, 10.001, 10.003, 10.023, 10.024,
                        16),
             StepRecord(1, "admit", 10.030, 10.060, 10.062, 14.462, 14.463,
                        16),
             StepRecord(2, "decode", 14.470, 14.471, 14.473, 14.493, 14.494,
                        16)]
    launches = [LaunchRecord("decode", 0, 10.001, 10.003, 10.023, None, 16,
                             0),
                LaunchRecord("chunk", 1, 10.031, 10.059, None, 7, 512, 0),
                LaunchRecord("decode", 1, 10.060, 10.062, 14.462, None, 16,
                             0),
                LaunchRecord("decode", 2, 14.471, 14.473, 14.493, None, 16,
                             0)]
    got = routed.longest_steps(steps, launches, 10.0, n=1)
    (worst,) = got["steps"]
    assert worst["kind"] == "admit" and worst["at_s"] == pytest.approx(0.03)
    assert worst["fetch"] == pytest.approx(4400.0)
    assert [name for name, _, _ in worst["launches"]] == ["chunk", "decode"]
    assert worst["launches"][0][2] is None
    assert worst["launches"][1][2] == pytest.approx(4400.0)
    ms, at = got["longest_pause_ms_at_s"]
    assert ms == pytest.approx(7.0) and at == pytest.approx(4.47)
    assert routed.longest_steps([], [], 0.0) == {
        "steps": [], "longest_pause_ms_at_s": [0.0, 0.0]}


def test_readers_on_counters_and_on_a_dense_cell():
    moe = {"expert_tokens": [[6, 2, 4, 4], [4, 4, 4, 4]],
           "experts_hit": [30, 40], "decode_calls": 10}
    run = _Run(TINY, {"moe": moe})
    assert routed.moe_expert_imbalance(run) == 1.5
    # no trace: the two device metrics are silent
    assert routed.moe_ffn_device_ms(run) is None
    assert routed.moe_hbm_roofline_pct(run) is None
    # a dense configuration, and a program that keeps no such counters
    for other in (_Run(DENSE, {"moe": moe}), _Run(TINY, {})):
        assert routed.moe_expert_imbalance(other) is None
        assert routed.moe_ffn_device_ms(other) is None
        assert routed.moe_hbm_roofline_pct(other) is None


def test_roofline_share_from_a_split_and_the_counters(monkeypatch):
    moe = {"expert_tokens": [[1] * 8] * 4, "experts_hit": [60, 60, 60, 60],
           "decode_calls": 10}
    run = _Run(dict(TINY, torch_dtype="bfloat16"), {"moe": moe})
    run.device_kind = "TPU v5 lite"
    run.routed_split = (0.001, 0.002)          # ms: routed, rest
    # 24 experts a step x 3 x 64 x 32 x 2 B + 4 routers of 64 x 8 x 2 B
    want_bytes = 24 * 12288 + 4 * 1024
    got = routed.moe_hbm_roofline_pct(run)
    assert got == pytest.approx(100 * want_bytes / 819e9 / 1e-6)
    assert run.said[-1][1]["bytes_a_step"] == want_bytes


# --- the tiny cell, from files alone --------------------------------------------

@pytest.mark.parametrize("trace,expected", [
    (0, {"setup_s", "serve_tokens_per_s", "itl_p95_ms"}),
    (1, {"programs_built", "window_compiles.serve", "engine_step_ms.decode",
         "engine_step_ms.admit", "batch_occupancy", "moe_expert_imbalance"}),
])
def test_the_tiny_routed_cell_runs_from_files_alone(trace, expected):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--data", os.path.join(HERE, "data"), "--workload",
         "tiny_code_chat", "--seed", str(2**31 + 4321), "--seconds", "1.5",
         "--trace", str(trace), "--allow-cpu"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert all("info" in ln for ln in lines[:-1])
    ties = [ln for ln in lines if ln.get("info") == "router_ties"]
    assert len(ties) == 1 and ties[0]["rows"] == 12
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected
    if trace:
        assert result["metrics"]["moe_expert_imbalance"]["value"] >= 1.0
    said = [ln for ln in lines if ln.get("info") == "longest_steps"]
    assert len(said) == 1 and len(said[0]["steps"]) == 3
    assert said[0]["steps"][0]["launches"]


# --- the controls: one fault in the program, through the driver's comparison ------

@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("unnormalised_top_k", False), ("dropped_pick", False),
    ("no_window", False), ("plain_table_on_full_layers", False),
    ("eight_bit_activations", False)])
def test_a_planted_fault_fails_the_drivers_comparison(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "controls.py"),
         "--data", os.path.join(HERE, "data"), "--workload",
         "tiny_code_chat", "--seed", str(2**31 + 5240), "--fault", fault,
         "--allow-cpu"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["control"] == fault
    assert lines[-1]["correct"] is correct
    (said,) = [ln for ln in lines if ln.get("info") == "reference"]
    assert (said["worst_gap_bf16_steps"] <= said["tolerance_bf16_steps"]) \
        is correct
