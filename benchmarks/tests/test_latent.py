"""The latent cell's arithmetic against the issue's table, its readers on
what a run keeps (and every reader's silence where it has nothing to
read), its manifest entries, and the tiny latent cell run from files alone
with its controls."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks import arithmetic, arithmetic_latent as al, run as harness
from benchmarks import (reference, reference_latent_moe, reference_mellum,
                        reference_sambay)
from benchmarks.drivers import serve
from benchmarks.readers import decode_roofline, latent, routed

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "kimi_k26_agent_closed_16k"


def _config(name, where=BENCH):
    with open(os.path.join(where, "configs", f"{name}.json")) as f:
        return json.load(f)


K = _config("kimi-k2.6-ep32")
TINY = _config("tiny-latent", os.path.join(HERE, "data"))


def test_the_parameter_counts_of_the_issues_table():
    assert al.mla_params(K) == 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 \
        + 512 * 64 * 256 + 8192 * 7168
    assert round(al.mla_params(K) / 1e6, 1) == 101.1
    assert round(al.expert_params(K) / 1e6, 2) == 44.04
    assert round(al.router_params(K) / 1e6, 2) == 2.75
    # a routed layer outside its routed experts, and with the 12 held
    assert round(al.routed_layer_params(K) / 1e6, 1) == 147.9
    assert round(2 * al.routed_layer_params(K, 12) / 1e9, 3) == 1.353
    assert round(al.dense_layer_params(K) / 1e6, 1) == 497.5
    assert al.line_bytes(K) == 1152
    # a layer's 384 experts whole: what no chip holds
    assert round(384 * al.expert_params(K) / 1e9, 2) == 16.91
    assert round(2 * al.head_params(K) / 1e9, 3) == 0.294


def test_bytes_and_operations_of_a_decode_step():
    # every held expert hit in each of the 4 routed layers
    w = al.weight_bytes(K, 4 * 12)
    assert w == 2 * (al.dense_layer_params(K)
                     + 4 * al.routed_layer_params(K, 12) + al.head_params(K))
    assert round(w / 1e9, 2) == 6.70
    lines = 32 * 9000
    assert al.decode_step_bytes(K, lines, 48) == w + 5 * lines * 1152
    assert al.kernel_flops_a_line(K) == 2 * 64 * 1088
    peaks = arithmetic.peaks("TPU v5 lite")
    # 121 FLOP/B under a ridge of 240: the bytes bound the kernel
    assert al.kernel_seconds(K, lines, peaks) \
        == 5 * lines * 1152 / 819e9
    assert 5 * lines * 2 * 64 * 1088 / 197e12 \
        < al.kernel_seconds(K, lines, peaks)


class _Run:
    def __init__(self, config, stats=None):
        self.config, self.samples = config, {"engine_stats": stats or {}}
        self.trace, self.said, self.device_kind = None, [], "TPU v5 lite"
        self.traffic = {"engine": {"prefill_chunk": 512}}

    def info(self, kind, **values):
        self.said.append((kind, values))


_STATS = {"latent": {"line_bytes": 1152, "decode_calls": 10,
                     "lines": 10 * 32 * 9000},
          "decode_lines_seen": {"calls": 0, "lines": 0, "in_window": 0},
          "moe": {"expert_tokens": [[10] * 11 + [30], [5] * 12, [5] * 12,
                                    [5] * 12],
                  "experts_hit": [40, 40, 40, 40], "decode_calls": 10,
                  "picks": 32000, "picks_held": 1000}}


def test_the_new_readers_on_counters_and_their_silence_elsewhere():
    run = _Run(K, _STATS)
    # the busiest held expert of the worst layer: 30 of a mean of 140 / 12
    assert latent.held_expert_imbalance(run) == 30 * 12 / 140
    (said,) = [v for k, v in run.said if k == "held_picks"]
    assert said["share"] == 1000 / 32000 == said["expected_share"]
    # no device trace: no device metric
    for reader in (latent.latent_attention_device_ms,
                   latent.routed_share_device_ms,
                   latent.chunk_latent_attention_device_ms,
                   latent.chunk_routed_share_device_ms,
                   latent.latent_attention_roofline_pct,
                   latent.latent_decode_hbm_roofline_pct):
        assert reader(run) is None
    # a reader of one metric says nothing of another's
    assert {k for k, _ in run.said} == {"held_picks"}
    # another configuration, or a program without the counters: silence
    for name in ("deepseek-llm-7b", "mellum2-12b-a2.5b"):
        other = _Run(_config(name), _STATS)
        for reader in (latent.held_expert_imbalance,
                       latent.latent_attention_device_ms,
                       latent.routed_share_device_ms,
                       latent.chunk_latent_attention_device_ms,
                       latent.chunk_routed_share_device_ms,
                       latent.latent_attention_roofline_pct,
                       latent.latent_decode_hbm_roofline_pct):
            assert reader(other) is None
    assert latent.held_expert_imbalance(_Run(K, {})) is None


def test_the_old_readers_are_silent_on_a_latent_cell():
    run = _Run(K, _STATS)
    assert decode_roofline.counts(K, _STATS) is None
    assert decode_roofline.decode_hbm_roofline_pct(run) is None
    assert routed.moe_expert_imbalance(run) is None
    assert routed.moe_ffn_device_ms(run) is None
    assert routed.moe_hbm_roofline_pct(run) is None


def test_the_shares_divide_the_least_time_by_the_device_time(monkeypatch):
    run = _Run(K, _STATS)
    monkeypatch.setattr(latent, "_split", lambda run: (4.0, 3.0, 12.0))
    lines = 32 * 9000
    assert latent.latent_attention_device_ms(run) == 4.0
    assert latent.routed_share_device_ms(run) == 3.0
    assert latent.latent_attention_roofline_pct(run) == pytest.approx(
        100 * 5 * lines * 1152 / 819e9 / 4.0e-3)
    assert latent.latent_decode_hbm_roofline_pct(run) == pytest.approx(
        100 * al.decode_step_bytes(K, lines, 16) / 819e9 / 12.0e-3)
    assert 0 < latent.latent_decode_hbm_roofline_pct(run) < 100


# operations of one decode-only step as the trace names them, in ns
_OPS = [
    ("paged_latent_attention.3 = bf16[4,4,128] custom-call(...)", 0, 100),
    ("fusion.1 = bf16[4,4,32] fusion(bf16[4,64] %m, bf16[4,64,32] %wg)",
     100, 160),
    ("fusion.2 = f32[4,16] fusion(bf16[4,64] %m, bf16[64,16] %wr)", 160, 170),
    ("fusion.3 = bf16[4,32] fusion(bf16[4,64] %m, bf16[64,32] %sg)", 170, 190),
    ("fusion.9 = bf16[4,512] fusion(bf16[4,64] %h)", 200, 500),
    ("paged_latent_attention.4 = bf16[4,4,128] custom-call(...)", 900, 950),
]


def test_the_decode_programs_runs_are_found_on_the_modules_line():
    events = [("/device:TPU:0", "XLA Modules", "jit__paged_chunk_impl(1)",
               0.0, 90.0),
              ("/device:TPU:0", "XLA Modules", "jit__paged_decode_impl(2)",
               100.0, 50.0),
              ("/device:TPU:0", "XLA Ops", "fusion.1 = bf16[4] fusion()",
               100.0, 10.0),
              ("/device:TPU:1", "XLA Modules", "jit__paged_decode_impl(2)",
               100.0, 50.0),
              ("/device:TPU:0", "XLA Modules", "jit__paged_decode_impl(2)",
               900.0, 50.0)]
    decode, chunk = latent.DECODE_PROGRAM, latent.CHUNK_PROGRAM
    assert latent.program_runs(events, 0.0, 500.0, decode) \
        == [(100.0, 150.0)]
    assert latent.program_runs(events, 0.0, 1000.0, decode) \
        == [(100.0, 150.0), (900.0, 950.0)]
    assert latent.program_runs(events, 0.0, 1000.0, chunk) == [(0.0, 90.0)]


def test_the_kernel_is_found_by_its_name_and_the_share_by_its_arrays():
    from benchmarks import trace_reduce
    assert latent.marks(TINY) == ("[4,64,32]", "[4,32,64]", "[64,16]",
                                  "[64,32]", "[32,64]")
    ops = trace_reduce.self_times(_OPS)
    kernel, _, _ = routed.split_steps(ops, [(0, 600)], (latent.KERNEL,))
    share, rest, by_name = routed.split_steps(ops, [(0, 600)],
                                              latent.marks(TINY))
    assert kernel == 100 and share == 60 + 10 + 20 and rest == 100 + 300
    assert set(by_name) == {"fusion"}


# operations of one run of the chunk program (8 rows, 4 heads, nope + v 32)
_CHUNK_OPS = [
    ("fusion.5 = bf16[16,4,32] fusion(bf16[16,16] %lines, bf16[16,128] %kvb)",
     0, 40),
    ("fusion.6 = f32[4,8,16] fusion(bf16[8,4,24] %q, bf16[16,4,32] %fusion.5)",
     40, 100),
    ("select_reduce_fusion = (f32[4,8], f32[4,8,16]) fusion(pred[8,16] %ok)",
     100, 180),
    ("fusion.7 = bf16[8,64] fusion(bf16[4,32,64] %wd, bf16[4,32,8] %act)",
     180, 300),
    ("fusion.8 = bf16[8,512] fusion(bf16[8,64] %h)", 300, 350),
]


class _Traced(_Run):
    def __init__(self, config, events):
        super().__init__(config, _STATS)
        self.traffic = {"engine": {"prefill_chunk": 8}}
        self.trace = {"events": events, "reduced": {"busy_s": 1.0}}


def test_the_chunks_attention_and_share_are_read_from_the_chunk_programs_runs(
        monkeypatch):
    from benchmarks import trace_reduce
    run = _Traced(TINY, [("/device:TPU:0", "XLA Modules",
                          "jit__paged_chunk_impl(1)", 0.0, 400.0)])
    assert latent.chunk_marks(run) == ("[4,8,", ",4,32]")
    monkeypatch.setattr(trace_reduce, "window_of", lambda events: (0.0, 1e3))
    monkeypatch.setattr(trace_reduce, "device_ops",
                        lambda events: {"/device:TPU:0": _CHUNK_OPS})
    # ns in, ms out: the expansion, the scores and the select-reduce are
    # attention's, the fusion over a held bank the share's
    assert latent.chunk_latent_attention_device_ms(run) \
        == pytest.approx(180e-6)
    assert latent.chunk_routed_share_device_ms(run) == pytest.approx(120e-6)
    (said,) = [v for k, v in run.said if k == "latent_chunk_ops"]
    assert said["chunk_runs"] == 1 and said["rest_ms"] == pytest.approx(50e-6)
    # no run of the decode program in this trace: its readers stay silent
    assert latent.latent_attention_device_ms(run) is None
    # a traffic without chunked prefill has no chunk to read
    run = _Traced(TINY, [])
    run.traffic = {"engine": {}}
    assert latent.chunk_latent_attention_device_ms(run) is None


# --- the manifest's new entries ---------------------------------------------

def test_the_manifest_names_the_new_configuration_cell_and_metrics():
    m = harness.load(os.path.join(ROOT, "BENCHMARK.json"))
    (c,) = [c for c in m["configs"] if c["name"] == "kimi-k2.6-ep32"]
    assert c["reduced"] == K["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert K["published"] == {"num_hidden_layers": 61,
                              "n_routed_experts": 384, "vocab_size": 163840}
    # every width as published; the router scores 384 and picks 8
    assert (K["hidden_size"], K["intermediate_size"],
            K["moe_intermediate_size"], K["q_lora_rank"], K["kv_lora_rank"],
            K["qk_nope_head_dim"], K["qk_rope_head_dim"], K["v_head_dim"],
            K["num_attention_heads"]) == (7168, 18432, 2048, 1536, 512, 128,
                                          64, 128, 64)
    assert (K["n_router_experts"], K["num_experts_per_tok"],
            K["n_routed_experts"], K["first_routed_expert"]) == (384, 8, 12, 0)
    assert "32 chips" in K["deployment"] or "chip of 32" in K["deployment"]
    (w,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "kimi-k2.6-ep32", "agent_closed_16k", 1)
    mine = {x["name"] for x in m["per_layer"] + m["end_to_end"]
            if CELL in x.get("workloads", [])}
    assert {"latent_decode_hbm_roofline_pct", "latent_attention_roofline_pct",
            "latent_attention_device_ms", "routed_share_device_ms",
            "chunk_latent_attention_device_ms",
            "chunk_routed_share_device_ms",
            "held_expert_imbalance", "serve_tokens_per_s", "itl_p95_ms",
            "programs_built", "window_compiles.serve",
            "engine_step_ms.admit", "batch_occupancy"} <= mine
    # silent here: the old shares, and what reads steps that only decode
    # (every step of this traffic carries a chunk)
    assert not mine & {"decode_hbm_roofline_pct", "moe_ffn_device_ms",
                       "moe_hbm_roofline_pct", "moe_expert_imbalance",
                       "prefill_ms.bucket", "decode_device_ms",
                       "engine_step_ms.decode", "engine_phase_ms.fetch",
                       "device_idle_ms.host"}
    for name in ("latent_decode_hbm_roofline_pct",
                 "latent_attention_roofline_pct", "latent_attention_device_ms",
                 "routed_share_device_ms", "held_expert_imbalance",
                 "chunk_latent_attention_device_ms",
                 "chunk_routed_share_device_ms"):
        (x,) = [x for x in m["per_layer"] if x["name"] == name]
        assert x["workloads"] == [CELL]
        # the manifest and the metric's own file agree on what it moves
        mine = harness.load(os.path.join(BENCH, "metrics", f"{name}.json"))
        assert (x["moves"], x["layer"], x["unit"]) == (
            mine["moves"], mine["layer"], mine["unit"])


def test_the_traffic_is_the_issues_but_for_its_sigmas():
    t = harness.load(os.path.join(BENCH, "traffic", "agent_closed_16k.json"))
    from benchmarks import traffic
    assert t["engine"] == {"n_slots": 32, "max_len": 16384,
                           "prefill_chunk": 512}
    turns = traffic.length_pool(t["turn_tokens"], t["pool"])
    outs = traffic.length_pool(t["output_tokens"], t["pool"])
    longest = max(t["shared_prefix_tokens"] + a + b for group in
                  traffic.groups(turns, outs, t["strata"]) for a, b in group)
    assert longest < t["engine"]["max_len"]
    assert min(turns) + 4096 > t["engine"]["prefill_chunk"]
    assert 7000 < 4096 + sum(turns) / len(turns) < 7500
    # the longest prompt passes 10 k of the 16 k the cell is named for
    assert 4096 + max(turns) > 10240
    # medians, clips, clients and the check as the issue gave them; both
    # sigmas narrowed from its 0.7 (the file's ``about`` says why)
    assert (t["turn_tokens"]["median"], t["output_tokens"]["median"],
            t["clients"], t["shared_prefix_tokens"]) == (3072, 160, 32, 4096)
    assert t["turn_tokens"]["sigma"] == t["output_tokens"]["sigma"] == 0.3
    # the check's prompts as given; 32 new tokens where the issue gave 8:
    # over 4 x 8 rows the 8-bit control passed on one seed of three
    assert t["check"] == {"count": 4, "min_prompt": 4608,
                          "max_prompt": 12288, "new_tokens": 32}


# --- the check's verdict on a run's rows --------------------------------------

def _failed_on(module, gaps):
    return [k for k, v in serve.verdict(module, gaps).items()
            if v["value"] > v["at_most"]]


def _rows(n, *over):
    return [1.0] * (n - len(over)) + list(over)


@pytest.mark.parametrize("gaps,failed_on", [
    (_rows(128, 4.76), []),
    (_rows(128, 4.1, 4.1), []),
    (_rows(128, 4.1, 4.1, 4.1), ["rows_over_tolerance"]),
    (_rows(128, 13.06), []),
    (_rows(128, 64.0), []),
    (_rows(128, 64.5), ["worst_gap_bf16_steps"]),
    (_rows(128, 197.0, 4.1), ["worst_gap_bf16_steps"]),
    (_rows(128, 4.1, 5.0, 298.0), ["worst_gap_bf16_steps",
                                   "rows_over_tolerance"]),
    (_rows(12, 4.1), ["rows_over_tolerance"]),
    (_rows(12, 4.0), []),
    (_rows(127, 5.0, 6.0), ["rows_over_tolerance"]),
], ids=["one_row_at_4.76", "two_rows_at_4.1", "three_rows_at_4.1",
        "a_row_of_a_tie_settled_in_part", "one_row_at_the_ceiling",
        "one_row_over_the_ceiling", "an_altered_token", "three_over_one_far",
        "12_rows_one_at_4.1", "12_rows_at_the_tolerance", "127_rows_two_over"])
def test_the_latent_check_lets_one_row_in_64_over_the_tolerance(gaps,
                                                                failed_on):
    assert reference_latent_moe.LOGIT_TOL_ULPS == 4
    assert reference_latent_moe.CEILING_ULPS == 64
    assert _failed_on(reference_latent_moe, gaps) == failed_on
    # the dense, routed and hybrid checks hold the worst row to the
    # tolerance, as before
    for other in (reference, reference_mellum, reference_sambay):
        assert (not _failed_on(other, gaps)) is (max(gaps) <= 4), other
        said = serve.verdict(other, gaps)
        assert said["worst_gap_bf16_steps"]["at_most"] == 4
        assert said["rows_over_tolerance"]["at_most"] == 0


# --- the tiny latent cell, from files alone ----------------------------------

def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--data",
         os.path.join(HERE, "data"), "--workload", "tiny_agent_closed",
         "--allow-cpu", *args],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(ln) for ln in p.stdout.strip().splitlines()]


@pytest.mark.parametrize("trace,expected", [
    (0, {"setup_s", "serve_tokens_per_s", "itl_p95_ms"}),
    (1, {"programs_built", "window_compiles.serve", "engine_step_ms.decode",
         "engine_step_ms.admit", "batch_occupancy", "held_expert_imbalance"}),
])
def test_the_tiny_latent_cell_runs_from_files_alone(trace, expected):
    lines = _run("run.py", "--seed", str(2**31 + 4321), "--seconds", "1.5",
                 "--trace", str(trace))
    assert all("info" in ln for ln in lines[:-1])
    ties = [ln for ln in lines if ln.get("info") == "router_ties"]
    assert len(ties) == 1 and ties[0]["rows"] == 12
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected
    (serve,) = [ln for ln in lines if ln.get("info") == "serve"]
    assert serve["prefix_hit_pct"] > 20 and serve["chunk_program"]
    if trace:
        (held,) = [ln for ln in lines if ln.get("info") == "held_picks"]
        # experts 4-7 of 16 are held
        assert held["expected_share"] == 0.25
        # (the toy's bias is drawn wide, so that it is live among 16)
        assert 0.05 < held["share"] < 0.5


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("no_rotary_score", False), ("no_selection_bias", False),
    ("no_routed_scale", False), ("no_shared_expert", False),
    ("altered_token", False)])
def test_a_planted_fault_fails_the_drivers_comparison(fault, correct):
    """At the tiny size; ``no_mscale``, ``no_latent_norm`` and
    ``eight_bit_activations`` move too little there to flip a token of 12
    in every seed and are held on the chip, at the cell's own widths
    (``PERF.md`` section 6)."""
    lines = _run("controls_latent.py", "--seed", str(2**31 + 5240),
                 "--fault", fault)
    assert lines[-1]["control"] == fault
    assert lines[-1]["correct"] is correct
    (said,) = [ln for ln in lines if ln.get("info") == "reference"]
    assert (not said["failed_on"]) is correct
    assert said["rows"] == 12 and said["rows_allowed_over_tolerance"] == 0
