"""The hybrid cell's reference against a hand-written scalar recurrence,
its arithmetic against the issue's byte counts, its readers on a stored
trace fragment (and every reader's silence where it has nothing to read),
its manifest entries, and the tiny cell run from files alone with its
controls."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import arithmetic_hybrid as ah, reference_sambay as ref, \
    trace_reduce
from benchmarks.readers import hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "phi4flash_reason_closed_8k"


def _config(name, where=BENCH):
    with open(os.path.join(where, "configs", f"{name}.json")) as f:
        return json.load(f)


P = _config("phi-4-mini-flash-reasoning")
TINY = _config("tiny-sambay", os.path.join(HERE, "data"))


# --- the reference -----------------------------------------------------------


def _mamba_weights(rng, h=6, di=4, ds=3, r=2, dc=4):
    f = np.float32
    return {"in_proj.weight": rng.normal(size=(h, 2 * di)).astype(f),
            "conv1d.weight": rng.normal(size=(dc, di)).astype(f),
            "conv1d.bias": rng.normal(size=(di,)).astype(f),
            "x_proj.weight": rng.normal(size=(di, r + 2 * ds)).astype(f),
            "dt_proj.weight": rng.normal(size=(r, di)).astype(f),
            "dt_proj.bias": rng.normal(size=(di,)).astype(f) - 2,
            "A_log": np.log(np.tile(np.arange(1, ds + 1, dtype=f), (di, 1))),
            "D": np.ones(di, f),
            "out_proj.weight": rng.normal(size=(di, h)).astype(f)}


def _scalar_mamba(x, w):
    """The recurrence written out number by number: 2 x 8 tokens take a
    few thousand scalar operations."""
    def silu(a):
        return a / (1.0 + math.exp(-a))

    T, di = x.shape[0], w["D"].shape[0]
    ds, r = w["A_log"].shape[1], w["dt_proj.weight"].shape[0]
    dc = w["conv1d.weight"].shape[0]
    uz = x @ w["in_proj.weight"]
    u_in, z = uz[:, :di], uz[:, di:]
    h = np.zeros((di, ds))
    out, mem = np.zeros((T, x.shape[1])), np.zeros((T, di))
    for t in range(T):
        u = np.zeros(di)
        for c in range(di):
            acc = w["conv1d.bias"][c]
            for j in range(dc):             # tap dc - 1 is the newest input
                src = t - (dc - 1) + j
                if src >= 0:
                    acc += w["conv1d.weight"][j, c] * u_in[src, c]
            u[c] = silu(acc)
        dbc = u @ w["x_proj.weight"]
        B, C = dbc[r:r + ds], dbc[r + ds:]
        gated = np.zeros(di)
        for c in range(di):
            pre = dbc[:r] @ w["dt_proj.weight"][:, c] + w["dt_proj.bias"][c]
            dt = math.log1p(math.exp(pre))
            y = 0.0
            for s in range(ds):
                a = -math.exp(w["A_log"][c, s])
                h[c, s] = math.exp(dt * a) * h[c, s] + dt * B[s] * u[c]
                y += C[s] * h[c, s]
            mem[t, c] = y + w["D"][c] * u[c]
            gated[c] = mem[t, c] * silu(z[t, c])
        out[t] = gated @ w["out_proj.weight"]
    return out, mem


@pytest.mark.parametrize("seed", [0, 1])
def test_the_recurrence_is_the_scalar_one_on_eight_tokens(seed):
    import jax

    rng = np.random.default_rng(seed)
    w = _mamba_weights(rng)
    x = rng.normal(size=(8, 6)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, mem = ref.mamba(x, w)
    want, want_mem = _scalar_mamba(x.astype(np.float64),
                                   {k: a.astype(np.float64)
                                    for k, a in w.items()})
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mem), want_mem, rtol=2e-4,
                               atol=2e-5)


def test_differential_attention_is_the_two_maps_written_out():
    import jax

    rng = np.random.default_rng(3)
    config = {"num_attention_heads": 4, "num_key_value_heads": 2,
              "hidden_size": 8}
    f = np.float32
    w = {"Wqkv.weight": rng.normal(size=(8, 16)).astype(f),
         "out_proj.weight": rng.normal(size=(8, 8)).astype(f),
         "subln": rng.normal(size=(4,)).astype(f) + 1,
         **{f"lambda_{n}": (0.1 * rng.normal(size=(2,))).astype(f)
            for n in ("q1", "k1", "q2", "k2")}}
    x = rng.normal(size=(5, 8)).astype(f)
    with jax.default_matmul_precision("highest"):
        got, (k, v) = ref.differential_attention(x, w, None, 3, config,
                                                 window=3)
    qkv = x.astype(np.float64) @ w["Wqkv.weight"]
    q, kk, vv = qkv[:, :8].reshape(5, 4, 2), qkv[:, 8:12].reshape(5, 2, 2), \
        qkv[:, 12:].reshape(5, 2, 2)
    li = 0.8 - 0.6 * math.exp(-0.9)
    lam = math.exp(w["lambda_q1"] @ w["lambda_k1"]) \
        - math.exp(w["lambda_q2"] @ w["lambda_k2"]) + li
    rows = []
    for t in range(5):
        pairs = []
        for p in range(2):              # one KV pair (heads 0, 1) for both
            maps = []
            for j in range(2):
                keys = range(max(0, t - 2), t + 1)
                s = np.array([q[t, 2 * p + j] @ kk[i, j] / math.sqrt(2)
                              for i in keys])
                pr = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
                maps.append(sum(pr[n] * vv[i].reshape(4)
                                for n, i in enumerate(keys)))
            d = maps[0] - lam * maps[1]
            d = d / math.sqrt((d * d).mean() + 1e-5) * w["subln"]
            pairs.append((1 - li) * d)
        rows.append(np.concatenate(pairs))
    np.testing.assert_allclose(np.asarray(got),
                               np.stack(rows) @ w["out_proj.weight"],
                               rtol=2e-4, atol=2e-5)
    assert k.shape == (5, 2, 2) and v.shape == (5, 2, 2)


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "reference_sambay.py")).read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]


# --- the arithmetic ----------------------------------------------------------


def test_the_parameter_counts_of_the_issues_table():
    assert ah.layer_counts(P) == {"mamba": 9, "window": 8, "full": 1,
                                  "gmu": 7, "cross": 7}
    assert round(ah.mlp_params(P) / 1e6, 1) == 78.6
    assert round(ah.attention_params(P) / 1e6, 1) == 19.7
    assert round(ah.attention_params(P, True) / 1e6, 1) == 13.1
    assert round(ah.mamba_params(P) / 1e6, 1) == 41.2
    assert round(ah.gmu_params(P) / 1e6, 1) == 26.2
    assert round(ah.embedding_params(P) / 1e6) == 512
    assert round(ah.total_params(P) / 1e9, 2) == 3.85
    assert round(ah.weight_bytes(P) / 1e9, 2) == 7.70


def test_the_bytes_of_the_state_and_of_a_decode_step():
    assert ah.line_bytes(P) == 5120
    held = ah.cache_bytes(P, 64, 8192)
    assert round(held["pool_bytes"] / 1e9, 2) == 2.68
    assert round(held["window_bytes"] / 1e9, 2) == 1.34
    assert round(held["state_bytes"] / 1e9, 2) == 0.21
    assert round((ah.weight_bytes(P) + sum(held.values())) / 1e9, 1) == 11.9
    # the issue's step: 64 rows at 1.2 k of live context
    lines = 64 * 1200
    step = ah.decode_step_bytes(P, lines, 64 * 512, 64)
    assert step == ah.weight_bytes(P) + 8 * lines * 5120 \
        + 8 * 64 * 512 * 5120 + 2 * 64 * ah.state_bytes(P)
    assert round(8 * lines * 5120 / 1e9, 1) == 3.1
    assert round(2 * 64 * ah.state_bytes(P) / 1e9, 1) == 0.4
    assert round(step / 1e9, 1) == 12.6
    assert round(step / 819e9 * 1e3, 1) == 15.4


# --- the readers -------------------------------------------------------------

# operations of one decode-only step of the tiny cell (4 slots, hidden 64,
# d_inner 128, state 4, rank 4; max_len 128 and a window of 16 in blocks
# of 16) as the trace names them, in ns
_OPS = [
    ("fusion.1 = bf16[4,1,256] fusion(bf16[4,1,64] %x, bf16[64,256] %w)",
     0, 100),                                    # a recurrent layer's W_in
    ("fusion.2 = f32[4,128] fusion(bf16[4,4,128] %ext, f32[4,128] %cw)",
     100, 130),                                  # its convolution
    ("fusion.3 = f32[4,4,128] fusion(f32[4,4,128] %ssm, f32[4,128] %dt)",
     130, 200),                                  # its state
    ("fusion.4 = bf16[4,1,64] fusion(bf16[4,1,128] %y, bf16[128,64] %wout)",
     200, 260),
    ("fusion.5 = bf16[4,1,256] fusion(bf16[4,1,64] %h, bf16[64,256] %wgu)",
     260, 300),      # an MLP: [64,256] is W_in's shape too in this toy
    ("fusion.6 = bf16[4,1,128] fusion(bf16[4,1,64] %h, bf16[64,128] %w)",
     300, 340),                                  # W_qkv or a unit's W_in
    ("paged_attention.1 = bf16[4,8,16] custom-call(s32[4,1] %rt, s32[4] %p)",
     340, 400),                                  # a window layer's ring
    ("paged_attention.2 = bf16[4,8,16] custom-call(s32[4,8] %t, s32[4] %p)",
     400, 500),                                  # the pool
    ("paged_attention.3 = bf16[4,8,16] custom-call(s32[4,8] %t, s32[4] %p)",
     500, 600),
    ("fusion.9 = bf16[4,512] fusion(bf16[4,64] %h, bf16[512,64] %embed)",
     600, 900),
]


def test_the_parts_of_a_decode_step_are_found_by_what_they_are():
    own, shared = hybrid.marks(TINY)
    assert shared == "[64,128]" and "[64,256]" in own and "[128,64]" in own
    assert hybrid.gated_share(P) == 7 / 16
    assert hybrid.gated_share(TINY) == 1 / 4
    ops = trace_reduce.self_times(_OPS)
    got = hybrid.split_steps(ops, [(0, 1000)], TINY,
                             ("s32[4,8]", "s32[4,1]"))
    # the toy's W_gate_up has W_in's shape: counted recurrent here, at the
    # published sizes ([2560,20480] against [2560,10240]) it is not
    assert got["recurrent"] == pytest.approx(
        100 + 30 + 70 + 60 + 40 + 40 / 4)
    assert got["shared_kv"] == 200 and got["window_kv"] == 60
    assert got["rest"] == pytest.approx(300 + 40 * 3 / 4)
    assert sum(got.values()) == pytest.approx(900)
    # an operation outside every step is nobody's
    assert sum(hybrid.split_steps(ops, [(0, 250)], TINY,
                                  ("s32[4,8]", "s32[4,1]")).values()) == 200


def test_a_float32_array_of_d_inner_is_the_recurrences():
    own, _ = hybrid.marks(P)
    assert hybrid.is_recurrent("fusion.29 = f32[64,5120] fusion(f32[9,64,"
                               "16,5120] %state", own, 5120)
    assert hybrid.is_recurrent("fusion.1 = f32[64,1,5120] fusion(bf16[64,1,"
                               "192] %dbc)", own, 5120)
    assert not hybrid.is_recurrent("fusion.2 = bf16[64,1,5120] fusion(bf16["
                                   "64,1,2560] %h, bf16[2560,5120] %w)", own,
                                   5120)
    assert not hybrid.is_recurrent("fusion.3 = bf16[64,1,20480] fusion(bf16["
                                   "64,1,2560] %h, bf16[2560,20480] %w)",
                                   own, 5120)


class _Run:
    device_kind = "TPU v5 lite"

    def __init__(self, config, stats, steps=()):
        self.config, self.trace = config, None
        self.traffic = {"engine": {"n_slots": 64, "max_len": 8192}}
        self.samples = {"engine_stats": stats, "engine_steps": list(steps),
                        "first_step": 0}
        self.said = []

    def info(self, kind, **values):
        self.said.append((kind, values))


_STATS = {"recurrent": {"decode_calls": 10, "decode_lines_seen": 10 * 76800,
                        "decode_lines_in_window": 10 * 64 * 512,
                        "shared_kv_readers": 8},
          "decode_lines_seen": {"calls": 0, "lines": 0, "in_window": 0}}


def test_the_share_divides_the_least_time_by_the_device_time(monkeypatch):
    from benchmarks.readers import device

    run = _Run(P, _STATS, [(0, 1, "decode", 64), (1, 2, "admit", 60),
                           (2, 3, "decode", 64)])
    monkeypatch.setattr(device, "decode_device_ms", lambda run: 25.0)
    got = hybrid.hybrid_decode_hbm_roofline_pct(run)
    want = ah.decode_step_bytes(P, 76800, 64 * 512, 64)
    assert got == pytest.approx(100 * want / 819e9 / 25e-3)
    assert 0 < got < 100
    said = dict(run.said)["hybrid_decode_step_bytes"]
    assert said["rows_a_step"] == 64 and said["readers"] == 8


def test_every_reader_is_silent_where_it_has_nothing_to_read():
    readers = (hybrid.recurrent_device_ms,
               hybrid.shared_kv_attention_device_ms,
               hybrid.hybrid_decode_hbm_roofline_pct)
    dense = _config("deepseek-llm-7b")
    for run in (_Run(dense, _STATS), _Run(P, {}), _Run(P, _STATS)):
        assert [r(run) for r in readers] == [None] * 3


def test_the_manifest_lists_the_cell_where_its_readers_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "itl_p95_ms", "setup_s",
            "recurrent_device_ms", "shared_kv_attention_device_ms",
            "hybrid_decode_hbm_roofline_pct", "decode_device_ms",
            "engine_step_ms.decode", "window_compiles.serve"} <= mine
    # a llama's bytes, and other models' layers
    assert not {"decode_hbm_roofline_pct", "moe_ffn_device_ms",
                "latent_attention_device_ms"} & mine
    config = next(c for c in manifest["configs"]
                  if c["name"] == "phi-4-mini-flash-reasoning")
    assert config["reduced"] == [] and P["reduced"] == []
    assert P["num_hidden_layers"] == 32 and P["vocab_size"] == 200064


# --- the tiny cell, from files alone -------------------------------------------


def _python(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--data",
         os.path.join(HERE, "data"), "--workload", "tiny_reason_closed",
         "--allow-cpu", *args], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=300)


def test_the_tiny_cell_runs_from_files_alone():
    p = _python("run.py", "--seed", str(2**31 + 35), "--seconds", "1.5",
                "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert {"programs_built", "window_compiles.serve",
            "engine_step_ms.decode", "batch_occupancy"} \
        <= set(result["metrics"])
    assert result["metrics"]["window_compiles.serve"]["value"] == 0


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("state_not_carried", False),
    ("window_one_too_long", False), ("no_window", False),
    ("zero_memory", False), ("stale_lines", False),
    ("lambda_dropped", False), ("eight_bit_activations", False)])
def test_every_planted_fault_fails_the_comparison(fault, correct):
    p = _python("controls_hybrid.py", "--seed", "5", "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"control": fault, "correct": correct,
                    "setup_phases": last["setup_phases"],
                    "compared": last["compared"]}
    # the hybrid check holds the worst row to the tolerance: no row over
    assert last["compared"]["worst_gap_bf16_steps"]["at_most"] == 4
    assert last["compared"]["rows_over_tolerance"]["at_most"] == 0
