"""One end-to-end run of each driver, on the CPU, on the tiny cells that
exist only as data under ``tests/data``: the harness runs a cell it has
never heard of from files alone."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
       "--data", os.path.join(HERE, "data")]


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(RUN + list(args), cwd=ROOT, env=env, text=True,
                          capture_output=True, timeout=300)


@pytest.mark.parametrize("cell,trace,expected", [
    ("tiny_pretrain", 0, {"setup_s", "train_tokens_per_s"}),
    ("tiny_pretrain", 1, {"programs_built", "window_compiles.train",
                          "loader_wait_ms"}),
    ("tiny_chat_closed", 0, {"setup_s", "serve_tokens_per_s", "itl_p95_ms"}),
    ("tiny_chat_closed", 1, {"programs_built", "window_compiles.serve",
                             "engine_step_ms.decode", "engine_step_ms.admit",
                             "batch_occupancy"}),
])
def test_a_cell_runs_from_files_alone(cell, trace, expected):
    p = run("--workload", cell, "--seed", str(2**31 + 12345), "--seconds",
            "1.5", "--trace", str(trace), "--allow-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert all("info" in json.loads(ln) for ln in lines[:-1])
    result = json.loads(lines[-1])
    # a CPU run reports no device metric and no breakdown; the numbers the
    # check compared come last, each within its limit
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["compared"] and all(
        m["value"] <= m["at_most"] if "at_most" in m
        else m["value"] >= m["at_least"] for m in result["compared"].values())
    err = p.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [ln.split()[1] for ln in err] == list(result["compared"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected
    assert all(set(m) == {"value", "unit"} and m["value"] is not None
               for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    if "window_compiles.train" in expected:
        assert result["metrics"]["window_compiles.train"]["value"] == 0


def test_without_a_chip_there_is_no_result():
    p = run("--workload", "tiny_pretrain", "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == "" and "TPU" in p.stderr
