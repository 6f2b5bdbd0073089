#!/usr/bin/env python
"""Retrace lint: a warm eager train loop must be trace-free.

Runs an MLP train step (forward, cross-entropy, backward, Adam step,
clear_grad) eagerly for a warmup phase, snapshots the dispatch-cache
counters, then runs a measured phase and fails if ANY signature was
compiled, missed, or bypassed during it — i.e. steady-state eager
execution must be 100% cache hits (0 traces). Also cross-checks with a
jax monitoring listener counting backend compile events, so a retrace
that sneaks around the dispatch counters still fails the build.

``--warm-cache`` exercises the paddle_tpu.aot persistent executable
cache instead: the same workload runs in two fresh subprocesses sharing
one cache directory (warmup thresholds floored so programs build on
step 1), and the gate is that the SECOND process performs 0 XLA backend
compiles across its whole training phase — including the first step —
with bitwise-identical losses. Without this mode a warm cache would
read as an impossibly-good budget, and with a broken one the tool
would report cold budget violations that are really cache misses.

CPU by default (JAX_PLATFORMS is set to cpu unless given). The
``--warm-cache`` parent imports no jax and runs its two children one
after the other, so each is the only jax process.

Modeled on tools/check_hlo_layout.py. Usage:

    JAX_PLATFORMS=cpu python tools/check_retrace.py [--json] [--warm-cache]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_workload(args):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.framework import dispatch_cache

    counter = analysis.CompileEventCounter().install()
    have_monitor = counter.available

    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((32, 64)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (32,)).astype(np.int64))
    net = paddle.nn.Sequential(paddle.nn.Linear(64, 64), paddle.nn.ReLU(),
                               paddle.nn.Linear(64, 10))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())

    def step():
        loss = paddle.nn.functional.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    counter.reset()          # whole-training window (AOT warm gate)
    for _ in range(args.warmup):
        loss = step()
    workload_compiles = counter.count

    warm = dispatch_cache.dispatch_stats()
    counter.reset()
    for _ in range(args.steps):
        loss = step()
    loss_val = float(loss.numpy())
    workload_compiles += counter.count

    stats = dispatch_cache.dispatch_stats()
    delta = {k: stats[k] - warm[k]
             for k in ("hits", "misses", "compiles", "bypasses")}
    traces = delta["misses"] + delta["compiles"] + delta["bypasses"]
    if have_monitor:
        traces += counter.count
    ok = stats["enabled"] and traces == 0 and delta["hits"] > 0

    # retrace-risk findings (blacklisted/megamorphic ops, with reasons)
    # ride along in the ledger; the exit code stays the trace count's
    findings = [f.to_dict() for f in analysis.audit_dispatch().findings]
    record = {"bench": "retrace_lint", "model": "mlp_adam",
              "warmup": args.warmup, "steps": args.steps,
              "steady_state_traces": traces, "delta": delta,
              "backend_compiles": counter.count if have_monitor else None,
              "workload_backend_compiles": (workload_compiles
                                            if have_monitor else None),
              "loss_bits": np.float32(loss_val).tobytes().hex(),
              "cache": stats, "findings": findings, "ok": ok}
    return record


def run_warm_cache(args):
    """Subprocess pair sharing one AOT cache dir: run 2 must train with
    ZERO backend compiles from its very first step."""
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="aot-retrace-")
    env = dict(os.environ,
               PADDLE_TPU_AOT_CACHE_DIR=cache_dir,
               PADDLE_TPU_EAGER_CACHE_WARMUP="1",
               PADDLE_TPU_FUSED_STEP_WARMUP="0")
    runs = []
    for tag in ("cold", "warm"):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--json",
             "--warmup", str(args.warmup), "--steps", str(args.steps)],
            capture_output=True, text=True, env=env)
        if not out.stdout.strip():
            return {"bench": "retrace_warm_cache", "ok": False,
                    "error": f"{tag} run failed: {out.stderr[-800:]}"}
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    have = warm["workload_backend_compiles"] is not None
    ok = (cold["ok"] and warm["ok"]
          and warm["loss_bits"] == cold["loss_bits"]
          and (not have or warm["workload_backend_compiles"] == 0))
    return {"bench": "retrace_warm_cache", "cache_dir": cache_dir,
            "cold_workload_compiles": cold["workload_backend_compiles"],
            "warm_workload_compiles": warm["workload_backend_compiles"],
            "loss_bits_equal": warm["loss_bits"] == cold["loss_bits"],
            "cold": cold, "warm": warm, "ok": ok}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true", help="emit a JSON line")
    # warmup must clear both engage thresholds at their defaults
    # (PADDLE_TPU_EAGER_CACHE_WARMUP=32 sightings per op signature,
    # PADDLE_TPU_FUSED_STEP_WARMUP=32 optimizer steps) plus the step
    # that compiles, so the measured phase is pure steady state
    ap.add_argument("--warmup", type=int, default=40)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warm-cache", action="store_true",
                    help="subprocess-pair AOT cache gate: the second "
                         "process must do 0 backend compiles")
    args = ap.parse_args()

    record = run_warm_cache(args) if args.warm_cache else run_workload(args)
    ok = record["ok"]
    if args.json:
        print(json.dumps(record))
    elif args.warm_cache:
        print(f"cold workload compiles: "
              f"{record.get('cold_workload_compiles')}")
        print(f"warm workload compiles: "
              f"{record.get('warm_workload_compiles')}")
        print("OK (warm process trains compile-free)" if ok else
              "FAIL: warm cache still compiles (or drifted bitwise)")
    else:
        for k, v in record["delta"].items():
            print(f"{k:12s} {v}")
        print(f"{'backend':12s} {record['backend_compiles']}")
        print("OK (0 steady-state traces)" if ok else
              "FAIL: warm eager loop still traces")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
