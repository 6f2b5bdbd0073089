#!/usr/bin/env python
"""Train-collective lint: the comm-opt train step's HLO contract,
enforced (ROADMAP item 2 CI gate, modeled on
tools/check_serving_compiles.py).

Gates (all lower-only — no XLA backend compile is needed to inspect the
program text):

- **int8 DP**: the quantized-allreduce train step's StableHLO carries
  int8 collective operands (the ``all_to_all`` payload travels as
  ``i8``) and NO full-size fp32 gradient ``all_reduce``.
- **ZeRO-1**: the sharded-update step's HLO contains ``reduce_scatter``
  (the fused update consumes the shard directly) + ``all_gather`` (the
  params re-materialize) and again no full-gradient ``all_reduce``.
- **overlap**: 0 high ``unoverlapped-collective`` findings on the REAL
  lowered tp-overlap train step, while a seeded serial ``psum(dx @ w)``
  train step (``tp_overlap=False``) IS caught by the same rule.

``--steps N`` additionally RUNS the ZeRO-1 / replicated pair and
asserts bitwise parameter equality plus ~1/dp optimizer memory (slower:
pays the backend compiles; the default lower-only mode is the fast CI
smoke).

``--warm-cache`` runs the int8+ZeRO-1 workload in two fresh
subprocesses sharing one paddle_tpu.aot cache directory and asserts the
SECOND process builds 0 train-step programs (service misses == 0,
compiled == 0 — the mesh-keyed signature restored the executable).

CPU-only: it checks program text on 8 virtual CPU devices. The
``--warm-cache`` parent imports no jax and runs its two children one
after the other.

Usage:
    JAX_PLATFORMS=cpu python tools/check_train_collectives.py [--json]
    JAX_PLATFORMS=cpu python tools/check_train_collectives.py --steps 8
    JAX_PLATFORMS=cpu python tools/check_train_collectives.py --warm-cache
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


def _build(grad_compress=None, zero1=False, mp=1, tp_overlap=True,
           seed=0):
    import paddle_tpu
    from paddle_tpu import nn, optimizer as optim
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy

    strategy = DistributedStrategy()
    # dp=4 fits the 8 virtual devices for both mp=1 and mp=2
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": 1}
    strategy.comm_opt = True
    strategy.comm_opt_configs = {"grad_compress": grad_compress,
                                 "zero1": zero1, "tp_overlap": tp_overlap,
                                 "qblock": 64}
    fleet.init(is_collective=True, strategy=strategy)
    paddle_tpu.seed(seed)
    if mp > 1:
        from paddle_tpu.distributed.fleet.meta_parallel.mp_layers import (
            ColumnParallelLinear, RowParallelLinear)

        class TPMLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.c = ColumnParallelLinear(8, 32, gather_output=False)
                self.r = RowParallelLinear(32, 8, input_is_parallel=True)
                self.head = nn.Linear(8, 1)

            def forward(self, x):
                import paddle_tpu.nn.functional as F
                return self.head(F.tanh(self.r(F.tanh(self.c(x)))))

        model = fleet.distributed_model(TPMLP())
    else:
        model = fleet.distributed_model(
            nn.Sequential(nn.Linear(8, 32), nn.Tanh(), nn.Linear(32, 1)))
    opt = fleet.distributed_optimizer(
        optim.Adam(learning_rate=0.01, parameters=model.parameters()),
        strategy=strategy)
    step = opt.make_train_step(model, lambda m, x, y: ((m(x) - y) ** 2)
                               .mean())
    return step, model


def _batch():
    import numpy as np

    import paddle_tpu
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    w = rng.standard_normal((8,)).astype(np.float32)
    y = (x @ w)[:, None].astype(np.float32)
    return paddle_tpu.to_tensor(x), paddle_tpu.to_tensor(y)


def _collective_profile(hlo_text):
    """Collective op counts + the largest all_reduce operand (elems) +
    int8 collective presence, from the parsed StableHLO."""
    from paddle_tpu.analysis.hlo import parse_stablehlo
    mod = parse_stablehlo(hlo_text)
    prof = {}
    for op in ("all_reduce", "reduce_scatter", "all_gather", "all_to_all",
               "collective_permute"):
        prof[op] = len(mod.ops_named(f"stablehlo.{op}", op))
    biggest_ar = 0
    for op in mod.ops_named("stablehlo.all_reduce", "all_reduce"):
        for t in op.types:
            biggest_ar = max(biggest_ar, t.elems)
    int8_coll = any(
        t.dtype in ("i8", "ui8")
        for kind in ("all_to_all", "all_gather", "reduce_scatter")
        for op in mod.ops_named(f"stablehlo.{kind}", kind)
        for t in op.types)
    prof["largest_all_reduce_elems"] = biggest_ar
    prof["int8_collective_operands"] = int8_coll
    return prof


def run_gates(steps=0):
    """The lower-only HLO gates (+ optional bitwise run), in-process.
    Returns the JSON record; record["ok"] is the pass verdict."""
    import numpy as np

    from paddle_tpu import analysis

    xt, yt = _batch()
    record = {"bench": "train_collective_lint", "gates": {}}

    # -- gate 1: int8 quantized-DP wire format --------------------------
    s_int8, _ = _build("int8")
    prof = _collective_profile(s_int8.lower_hlo(xt, yt))
    ok_int8 = (prof["int8_collective_operands"]
               and prof["largest_all_reduce_elems"] <= 1
               and prof["all_to_all"] >= 1)
    record["gates"]["int8_dp"] = {**prof, "ok": bool(ok_int8),
                                  "compression_ratio":
                                      s_int8.compression_ratio}

    # -- gate 2: ZeRO-1 exchange shape ----------------------------------
    s_z1, m_z1 = _build(None, zero1=True)
    prof = _collective_profile(s_z1.lower_hlo(xt, yt))
    ok_z1 = (prof["reduce_scatter"] >= 1 and prof["all_gather"] >= 1
             and prof["largest_all_reduce_elems"] <= 1)
    record["gates"]["zero1"] = {**prof, "ok": bool(ok_z1)}

    # -- gate 3: overlap on the REAL tp train step ----------------------
    s_tp, _ = _build(None, mp=2, tp_overlap=True)
    rep = analysis.audit_train_step(s_tp, xt, yt)
    high = [f for f in rep.findings
            if f.rule_id == "unoverlapped-collective"
            and f.severity == "high"]
    s_serial, _ = _build(None, mp=2, tp_overlap=False)
    srep = analysis.audit_train_step(s_serial, xt, yt)
    caught = any(f.rule_id == "unoverlapped-collective"
                 and f.severity == "high" for f in srep.findings)
    record["gates"]["overlap"] = {
        "high_on_overlap_step": len(high),
        "metrics": rep.metrics.get("unoverlapped-collective"),
        "seeded_serial_caught": bool(caught),
        "ok": bool(not high and caught)}

    # -- optional run gate: bitwise zero1 + 1/dp moments ----------------
    if steps:
        import paddle_tpu
        paddle_tpu.seed(0)
        s_ex, m_ex = _build(None, zero1=False, seed=0)
        for _ in range(steps):
            s_ex(xt, yt)
        paddle_tpu.seed(0)
        s_z1b, m_z1b = _build(None, zero1=True, seed=0)
        for _ in range(steps):
            s_z1b(xt, yt)
        p_ex = {k: np.asarray(p._data) for k, p in m_ex.named_parameters()}
        p_z1 = {k: np.asarray(p._data)
                for k, p in m_z1b.named_parameters()}
        bitwise = all(np.array_equal(p_ex[k], p_z1[k]) for k in p_ex)
        ratio = (s_z1b.optimizer_state_elems_per_replica()
                 / max(1, s_ex.optimizer_state_elems_per_replica()))
        record["gates"]["zero1_run"] = {
            "steps": steps, "params_bitwise_equal": bool(bitwise),
            "opt_state_fraction_per_replica": round(ratio, 4),
            "ok": bool(bitwise and ratio < 1.5 / s_z1b.dp)}

    try:
        from paddle_tpu.aot import aot_stats
        record["aot"] = {k: aot_stats()[k]
                         for k in ("hits", "misses", "compiled")}
    except Exception:   # tpu_lint: allow(silent-except) — the aot view
        # is advisory ledger context, not a gate
        pass
    record["ok"] = all(g["ok"] for g in record["gates"].values())
    return record


def run_warm_cache(args):
    """Subprocess pair sharing one AOT cache dir: the second process
    must resolve every train-step program from the store (0 misses, 0
    backend builds through the service)."""
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="aot-commopt-")
    env = dict(os.environ, PADDLE_TPU_AOT_CACHE_DIR=cache_dir)
    runs = []
    for tag in ("cold", "warm"):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--json",
             "--workload"],
            capture_output=True, text=True, env=env)
        if not out.stdout.strip():
            print(json.dumps({"bench": "train_collective_warm_cache",
                              "ok": False,
                              "error": f"{tag}: {out.stderr[-800:]}"}))
            return 1
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    ok = (cold["ok"] and warm["ok"] and warm["service_misses"] == 0
          and warm["service_compiled"] == 0
          and warm["loss"] == cold["loss"])
    record = {"bench": "train_collective_warm_cache",
              "cache_dir": cache_dir, "cold": cold, "warm": warm,
              "ok": bool(ok)}
    if args.json:
        print(json.dumps(record))
    else:
        print(f"cold-process train-step builds {cold['service_compiled']}")
        print(f"warm-process train-step builds {warm['service_compiled']} "
              f"(misses {warm['service_misses']})")
        print("OK (warm process trains compile-free, bitwise loss)"
              if ok else "FAIL: warm process still builds train-step "
              "programs (or loss drifted)")
    return 0 if ok else 1


def run_workload(args):
    """One short int8+ZeRO-1 training run; emits the AOT service view
    (the --warm-cache subprocess body)."""
    import numpy as np

    s, _ = _build("int8", zero1=True)
    xt, yt = _batch()
    loss = None
    for _ in range(3):
        loss = s(xt, yt)
    from paddle_tpu.aot import get_service
    st = get_service().stats()
    print(json.dumps({
        "bench": "train_collective_workload", "ok": True,
        "loss": float(np.asarray(loss._data)),
        "source": s._handle.source,
        "service_misses": st["misses"],
        "service_compiled": st["compiled"],
        "service_exec_hits": st["disk_exec_hits"]}))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--steps", type=int, default=0,
                    help="also run the zero1/replicated pair this many "
                         "steps and assert bitwise params + 1/dp moments")
    ap.add_argument("--warm-cache", action="store_true",
                    help="subprocess-pair AOT gate: the second process "
                         "must build 0 train-step programs")
    ap.add_argument("--workload", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.workload:
        return run_workload(args)
    if args.warm_cache:
        return run_warm_cache(args)
    record = run_gates(steps=args.steps)
    if args.json:
        print(json.dumps(record))
    else:
        for name, g in record["gates"].items():
            print(f"{name}: {'OK' if g['ok'] else 'FAIL'}  "
                  f"{ {k: v for k, v in g.items() if k != 'ok'} }")
        print("OK (train-collective contract holds)" if record["ok"]
              else "FAIL: quantized/sharded/overlapped train-step HLO "
              "contract broken")
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
