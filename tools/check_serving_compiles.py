#!/usr/bin/env python
"""Serving compile lint: the engine's static-shape contract, enforced.

Drives a staggered 16-request workload (prompt lengths spanning >= 2
power-of-two prefill buckets, mid-stream admissions and evictions,
slot reuse) through paddle_tpu.serving.Engine and fails if:

- the workload compiles more than (n_prefill_buckets + 1 decode) XLA
  programs (counted via the jax monitoring compile-event listener, the
  same cross-check tools/check_retrace.py uses), or
- a SECOND identical workload on the warm engine triggers ANY compile
  (warm decode/prefill retrace), or
- any request's greedy output differs from batch generate() on the same
  prompt (token-identical, per request).

``--warm-cache`` runs the same workload in two fresh subprocesses
sharing one paddle_tpu.aot cache directory and asserts the SECOND
process drives the whole workload with 0 cold XLA backend compiles
(deserialized executables) and unchanged token parity — the honest
budget once the persistent executable cache lands (without this mode a
warm cache would read as a spurious budget pass/violation).

``--spec`` is the speculative-decoding contract: a staggered workload
(half vocab-masked repetitive traffic, so the n-gram proposer
deterministically fires; half plain random, so the fused-decode
fallback stays live) through a non-speculative engine and an
``Engine(speculative=SpecConfig(draft="ngram", k=4))`` engine. The
speculative engine must compile EXACTLY its declared budget (prefill
buckets + decode + the ONE chunk-shaped verify program), do 0 warm
compiles, and emit token-identical output to the non-speculative
engine (greedy AND sampled) and to batch ``generate()``. Composes with
``--warm-cache`` (the second process must serve the speculative
workload, verify program included, at 0 cold backend compiles).

``--mesh N`` is the tensor-parallel contract: N virtual CPU devices, the
same workload through a single-device engine and a tp=N engine. The TP
engine must compile exactly its declared budget (buckets + decode —
shard_map SPMD programs count once each), do 0 warm compiles, emit
token-identical output to the single-device engine AND batch
``generate()``, and its lowered decode HLO must carry 0 high
``unoverlapped-collective`` findings while a seeded serial
``psum(x @ w)`` program IS caught by the same rule.

``--fleet N`` is the replica-fleet contract: the SAME staggered
workload routed through a ``ReplicaFleet`` of N replicas in one process
must compile exactly the single-engine program set (module-level jitted
programs are shared across replicas — 0 extra lowerings, gated against
a fresh single engine's budget), do 0 warm compiles on a second pass,
and keep every request token-identical to batch ``generate()``.

CPU by default (JAX_PLATFORMS is set to cpu unless given). The
``--warm-cache`` parent imports no jax and runs its two children one
after the other, so each is the only jax process; every other mode is
one process.

Modeled on tools/check_retrace.py. Usage:

    JAX_PLATFORMS=cpu python tools/check_serving_compiles.py [--json]
    JAX_PLATFORMS=cpu python tools/check_serving_compiles.py --warm-cache
    JAX_PLATFORMS=cpu python tools/check_serving_compiles.py --mesh 4
    JAX_PLATFORMS=cpu python tools/check_serving_compiles.py --fleet 3
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_warm_cache(args):
    """Subprocess pair sharing one AOT cache dir: the second process
    must serve the whole workload with 0 cold backend compiles."""
    import json as _json
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="aot-serving-")
    env = dict(os.environ, PADDLE_TPU_AOT_CACHE_DIR=cache_dir)
    runs = []
    for tag in ("cold", "warm"):
        cmd = [sys.executable, os.path.abspath(__file__), "--json",
               "--requests", str(args.requests), "--slots",
               str(args.slots), "--max-new", str(args.max_new)]
        if getattr(args, "spec", False):
            cmd.append("--spec")
        out = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if not out.stdout.strip():
            print(_json.dumps({"bench": "serving_compile_warm_cache",
                               "ok": False,
                               "error": f"{tag}: {out.stderr[-800:]}"}))
            return 1
        runs.append(_json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    have = warm["cold_compiles"] is not None
    ok = (cold["ok"] and warm["ok"]
          and not warm.get("greedy_mismatches")
          and (not have or warm["cold_compiles"] == 0))
    record = {"bench": "serving_compile_warm_cache",
              "cache_dir": cache_dir,
              "cold_run_compiles": cold["cold_compiles"],
              "warm_run_compiles": warm["cold_compiles"],
              "cold": cold, "warm": warm, "ok": ok}
    if args.json:
        print(_json.dumps(record))
    else:
        print(f"cold-process compiles {record['cold_run_compiles']}")
        print(f"warm-process compiles {record['warm_run_compiles']}")
        print("OK (warm process serves compile-free)" if ok else
              "FAIL: warm cache still compiles (or parity broke)")
    return 0 if ok else 1


def run_spec(args):
    """Speculative serving contract: budget (buckets + decode + verify,
    exact), 0 warm compiles, token identity vs the non-speculative
    engine AND batch generate(), greedy and sampled — with the verify
    program provably exercised and the plain decode fallback provably
    live."""
    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.serving import Engine, SpecConfig
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    counter = analysis.CompileEventCounter().install()
    have_monitor = counter.available

    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    V = cfg.vocab_size
    min_bucket = 8
    # even requests: plain random prompts (no n-gram ever matches on a
    # random model -> the fused decode fallback runs). Odd requests:
    # single-token repetitive prompts vocab-masked to that token, so
    # the emitted stream repeats it and the n-gram proposer fires
    # deterministically -> the verify program runs.
    reqs = []
    for i in range(args.requests):
        if i % 2 == 0:
            n = 5 + (i % 8)
            reqs.append((rng.integers(0, V, (n,)).astype(np.int32),
                         None))
        else:
            tok = int(rng.integers(0, V))
            n = 9 + (i % 4)
            mask = np.zeros(V, bool)
            mask[tok] = True
            reqs.append((np.full((n,), tok, np.int32), mask))
    new_tokens = [3 + (i % (args.max_new - 2))
                  for i in range(args.requests)]
    n_buckets = len({max(min_bucket, 1 << (n - 1).bit_length())
                     for n, _ in ((len(p), m) for p, m in reqs)})
    budget = n_buckets + 1                     # the non-spec program set
    spec_budget = budget + 1                   # + the ONE verify program

    def drive(engine, sampled=False):
        handles = []
        it = iter(range(args.requests))
        for i in (next(it), next(it), next(it)):
            handles.append(engine.submit(
                reqs[i][0], max_new_tokens=new_tokens[i],
                temperature=0.9 if sampled else 1.0, seed=100 + i,
                logit_mask=reqs[i][1]))
        for i in it:
            engine.step()
            handles.append(engine.submit(
                reqs[i][0], max_new_tokens=new_tokens[i],
                temperature=0.9 if sampled else 1.0, seed=100 + i,
                logit_mask=reqs[i][1]))
        engine.drain()
        return handles

    # the plain arm compiles the shared program set (buckets + decode);
    # the spec arm of the same sampling mode then cold-compiles EXACTLY
    # ONE more program — the verify chunk (module-level jit cache:
    # prefill/decode are shared shapes). The spec engine's own declared
    # budget stays buckets + decode + verify — that is what a fresh
    # process pays, and the audit compile-budget rule gates it below.
    # Under a warm AOT cache dir every expected count may also be 0
    # (deserialized executables).
    cache_warm = bool(os.environ.get("PADDLE_TPU_AOT_CACHE_DIR"))
    arms = {}
    for label, kw, sampled, arm_budget, expected_cold in (
            ("plain_greedy", {}, False, budget, budget),
            ("spec_greedy",
             {"speculative": SpecConfig(draft="ngram", k=4)}, False,
             spec_budget, 1),
            ("plain_sampled", {"do_sample": True, "top_k": 8}, True,
             budget, budget),
            ("spec_sampled",
             {"do_sample": True, "top_k": 8,
              "speculative": SpecConfig(draft="ngram", k=4)}, True,
             spec_budget, 1)):
        engine = Engine(model, n_slots=args.slots, max_len=64,
                        min_prompt_bucket=min_bucket,
                        compile_budget=arm_budget, **kw)
        counter.reset()
        handles = drive(engine, sampled)
        cold = counter.count
        counter.reset()
        handles2 = drive(engine, sampled)
        warm = counter.count
        arms[label] = {
            "cold_compiles": cold if have_monitor else None,
            "warm_compiles": warm if have_monitor else None,
            "budget": arm_budget, "expected_cold": expected_cold,
            "tokens": [list(h.tokens) for h in handles],
            "tokens2": [list(h.tokens) for h in handles2],
            "engine": engine}

    greedy_parity = (arms["spec_greedy"]["tokens"]
                     == arms["plain_greedy"]["tokens"]
                     == arms["plain_greedy"]["tokens2"]
                     == arms["spec_greedy"]["tokens2"])
    sampled_parity = (arms["spec_sampled"]["tokens"]
                      == arms["plain_sampled"]["tokens"]
                      == arms["spec_sampled"]["tokens2"])
    # generate() parity on the unmasked requests (the prefill-sampled
    # first token of masked requests is unconstrained either way, but
    # generate() has no mask operand to compare the rest against)
    gen_parity = all(
        np.array_equal(
            np.asarray(arms["spec_greedy"]["tokens"][i], np.int32),
            np.asarray(model.generate(
                paddle.to_tensor(reqs[i][0][None]),
                max_new_tokens=new_tokens[i])._data)
            [0, len(reqs[i][0]):])
        for i in range(args.requests) if reqs[i][1] is None)

    spec_eng = arms["spec_greedy"]["engine"]
    verify_used = (spec_eng.verify_used
                   and arms["spec_sampled"]["engine"].verify_used)
    decode_used = ("decode",) in spec_eng._aot
    acceptance = spec_eng.metrics.acceptance_rate()
    rep = analysis.audit_engine(spec_eng)
    budget_high = [f for f in rep.findings
                   if f.rule_id == "compile-budget"
                   and f.severity == "high"]

    budgets_ok = not have_monitor or all(
        (arms[a]["cold_compiles"] == arms[a]["expected_cold"]
         or (cache_warm and arms[a]["cold_compiles"] == 0))
        and arms[a]["warm_compiles"] == 0 for a in arms)
    ok = bool(budgets_ok and greedy_parity and sampled_parity
              and gen_parity and verify_used and decode_used
              and not budget_high)
    for a in arms.values():
        a.pop("engine")
        a.pop("tokens")
        a.pop("tokens2")
    record = {
        "bench": "serving_compile_spec", "requests": args.requests,
        "k": 4, "compile_budget": spec_budget, "arms": arms,
        "greedy_parity": greedy_parity, "sampled_parity": sampled_parity,
        "generate_parity": gen_parity, "verify_used": verify_used,
        "decode_fallback_used": decode_used,
        "acceptance_rate": acceptance,
        "budget_metrics": rep.metrics.get("compile-budget"),
        "ok": ok,
    }
    record["cold_compiles"] = (
        None if not have_monitor
        else sum(a["cold_compiles"] for a in arms.values()))
    if args.json:
        print(json.dumps(record))
    else:
        print(f"spec budget {spec_budget} (= {n_buckets} buckets + "
              "decode + verify)")
        for a, r in arms.items():
            print(f"  {a}: cold={r['cold_compiles']} "
                  f"(expected {r['expected_cold']}) "
                  f"warm={r['warm_compiles']} budget={r['budget']}")
        print(f"parity greedy={greedy_parity} sampled={sampled_parity} "
              f"generate={gen_parity}")
        print(f"verify used {verify_used}  decode fallback {decode_used}"
              f"  acceptance {acceptance}")
        print("OK (speculative serving contract holds)" if ok else
              "FAIL: speculative engine recompiles or diverges")
    return 0 if ok else 1


def run_mesh(args):
    """Tensor-parallel serving contract on a virtual-device mesh: the
    TP engine compiles exactly its budget, recompiles nothing warm, and
    stays token-identical to the single-device engine (greedy AND
    sampled, including one adopt()-replayed request) — with the decode
    HLO overlap-verified by the unoverlapped-collective rule."""
    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.serving import Engine
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    tp = args.mesh
    counter = analysis.CompileEventCounter().install()
    have_monitor = counter.available

    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    lens = [5 + (i % 8) for i in range(args.requests)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    new_tokens = [3 + (i % (args.max_new - 2))
                  for i in range(args.requests)]
    min_bucket = 8
    n_buckets = len({max(min_bucket, 1 << (n - 1).bit_length())
                     for n in lens})
    budget = n_buckets + 1

    def drive(engine, sampled=False):
        handles = []
        for i in range(args.requests):
            if i >= 3:
                engine.step()
            handles.append(engine.submit(
                prompts[i], max_new_tokens=new_tokens[i],
                temperature=0.9 if sampled else 1.0, seed=100 + i))
        engine.drain()
        return handles

    record = {"bench": "serving_tp_mesh", "tp": tp,
              "requests": args.requests, "compile_budget": budget}
    arms = {}
    for label, kw, sampled in (
            ("single_greedy", {}, False),
            ("tp_greedy", {"tp": tp}, False),
            ("single_sampled", {"do_sample": True, "top_k": 8}, True),
            ("tp_sampled", {"tp": tp, "do_sample": True, "top_k": 8},
             True)):
        engine = Engine(model, n_slots=args.slots, max_len=64,
                        min_prompt_bucket=min_bucket,
                        compile_budget=budget, **kw)
        counter.reset()
        handles = drive(engine, sampled)
        cold = counter.count
        counter.reset()
        handles2 = drive(engine, sampled)
        warm = counter.count
        arms[label] = {
            "cold_compiles": cold if have_monitor else None,
            "warm_compiles": warm if have_monitor else None,
            "tokens": [list(h.tokens) for h in handles],
            "tokens2": [list(h.tokens) for h in handles2],
            "engine": engine, "stats": engine.stats()}

    # one adopt()-replayed request on a rebuilt TP engine mid-decode
    eng_a = Engine(model, n_slots=args.slots, max_len=64,
                   min_prompt_bucket=min_bucket, tp=tp)
    h = eng_a.submit(prompts[0], max_new_tokens=8, seed=7)
    for _ in range(3):
        eng_a.step()
    eng_a._condemned = True
    counter.reset()
    eng_b = Engine(model, n_slots=args.slots, max_len=64,
                   min_prompt_bucket=min_bucket, tp=tp)
    eng_b.adopt(h)
    h.result()
    adopt_compiles = counter.count
    base = Engine(model, n_slots=args.slots, max_len=64,
                  min_prompt_bucket=min_bucket).generate_all(
        [prompts[0]], max_new_tokens=8, seed=7)[0]

    greedy_parity = arms["tp_greedy"]["tokens"] == \
        arms["single_greedy"]["tokens"] == arms["single_greedy"]["tokens2"]
    sampled_parity = arms["tp_sampled"]["tokens"] == \
        arms["single_sampled"]["tokens"]
    gen_parity = all(
        np.array_equal(
            np.asarray(t, np.int32),
            np.asarray(model.generate(
                paddle.to_tensor(p[None]), max_new_tokens=n)._data)
            [0, len(p):])
        for t, p, n in zip(arms["tp_greedy"]["tokens"], prompts,
                           new_tokens))

    # overlap evidence: 0 high unoverlapped-collective findings on the
    # REAL TP decode HLO, while a seeded serial psum(x @ w) is caught
    rep = analysis.audit_engine(arms["tp_greedy"]["engine"])
    tp_high = [f for f in rep.findings
               if f.rule_id == "unoverlapped-collective"
               and f.severity == "high"]
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.mesh import shard_map
    from paddle_tpu.distributed.collective_matmul import \
        serial_rowparallel_matmul
    mesh = mesh_mod.build_mesh(tp=tp)
    seeded = shard_map(
        lambda a, b: serial_rowparallel_matmul(a, b, "tp"), mesh=mesh,
        in_specs=(P(None, "tp"), P("tp", None)), out_specs=P(),
        check_vma=False)
    srep = analysis.audit(
        seeded, np.zeros((4, 8 * tp), np.float32),
        np.zeros((8 * tp, 16 * tp), np.float32), name="seeded-serial")
    seeded_caught = any(f.rule_id == "unoverlapped-collective"
                        and f.severity == "high" for f in srep.findings)

    budgets_ok = not have_monitor or all(
        arms[a]["cold_compiles"] <= budget
        and arms[a]["warm_compiles"] == 0
        for a in arms)
    ok = bool(budgets_ok and greedy_parity and sampled_parity
              and gen_parity and h.tokens == list(base.tokens)
              and (not have_monitor or adopt_compiles == 0)
              and not tp_high and seeded_caught)
    for a in arms:
        arms[a].pop("engine")
        arms[a].pop("tokens")
        arms[a].pop("tokens2")
    record.update({
        "arms": arms, "greedy_parity": greedy_parity,
        "sampled_parity": sampled_parity,
        "generate_parity": gen_parity,
        "adopt_parity": h.tokens == list(base.tokens),
        "adopt_warm_compiles": adopt_compiles if have_monitor else None,
        "unoverlapped_high_on_tp_decode": len(tp_high),
        "decode_collective_metrics": rep.metrics.get(
            "unoverlapped-collective"),
        "seeded_serial_caught": seeded_caught, "ok": ok})
    if args.json:
        print(json.dumps(record))
    else:
        print(f"tp={tp} compile budget {budget}")
        for a, r in arms.items():
            print(f"  {a}: cold={r['cold_compiles']} "
                  f"warm={r['warm_compiles']}")
        print(f"parity greedy={greedy_parity} sampled={sampled_parity} "
              f"generate={gen_parity} adopt={record['adopt_parity']}")
        print(f"unoverlapped high on TP decode: {len(tp_high)}  "
              f"seeded serial caught: {seeded_caught}")
        print("OK (TP serving contract holds)" if ok else
              "FAIL: TP engine recompiles, diverges, or serializes "
              "collectives")
    return 0 if ok else 1


def run_fleet(args):
    """Replica-fleet compile contract: N replicas in one process pay
    for exactly ONE engine's program set (cold == single-engine budget,
    0 extra lowerings from replication or rebuild), 0 warm compiles,
    full token parity vs batch generate()."""
    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.serving import ReplicaFleet
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    counter = analysis.CompileEventCounter().install()
    have_monitor = counter.available

    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    min_bucket = 8
    lens = [5 + (i % 8) for i in range(args.requests)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    new_tokens = [3 + (i % (args.max_new - 2))
                  for i in range(args.requests)]
    n_buckets = len({max(min_bucket, 1 << (n - 1).bit_length())
                     for n in lens})
    budget = n_buckets + 1          # the SINGLE-engine program set

    def drive(fleet):
        handles = []
        it = iter(range(args.requests))
        for i in (next(it), next(it), next(it)):
            handles.append(fleet.submit(prompts[i],
                                        max_new_tokens=new_tokens[i]))
        for i in it:
            fleet.step()
            handles.append(fleet.submit(prompts[i],
                                        max_new_tokens=new_tokens[i]))
        fleet.drain()
        fleet.reopen()
        return handles

    fleet = ReplicaFleet(model, args.fleet, n_slots=args.slots,
                         max_len=64, min_prompt_bucket=min_bucket,
                         compile_budget=budget)
    counter.reset()
    handles = drive(fleet)
    cold_compiles = counter.count
    counter.reset()
    handles2 = drive(fleet)
    warm_compiles = counter.count

    mismatches = []
    for run in (handles, handles2):
        for h, p in zip(run, prompts):
            want = np.asarray(model.generate(
                paddle.to_tensor(p[None]),
                max_new_tokens=h.max_new_tokens)._data)[0, len(p):]
            if not np.array_equal(np.asarray(h.tokens, np.int32), want):
                mismatches.append(h.request_id)
    spread = {rid: r["requests_completed"] + r["active"]
              for rid, r in ((rep.id, rep.engine.stats())
                             for rep in fleet.replicas.values())}
    rep = analysis.audit_fleet(fleet)
    budget_high = [f for f in rep.findings
                   if f.rule_id == "compile-budget"
                   and f.severity == "high"]
    ok = ((not have_monitor or (cold_compiles <= budget
                                and warm_compiles == 0))
          and not mismatches and not budget_high
          and sum(1 for n in spread.values() if n > 0) > 1)
    record = {
        "bench": "serving_compile_fleet", "replicas": args.fleet,
        "requests": args.requests, "prompt_buckets": n_buckets,
        "compile_budget": budget,
        "cold_compiles": cold_compiles if have_monitor else None,
        "warm_compiles": warm_compiles if have_monitor else None,
        "greedy_mismatches": mismatches,
        "requests_per_replica": spread,
        "budget_metrics": rep.metrics.get("compile-budget"),
        "fleet": fleet.stats(), "ok": ok,
    }
    if args.json:
        print(json.dumps(record, default=str))
    else:
        print(f"replicas {args.fleet}  single-engine budget {budget}")
        print(f"cold compiles   {record['cold_compiles']}")
        print(f"warm compiles   {record['warm_compiles']}")
        print(f"spread          {spread}")
        print(f"parity          {'OK' if not mismatches else mismatches}")
        print("OK (N replicas = one engine's programs)" if ok else
              "FAIL: fleet recompiles or diverges")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true", help="emit a JSON line")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--warm-cache", action="store_true",
                    help="subprocess-pair AOT cache gate: the second "
                         "process must do 0 cold backend compiles")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding mode: ngram-draft engine "
                         "vs non-speculative parity + budget (the "
                         "verify program is exactly ONE extra "
                         "lowering); composes with --warm-cache")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="tensor-parallel mode: N virtual devices, "
                         "tp=N engine vs single-device parity + budget")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="replica-fleet mode: N replicas in one "
                         "process must compile exactly the "
                         "single-engine program set, 0 warm")
    args = ap.parse_args()

    if args.fleet:
        return run_fleet(args)

    if args.mesh:
        # must win before the first jax import in this process
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()
        return run_mesh(args)

    if args.warm_cache:
        return run_warm_cache(args)

    if args.spec:
        return run_spec(args)

    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.serving import Engine
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    counter = analysis.CompileEventCounter().install()
    have_monitor = counter.available

    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)

    # prompt lengths 5..12 with min bucket 8 -> exactly 2 buckets (8, 16)
    min_bucket = 8
    lens = [5 + (i % 8) for i in range(args.requests)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    new_tokens = [3 + (i % (args.max_new - 2)) for i in range(args.requests)]

    def bucket(n):
        b = min_bucket
        while b < n:
            b <<= 1
        return b

    n_buckets = len({bucket(n) for n in lens})
    budget = n_buckets + 1          # prefill programs + ONE decode program

    def drive(engine):
        """Staggered arrivals: a few up front, the rest fed one per step
        so admissions/evictions interleave and slots get reused."""
        handles = []
        it = iter(range(args.requests))
        for i in (next(it), next(it), next(it)):
            handles.append(engine.submit(prompts[i],
                                         max_new_tokens=new_tokens[i]))
        for i in it:
            engine.step()
            handles.append(engine.submit(prompts[i],
                                         max_new_tokens=new_tokens[i]))
        engine.drain()
        return handles

    engine = Engine(model, n_slots=args.slots, max_len=64,
                    min_prompt_bucket=min_bucket, compile_budget=budget)
    # engine construction (weight stacking) compiles host-side stacks;
    # the serving budget is about the REQUEST WORKLOAD only
    counter.reset()
    handles = drive(engine)
    cold_compiles = counter.count

    counter.reset()
    handles2 = drive(engine)
    warm_compiles = counter.count

    mismatches = []
    for run in (handles, handles2):
        for h, p in zip(run, prompts):
            want = np.asarray(model.generate(
                paddle.to_tensor(p[None]),
                max_new_tokens=h.max_new_tokens)._data)[0, len(p):]
            if not np.array_equal(np.asarray(h.tokens, np.int32), want):
                mismatches.append(h.request_id)

    ok = (not have_monitor or (cold_compiles <= budget
                               and warm_compiles == 0)) \
        and not mismatches \
        and engine.metrics.requests_completed == 2 * args.requests

    # the static audit of the same engine rides along in the ledger
    # (compile-budget / padding / donation rules); exit code unchanged
    findings = [f.to_dict()
                for f in analysis.audit_engine(engine).findings]
    record = {
        "bench": "serving_compile_lint",
        "requests": args.requests, "slots": args.slots,
        "prompt_buckets": n_buckets, "compile_budget": budget,
        "cold_compiles": cold_compiles if have_monitor else None,
        "warm_compiles": warm_compiles if have_monitor else None,
        "greedy_mismatches": mismatches,
        "engine": engine.stats(), "findings": findings, "ok": ok,
    }
    if args.json:
        print(json.dumps(record))
    else:
        print(f"prefill buckets {n_buckets}  compile budget {budget}")
        print(f"cold compiles   {record['cold_compiles']}")
        print(f"warm compiles   {record['warm_compiles']}")
        print(f"parity          {'OK' if not mismatches else mismatches}")
        print("OK (static-shape serving contract holds)" if ok else
              "FAIL: serving engine recompiles or diverges")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
