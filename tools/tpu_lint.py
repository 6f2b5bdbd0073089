#!/usr/bin/env python
"""tpu_lint — static jaxpr/StableHLO + AST audit CLI over
paddle_tpu.analysis.

Self-lint the source tree, or audit representative compiled programs,
and gate on severity:

    # AST self-lint of paddle_tpu/ (the CI gate)
    JAX_PLATFORMS=cpu python tools/tpu_lint.py --self --fail-on=high

    # lint specific files/dirs
    python tools/tpu_lint.py paddle_tpu/serving tools/chaos_serve.py

    # audit compiled demo programs (findings are machine-readable)
    JAX_PLATFORMS=cpu python tools/tpu_lint.py --audit resnet18 \
        --audit static-train --audit serving --json

Audit targets:

* ``resnet18``     — the channels-last jitted resnet18 forward (the
  PR-2 layout-planner contract: zero interior transposes)
* ``static-train`` — a fluid 1.x minimize+run train program compiled by
  the PR-1 whole-program Executor (donated state, no host splits)
* ``serving``      — a 2-bucket continuous-batching Engine with a
  declared compile budget (PR-4 static-shape contract)
* ``dispatch``     — the live eager-dispatch cache (blacklist reasons,
  megamorphic ops)

``--fail-on=SEVERITY`` (default high) exits 1 when any finding at or
above that severity survives; ``--allowlist FILE`` drops findings
matching ``rule-id location-prefix`` lines (inline ``# tpu_lint:
allow(...)`` annotations are the preferred suppression — the allowlist
file exists for third-party/generated locations only). ``--rules``
lists every registered rule.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _audit_resnet18(analysis):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import to_channels_last
    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    cl = to_channels_last(resnet18(num_classes=10).eval())
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((1, 3, 32, 32)).astype(np.float32))
    return analysis.audit_model(cl, x)


def _audit_static_train(analysis):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer, static

    paddle.seed(0)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [None, 4], "float32")
        yt = static.data("y", [None, 1], "float32")
        layer = nn.Linear(4, 8)
        head = nn.Linear(8, 1)
        loss = ((head(paddle.nn.functional.relu(layer(x))) - yt) ** 2
                ).mean()
        opt = optimizer.Adam(
            learning_rate=0.05,
            parameters=layer.parameters() + head.parameters())
        opt.minimize(loss)
    exe = static.Executor()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, 4)).astype(np.float32)
    ys = rng.normal(size=(16, 1)).astype(np.float32)
    for _ in range(3):   # step 1 eager, step 2 builds the plan
        exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
    return analysis.audit_plan(main, name="fluid_train")


def _audit_serving(analysis):
    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.serving import Engine
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    # prompt lengths 5 and 12 with min bucket 8 -> exactly 2 buckets
    engine = Engine(model, n_slots=2, max_len=32, min_prompt_bucket=8,
                    compile_budget=3)
    for n in (5, 12):
        prompt = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        engine.submit(prompt, max_new_tokens=2)
    engine.drain()
    return analysis.audit_engine(engine)


_AUDITS = {
    "resnet18": _audit_resnet18,
    "static-train": _audit_static_train,
    "serving": _audit_serving,
    "dispatch": lambda analysis: analysis.audit_dispatch(),
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpu_lint",
        description="static TPU perf/correctness audit "
        "(paddle_tpu.analysis)")
    ap.add_argument("paths", nargs="*",
                    help="python files/dirs to self-lint")
    ap.add_argument("--self", action="store_true", dest="self_",
                    help="self-lint the paddle_tpu package")
    ap.add_argument("--audit", action="append", default=[],
                    choices=sorted(_AUDITS),
                    help="audit a compiled demo program (repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object")
    ap.add_argument("--fail-on", default="high",
                    choices=("info", "low", "medium", "high", "never"),
                    help="exit 1 when a finding at/above this severity "
                    "survives (default: high)")
    ap.add_argument("--allowlist", metavar="FILE",
                    help="file of 'rule-id location-prefix' suppressions")
    ap.add_argument("--rules", action="store_true",
                    help="list registered rules and exit")
    args = ap.parse_args(argv)

    from paddle_tpu import analysis

    if args.rules:
        for rid, kind, sev, title in analysis.rules_table():
            print(f"{rid:20s} {kind:8s} {sev:7s} {title}")
        return 0

    if not (args.paths or args.self_ or args.audit):
        ap.error("nothing to do: pass paths, --self, or --audit TARGET")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = analysis.Report(origin="tpu_lint")
    if args.self_ or args.paths:
        paths = list(args.paths)
        if args.self_:
            paths.append(os.path.join(repo, "paddle_tpu"))
        report.extend(analysis.selflint(paths))
    for target in args.audit:
        report.extend(_AUDITS[target](analysis))

    if args.allowlist:
        with open(args.allowlist, encoding="utf-8") as f:
            report.apply_allowlist(analysis.parse_allowlist(f.read()))

    ok = True if args.fail_on == "never" else report.ok(args.fail_on)
    if args.json:
        out = report.to_dict()
        out["fail_on"] = args.fail_on
        out["ok"] = ok
        print(json.dumps(out, default=str))
    else:
        for f in report.findings:
            print(f)
        print(report.summary_line())
        print("OK" if ok else
              f"FAIL: findings at/above --fail-on={args.fail_on}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
