#!/usr/bin/env python
"""HLO layout lint: the channels-last plan must emit ZERO interior
layout transposes.

Thin CLI over ``paddle_tpu.analysis`` (the ``interior-transpose`` rule):
lowers the jitted resnet18 forward on CPU and reads the shared StableHLO
parse's transpose counts (the ops THIS framework inserted — backend
layout assignment is the compiler's business and is reported separately):

* bare converted model on NHWC input  -> budget 0   (interior)
* ChannelsLast wrapper on NCHW input  -> budget 1   (the entry boundary;
  the classifier head returns 2D, so there is no exit transpose)

Exits nonzero when a budget is exceeded, so the conv pipeline cannot
silently regress to per-op transposes. Run with --json for a ledger
line.

Usage: JAX_PLATFORMS=cpu python tools/check_hlo_layout.py [--json]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

INTERIOR_BUDGET = 0
BOUNDARY_BUDGET = 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true", help="emit a JSON line")
    ap.add_argument("--size", type=int, default=32,
                    help="input spatial size (transpose counts are "
                    "shape-independent; small keeps CPU lowering fast)")
    args = ap.parse_args()

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.framework import count_hlo_transposes, to_channels_last
    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((1, 3, args.size, args.size)).astype(np.float32))
    xn = paddle.transpose(x, [0, 2, 3, 1])

    nchw = resnet18(num_classes=10)
    nchw.eval()
    paddle.seed(0)
    cl = to_channels_last(resnet18(num_classes=10).eval())

    def total(model, inp):
        rep = analysis.audit_model(model, inp,
                                   rules=("interior-transpose",))
        return rep.metrics["interior-transpose"]["total"], rep

    interior_total, rep_interior = total(cl.model, xn)
    boundary_total, rep_boundary = total(cl, x)
    nchw_total, _ = total(nchw, x)
    counts = {
        "interior_stablehlo": interior_total,
        "boundary_stablehlo": boundary_total,
        "nchw_stablehlo": nchw_total,
        # compiled counts are backend evidence, not linted: XLA:CPU
        # inserts per-conv weight relayouts either way
        "nchw_compiled": count_hlo_transposes(nchw, x, optimized=True),
        "channels_last_compiled": count_hlo_transposes(cl, x, optimized=True),
    }
    # the rule's boundary/interior split must agree with the budgets:
    # the wrapper's one transpose is a boundary, never an interior
    ok = (counts["interior_stablehlo"] <= INTERIOR_BUDGET
          and counts["boundary_stablehlo"] <= BOUNDARY_BUDGET
          and rep_interior.ok("high") and rep_boundary.ok("high"))
    record = {"bench": "hlo_layout_lint", "model": "resnet18",
              "budgets": {"interior": INTERIOR_BUDGET,
                          "boundary": BOUNDARY_BUDGET},
              "counts": counts, "ok": ok}
    if args.json:
        print(json.dumps(record))
    else:
        for k, v in counts.items():
            print(f"{k:24s} {v}")
        print("OK" if ok else "FAIL: transpose budget exceeded")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
