#!/usr/bin/env python3
"""Controls of a serve cell's ``correct``: the program with ONE fault
planted, through the harness's own comparison, at the cell's own sizes.

    python3 benchmarks/controls.py --workload <cell> --seed <n> --fault <name>

A comparison that a faulty program passes guards nothing, and a tiny
model on the CPU does not say what it catches at the published widths. So
this builds the cell's model and engine as ``run.py`` does, plants one
fault in the PROGRAM (the reference is left as it is), runs the driver's
set-up up to and including its reference check (the sample through the
engine, then ``reference.logits`` teacher-forced over the engine's own
tokens, held to ``LOGIT_TOL_ULPS``), and prints the driver's information
lines and, last, ``{"control": <fault>, "correct": <bool>, ...}``. Every
fault but ``none`` has to come out ``correct: false``; ``none`` is the
control of the controls. No window is measured and no metric is read: this
is no cell and the driver of the benchmark never runs it.

The faults, each a departure from the equations that still runs:

``unnormalised_top_k``  the picks keep their softmax weights (no division
                        by their sum);
``dropped_pick``        the smallest of a row's picks is computed with
                        weight zero, nothing renormalised;
``no_window``           a ``sliding_attention`` layer sees every earlier
                        position;
``plain_table_on_full_layers``  a ``full_attention`` layer is rotated by
                        the plain table of its ``rope_theta``;
``eight_bit_activations``  the program in the nearest precision below the
                        bfloat16 it is served in: every norm's output, which
                        is what each projection, router and expert reads, is
                        rounded to ``float8_e4m3fn`` (3 bits of mantissa for
                        bfloat16's 7) and widened again. No equation is
                        broken: this is the reading from above of the
                        comparison's limits, as the program itself is the
                        reading from below.
``altered_token``       one token altered where it is produced: the second
                        token of the first request to reach one, its
                        lowest bit flipped as the engine emits it. One row
                        of the check scores like a random token.

The first two need a routed model, the next two one with layer kinds; on
any other model they change nothing and the control reads ``correct:
true``, which says so. The fifth lowers any llama-bodied program; the
last runs on any engine.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _unnormalised_top_k():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn import routed_ffn

    def route(m, wr, k):
        g = jax.nn.softmax(
            jnp.dot(m, wr, preferred_element_type=jnp.float32), axis=-1)
        vals, experts = jax.lax.top_k(g, k)
        return experts.astype(jnp.int32), vals

    return mock.patch.object(routed_ffn, "route", route)


def _dropped_pick():
    from paddle_tpu.nn import routed_ffn

    true = routed_ffn.route

    def route(m, wr, k):
        experts, weights = true(m, wr, k)     # top_k: largest first
        return experts, weights.at[:, -1].set(0.0)

    return mock.patch.object(routed_ffn, "route", route)


def _no_window():
    from paddle_tpu.serving import engine

    true = engine._by_kind
    return mock.patch.object(
        engine, "_by_kind", lambda make, kinds, window: true(make, kinds,
                                                             None))


def _plain_table_on_full_layers():
    from paddle_tpu.text.models import mellum

    true = mellum.rope_table
    return mock.patch.object(
        mellum, "rope_table", lambda params, head_dim: true(
            {"rope_type": "default", "rope_theta": params["rope_theta"]},
            head_dim))


def _eight_bit_activations():
    import jax.numpy as jnp
    from paddle_tpu.text import generation

    true = generation._rms

    def rms(x, w, eps):
        y = true(x, w, eps)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

    return mock.patch.object(generation, "_rms", rms)


def _altered_token():
    from paddle_tpu.serving import engine

    true = engine.Engine._emit
    altered = []

    def emit(self, h, token):
        if not altered and len(h.tokens) == 1:
            altered.append(h)
            token ^= 1
        return true(self, h, token)

    return mock.patch.object(engine.Engine, "_emit", emit)


FAULTS = {"none": contextlib.nullcontext,
          "unnormalised_top_k": _unnormalised_top_k,
          "dropped_pick": _dropped_pick,
          "no_window": _no_window,
          "plain_table_on_full_layers": _plain_table_on_full_layers,
          "eight_bit_activations": _eight_bit_activations,
          "altered_token": _altered_token}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--data", default=HERE)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run as harness

    cell = harness.load(os.path.join(args.data, "workloads",
                                     f"{args.workload}.json"))
    config = harness.load(os.path.join(args.data, "configs",
                                       f"{cell['config']}.json"))
    traffic = harness.load(os.path.join(args.data, "traffic",
                                        f"{cell['traffic']}.json"))
    # the check alone: no request is served after it
    traffic = dict(traffic, warmup_requests=0)

    import jax

    devices = jax.devices()
    if devices[0].platform == "tpu":
        harness.compile_cache()
    elif not args.allow_cpu:
        print(f"the controls need a TPU; jax found only "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    run = harness.Run(cell, config, traffic, args.seed, devices[0])
    run.info("start", workload=args.workload, seed=args.seed,
             fault=args.fault, device_kind=run.device_kind)
    driver = importlib.import_module(
        f"benchmarks.drivers.{traffic['driver']}").Driver(run)
    with FAULTS[args.fault]():
        correct = driver.setup()
    print(json.dumps({"control": args.fault, "correct": bool(correct),
                      "setup_phases": dict(run.phases),
                      "compared": run.compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
