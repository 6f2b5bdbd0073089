#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell
(``workloads/<cell>.json``) names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<traffic>.json``),
the traffic mix names its driver (``drivers/<driver>.py``), and every file of
``metrics/`` names the reader (``readers/*.py``) that takes one metric from
what the run recorded. A reader that finds nothing to read returns ``None``
and its metric is left out.

Information lines (one JSON object each, key ``info``) come first; the last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and last
``compared``: each number the check compared, with its limit (also the
last lines of standard error).
With ``--trace 0`` the metrics are the end-to-end ones, with ``--trace 1``
the per-layer ones. Without a TPU, or with another number of chips than the
cell asks for, the run exits non-zero and prints no result; ``--allow-cpu``
is for rehearsal only, and such a run reports no device metric.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse    # noqa: E402
import glob        # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 3.0


def load(path):
    with open(path) as f:
        return json.load(f)


class Run:
    """What a driver is given, and what the readers read afterwards."""

    def __init__(self, cell, config, traffic, seed, device):
        from benchmarks import probe

        self.cell, self.config, self.traffic, self.seed = \
            cell, config, traffic, seed
        self.device_kind = device.device_kind
        self.on_device = device.platform == "tpu"
        self.chips = cell["chips"]
        self.spans = probe.Spans()
        self.builds = probe.Builds()
        self.counters = {}
        self.samples = {}
        self.trace = None
        self.phases = []
        # what the cell's check compared: name -> {"value", and the limit
        # as "at_most" or "at_least"}
        self.compared = {}

    def phase(self, name):
        """Mark the end of a phase of set-up, by the process's clock."""
        self.phases.append((name, time.perf_counter() - _PROCESS_START))

    def info(self, kind, **values):
        print(json.dumps({"info": kind, **values}), flush=True)


def compile_cache():
    """jax's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` where that
    is set, else at a fixed path inside the checkout (the path is part of
    the cache's key). Every program is kept, however quickly it compiled:
    set-up builds tens of one-operation programs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def read_metrics(run, level):
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        m = load(path)
        if m["level"] != level:
            continue
        module, _, function = m["reader"].partition(":")
        value = getattr(importlib.import_module(module), function)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--data", default=HERE,
                    help="directory holding workloads/, configs/, traffic/")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    cell = load(os.path.join(args.data, "workloads", f"{args.workload}.json"))
    config = load(os.path.join(args.data, "configs",
                               f"{cell['config']}.json"))
    traffic = load(os.path.join(args.data, "traffic",
                                f"{cell['traffic']}.json"))

    import jax

    from benchmarks import models, probe, trace_reduce

    # without the program there is nothing to measure: fail before a line
    # is printed (a tree that holds the benchmark alone ends here)
    models.resolve(config["program"]["model"])
    devices = jax.devices()
    on_device = devices[0].platform == "tpu"
    # a rehearsal on the CPU keeps no cache: its programs are no set-up
    # worth saving, and XLA's CPU loader warns at length on reading them
    cache_dir = compile_cache() if on_device else None
    if not on_device and not args.allow_cpu:
        print(f"the benchmark needs a TPU; jax found only "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if on_device and len(devices) != cell["chips"]:
        print(f"cell {args.workload} needs {cell['chips']} chip(s); jax "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    run = Run(cell, config, traffic, args.seed, devices[0])
    run.info("start", workload=args.workload, seed=args.seed,
             seconds=args.seconds, trace=args.trace, compile_cache=cache_dir,
             device_kind=run.device_kind,
             bytes_limit=probe.bytes_limit(devices[0]), **probe.versions())

    run.phase("imports_and_device")
    driver = importlib.import_module(
        f"benchmarks.drivers.{traffic['driver']}").Driver(run)
    correct = driver.setup()
    run.info("setup_phases", ended_at_s=dict(run.phases))
    run.counters["programs_built"] = run.builds.count
    run.counters["programs_from_cache"] = run.builds.cache_hits
    run.builds.reset()
    tracer = probe.Tracer(bool(args.trace),
                          max(args.seconds - TRACE_SECONDS, 0.0))
    run.setup_s = time.perf_counter() - _PROCESS_START
    run.samples = driver.window(args.seconds, tracer)
    run.counters["window_compiles"] = run.builds.count
    driver.close()

    events = tracer.events()
    if events is not None:
        run.info("trace_layout", planes=trace_reduce.layout(events))
        run.trace = {"events": events,
                     "reduced": trace_reduce.reduce(events)}
    t0, t1 = run.samples["window"]
    run.info("window", seconds=t1 - t0, setup_s=run.setup_s, **run.counters)
    driver.report()

    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices),
              "memory_peak_bytes": probe.memory_peak(devices)}
    result = {"correct": bool(correct and run.samples["failed"] == 0),
              "attempted": run.samples["attempted"],
              "failed": run.samples["failed"]}
    for level in ("end_to_end", "per_layer"):
        found = read_metrics(run, level)
        if (level == "per_layer") == bool(args.trace):
            result["metrics"] = found
        else:
            run.info(level, **{k: v["value"] for k, v in found.items()})
    # a trace without a device plane (a rehearsal on the CPU) reduces to
    # nothing: no busy time, no breakdown, no device metric
    reduced = run.trace and run.trace["reduced"]
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        run.info("idle", idle_share=reduced["idle_share"],
                 by_host_annotation=reduced["idle_by_label"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["device"] = device
    # the numbers that decided ``correct``, last on standard error and last
    # in the result
    for name, limit in run.compared.items():
        print(f"compared {name} {json.dumps(limit)}", file=sys.stderr)
    result["compared"] = run.compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
