"""Per-layer metrics from the benchmark's own host spans and from the
program's counters."""
from __future__ import annotations

from benchmarks import stats


def programs_built(run):
    """Programs XLA built (or read from the compile cache) in set-up."""
    return run.counters.get("programs_built")


def window_compiles_train(run):
    return run.counters.get("window_compiles") \
        if "steps" in run.samples else None


def window_compiles_serve(run):
    return run.counters.get("window_compiles") \
        if "requests" in run.samples else None


def loader_wait_ms(run):
    """Mean time the measuring loop waited in ``next(loader)``, a step."""
    t0, t1 = run.samples["window"]
    waits = [b - a for a, b, _ in run.spans.by_name.get("loader", ())
             if t0 <= a and b <= t1]
    return sum(waits) / len(waits) * 1e3 if waits else None


def _engine_steps(run, kind):
    s = run.samples
    if "engine_steps" not in s:
        return None
    times = [b - a for a, b, k, _ in s["engine_steps"][s["first_step"]:]
             if k == kind]
    m = stats.median(times)
    return None if m is None else m * 1e3


def engine_step_ms_decode(run):
    """Median ``Engine.step()`` in which nothing was prefilled."""
    return _engine_steps(run, "decode")


def engine_step_ms_admit(run):
    """Median ``Engine.step()`` in which a prompt, or a chunk of one, was
    prefilled beside the decode."""
    return _engine_steps(run, "admit")


def batch_occupancy(run):
    """Mean of ``EngineMetrics.sample``'s occupancy over the window."""
    c = run.samples.get("engine_counters")
    if not c or not c["samples"]:
        return None
    return 100.0 * c["occupancy_sum"] / c["samples"]
