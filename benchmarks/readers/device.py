"""Per-layer metrics from the device trace of a traced run. Host spans
are found in the trace by their annotation's name and index, so both sit
on the trace's clock."""
from __future__ import annotations

from benchmarks import trace_reduce


def _trace(run):
    return run.trace if run.trace and run.trace["reduced"] else None


def train_step_device_ms(run):
    """Device busy time a train step: the loss of step ``i`` is fetched
    when the device has finished step ``i``, so between the ends of the
    first and the last fetch inside the traced window the device ran a
    whole number of steps."""
    t = _trace(run)
    if t is None:
        return None
    lo, hi = trace_reduce.window_of(t["events"])
    ends = {i: e for i, (s, e) in trace_reduce.spans_named(
        t["events"], "bench.fetch").items() if s >= lo and e <= hi}
    if len(ends) < 2:
        return None
    a, b = min(ends), max(ends)
    return trace_reduce.busy_between(t["reduced"], ends[a], ends[b]) \
        / (b - a) * 1e-6


def decode_device_ms(run):
    """Device busy time inside an ``Engine.step()`` that only decoded."""
    t = _trace(run)
    if t is None or "engine_steps" not in run.samples:
        return None
    lo, hi = trace_reduce.window_of(t["events"])
    kinds = [k for _, _, k, _ in run.samples["engine_steps"]]
    busy = [trace_reduce.busy_between(t["reduced"], s, e)
            for i, (s, e) in trace_reduce.spans_named(
                t["events"], "bench.engine_step").items()
            if s >= lo and e <= hi and kinds[i] == "decode"]
    return sum(busy) / len(busy) * 1e-6 if busy else None
