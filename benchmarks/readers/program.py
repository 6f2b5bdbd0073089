"""Per-layer metrics from the program's own stamps: the ``StepRecord`` and
``LaunchRecord`` rings that ``serving.Engine`` fills on every step
(``paddle_tpu/serving/metrics.py``), found through the program's accessor
of its live engines. The records are ``time.perf_counter()`` of this
process, as ``run.samples["window"]`` is, so they share the benchmark's
clock already.

For the device's clock no annotation of the program's is needed: every
``bench.engine_step:<i>`` span exists twice, in ``run.spans`` on
``perf_counter`` and in the trace in trace nanoseconds. ``clock_offset``
takes the offset from those pairs; with it the idle time of the first
device inside the traced window is laid under the program's phases and
its ``submit()`` calls (``idle_by_phase``), by exact overlap: in a decode
loop the device's one gap a step runs from the end of one fetch through
emit, the caller's loop, schedule and dispatch into the next fetch, and
its midpoint would name one of the five.

Every reader returns ``None`` off the device (a CPU backend's phase split
says nothing about the chip), where the program has no rings (a parent of
the change that brought them), and where they hold nothing in the window.
The arithmetic is in pure functions of plain tuples; the tests call them.
"""
from __future__ import annotations

import bisect

from benchmarks import stats, trace_reduce

PHASES = ("schedule", "dispatch", "fetch", "emit")
RESIDUAL_LIMIT_NS = 50e3


def inside(records, window, first, last):
    """The records whose stamps ``first`` .. ``last`` lie in ``window``."""
    return [r for r in records
            if window[0] <= getattr(r, first)
            and getattr(r, last) <= window[1]]


def phase_ms(steps, phase):
    """Median milliseconds of ``phase`` over the decode-only steps."""
    m = stats.median([r.phases()[phase] for r in steps
                      if r.kind == "decode"])
    return None if m is None else m * 1e3


def bucket_prefill_ms(launches):
    """Median milliseconds from the call to the fetched first token of
    the bucket prefills."""
    m = stats.median([r.fetched - r.called for r in launches
                      if r.program.startswith("prefill:")])
    return None if m is None else m * 1e3


def clock_offset(host, traced):
    """``(offset_ns, residual_ns, pairs)`` with trace time = host seconds
    x 1e9 + offset, from the spans that exist on both clocks: ``host`` is
    ``{index: (t0, t1)}`` in seconds, ``traced`` ``{index: (start, end)}``
    in nanoseconds. The annotation is entered just before ``t0`` and left
    just after ``t1``, so the mean of the two differences cancels the
    bias. The offset is the median over the pairs, the residual their
    largest less their smallest. ``None`` without a pair."""
    each = sorted(((traced[i][0] - t0 * 1e9) + (traced[i][1] - t1 * 1e9))
                  / 2 for i, (t0, t1) in host.items() if i in traced)
    if not each:
        return None
    return stats.median(each), each[-1] - each[0], len(each)


class Device:
    """Idle time of one device from ``busy``, the sorted union of its
    operations' intervals in trace nanoseconds (tens of thousands in a
    traced window: hence the bisection)."""

    def __init__(self, busy):
        self.busy = busy
        self.starts = [s for s, _ in busy]

    def _between(self, lo, hi):
        """The busy intervals that can reach into ``[lo, hi]``."""
        first = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        return self.busy[first:bisect.bisect_left(self.starts, hi)]

    def idle(self, lo, hi):
        """Idle nanoseconds inside ``[lo, hi]``; 0 where ``hi <= lo``."""
        if hi <= lo:
            return 0.0
        return hi - lo - trace_reduce.covered(self._between(lo, hi), lo, hi)

    def head_and_tail(self, lo, hi):
        """Idle nanoseconds at the two ends of ``[lo, hi]``: before the
        first operation inside it starts and after the last one ends
        (``(hi - lo, 0)`` where none runs inside)."""
        inside = [(s, e) for s, e in self._between(lo, hi) if e > lo]
        if not inside:
            return max(hi - lo, 0.0), 0.0
        return max(inside[0][0] - lo, 0.0), max(hi - inside[-1][1], 0.0)


def idle_by_phase(busy, window, steps, launches, submits, offset):
    """Idle nanoseconds of a device inside the traced ``window``, by what
    the program was doing: the four phases of its steps; a prefill
    launched inside ``submit()`` (``prefill_in_submit``: launch records
    without a step, call to fetched token); the rest of those calls
    (``submit_host``: the submit records less the launches inside them);
    and ``outside`` any record. The records are on the host's clock and
    moved by ``offset``; each is clipped to the window.

    Also ``per_step``, a dict for each decode-only step wholly inside the
    window, in nanoseconds: its ``length``, the idle under schedule +
    dispatch + emit (``host``) and under ``fetch``, and of the latter the
    part before the device starts the step's program (``fetch_head``: the
    call has returned, its arguments are still on their way) and after it
    ends (``fetch_tail``: the host does not know yet)."""
    lo, hi = window
    device = Device(busy)

    def trace(t):
        return t * 1e9 + offset

    def idle(a, b):
        return device.idle(max(trace(a), lo), min(trace(b), hi))

    out = dict.fromkeys(PHASES + ("prefill_in_submit", "submit_host"), 0.0)
    per_step = []
    for r in steps:
        each = {p: idle(a, b) for p, a, b in r.intervals()}
        for p, ns in each.items():
            out[p] += ns
        if r.kind == "decode" and lo <= trace(r.begin) \
                and trace(r.end) <= hi:
            head, tail = device.head_and_tail(trace(r.dispatched),
                                              trace(r.fetched))
            per_step.append({
                "step": r.index, "length": (r.end - r.begin) * 1e9,
                "host": each["schedule"] + each["dispatch"] + each["emit"],
                "fetch": each["fetch"], "fetch_head": head,
                "fetch_tail": tail})
    out["prefill_in_submit"] = sum(
        idle(r.called, r.fetched or r.dispatched)
        for r in launches if r.step is None)
    out["submit_host"] = sum(idle(r.begin, r.end) for r in submits) \
        - out["prefill_in_submit"]
    out["outside"] = device.idle(lo, hi) - sum(out.values())
    return out, per_step


def _rings():
    """``(steps, launches, submits)`` of the program's live engines, or
    ``None`` where the program keeps none."""
    from paddle_tpu.serving import metrics

    live = getattr(metrics, "live_metrics", None)
    if live is None:
        return None
    engines = live()
    return tuple([r for m in engines for r in getattr(m, ring)]
                 for ring in ("steps", "launches", "submits"))


def _read(run):
    """What every reader here reads, computed once a run: the records
    inside the window and, in a traced run, the idle split (printed as
    information lines ``program_clock`` and ``idle_by_program_phase``)."""
    if hasattr(run, "program_stamps"):
        return run.program_stamps
    run.program_stamps = None
    if not run.on_device or "engine_steps" not in run.samples:
        return None
    # what the engine's own ITL estimate read after the run (the number
    # behind retry_after_s, brownout and fleet routing), rings or none
    snapshot = run.samples.get("engine_stats", {})
    run.info("program_itl", **{k: snapshot.get(k) for k in (
        "itl_estimate_ms", "itl_p95_ms", "step_phase_seconds",
        "submit_seconds")})
    rings = _rings()
    if rings is None or not rings[0]:
        return None
    window = run.samples["window"]
    steps = inside(rings[0], window, "begin", "end")
    launches = inside(rings[1], window, "called", "dispatched")
    submits = inside(rings[2], window, "begin", "end")
    run.info("program_records", steps=len(steps),
             decode_only_steps=sum(r.kind == "decode" for r in steps),
             launches=len(launches), submits=len(submits),
             submit_ms_median=(stats.median(
                 [r.end - r.begin for r in submits]) or 0.0) * 1e3)
    run.program_stamps = {"steps": steps, "launches": launches}
    if run.trace and run.trace["reduced"]:
        run.program_stamps["idle"] = _idle(run, rings)
    return run.program_stamps


def _idle(run, rings):
    events, reduced = run.trace["events"], run.trace["reduced"]
    clock = clock_offset(
        {i: (t0, t1) for t0, t1, i in run.spans.by_name["engine_step"]},
        trace_reduce.spans_named(events, "bench.engine_step"))
    if clock is None:
        return None
    offset, residual, pairs = clock
    run.info("program_clock", offset_ns=offset, residual_ns=residual,
             pairs=pairs, residual_limit_ns=RESIDUAL_LIMIT_NS)
    if residual > RESIDUAL_LIMIT_NS:
        return None
    merged = reduced["merged"]
    window = trace_reduce.window_of(events)
    split, per_step = idle_by_phase(merged[sorted(merged)[0]], window,
                                    *rings, offset)
    total = sum(split.values())
    run.info("idle_by_program_phase",
             seconds={k: v * 1e-9 for k, v in split.items()},
             outside_share=split["outside"] / total if total else 0.0,
             decode_only_steps=len(per_step),
             decode_only_step_ms={k: _mean_ms(per_step, k) for k in (
                 "length", "host", "fetch", "fetch_head", "fetch_tail")})
    return per_step


def _mean_ms(per_step, key):
    if not per_step:
        return None
    return sum(row[key] for row in per_step) / len(per_step) * 1e-6


def _phase(run, phase):
    p = _read(run)
    return p and phase_ms(p["steps"], phase)


def engine_phase_ms_schedule(run):
    """Median begin -> scheduled of the window's decode-only steps."""
    return _phase(run, "schedule")


def engine_phase_ms_dispatch(run):
    """Median scheduled -> dispatched: argument copies, upload, enqueue."""
    return _phase(run, "dispatch")


def engine_phase_ms_fetch(run):
    """Median dispatched -> fetched: the wait for the device's tokens."""
    return _phase(run, "fetch")


def engine_phase_ms_emit(run):
    """Median fetched -> end: per-token bookkeeping and callbacks."""
    return _phase(run, "emit")


def prefill_ms_bucket(run):
    """Median call -> fetched first token of the bucket prefills launched
    in the window, inside ``submit()`` or inside a step."""
    p = _read(run)
    return p and bucket_prefill_ms(p["launches"])


def _device_idle(run, key):
    p = _read(run)
    return p and _mean_ms(p.get("idle"), key)


def device_idle_ms_host(run):
    """Device idle a decode-only step under the program's schedule,
    dispatch and emit phases: the host was at work and the chip was
    not."""
    return _device_idle(run, "host")


def device_idle_ms_fetch(run):
    """Device idle a decode-only step under the fetch phase: the device
    had finished and the host did not know yet."""
    return _device_idle(run, "fetch")
