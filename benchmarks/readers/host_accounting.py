"""Per-layer metrics of the engine's host thread, from the rings that
``program.py`` reads and from the fields the program's host accounting
added to them (``paddle_tpu/serving/metrics.py``):

* ``StepRecord.admitted`` and ``.chunked`` split the schedule phase into
  ``admission`` (expiry, the admission attempt, a bucket prefill launched
  inside the step), ``chunk`` (the chunk tick, a final chunk's token wait)
  and ``capacity`` (``ensure``, evictions, pre-emption, the occupancy
  sample);
* ``AdmitRecord`` (``EngineMetrics.admits``): each admission attempt,
  ``begin -> placed`` being the cache's part (radix match, allocation,
  evictions, copy-on-write);
* ``thread`` on a step and a submit: the thread's CPU seconds (the
  whole call's: a host may count them in ticks of 10 ms), involuntary and
  voluntary switches, minor and major faults, and the collector's pauses
  over the call.

The records are ``time.perf_counter()`` of this process, as
``run.samples["window"]`` is; in a traced run the device's clock is
reached by ``program.clock_offset`` over the ``bench.engine_step:<i>``
pairs. Every reader returns ``None`` off the device, where the records
lack these fields (a program without host accounting) and where the
window holds nothing to read. Besides the metrics, a run prints
``host_by_phase`` (wall by phase and part, CPU, switches and faults a
call, per kind of step, and of the ``submit()`` calls), ``host_stalls`` (every step
or ``submit()`` that ran more than ``STALL_S`` past the median of its
kind, with what its thread went through) and, traced,
``idle_by_chunk_step_part`` (the device's idle under each part of the
steps that ran a chunk). The arithmetic is in pure functions of the
records; the tests call them. The order of a step's stamps is the
program's (``StepRecord.part_intervals()`` and ``.intervals()``).
"""
from __future__ import annotations

from benchmarks import stats, trace_reduce
from benchmarks.readers.program import (RESIDUAL_LIMIT_NS, Device, _rings,
                                        clock_offset, inside)

STALL_S = 0.050
STALLS_PRINTED = 20


def accounted(steps):
    """Whether the records carry the host accounting's fields."""
    return any(getattr(r, "admitted", None) is not None for r in steps)


def by_step(launches):
    """``{step index: [launch, ..]}`` of the launches made in a step."""
    out = {}
    for r in launches:
        if r.step is not None:
            out.setdefault(r.step, []).append(r)
    return out


def step_kind(step, launches):
    """``chunk`` where the step ran a chunk, else the record's kind
    (``decode``, ``admit`` for a bucket prefill, ``spec``)."""
    if any(x.program == "chunk" for x in launches):
        return "chunk"
    return step.kind


def spans(step):
    """``[(part, t0, t1)]`` of a step's six parts in time order: the
    schedule phase's three parts (admission, chunk, capacity), then
    dispatch, fetch and emit."""
    return step.part_intervals() + step.intervals()[1:]


def walls(step):
    """Wall seconds of the six parts of a step, in time order."""
    return {p: t1 - t0 for p, t0, t1 in spans(step)}


def part_ms(steps, part, kinds, want):
    """Median milliseconds of ``part`` over the steps of kind ``want``
    (``kinds``: ``{index: step_kind}``)."""
    m = stats.median([walls(r)[part] for r in steps
                      if kinds[r.index] == want])
    return None if m is None else m * 1e3


def admission_ms(admits):
    """Median milliseconds from an admission's begin to the cache's
    answer."""
    m = stats.median([r.placed - r.begin for r in admits])
    return None if m is None else m * 1e3


def gc_ms_per_s(records, seconds):
    """Collector pause milliseconds inside ``records`` (steps and submits:
    they never overlap) per second of a window of ``seconds``."""
    return 1e3 * sum(r.thread.gc_seconds for r in records) / seconds


def _length(r):
    return r.end - r.begin


def stalls(steps, submits, launches, admits=(), limit=STALL_S):
    """Every step or ``submit()`` call that ran more than ``limit`` seconds
    past the median of its kind (a step's by :func:`step_kind`), as a dict:
    where it was (``step`` or ``request_id``, ``begin``), its ``wall_s``,
    ``excess_s`` over the median and ``stall_s``, the excess less the
    collector's pauses inside it; ``cpu_excess_s``, the call's thread CPU
    past the median of its kind; the ``phase`` (or part) that ran furthest
    past its own median, with its wall seconds; the step's ``launch`` that
    took longest (``[program, call s, wait s]``); the admissions inside
    the call (``[placed - begin, end - placed, shared, evicted]``); what
    the thread went through (``thread``); and a ``signature``:
    ``collector`` (pauses are half the excess or more), ``paging`` (a
    major fault), ``work`` (CPU past the median is half the excess or
    more), ``neighbour`` (more involuntary switches than the median of its
    kind: held off the CPU) or ``blocked`` (off the CPU of its own accord,
    waiting inside a call)."""
    per = by_step(launches)
    kind = {r.index: step_kind(r, per.get(r.index, ())) for r in steps}
    groups = {}
    for r in steps:
        groups.setdefault(kind[r.index], []).append(r)
    if submits:
        groups["submit"] = list(submits)
    out = []
    for k, rows in groups.items():
        median = stats.median([_length(r) for r in rows])
        cpu = stats.median([r.thread.cpu for r in rows])
        switches = stats.median([r.thread.involuntary for r in rows])
        part_median = (None if k == "submit" else
                       {p: stats.median([walls(r)[p] for r in rows])
                        for p in walls(rows[0])})
        for r in rows:
            excess = _length(r) - median
            if excess <= limit:
                continue
            d = r.thread
            row = {"kind": k, "begin": r.begin, "wall_s": _length(r),
                   "excess_s": excess,
                   "stall_s": max(excess - d.gc_seconds, 0.0),
                   "cpu_excess_s": d.cpu - cpu, "thread": d._asdict()}
            row["admissions"] = [
                [a.placed - a.begin, a.end - a.placed, a.shared, a.evicted]
                for a in admits if r.begin <= a.begin <= r.end]
            if k == "submit":
                row["request_id"] = r.request_id
                row["phase"] = "submit"
                row["phase_wall_s"] = row["wall_s"]
            else:
                row["step"] = r.index
                wall = walls(r)
                phase = max(wall, key=lambda p: wall[p] - part_median[p])
                row["phase"], row["phase_wall_s"] = phase, wall[phase]
                longest = max(per.get(r.index, ()), default=None,
                              key=lambda x: (x.fetched or x.dispatched)
                              - x.called)
                row["launch"] = longest and [
                    longest.program, longest.dispatched - longest.called,
                    None if longest.fetched is None
                    else longest.fetched - longest.dispatched]
            row["signature"] = signature(d, excess, d.cpu - cpu, switches)
            out.append(row)
    return sorted(out, key=lambda row: row["begin"])


def signature(thread, excess, cpu_excess, usual_switches):
    """What most likely held a stalled call (see :func:`stalls`)."""
    if thread.gc_seconds >= excess / 2:
        return "collector"
    if thread.major_faults:
        return "paging"
    if cpu_excess >= excess / 2:
        return "work"
    if thread.involuntary > usual_switches:
        return "neighbour"
    return "blocked"


def stall_ms_per_s(found, seconds):
    """Milliseconds of stall (:func:`stalls`' ``stall_s``) per second of a
    window of ``seconds``."""
    return 1e3 * sum(row["stall_s"] for row in found) / seconds


def by_phase(steps, submits, launches):
    """Means a call, per kind of step and of the ``submit()`` calls: wall
    milliseconds of each part, and the call's CPU milliseconds, switches,
    faults and collector milliseconds (the information line
    ``host_by_phase``)."""
    per = by_step(launches)
    groups = {}
    for r in steps:
        groups.setdefault(step_kind(r, per.get(r.index, ())), []).append(r)
    out = {}
    for k, rows in groups.items():
        n = len(rows)
        out[k] = {"steps": n,
                  "wall_ms": {p: 1e3 * sum(walls(r)[p] for r in rows) / n
                              for p in walls(rows[0])},
                  **_thread_means(rows)}
    if submits:
        n = len(submits)
        out["submit"] = {
            "calls": n,
            "wall_ms": 1e3 * sum(_length(r) for r in submits) / n,
            **_thread_means(submits)}
    return out


def _thread_means(rows):
    n = len(rows)
    return {"cpu_ms": 1e3 * sum(r.thread.cpu for r in rows) / n,
            "involuntary": sum(r.thread.involuntary for r in rows) / n,
            "voluntary": sum(r.thread.voluntary for r in rows) / n,
            "minor_faults": sum(r.thread.minor_faults for r in rows) / n,
            "major_faults": sum(r.thread.major_faults for r in rows) / n,
            "gc_ms": 1e3 * sum(r.thread.gc_seconds for r in rows) / n}


def chunk_step_idle(busy, window, steps, launches, offset):
    """For each step that ran a chunk and lies wholly inside the traced
    ``window`` (trace nanoseconds), the idle nanoseconds of a device
    (``busy``: its merged operations) under each of the step's six parts,
    the records moved onto the trace's clock by ``offset``."""
    lo, hi = window
    device = Device(busy)
    chunked = {r.step for r in launches if r.program == "chunk"}
    out = []
    for r in steps:
        if (r.index not in chunked or r.begin * 1e9 + offset < lo
                or r.end * 1e9 + offset > hi):
            continue
        out.append({p: device.idle(t0 * 1e9 + offset, t1 * 1e9 + offset)
                    for p, t0, t1 in spans(r)})
    return out


def _admit_ring():
    """The ``AdmitRecord``s of the program's live engines."""
    from paddle_tpu.serving import metrics

    return [r for m in metrics.live_metrics() for r in m.admits]


def _read(run):
    """The window's records, once a run, or ``None`` where there is
    nothing this module can read; prints the information lines."""
    if hasattr(run, "host_accounting"):
        return run.host_accounting
    run.host_accounting = None
    if not run.on_device or "engine_steps" not in run.samples:
        return None
    rings = _rings()
    if rings is None or not accounted(rings[0]):
        return None
    window = run.samples["window"]
    steps = inside(rings[0], window, "begin", "end")
    if not steps:
        return None
    launches = inside(rings[1], window, "called", "dispatched")
    submits = inside(rings[2], window, "begin", "end")
    admits = inside(_admit_ring(), window, "begin", "end")
    seconds = window[1] - window[0]
    per = by_step(launches)
    kind = {r.index: step_kind(r, per.get(r.index, ())) for r in steps}
    found = stalls(steps, submits, launches, admits)
    run.info("host_by_phase", **by_phase(steps, submits, launches))
    run.info("host_stalls", limit_s=STALL_S, window_s=seconds,
             count=len(found), stall_s=sum(r["stall_s"] for r in found),
             stalls=[dict(r, at_s=r["begin"] - window[0])
                     for r in found[:STALLS_PRINTED]])
    run.host_accounting = {
        "steps": steps, "launches": launches, "submits": submits,
        "admits": admits, "seconds": seconds, "kinds": kind,
        "stalls": found}
    if run.trace and run.trace["reduced"]:
        run.host_accounting["idle"] = _idle(run, steps, launches)
    return run.host_accounting


def _idle(run, steps, launches):
    events, reduced = run.trace["events"], run.trace["reduced"]
    clock = clock_offset(
        {i: (t0, t1) for t0, t1, i in run.spans.by_name["engine_step"]},
        trace_reduce.spans_named(events, "bench.engine_step"))
    if clock is None or clock[1] > RESIDUAL_LIMIT_NS:
        return None
    merged = reduced["merged"]
    per_step = chunk_step_idle(merged[sorted(merged)[0]],
                               trace_reduce.window_of(events), steps,
                               launches, clock[0])
    if not per_step:
        return None
    n = len(per_step)
    run.info("idle_by_chunk_step_part", steps=n,
             ms={p: sum(row[p] for row in per_step) / n * 1e-6
                 for p in per_step[0]})
    return per_step


def engine_phase_ms_admission(run):
    """Median ``begin -> admitted`` of the window's decode-only steps."""
    p = _read(run)
    return p and part_ms(p["steps"], "admission", p["kinds"], "decode")


def engine_phase_ms_capacity(run):
    """Median ``chunked -> scheduled`` of the window's decode-only steps."""
    p = _read(run)
    return p and part_ms(p["steps"], "capacity", p["kinds"], "decode")


def engine_phase_ms_chunk(run):
    """Median ``admitted -> chunked`` of the window's steps that ran a
    chunk: its launch and, on a final chunk, the wait for its token."""
    p = _read(run)
    return p and part_ms(p["steps"], "chunk", p["kinds"], "chunk")


def request_admission_ms(run):
    """Median ``begin -> placed`` of the window's admission attempts."""
    p = _read(run)
    return p and admission_ms(p["admits"])


def gc_pause_ms(run):
    """Collector pause ms inside the window's steps and submits, a second
    of window."""
    p = _read(run)
    return p and gc_ms_per_s(p["steps"] + p["submits"], p["seconds"])


def host_stall_ms(run):
    """Stalled ms (:func:`stalls`) a second of window."""
    p = _read(run)
    return p and stall_ms_per_s(p["stalls"], p["seconds"])


def device_idle_ms_chunk(run):
    """Mean device idle a chunk-bearing step of the traced window."""
    p = _read(run)
    rows = p and p.get("idle")
    if not rows:
        return None
    return sum(sum(row.values()) for row in rows) / len(rows) * 1e-6
