"""The readers of the engine's host accounting (``readers/host_accounting.py``)
on hand-made records of the program's own types and a synthetic device
plane: a CPU run has no chip to be idle."""
import json
import os
import types

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import host_accounting as ha
from paddle_tpu.serving import metrics
from paddle_tpu.serving.metrics import (AdmitRecord, LaunchRecord,
                                        StepRecord, SubmitRecord,
                                        ThreadDelta)

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6                       # nanoseconds
OFFSET = 7_000_000_123.0       # trace ns = host s x 1e9 + OFFSET
QUIET = ThreadDelta(0.0, 1, 3, 0, 0, 0.0, 0)
# a step's six parts in time order
PARTS = tuple(metrics.PARTS) + metrics.PHASES[1:]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ALL = ["deepseek7b_chat_closed", "mellum2_code_chat_closed_8k",
       "kimi_k26_agent_closed_16k", "phi4flash_reason_closed_8k"]
NO_LATENT = [c for c in ALL if not c.startswith("kimi")]
METRICS = {
    "engine_phase_ms.admission": ("engine_phase_ms_admission", NO_LATENT),
    "engine_phase_ms.capacity": ("engine_phase_ms_capacity", NO_LATENT),
    "engine_phase_ms.chunk": ("engine_phase_ms_chunk", ALL),
    "request_admission_ms": ("request_admission_ms", ALL),
    "gc_pause_ms": ("gc_pause_ms", ALL),
    "host_stall_ms": ("host_stall_ms", ALL),
    "device_idle_ms.chunk": ("device_idle_ms_chunk", ALL),
}
READERS = [getattr(ha, f) for f, _ in METRICS.values()]


def step(index, begin_ms, parts_ms, kind="decode", cpu_share=1.0,
         thread=QUIET):
    """A step beginning ``begin_ms`` after second 100 whose six parts
    (admission, chunk, capacity, dispatch, fetch, emit) last
    ``parts_ms``; its thread is on the CPU for ``cpu_share`` of every part
    but the fetch."""
    t = [100.0 + begin_ms * 1e-3]
    for ms in parts_ms:
        t.append(t[-1] + ms * 1e-3)
    cpu = sum(ms for part, ms in zip(PARTS, parts_ms)
              if part != "fetch") * 1e-3 * cpu_share
    return StepRecord(index, kind, t[0], t[3], t[4], t[5], t[6], 16, t[1],
                      t[2], thread._replace(cpu=cpu))


def decode_launch(r):
    return LaunchRecord("decode", r.index, r.scheduled, r.dispatched,
                        r.fetched, None, 16, 0)


def chunk_launch(r, final):
    """A chunk launched at ``admitted``, its call returning 1 ms later and
    a final one's token fetched at ``chunked``."""
    return LaunchRecord("chunk", r.index, r.admitted, r.admitted + 1e-3,
                        r.chunked if final else None, 7, 512, 0)


# ten-millisecond decode steps (admission 0.2, chunk 0, capacity 0.8,
# dispatch 1, fetch 7, emit 1) and two 30 ms steps that ran a chunk
# (20 ms of it, the last a final one that waited 18 ms for its token)
DECODE = (0.2, 0.0, 0.8, 1.0, 7.0, 1.0)
CHUNK = (0.2, 20.0, 0.8, 1.0, 7.0, 1.0)
STEPS = [step(0, 0, DECODE), step(1, 10, CHUNK, "admit"),
         step(2, 40, DECODE), step(3, 50, CHUNK, "admit"),
         step(4, 80, DECODE)]


def launches_of(steps):
    out = []
    for r in steps:
        if r.kind == "admit":
            out.append(chunk_launch(r, final=r.index == 3))
        out.append(decode_launch(r))
    return out


def test_the_parts_split_the_schedule_phase():
    launches = launches_of(STEPS)
    kinds = {r.index: ha.step_kind(r, [x for x in launches
                                       if x.step == r.index])
             for r in STEPS}
    assert kinds == {0: "decode", 1: "chunk", 2: "decode", 3: "chunk",
                     4: "decode"}
    assert ha.part_ms(STEPS, "admission", kinds, "decode") == \
        pytest.approx(0.2)
    assert ha.part_ms(STEPS, "capacity", kinds, "decode") == \
        pytest.approx(0.8)
    assert ha.part_ms(STEPS, "chunk", kinds, "chunk") == pytest.approx(20)
    assert ha.part_ms(STEPS, "chunk", kinds, "spec") is None


def test_the_parts_follow_the_programs_stamps():
    """A step's six parts come in time order from the record's own
    methods and tile ``begin -> end``."""
    r = STEPS[1]
    got = ha.spans(r)
    assert [p for p, _, _ in got] == list(PARTS)
    assert got[0][1] == r.begin and got[-1][2] == r.end
    assert all(a[2] == b[1] for a, b in zip(got, got[1:]))
    assert list(ha.walls(r).values()) == pytest.approx(
        [ms * 1e-3 for ms in CHUNK])


def test_admission_is_begin_to_placed():
    admits = [AdmitRecord(5, None, 1.0, 1.002, 1.010, 64, 0),
              AdmitRecord(6, 3, 2.0, 2.004, 2.005, 0, 2),
              AdmitRecord(7, 4, 3.0, 3.003, 3.004, 16, 1)]
    assert ha.admission_ms(admits) == pytest.approx(3.0)
    assert ha.admission_ms([]) is None


def _stalled(index, begin_ms, extra_ms, part="dispatch", **kw):
    ms = list(DECODE)
    ms[PARTS.index(part)] += extra_ms
    return step(index, begin_ms, ms, **kw)


def test_a_call_that_runs_50_ms_past_its_kinds_median_is_a_stall():
    """Five kinds of stall, each by its signature; 40 ms past the median
    is no stall."""
    base = [step(i, 10 * i, DECODE) for i in range(20)]
    t = 1000
    stalled = [
        _stalled(20, t, 40),                                   # none
        _stalled(21, t + 100, 60, cpu_share=0.05,              # neighbour
                 thread=ThreadDelta(0.0, 9, 3, 0, 0, 0.0, 0)),
        _stalled(22, t + 200, 60, cpu_share=0.05),             # blocked
        _stalled(23, t + 300, 60, part="emit"),                # work
        _stalled(24, t + 400, 60, "capacity", cpu_share=0.05,  # paging
                 thread=ThreadDelta(0.0, 1, 3, 900, 4, 0.0, 0)),
        _stalled(25, t + 500, 120, cpu_share=0.05,             # collector
                 thread=ThreadDelta(0.0, 1, 3, 0, 0, 0.09, 1))]
    steps = base + stalled
    submits = [SubmitRecord(i, 200.0 + i, 200.0 + i + 0.002,
                            QUIET._replace(cpu=0.002)) for i in range(8)]
    submits.append(SubmitRecord(9, 300.0, 300.2,
                                ThreadDelta(0.01, 1, 4, 10, 0, 0.0, 0)))
    admits = [AdmitRecord(9, None, 300.001, 300.15, 300.19, 0, 3)]
    found = ha.stalls(steps, submits, launches_of(steps), admits)
    by = {r.get("step", r.get("request_id")): r for r in found}
    assert sorted(by) == [9, 21, 22, 23, 24, 25]
    assert by[21]["signature"] == "neighbour"
    assert by[21]["phase"] == "dispatch"
    assert by[22]["signature"] == "blocked"
    assert by[23]["signature"] == "work" and by[23]["phase"] == "emit"
    # the CPU a stalled call spent past its kind's median: 60 ms of work
    # and, off the CPU, 5 % of the 60 ms
    assert by[23]["cpu_excess_s"] == pytest.approx(0.060)
    assert by[22]["cpu_excess_s"] == pytest.approx(0.05 * 0.063 - 0.003)
    assert by[9]["cpu_excess_s"] == pytest.approx(0.008)
    assert by[24]["signature"] == "paging" and by[24]["phase"] == "capacity"
    assert by[25]["signature"] == "collector"
    # the stall is the excess over the median less the collector's pause
    assert by[25]["excess_s"] == pytest.approx(0.120)
    assert by[25]["stall_s"] == pytest.approx(0.030)
    assert by[21]["launch"][0] == "decode"
    assert by[21]["launch"][1] == pytest.approx(0.061)
    sub = by[9]
    assert sub["phase"] == "submit" and sub["signature"] == "blocked"
    assert sub["admissions"] == [pytest.approx([0.149, 0.04, 0, 3])]
    assert by[21]["admissions"] == []
    total = sum(r["stall_s"] for r in found)
    assert total == pytest.approx(0.06 * 4 + 0.03 + 0.198, abs=1e-6)
    assert ha.stall_ms_per_s(found, 10.0) == pytest.approx(total * 100)
    assert ha.stalls(base, [], launches_of(base)) == []
    # the information line's means, by kind
    means = ha.by_phase(steps, submits, launches_of(steps))
    assert set(means) == {"decode", "submit"}
    assert means["decode"]["steps"] == 26
    assert means["submit"]["calls"] == 9
    assert means["decode"]["gc_ms"] == pytest.approx(90 / 26)
    assert means["decode"]["wall_ms"]["emit"] == pytest.approx(1 + 60 / 26)
    assert means["submit"]["cpu_ms"] == pytest.approx(26 / 9)
    assert ha.gc_ms_per_s(steps + submits, 10.0) == pytest.approx(9.0)


def to_trace(seconds):
    return seconds * 1e9 + OFFSET


def ev(plane, line, name, start, end):
    return (plane, line, name, float(start), float(end - start))


def traced_events(steps):
    """The ``bench.engine_step`` twin of every step (entered 2 us before
    it, left 2 us after) and a device busy from the end of each dispatch
    phase to 1 ms before the end of its fetch, and, in a step that ran a
    chunk, from 1 ms into the chunk part to 1 ms before its end."""
    lo = to_trace(steps[0].begin) - 1 * MS
    hi = to_trace(steps[-1].end) + 1 * MS
    events = [ev(HOST, "python", "bench.window", lo, hi)]
    for r in steps:
        events.append(ev(HOST, "python", f"bench.engine_step:{r.index}",
                         to_trace(r.begin) - 2e3, to_trace(r.end) + 2e3))
        events.append(ev(DEV, "XLA Ops", "fusion", to_trace(r.dispatched),
                         to_trace(r.fetched) - 1 * MS))
        if r.kind == "admit" and r.admitted is not None:
            events.append(ev(DEV, "XLA Ops", "chunk",
                             to_trace(r.admitted) + 1 * MS,
                             to_trace(r.chunked) - 1 * MS))
    return events


def test_idle_inside_a_chunk_step_lands_under_its_part():
    events = traced_events(STEPS)
    busy = tr.reduce(events)["merged"][DEV]
    rows = ha.chunk_step_idle(busy, tr.window_of(events), STEPS,
                              launches_of(STEPS), OFFSET)
    assert len(rows) == 2
    for row in rows:
        assert row["admission"] == pytest.approx(0.2 * MS)
        assert row["chunk"] == pytest.approx(2 * MS)
        assert row["capacity"] == pytest.approx(0.8 * MS)
        assert row["dispatch"] == pytest.approx(1 * MS)
        assert row["fetch"] == pytest.approx(1 * MS)
        assert row["emit"] == pytest.approx(1 * MS)
    # a step the traced window cuts is left out
    window = (to_trace(STEPS[2].begin), tr.window_of(events)[1])
    assert len(ha.chunk_step_idle(busy, window, STEPS, launches_of(STEPS),
                                  OFFSET)) == 1


def fake_run(steps, launches=None, submits=(), admits=(), traced=True,
             on_device=True):
    launches = launches_of(steps) if launches is None else launches
    events = traced_events(steps)
    run = types.SimpleNamespace(
        on_device=on_device, lines=[],
        spans=types.SimpleNamespace(by_name={"engine_step": [
            (r.begin - 1e-6, r.end + 1e-6, r.index) for r in steps]}),
        samples={"window": (steps[0].begin - 1.0, steps[-1].end + 1.0),
                 "engine_steps": []},
        trace={"events": events, "reduced": tr.reduce(events)}
        if traced else None)
    run.info = lambda kind, **values: run.lines.append((kind, values))
    run.rings = (list(steps), list(launches), list(submits), list(admits))
    return run


@pytest.fixture
def rings(monkeypatch):
    """Point the module at a run's records instead of live engines."""
    def use(run):
        monkeypatch.setattr(ha, "_rings", lambda: run.rings[:3])
        monkeypatch.setattr(ha, "_admit_ring", lambda: run.rings[3])
        return run
    return use


def test_the_eight_readers_on_a_traced_run(rings):
    submits = [SubmitRecord(9, 99.9, 99.902, QUIET)]
    admits = [AdmitRecord(9, None, 99.9001, 99.9011, 99.9019, 128, 0)]
    run = rings(fake_run(STEPS, submits=submits, admits=admits))
    values = {name: getattr(ha, f)(run) for name, (f, _) in METRICS.items()}
    assert values["engine_phase_ms.admission"] == pytest.approx(0.2)
    assert values["engine_phase_ms.capacity"] == pytest.approx(0.8)
    assert values["engine_phase_ms.chunk"] == pytest.approx(20.0)
    assert values["request_admission_ms"] == pytest.approx(1.0)
    assert values["gc_pause_ms"] == 0.0
    assert values["host_stall_ms"] == 0.0
    # a chunk step's idle: 0.2 + 2 + 0.8 + 1 + 1 + 1 ms
    assert values["device_idle_ms.chunk"] == pytest.approx(6.0)
    lines = dict(run.lines)
    assert set(lines) == {"host_by_phase", "host_stalls",
                          "idle_by_chunk_step_part"}
    assert lines["host_stalls"]["count"] == 0
    assert lines["host_by_phase"]["chunk"]["steps"] == 2
    assert lines["host_by_phase"]["chunk"]["cpu_ms"] == pytest.approx(23)
    assert lines["idle_by_chunk_step_part"]["ms"]["chunk"] == \
        pytest.approx(2.0)
    json.dumps(run.lines)          # every line prints as JSON
    # untraced, the device reader alone is silent
    run = rings(fake_run(STEPS, traced=False))
    assert ha.device_idle_ms_chunk(run) is None
    assert ha.engine_phase_ms_chunk(run) == pytest.approx(20.0)


@pytest.mark.parametrize("reader", READERS, ids=lambda f: f.__name__)
def test_silent_off_the_device_without_the_fields_or_records(rings, reader):
    run = rings(fake_run(STEPS, on_device=False))
    assert reader(run) is None and run.lines == []
    # a program whose records have no host accounting: the parent's
    bare = [StepRecord(*r[:8]) for r in STEPS]
    run = rings(fake_run(bare, launches_of(STEPS)))
    assert reader(run) is None and run.lines == []
    run = rings(fake_run(STEPS))
    run.samples["window"] = (0.0, 1.0)           # nothing inside
    assert reader(run) is None
    run = rings(fake_run(STEPS))
    run.samples.pop("engine_steps")              # not a serving run
    assert reader(run) is None


def test_silent_where_the_program_keeps_no_rings(monkeypatch):
    monkeypatch.setattr(ha, "_rings", lambda: None)
    run = fake_run(STEPS)
    assert all(reader(run) is None for reader in READERS)
    assert run.lines == []


def test_the_manifest_and_the_metric_files_agree_on_the_eight():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, (function, cells) in METRICS.items():
        with open(os.path.join(ROOT, "benchmarks", "metrics",
                               f"{name}.json")) as f:
            m = json.load(f)
        assert m["reader"] == f"benchmarks.readers.host_accounting:{function}"
        assert m["level"] == "per_layer"
        entry = listed[name]
        assert entry["workloads"] == cells
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == m[key], (name, key)
