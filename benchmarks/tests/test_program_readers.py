"""The readers of the program's own stamps, on hand-made records and a
synthetic device plane: a CPU run has no chip to be idle."""
import collections
import types

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import program

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6                       # nanoseconds
OFFSET = 7_000_000_123.0       # trace ns = host s x 1e9 + OFFSET


class Step(collections.namedtuple(
        "Step", "index kind begin scheduled dispatched fetched end "
                "n_active")):
    """Shaped like the program's ``StepRecord``."""

    def intervals(self):
        return list(zip(program.PHASES, self[2:6], self[3:7]))

    def phases(self):
        return {p: b - a for p, a, b in self.intervals()}


Launch = collections.namedtuple(
    "Launch", "program step called dispatched fetched request_id tokens "
              "radix_tokens")
Submit = collections.namedtuple("Submit", "request_id begin end")


def ev(plane, line, name, start, end):
    return (plane, line, name, float(start), float(end - start))


def step(index, kind, begin_ms, phases_ms):
    """A step beginning ``begin_ms`` after second 100 of the host's clock
    whose phases last ``phases_ms`` milliseconds."""
    stamps = [100.0 + begin_ms * 1e-3]
    for ms in phases_ms:
        stamps.append(stamps[-1] + ms * 1e-3)
    return Step(index, kind, *stamps, 16)


def to_trace(seconds):
    return seconds * 1e9 + OFFSET


# ten-millisecond steps: schedule 1, dispatch 2, fetch 6, emit 1; step 2
# ran a chunk beside its decode and is three times as long
STEPS = [step(0, "decode", 0, (1, 2, 6, 1)),
         step(1, "decode", 10, (1, 2, 6, 1)),
         step(2, "admit", 20, (21, 2, 6, 1)),
         step(3, "decode", 50, (1.5, 3, 7, 0.5)),
         step(4, "decode", 62, (1, 2, 6, 1))]


def traced_run(steps, launches=(), jitter_ns=0.0, window=None, submits=()):
    """A run whose trace holds the ``bench.engine_step`` twin of every
    step (entered 2 us before it, left 2 us after) and a device that is
    busy from the end of each dispatch phase until 1 ms before the end of
    the fetch phase."""
    lo = to_trace(steps[0].begin) - 1 * MS
    hi = to_trace(steps[-1].end) + 1 * MS
    events = [ev(HOST, "python", "bench.window", *(window or (lo, hi)))]
    spans = []
    for n, r in enumerate(steps):
        spans.append((r.begin - 1e-6, r.end + 1e-6, r.index))
        shift = jitter_ns if n == 1 else 0.0
        events.append(ev(HOST, "python", f"bench.engine_step:{r.index}",
                         to_trace(r.begin) - 3e3 + shift,
                         to_trace(r.end) + 3e3 + shift))
        events.append(ev(DEV, "XLA Ops", "fusion", to_trace(r.dispatched),
                         to_trace(r.fetched) - 1 * MS))
    run = types.SimpleNamespace(
        on_device=True, lines=[],
        spans=types.SimpleNamespace(by_name={"engine_step": spans}),
        samples={"window": (steps[0].begin - 1.0, steps[-1].end + 1.0),
                 "engine_steps": [], "engine_stats": {
                     "itl_estimate_ms": 8.0, "itl_p95_ms": 9.0}},
        trace={"events": events, "reduced": tr.reduce(events)})
    run.info = lambda kind, **values: run.lines.append((kind, values))
    program._rings = lambda: (list(steps), list(launches), list(submits))
    return run


@pytest.fixture(autouse=True)
def _restore_rings():
    rings = program._rings
    yield
    program._rings = rings


def test_the_offset_is_recovered_exactly_from_the_pairs():
    host = {i: (100.0 + i * 0.01, 100.008 + i * 0.01) for i in range(5)}
    traced = {i: (to_trace(t0) - 4e3, to_trace(t1) + 4e3)
              for i, (t0, t1) in host.items()}
    traced[9] = (0.0, 1.0)                     # no twin on the host
    offset, residual, pairs = program.clock_offset(host, traced)
    assert offset == pytest.approx(OFFSET, abs=1.0)
    assert residual == pytest.approx(0.0, abs=1.0) and pairs == 5
    assert program.clock_offset(host, {}) is None
    # a skew between entering and leaving does not cancel: it shows
    traced[2] = (traced[2][0], traced[2][1] + 80e3)
    offset, residual, _ = program.clock_offset(host, traced)
    assert offset == pytest.approx(OFFSET, abs=1.0)      # the median
    assert residual == pytest.approx(40e3, abs=1.0)


def test_pairs_that_disagree_by_more_than_50_us_are_refused():
    run = traced_run(STEPS, jitter_ns=60e3)
    assert program.device_idle_ms_host(run) is None
    assert program.device_idle_ms_fetch(run) is None
    kinds = [k for k, _ in run.lines]
    assert "program_clock" in kinds
    assert "idle_by_program_phase" not in kinds
    clock = dict(run.lines)["program_clock"]
    assert clock["residual_ns"] == pytest.approx(60e3, abs=1.0)
    # the host's own medians need no device clock
    assert program.engine_phase_ms_fetch(run) == pytest.approx(6.0)
    agree = traced_run(STEPS, jitter_ns=40e3)
    assert program.device_idle_ms_host(agree) is not None


def test_idle_lands_under_the_phase_that_covers_it():
    # a bucket prefill inside submit() just before step 0, and one
    # inside step 2 (which its schedule phase covers already)
    launches = [Launch("prefill:L256", None, 99.9995, 99.9996, 99.9999,
                       7, 200, 128),
                Launch("prefill:L256", 2, 100.021, 100.022, 100.040,
                       8, 200, 128)]
    submits = [Submit(7, 99.9993, 100.0)]       # 0.2 ms before its launch
    run = traced_run(STEPS, launches, submits=submits)
    # the device is idle all through schedule, dispatch and emit and for
    # the last millisecond of fetch, of every step
    assert program.device_idle_ms_host(run) == pytest.approx(
        (4 + 4 + 5 + 4) / 4)
    assert program.device_idle_ms_fetch(run) == pytest.approx(1.0)
    lines = dict(run.lines)
    split = lines["idle_by_program_phase"]["seconds"]
    assert split["schedule"] == pytest.approx((1 + 1 + 21 + 1.5 + 1) * 1e-3)
    assert split["dispatch"] == pytest.approx((2 + 2 + 2 + 3 + 2) * 1e-3)
    assert split["fetch"] == pytest.approx(5e-3)
    assert split["emit"] == pytest.approx((1 + 1 + 1 + 0.5 + 1) * 1e-3)
    # the steps follow one another without a gap: the only idle outside
    # them is the window's two margins of 1 ms, less the 0.7 ms that a
    # submit() took of the first, 0.4 of them its prefill
    assert split["prefill_in_submit"] == pytest.approx(0.4e-3)
    assert split["submit_host"] == pytest.approx(0.3e-3)
    assert split["outside"] == pytest.approx(1.3e-3)
    # the device starts with the fetch phase and ends 1 ms before it
    per_step = lines["idle_by_program_phase"]["decode_only_step_ms"]
    assert per_step["fetch_head"] == pytest.approx(0.0, abs=1e-6)
    assert per_step["fetch_tail"] == pytest.approx(1.0)
    assert per_step["length"] == pytest.approx((10 + 10 + 12 + 10) / 4)
    assert lines["idle_by_program_phase"]["decode_only_steps"] == 4
    assert lines["program_clock"]["pairs"] == 5
    assert lines["program_clock"]["residual_ns"] < 1.0


def test_a_prefill_inside_submit_takes_the_idle_between_steps():
    # 12 ms between the steps, 10 of them a bucket prefill in submit():
    # busy for its middle 8 ms
    steps = [step(0, "decode", 0, (1, 2, 6, 1)),
             step(1, "decode", 22, (1, 2, 6, 1))]
    launch = Launch("prefill:L256", None, 100.011, 100.012, 100.021, 3,
                    200, 128)
    run = traced_run(steps, [launch], submits=[Submit(3, 100.0105, 100.0215)])
    run.trace["events"].append(ev(DEV, "XLA Ops", "fusion",
                                  to_trace(100.012), to_trace(100.020)))
    run.trace["reduced"] = tr.reduce(run.trace["events"])
    assert program.device_idle_ms_host(run) == pytest.approx(4.0)
    split = dict(run.lines)["idle_by_program_phase"]
    assert split["seconds"]["prefill_in_submit"] == pytest.approx(2e-3)
    # the call began 0.5 ms before its launch and returned 0.5 ms after
    assert split["seconds"]["submit_host"] == pytest.approx(1e-3)
    # 0.5 ms before and after the call, and the window's two margins
    assert split["seconds"]["outside"] == pytest.approx(3e-3)
    assert split["outside_share"] == pytest.approx(3 / (3 + 1 + 2 + 10))


def test_a_step_half_outside_the_traced_window_is_clipped_and_not_a_sample():
    lo = to_trace(STEPS[0].begin) - 1 * MS
    hi = to_trace(STEPS[4].scheduled)          # closes inside step 4
    run = traced_run(STEPS, window=(lo, hi))
    # steps 0, 1 and 3 are the decode-only steps wholly inside
    assert program.device_idle_ms_host(run) == pytest.approx(
        (4 + 4 + 5) / 3)
    split = dict(run.lines)["idle_by_program_phase"]
    assert split["decode_only_steps"] == 3
    # of step 4 only its schedule phase is inside
    assert split["seconds"]["schedule"] == pytest.approx(25.5e-3)
    assert split["seconds"]["dispatch"] == pytest.approx(9e-3)


def test_medians_are_over_decode_only_steps_inside_the_window():
    launches = [Launch("prefill:L256", None, 100.001, 100.002, 100.021,
                       1, 200, 128),
                Launch("prefill:L512", 2, 100.021, 100.0215, 100.051,
                       2, 400, 128),
                Launch("prefill:L256", None, 100.060, 100.061, 100.085,
                       3, 200, 128),
                Launch("chunk", 2, 100.021, 100.022, None, 4, 256, 0),
                Launch("decode", 3, 100.0515, 100.0545, 100.0615, None,
                       16, 0),
                # called before the window opened: left out
                Launch("prefill:L256", None, 99.0, 99.1, 99.9, 5, 200, 0)]
    run = traced_run(STEPS, launches)
    run.samples["window"] = (100.0095, 100.0725)   # steps 1 .. 4
    assert program.engine_phase_ms_schedule(run) == pytest.approx(1.0)
    assert program.engine_phase_ms_dispatch(run) == pytest.approx(2.0)
    assert program.engine_phase_ms_fetch(run) == pytest.approx(6.0)
    assert program.engine_phase_ms_emit(run) == pytest.approx(1.0)
    # bucket prefills called in the window, in submit() or in a step:
    # 30 and 25 ms (the third's token came after the window closed, its
    # call did not)
    assert program.prefill_ms_bucket(run) == pytest.approx(27.5)
    records = dict(run.lines)["program_records"]
    assert records == {"steps": 4, "decode_only_steps": 3, "launches": 4,
                       "submits": 0, "submit_ms_median": 0.0}
    assert dict(run.lines)["program_itl"]["itl_estimate_ms"] == 8.0
    # step 3 alone: its own phases
    run = traced_run(STEPS[3:4])
    assert program.engine_phase_ms_schedule(run) == pytest.approx(1.5)
    assert program.engine_phase_ms_emit(run) == pytest.approx(0.5)
    assert program.prefill_ms_bucket(run) is None


READERS = [program.engine_phase_ms_schedule, program.engine_phase_ms_dispatch,
           program.engine_phase_ms_fetch, program.engine_phase_ms_emit,
           program.prefill_ms_bucket, program.device_idle_ms_host,
           program.device_idle_ms_fetch]


@pytest.mark.parametrize("reader", READERS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("why", ["off_device", "empty_rings", "no_rings",
                                 "train_cell"])
def test_every_reader_is_silent_without_its_data(reader, why):
    run = traced_run(STEPS)
    if why == "off_device":
        run.on_device = False
    elif why == "empty_rings":
        program._rings = lambda: ([], [], [])
    elif why == "no_rings":
        program._rings = lambda: None
    else:
        del run.samples["engine_steps"]
    assert reader(run) is None
    assert not any(k in ("program_clock", "idle_by_program_phase",
                         "program_records") for k, _ in run.lines)


@pytest.mark.parametrize("reader", READERS[5:], ids=lambda f: f.__name__)
def test_the_device_readers_are_silent_without_a_trace(reader):
    run = traced_run(STEPS)
    run.trace = None
    assert reader(run) is None
    assert program.engine_phase_ms_fetch(run) == pytest.approx(6.0)
    run = traced_run(STEPS)
    run.trace = {"events": [], "reduced": None}    # no device plane
    assert reader(run) is None


def test_the_programs_rings_are_found_through_its_accessor():
    """Against the program itself: the accessor exists, and an engine's
    ``EngineMetrics`` shows up with records the readers can use."""
    from paddle_tpu.serving import metrics

    m = metrics.EngineMetrics()
    m.mark_step(metrics.StepRecord(0, "decode", 1.0, 1.001, 1.003, 1.009,
                                   1.010, 16))
    m.mark_launch(metrics.LaunchRecord("decode", 0, 1.001, 1.003, 1.009,
                                       None, 16, 0))
    m.mark_submit(metrics.SubmitRecord(5, 0.9, 0.95))
    steps, launches, submits = program._rings()
    assert any(r.request_id == 5 and r.begin == 0.9 for r in submits)
    mine = [r for r in steps if r.begin == 1.0]
    assert len(mine) == 1 and mine[0].phases()["fetch"] == pytest.approx(
        0.006)
    assert program.phase_ms(mine, "dispatch") == pytest.approx(2.0)
    assert any(r.program == "decode" and r.called == 1.001
               for r in launches)
    assert tuple(metrics.PHASES) == program.PHASES
