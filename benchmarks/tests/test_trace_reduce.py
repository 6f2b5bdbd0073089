"""The reduction on synthetic events: what a recorded trace would show,
without a trace in the tree."""
import pytest

from benchmarks import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, end):
    return (plane, line, name, float(start), float(end - start))


@pytest.fixture
def events():
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.loader:0", 100, 200),
        ev(HOST, "python", "bench.dispatch:0", 200, 320),
        ev(HOST, "python", "bench.engine_step:3", 600, 900),
        ev(HOST, "python", "bench.submit", 580, 950),
        ev(HOST, "python", "PjitFunction(step)", 210, 300),
        # operations that overlap, touch and nest
        ev(DEV, "XLA Ops", "fusion.1", 0, 100),
        ev(DEV, "XLA Ops", "fusion.2", 50, 150),
        ev(DEV, "XLA Ops", "fusion.1", 250, 400),
        ev(DEV, "XLA Ops", "copy.3", 300, 350),
        ev(DEV, "XLA Ops", "fusion.2", 400, 500),
        ev(DEV, "XLA Ops", "fusion.1", 800, 1100),
        # the module's line spans its operations: not counted again
        ev(DEV, "XLA Modules", "jit_step", 0, 1100),
    ]


def test_union_merges_overlapping_touching_and_nested():
    assert tr.union([(50, 150), (0, 100), (250, 400), (300, 350),
                     (400, 500), (7, 7)]) == [(0, 150), (250, 500)]
    assert tr.covered([(0, 150), (250, 500)], 100, 300) == 100


def test_busy_window_and_idle_share(events):
    r = tr.reduce(events)
    # busy inside [0, 1000]: 0-150, 250-500, 800-1000
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.4)


def test_top_operations_by_self_time_inside_the_window(events):
    ops = dict(tr.reduce(events)["device_ops"])
    # fusion.1: 0-100 less the 50 that fusion.2 overlaps, 250-400 less the
    # copy nested in it; its third run ends after the window: left out
    assert ops["fusion.1"] == pytest.approx((50 + 100) * 1e-9)
    assert ops["copy.3"] == pytest.approx(50e-9)
    assert ops["fusion.2"] == pytest.approx(200e-9)
    assert "jit_step" not in ops
    assert tr.reduce(events, top=1)["device_ops"] == [
        ["fusion.2", pytest.approx(200e-9)]]


def test_a_loop_is_charged_only_what_its_body_leaves():
    ops = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 40, 90),
           ("inner", 50, 60), ("after", 100, 120)]
    own = {n: t for n, _, _, t in tr.self_times(ops)}
    assert own == {"while": 20, "body.a": 30, "body.b": 40, "inner": 10,
                   "after": 20}


def test_gaps_carry_the_innermost_host_annotation(events):
    r = tr.reduce(events)
    # gaps: 150-250 (mid 200: loader ends, dispatch begins -> the shorter
    # one that covers 200) and 500-800 (mid 650: engine_step inside submit)
    assert r["idle_gaps"][0] == ["engine_step", pytest.approx(300e-9)]
    assert r["idle_gaps"][1][1] == pytest.approx(100e-9)
    assert r["idle_gaps"][1][0] in ("loader", "dispatch")
    assert dict(r["idle_by_label"])["engine_step"] == pytest.approx(300e-9)


def test_a_gap_no_annotation_covers_is_unannotated():
    events = [ev(DEV, "XLA Ops", "a", 0, 10), ev(DEV, "XLA Ops", "a", 90, 100)]
    assert tr.reduce(events)["idle_gaps"] == [["unannotated",
                                               pytest.approx(80e-9)]]


def test_spans_are_found_by_name_and_index(events):
    assert tr.spans_named(events, "bench.engine_step") == {3: (600, 900)}
    assert tr.spans_named(events, "bench.loader") == {0: (100, 200)}
    assert tr.spans_named(events, "bench.submit") == {}


def test_busy_between_and_two_devices(events):
    second = [ev("/device:TPU:1", "XLA Ops", "fusion.1", 0, 1000)]
    r = tr.reduce(events + second)
    assert r["busy_s"] == pytest.approx((600 + 1000) / 2 * 1e-9)
    assert tr.busy_between(tr.reduce(events), 600, 900) == 100


def test_a_plane_without_an_ops_line_counts_every_line():
    events = [ev(DEV, "Steps", "s", 0, 50), ev(DEV, "other", "o", 40, 80)]
    assert tr.reduce(events)["busy_s"] == pytest.approx(80e-9)


def test_no_device_operation_gives_nothing():
    assert tr.reduce([ev(HOST, "python", "bench.window", 0, 10)]) is None
    assert tr.reduce([]) is None


def test_an_operations_name_is_cut_to_what_tells_it_apart():
    hlo = ("%fusion.307 = (bf16[4096,32768]{1,0:T(8,128)(2,1)}, "
           "f32[4096,32768]{1,0:T(8,128)}) fusion(f32[]{:T(128)S(6)} "
           "%sub.212), kind=kOutput, calls=%fused_computation.425")
    assert tr.short_name(hlo) == (
        "fusion.307 = (bf16[4096,32768], f32[4096,32768]) fusion(f32[] "
        "%sub.212), kind=kOutput, calls=%fused_computation.425")
    assert len(tr.short_name(hlo * 9)) == 160
    assert tr.short_name("while.3") == "while.3"


def test_layout_counts_events(events):
    assert tr.layout(events)[DEV] == {"XLA Ops": 6, "XLA Modules": 1}


def test_the_adapter_reads_what_the_profiler_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fetch:7"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    events = tr.events_from_xplane(tr.find_xplane(str(tmp_path)))
    assert 7 in tr.spans_named(events, "bench.fetch")
    lo, hi = tr.window_of(events)
    assert hi > lo
    # the CPU has no device plane: nothing is reduced, nothing reported
    assert tr.reduce(events) is None
