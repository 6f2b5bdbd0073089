"""The device readers on a synthetic trace, and the host readers on
hand-made samples: a CPU run can exercise neither on real data."""
import types

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import device, end_to_end, host

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, end):
    return (plane, line, name, float(start), float(end - start))


def traced(events, **samples):
    return types.SimpleNamespace(
        trace={"events": events, "reduced": tr.reduce(events)},
        samples=samples)


def test_train_step_device_ms_counts_whole_steps_between_fetches():
    ms = 1e6
    events = [ev(HOST, "python", "bench.window", 0, 100 * ms)]
    # a step is 8 ms busy and 2 ms idle; the loss of step i is fetched
    # when the device has finished step i
    for i in range(10):
        events.append(ev(DEV, "XLA Ops", "fusion", i * 10 * ms,
                         (i * 10 + 8) * ms))
        events.append(ev(HOST, "python", f"bench.fetch:{i}",
                         (i * 10 + 1) * ms, (i * 10 + 8) * ms))
    assert device.train_step_device_ms(traced(events)) == pytest.approx(8.0)


def test_decode_device_ms_takes_decode_only_steps():
    ms = 1e6
    events = [ev(HOST, "python", "bench.window", 0, 100 * ms),
              # step 5 decodes: 6 ms busy inside a 10 ms span
              ev(HOST, "python", "bench.engine_step:5", 10 * ms, 20 * ms),
              ev(DEV, "XLA Ops", "fusion", 11 * ms, 17 * ms),
              # step 6 ran a chunk too: left out
              ev(HOST, "python", "bench.engine_step:6", 20 * ms, 50 * ms),
              ev(DEV, "XLA Ops", "fusion", 21 * ms, 49 * ms),
              # step 7 decodes: 4 ms busy
              ev(HOST, "python", "bench.engine_step:7", 50 * ms, 60 * ms),
              ev(DEV, "XLA Ops", "fusion", 52 * ms, 56 * ms)]
    steps = [(0, 0, "decode", 1)] * 6 + [(0, 0, "admit", 1),
                                          (0, 0, "decode", 1)]
    run = traced(events, engine_steps=steps)
    assert device.decode_device_ms(run) == pytest.approx(5.0)


def test_device_readers_return_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, samples={})
    assert device.train_step_device_ms(run) is None
    assert device.decode_device_ms(run) is None


def test_host_and_end_to_end_readers_on_hand_made_samples():
    steps = [(0.0, 0.010, "decode", 4), (0.0, 0.030, "admit", 4),
             (0.0, 0.012, "decode", 4), (0.0, 0.050, "admit", 4),
             (0.0, 0.014, "decode", 4)]
    requests = [{"submit": 0.5, "token_times": [1.0, 1.5, 2.5]}]
    run = types.SimpleNamespace(
        setup_s=3.0, counters={"programs_built": 7, "window_compiles": 0},
        spans=types.SimpleNamespace(by_name={"loader": [
            (0.5, 0.9, 0), (1.0, 1.002, 1), (2.0, 2.004, 2)]}),
        samples={"window": (1.0, 3.0), "engine_steps": steps,
                 "first_step": 1, "requests": requests,
                 "engine_counters": {"occupancy_sum": 7.5, "samples": 10}})
    assert host.engine_step_ms_decode(run) == pytest.approx(13.0)
    assert host.engine_step_ms_admit(run) == pytest.approx(40.0)
    assert host.batch_occupancy(run) == pytest.approx(75.0)
    assert host.loader_wait_ms(run) == pytest.approx(3.0)   # in the window
    assert host.window_compiles_serve(run) == 0
    assert host.window_compiles_train(run) is None
    assert end_to_end.serve_tokens_per_s(run) == pytest.approx(1.5)
    assert end_to_end.itl_p95_ms(run) == pytest.approx(975.0)
    assert end_to_end.train_tokens_per_s(run) is None
    train = types.SimpleNamespace(samples={
        "window": (0.0, 2.0), "steps": 4, "tokens_per_step": 100})
    assert end_to_end.train_tokens_per_s(train) == pytest.approx(200.0)
    assert end_to_end.serve_tokens_per_s(train) is None
