"""``decode_hbm_roofline_pct``: the arithmetic against the two serve
cells' published sizes, and the reader on what a run keeps."""
import json
import os
import types

import pytest

from benchmarks import arithmetic_decode as ad, arithmetic_moe
from benchmarks.readers import decode_roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_a_dense_step_reads_its_weights_and_the_lines_its_rows_see():
    c = _config("deepseek-llm-7b")
    # 6 layers of 4 x 4096 x 4096 + 3 x 4096 x 11008, a 102400-row head
    assert ad.weight_bytes(c) == 2 * (6 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
                                      + 4096 * 102400)
    assert ad.line_bytes(c) == 2 * 32 * 128 * 2
    # no window layer: the cut count is never read
    assert ad.kv_bytes(c, 7000, 1) == 6 * 7000 * 16384
    assert ad.decode_step_bytes(c, 7000, 1) \
        == ad.weight_bytes(c) + 6 * 7000 * 16384


def test_a_routed_step_reads_its_picked_experts_and_cuts_window_layers():
    c = _config("mellum2-12b-a2.5b")
    hit = 8 * 40.0
    attention = 8 * 2 * (2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128)
    assert ad.weight_bytes(c, hit) == attention \
        + arithmetic_moe.routed_decode_bytes(c, hit) + 2 * 2304 * 98304
    # 6 window layers see the cut lines, 2 full layers all of them
    assert ad.kv_bytes(c, 40000, 16000) \
        == (2 * 40000 + 6 * 16000) * 2 * 4 * 128 * 2


def test_mixed_feed_forwards_are_refused():
    c = dict(_config("mellum2-12b-a2.5b"),
             mlp_layer_types=["dense"] + ["sparse"] * 7)
    with pytest.raises(ValueError, match="not counted"):
        ad.weight_bytes(c, 1.0)


def _run(config, stats, ms):
    run = types.SimpleNamespace(
        config=config, samples={"engine_stats": stats},
        device_kind="TPU v5 lite", said=[])
    run.info = lambda kind, **values: run.said.append((kind, values))
    return run


def test_the_reader_divides_the_least_time_by_the_steps_device_time(
        monkeypatch):
    c = _config("deepseek-llm-7b")
    stats = {"decode_lines_seen": {"calls": 10, "lines": 70000,
                                   "in_window": 70000}}
    monkeypatch.setattr(decode_roofline.device, "decode_device_ms",
                        lambda run: 8.0)
    run = _run(c, stats, 8.0)
    least_ms = ad.decode_step_bytes(c, 7000.0, 7000.0) / 819e9 * 1e3
    assert decode_roofline.decode_hbm_roofline_pct(run) \
        == pytest.approx(100 * least_ms / 8.0)
    (kind, said), = run.said
    assert kind == "decode_step_bytes" and said["lines_seen_a_step"] == 7000


@pytest.mark.parametrize("stats", [
    {}, {"decode_lines_seen": {"calls": 0, "lines": 0, "in_window": 0}}],
    ids=["a_program_without_the_count", "no_decode_call"])
def test_the_reader_is_silent_where_there_is_nothing_to_read(monkeypatch,
                                                             stats):
    monkeypatch.setattr(decode_roofline.device, "decode_device_ms",
                        lambda run: 8.0)
    assert decode_roofline.decode_hbm_roofline_pct(
        _run(_config("deepseek-llm-7b"), stats, 8.0)) is None


def test_the_reader_is_silent_without_a_trace():
    run = _run(_config("deepseek-llm-7b"), {}, None)
    run.trace = None
    assert decode_roofline.decode_hbm_roofline_pct(run) is None
