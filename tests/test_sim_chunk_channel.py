"""tools/sim_chunk_channel.py: the closed-loop replay of a serve traffic
file through the engine's one-chunk-a-step channel."""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "sim_chunk_channel", os.path.join(ROOT, "tools", "sim_chunk_channel.py"))
sim = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sim)

COST = {"chunk_ms": 40.0, "chunk_ms_per_k": 5.0, "decode_ms": 10.0,
        "decode_ms_per_row": 0.16}


def _traffic(sigma):
    lengths = {"distribution": "lognormal", "sigma": sigma}
    return {"engine": {"prefill_chunk": 512}, "clients": 8,
            "shared_prefix_tokens": 1024, "pool": 16, "strata": 8,
            "warmup_requests": 8,
            "turn_tokens": {**lengths, "median": 1536, "min": 512,
                            "max": 4096},
            "output_tokens": {**lengths, "median": 40, "min": 8, "max": 128}}


def test_a_seed_orders_the_work_and_nothing_else():
    a = sim.one_run(_traffic(0.5), 7, COST, 20.0)
    assert a == sim.one_run(_traffic(0.5), 7, COST, 20.0)
    assert a != sim.one_run(_traffic(0.5), 8, COST, 20.0)
    rate, gap_p95, steps = a
    # a step is a decode of at least decode_ms, with a chunk of at least
    # chunk_ms in some, and at most 8 rows emit a token in it
    assert steps <= 20.0 / 0.010 + 1 and 10.0 < gap_p95
    assert 0 < rate <= 8 * steps / 20.0


@pytest.mark.parametrize("narrow,wide", [(0.1, 0.7)])
def test_wider_lengths_spread_the_rate_more(narrow, wide):
    def over_seeds(sigma):
        return sim.spread([sim.one_run(_traffic(sigma), s, COST, 20.0)[0]
                           for s in range(12)])
    assert over_seeds(narrow) < over_seeds(wide)


def test_the_command_prints_one_line_a_setting(tmp_path, capsys):
    path = tmp_path / "t.json"
    path.write_text(json.dumps(_traffic(0.5)))
    assert sim.main([str(path), "--seeds", "6", "--seconds", "10",
                     "--sigma", "0.2", "0.6"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["sigma"] for ln in lines] == [[0.2, 0.2], [0.6, 0.6]]
    assert all(ln["sets_of_six"] == 1 for ln in lines)
