"""chip_smoke.py's phases, rehearsed small on the virtual CPU devices.

The chip run is the proof; this keeps its control flow, its checks and
its four-device meshes from breaking between chip runs. Kernels compile
in interpret mode here (tests/test_tpu_compile.py compiles them for the
chip), so the kernel phase — compiled kernels against references — has
no rehearsal.
"""
import dataclasses
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.text.models.llama import LLAMA_TINY  # noqa: E402

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32")
SIZES = dict(
    train=dict(depth=2, batch=2, seqlen=64, steps=4, warmup=2),
    serve=dict(depth=2, max_len=128, prefill_chunk=32, prefix=16,
               tails=(3, 3, 10), solo=5, long=30, max_new=4),
)
# another max_len, so that this phase's engines build programs of their
# own: the count of programs is part of what run_server checks
FOUR = dict(
    train=dict(SIZES["train"], hybrid=dict(sharding=2, tp=2)),
    serve=dict(SIZES["serve"], max_len=256, tp=4),
)


@pytest.fixture(scope="module")
def builds():
    return chip_smoke.Builds()


def test_trainer_phase(builds):
    cfg, train = chip_smoke.cut(CFG, SIZES["train"])
    info = chip_smoke.run_trainer(cfg, **train, seed=0, builds=builds,
                                  platform="cpu",
                                  devices=jax.devices()[:1])
    assert info["losses"][-1] < info["losses"][0]
    assert info["programs_built_after_warmup"] == 0
    assert info["devices"] == 1 and not info["collectives"]


def test_server_phase(builds):
    cfg, serve = chip_smoke.cut(CFG, SIZES["serve"])
    info = chip_smoke.run_server(cfg, **serve, seed=0, builds=builds,
                                 platform="cpu")
    assert [e["engine"] for e in info["engines"]] == ["greedy", "sampled"]
    # float32 on the CPU: identity is exact, no near-tie to explain
    assert all(e["vs_generate"]["equal"] for e in info["engines"])
    # the platform decides: no kernel in a decode program off the TPU
    assert not info["engines"][0]["kernel_in_decode"]


def test_four_device_phase(builds):
    phases = dict(chip_smoke.run_four_devices(
        CFG, FOUR, seed=0, builds=builds, platform="cpu",
        devices=jax.devices()[:4]))
    assert list(phases) == ["trainer.hybrid", "trainer.one_device",
                            "server.tp"]
    assert phases["trainer.hybrid"]["mesh"] == {"sharding": 2, "tp": 2}
    assert phases["trainer.hybrid"]["collectives"]
    tp = phases["server.tp"]["engines"][-1]
    assert tp["engine"] == "tp=4"
    assert tp["vs_generate"]["equal"] and tp["vs_one_device_engine"]["equal"]


def test_a_failed_check_raises(builds):
    cfg, train = chip_smoke.cut(CFG, SIZES["train"])
    with pytest.raises(chip_smoke.SmokeFailure, match="lives on"):
        chip_smoke.run_trainer(cfg, **train, seed=0, builds=builds,
                               platform="tpu", devices=jax.devices()[:1])


@pytest.mark.parametrize("steps,accepted", [(0, True), (1, True), (2, False)])
def test_a_parting_is_accepted_only_as_a_near_tie(steps, accepted):
    """Logits of size 8..16 sit on a bf16 grid of 1/16: the reference's
    token may score a tie or one step above the engine's, no more."""
    import types

    import jax.numpy as jnp
    import numpy as np

    z = np.zeros((4, 8), np.float32)
    z[1, 0] = 8.0                           # index 0: both say token 0
    z[2, 1], z[2, 2] = 8.0, 8.0 + steps / 16    # index 1: engine 1, ref 2

    def model(ids):
        return types.SimpleNamespace(_data=jnp.asarray(z[None]))

    def compare(**kw):
        return chip_smoke._compare(model, [np.array([5, 6], np.int32)],
                                   [[0, 1]], [[0, 2]], "fake", **kw)

    if accepted:
        (tie,) = compare()["near_ties"]
        assert tie["index"] == 1 and tie["gap"] == steps / 16
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="below the best"):
            compare()
    with pytest.raises(chip_smoke.SmokeFailure):     # sampled: no excuse
        compare(exact=True)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_to_run_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    assert '"ok": true' not in capsys.readouterr().out
