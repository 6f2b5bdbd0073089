"""Profiler, int8 quantization, StableHLO export."""
import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler


def test_profiler_timer_and_scheduler():
    sched = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(5)]
    assert states[0] == profiler.ProfilerState.CLOSED
    assert states[1] == profiler.ProfilerState.READY
    assert states[2] == profiler.ProfilerState.RECORD
    assert states[3] == profiler.ProfilerState.RECORD_AND_RETURN
    assert states[4] == profiler.ProfilerState.CLOSED

    p = profiler.Profiler(timer_only=True)
    p.start()
    x = paddle.ones([64, 64])
    for _ in range(3):
        with profiler.RecordEvent("matmul_step"):
            y = x @ x
        p.step()
    p.stop()
    assert len(p._step_times) == 3
    assert "steps: 3" in p.step_info()


def test_int8_quant_roundtrip():
    from paddle_tpu.nn.quant import dequantize_int8, quantize_int8

    rng = np.random.default_rng(0)
    w = paddle.to_tensor(rng.normal(size=(64, 32)).astype(np.float32))
    q, s = quantize_int8(w, axis=0)
    assert str(q.dtype).endswith("int8")
    wd = dequantize_int8(q, s)
    err = np.abs(wd.numpy() - w.numpy()).max()
    # worst-case per-channel quant error = scale/2
    assert err <= np.abs(w.numpy()).max() / 127.0, err


def test_int8_linear_matches_fp_within_quant_error():
    from paddle_tpu.nn.quant import Int8Linear

    paddle.seed(0)
    lin = nn.Linear(32, 16)
    qlin = Int8Linear.from_linear(lin)
    rng = np.random.default_rng(1)
    x = paddle.to_tensor(rng.normal(size=(4, 32)).astype(np.float32))
    y_fp = lin(x).numpy()
    y_q = qlin(x).numpy()
    rel = np.abs(y_q - y_fp).max() / (np.abs(y_fp).max() + 1e-9)
    assert rel < 0.02, f"quantized output off by {rel:.4f}"


def test_quantize_model_swaps_linears():
    from paddle_tpu.nn.quant import Int8Linear, quantize_model

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    x = paddle.to_tensor(
        np.random.default_rng(2).normal(size=(2, 8)).astype(np.float32))
    y_fp = model(x).numpy()
    quantize_model(model)
    swapped = [m for _, m in model.named_sublayers()
               if isinstance(m, Int8Linear)]
    assert len(swapped) == 2
    y_q = model(x).numpy()
    rel = np.abs(y_q - y_fp).max() / (np.abs(y_fp).max() + 1e-9)
    assert rel < 0.05


def test_quantize_int8_stochastic_tpu():
    """pltpu PRNG has no CPU lowering; runs only on real TPU."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        pytest.skip("needs TPU (pallas PRNG has no CPU interpret support)")
    from paddle_tpu.nn.quant import quantize_int8_stochastic

    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    q, s = quantize_int8_stochastic(w, seed=7)
    assert q.dtype == jnp.int8
    wd = np.asarray(q, dtype=np.float32) * float(s[0, 0])
    # stochastic rounding: unbiased, error bounded by one scale step
    assert np.abs(wd - np.asarray(w)).max() <= float(s[0, 0]) + 1e-6


def test_stochastic_round_bf16_tpu():
    """fp32->bf16 stochastic rounding:
    target dtype gated to MOSAIC_SR_TARGETS, output lands on one of the
    two bracketing bf16 values."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.quant import MOSAIC_SR_TARGETS, stochastic_round

    w32 = np.random.default_rng(5).normal(size=(32, 128)).astype(np.float32)
    with pytest.raises(ValueError):
        stochastic_round(jnp.asarray(w32), jnp.int8)
    assert "bfloat16" in MOSAIC_SR_TARGETS
    if jax.default_backend() != "tpu":
        pytest.skip("needs TPU (pallas PRNG has no CPU interpret support)")
    r = stochastic_round(jnp.asarray(w32), jnp.bfloat16, seed=7)
    assert r.dtype == jnp.bfloat16
    rf = np.asarray(r, dtype=np.float32)
    # each element must equal its value truncated to bf16 or one ulp up
    lo = jnp.asarray(w32).astype(jnp.bfloat16)
    err = np.abs(rf - w32)
    ulp = np.abs(np.asarray(lo, np.float32)) * 2.0 ** -7 + 1e-30
    assert (err <= ulp + 1e-6).all()


def test_stablehlo_export_roundtrip():
    import jax

    paddle.seed(0)
    layer = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    layer.eval()
    with tempfile.TemporaryDirectory() as td:
        path = paddle.onnx.export(
            layer, os.path.join(td, "model"),
            input_spec=[paddle.static.InputSpec([2, 8], "float32")],
            format="stablehlo")
        assert os.path.exists(path)
        with open(path, "rb") as f:
            rt = jax.export.deserialize(f.read())
        x = np.random.default_rng(4).normal(size=(2, 8)).astype(np.float32)
        params = {k: p._data for k, p in dict(
            layer.named_parameters()).items()}
        out = rt.call(params, x)
        ref = layer(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_dlpack_torch_roundtrip():
    import numpy as np
    import pytest

    torch = pytest.importorskip("torch")
    import paddle_tpu as paddle
    from paddle_tpu.utils.dlpack import from_dlpack, to_dlpack

    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
    t = torch.utils.dlpack.from_dlpack(to_dlpack(x))
    assert tuple(t.shape) == (3, 4) and float(t.sum()) == 66.0
    # capsule path (the reference API's currency)
    back = from_dlpack(torch.utils.dlpack.to_dlpack(t * 2))
    np.testing.assert_allclose(np.asarray(back._data).sum(), 132.0)
    # protocol-object path
    back2 = from_dlpack(t * 3)
    np.testing.assert_allclose(np.asarray(back2._data).sum(), 198.0)


def test_download_helpers_offline():
    import os

    from paddle_tpu.utils.download import get_weights_path_from_url

    # file:// URLs exercise the cache path without network
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        src = os.path.join(td, "w.bin")
        with open(src, "wb") as f:
            f.write(b"weights")
        p = get_weights_path_from_url("file://" + src)
        with open(p, "rb") as f:
            assert f.read() == b"weights"
