"""Weight-only int8 quantization.

Reference: paddle/nn/quant + incubate weight_only_linear (CUDA int8/int4
GEMM epilogues). TPU-native form: weights stored int8 with per-output-
channel fp scales; the forward dequantizes right at the matmul so XLA fuses
scale multiplication into the MXU epilogue (int8 VMEM residency halves/
quarters HBM traffic — the win weight-only quant is for). A pallas
stochastic-rounding quantizer covers on-device conversion.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...tensor import Tensor, apply
from ..layer_base import Layer
from ..layer.common import Linear

__all__ = ["quantize_int8", "dequantize_int8", "Int8Linear",
           "quantize_model", "quantize_int8_stochastic",
           "stochastic_round", "MOSAIC_SR_TARGETS",
           "FakeQuantAbsMax", "FakeQuantMovingAverageAbsMax",
           "FakeQuantChannelWiseAbsMax", "QuantizedLinear",
           "QuantizedConv2D", "ImperativeQuantAware",
           "PostTrainingQuantization", "fake_quant_dequant"]


def _quant_raw(w, axis=-1):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-10)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_int8(w, axis: int = -1):
    """Per-channel symmetric int8: returns (int8 Tensor, fp32 scale)."""
    if isinstance(w, Tensor):
        q, s = _quant_raw(w._data, axis)
        return Tensor(q), Tensor(s)
    return _quant_raw(w, axis)


def dequantize_int8(q, scale, dtype="float32"):
    f = lambda q, s: q.astype(dtype) * s.astype(dtype)
    if isinstance(q, Tensor):
        return apply(f, q, scale)
    return f(q, scale)


# float targets Mosaic's stochastic_round lowering accepts; every other
# narrowing conversion inside a kernel must route around it (fp32→int8
# direct casts get rewritten onto that lowering by current libtpu and die
# with "Only bfloat16, float8_* ... are supported as target dtypes")
MOSAIC_SR_TARGETS = ("bfloat16", "float8_e5m2", "float8_e4m3fn",
                     "float8_e4m3b11fnuz")


def _row_blocked(kernel, x, scalars, out_dtype, interpret):
    """Run an elementwise PRNG kernel over [rows, cols] fp32 ``x`` on a
    grid of row blocks of about 1 MB (whole arrays in VMEM stop fitting
    at a [4096, 4096] weight). ``kernel(x_ref, *scalar_refs, o_ref)``;
    ``scalars`` ride in SMEM. Blocks are multiples of 32 rows, the int8
    sublane tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, cols = x.shape
    br = max(32, (1 << 20) // (4 * cols) // 32 * 32)
    if br >= rows:
        br = rows
    block = pl.BlockSpec((br, cols), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[block] + [pl.BlockSpec(memory_space=pltpu.SMEM)
                            for _ in scalars],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, *scalars)


def _block_random_bits(seed_ref, shape):
    """int32 PRNG words for this grid step's block: the stream is seeded
    by (seed, block index) so blocks draw independent bits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.int32)


def stochastic_round(x, dtype=jnp.bfloat16, seed: int = 0,
                     interpret: bool = False):
    """fp32 → low-precision-float stochastic rounding (pallas PRNG).

    The target dtype is gated to :data:`MOSAIC_SR_TARGETS`; for bf16 the
    rounding is the classic add-uniform-to-discarded-mantissa-bits
    construction (int ops + bitcasts only, so Mosaic never sees an
    unsupported narrowing cast)."""
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.dtype(dtype)
    if dt.name not in MOSAIC_SR_TARGETS:
        raise ValueError(
            f"stochastic_round target {dt.name!r} unsupported; Mosaic "
            f"accepts {MOSAIC_SR_TARGETS} (integer targets: use "
            "quantize_int8_stochastic, which rounds in fp32)")
    if dt != jnp.bfloat16:
        raise NotImplementedError(
            "only the bf16 target is implemented on this backend")

    def kernel(x_ref, seed_ref, o_ref):
        bits = _block_random_bits(seed_ref, x_ref.shape)
        # add U[0, 2^16) to the 16 mantissa bits bf16 truncation drops:
        # carries propagate into the kept bits with probability equal to
        # the dropped fraction — exactly stochastic rounding to bf16
        u16 = jax.lax.shift_right_logical(bits, 16)
        xi = pltpu.bitcast(x_ref[:], jnp.int32)
        rounded = xi + u16
        kept = jax.lax.shift_left(
            jax.lax.shift_right_logical(rounded, 16), 16)
        # emit fp32 with zeroed low mantissa: the bf16 cast outside the
        # kernel is then exact (no second rounding, and no narrowing
        # Mosaic has to reroute)
        o_ref[:] = pltpu.bitcast(kept, jnp.float32)

    out = _row_blocked(kernel, x.astype(jnp.float32),
                       [jnp.asarray([seed], dtype=jnp.int32)],
                       jnp.float32, interpret)
    return out.astype(jnp.bfloat16)


def quantize_int8_stochastic(w, seed: int = 0, interpret: bool = False):
    """On-device int8 quantization with stochastic rounding (pallas PRNG).

    w: [rows, cols] raw array; per-tensor scale. Returns (int8, scale[1,1]).
    """
    w = w.astype(jnp.float32)
    # per-tensor: the max spans every block, so it is taken before the grid
    scale = jnp.maximum(jnp.max(jnp.abs(w)) / 127.0, 1e-10).reshape(1, 1)

    def kernel(x_ref, seed_ref, s_ref, q_ref):
        scaled = x_ref[:] / s_ref[0, 0]
        # Mosaic's stochastic_round primitive only targets float dtypes
        # (MOSAIC_SR_TARGETS); integer stochastic rounding is floor(x+u)
        # with u ~ U[0,1): E[q] == x. Keep the PRNG word in int32 lanes
        # (shift_right_logical, no uint casts) and narrow the result via
        # fp32 → int32 → int8 — current libtpu rewrites both unsigned
        # converts and direct fp32→int8 truncation onto the
        # stochastic_round lowering, which rejects integer targets.
        bits = _block_random_bits(seed_ref, scaled.shape)
        u = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) \
            * (1.0 / (1 << 24))
        q = jnp.floor(scaled + u)
        q32 = jnp.clip(q, -127.0, 127.0).astype(jnp.int32)
        q_ref[:] = q32.astype(jnp.int8)

    q = _row_blocked(kernel, w,
                     [jnp.asarray([seed], dtype=jnp.int32), scale],
                     jnp.int8, interpret)
    return q, scale


class Int8Linear(Layer):
    """Linear with int8 weight + per-output-channel scale (weight-only).

    ``act_scale`` (optional, set by PTQ calibration): when present, the
    input is quantize-dequantized to the calibrated int8 grid before the
    matmul, so the deployed model reproduces full activation-quantization
    error, not just weight error."""

    def __init__(self, in_features, out_features, bias=True):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        qw = np.zeros((in_features, out_features), dtype=np.int8)
        self.register_buffer("qweight", Tensor(jnp.asarray(qw)))
        self.register_buffer(
            "scale", Tensor(jnp.ones((1, out_features), dtype=jnp.float32)))
        # activation QDQ grid step; 0 = disabled. A buffer so PTQ
        # calibration survives state_dict save/load.
        self.register_buffer("act_scale",
                             Tensor(jnp.zeros((), dtype=jnp.float32)))
        self.bias = self.create_parameter((out_features,), is_bias=True) \
            if bias else None

    @classmethod
    def from_linear(cls, linear: Linear, scale=None) -> "Int8Linear":
        m = cls(linear.in_features, linear.out_features,
                bias=linear.bias is not None)
        # an explicit scale (or one pinned by AdaRound) must be honored:
        # recomputing abs-max from an adarounded weight can SHIFT the
        # grid (a channel max rounded down), silently destroying the
        # learned rounding for that channel
        if scale is None:
            scale = getattr(linear, "_adaround_scale", None)
        if scale is not None:
            s = jnp.asarray(scale, jnp.float32).reshape(1, -1)
            q = jnp.clip(jnp.round(linear.weight._data.astype(jnp.float32)
                                   / s), -127, 127).astype(jnp.int8)
            m.qweight._data = q
            m.scale._data = s
        else:
            q, s = quantize_int8(linear.weight, axis=0)  # per out-channel
            m.qweight._data = q._data
            m.scale._data = s._data
        if linear.bias is not None:
            m.bias._data = linear.bias._data
        return m

    def forward(self, x):
        import os

        mode = os.environ.get("PADDLE_TPU_INT8_MXU", "auto")
        use_mxu = (mode == "1"
                   or (mode == "auto"
                       and jax.default_backend() == "tpu"
                       and self.in_features % 128 == 0
                       and self.in_features <= 16384))

        if use_mxu:
            from ...ops.pallas.int8_matmul import int8_linear

            def f(x, q, s, *b):
                y = int8_linear(x, q, s, jnp.dtype(x.dtype))
                return y + b[0].astype(y.dtype) if b else y
        else:
            def f(x, q, s, *b):
                w = q.astype(x.dtype) * s.astype(x.dtype)  # fused by XLA
                y = x @ w
                return y + b[0].astype(x.dtype) if b else y

        if float(np.asarray(self.act_scale._data)) > 0:
            from .qat import fake_quant_dequant
            x = fake_quant_dequant(x, self.act_scale._data)
        args = (x, self.qweight, self.scale) + (
            (self.bias,) if self.bias is not None else ())
        return apply(f, *args)


def quantize_model(model: Layer, include=None) -> Layer:
    """Swap every nn.Linear (optionally filtered by name substring list)
    for an Int8Linear holding the quantized weights. In-place; returns
    model."""
    for name, sub in list(model.named_sublayers(include_self=True)):
        for child_name, child in list(sub._sub_layers.items()):
            if isinstance(child, Linear) and not isinstance(child,
                                                            Int8Linear):
                full = f"{name}.{child_name}" if name else child_name
                if include and not any(k in full for k in include):
                    continue
                sub._sub_layers[child_name] = Int8Linear.from_linear(child)
    if isinstance(model, Linear) and not isinstance(model, Int8Linear):
        raise TypeError("pass a container Layer, not a bare Linear")
    return model


from .qat import (FakeQuantAbsMax, FakeQuantChannelWiseAbsMax,  # noqa: E402
                  FakeQuantMovingAverageAbsMax, ImperativeQuantAware,
                  PostTrainingQuantization, QuantizedConv2D,
                  QuantizedLinear, fake_quant_dequant)
from .adaround import adaround_weight, run_adaround  # noqa: E402
