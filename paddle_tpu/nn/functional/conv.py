"""Convolutions. Reference: python/paddle/nn/functional/conv.py.

All convs lower to jax.lax.conv_general_dilated (one XLA HLO), which the TPU
compiler maps straight onto the MXU. Weight layout matches paddle:
[out_c, in_c/groups, *kernel]; default data_format NCHW.

Layout policy (framework/layout.py): channels-last (NHWC) activations are
consumed *natively* via conv dimension numbers — the weight stays in the
paddle OI* layout and the spec becomes ("NHWC", "OIHW", "NHWC"), so the
emitted HLO contains no transpose ops at all. TPUs (and XLA:CPU) are
natively channels-last; keeping whole regions NHWC removes the per-op
layout copies the NCHW spelling forces the backend to insert.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ...amp.auto_cast import maybe_cast_compute
from ...tensor import apply


def _norm_tuple(v, n):
    if isinstance(v, (list, tuple)):
        if len(v) == n:
            return tuple(int(x) for x in v)
        if len(v) == 2 * n:  # paddle allows per-side pairs flattened
            return tuple(v)
        return tuple(int(v[0]) for _ in range(n))
    return tuple(int(v) for _ in range(n))


def _padding(padding, n, stride, dilation, kernel, channel_last=False):
    if isinstance(padding, str):
        p = padding.upper()
        if p == "SAME":
            return "SAME"
        if p == "VALID":
            return "VALID"
        raise ValueError(padding)
    if isinstance(padding, (list, tuple)) and len(padding) == 2 * n \
            and not (padding and isinstance(padding[0], (list, tuple))):
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    if isinstance(padding, (list, tuple)) and padding and isinstance(padding[0], (list, tuple)):
        # full-rank form incl. batch/channel dims: NCHW-style
        # [[0,0],[0,0],[ph,ph],[pw,pw]] or NHWC-style
        # [[0,0],[ph,ph],[pw,pw],[0,0]] — spatial entries depend on layout
        if len(padding) == n + 2:
            spatial = padding[1:-1] if channel_last else padding[2:]
            return [tuple(int(v) for v in p) for p in spatial]
        return [tuple(int(v) for v in p) for p in padding]
    pads = _norm_tuple(padding, n)
    return [(p, p) for p in pads]


def _dim_numbers(n, channel_last):
    # channels-last keeps the paddle OI* weight layout: XLA consumes any
    # (lhs, rhs, out) spec directly, so NO weight transpose is emitted —
    # this is what makes whole NHWC regions transpose-free end to end
    if n == 1:
        return ("NWC", "OIW", "NWC") if channel_last else ("NCW", "OIW", "NCW")
    if n == 2:
        return ("NHWC", "OIHW", "NHWC") if channel_last else ("NCHW", "OIHW", "NCHW")
    return ("NDHWC", "OIDHW", "NDHWC") if channel_last else ("NCDHW", "OIDHW", "NCDHW")


# -- bf16 accumulation policy ------------------------------------------------
# The MXU accumulates bf16 convs in fp32 internally, but the *output* dtype
# follows the inputs unless preferred_element_type is requested. Requesting
# fp32 outputs under autodiff breaks the conv transpose (grad) rule: the
# cotangent arrives as fp32 while lhs stays bf16, and conv_general_dilated
# rejects the mix. So fp32 accumulation is an
# INFERENCE-ONLY, opt-in policy: inside conv_accum_fp32() regions, bf16
# convs request fp32 accumulation and cast the result back to bf16. The
# channels-last inference wrapper (framework/layout.py) enables it for
# eval-mode bf16 models.
_ACCUM_FP32 = False


@contextlib.contextmanager
def conv_accum_fp32():
    """Inference-only: bf16 convs accumulate in fp32 (cast back to bf16).

    Do not wrap code that differentiates through the conv — the fp32
    cotangent/bf16 lhs mix is rejected by the conv transpose rule.
    """
    global _ACCUM_FP32
    prev = _ACCUM_FP32
    _ACCUM_FP32 = True
    try:
        yield
    finally:
        _ACCUM_FP32 = prev


def _accum_kwargs(a, w):
    if _ACCUM_FP32 and a.dtype == jnp.bfloat16 and w.dtype == jnp.bfloat16:
        return {"preferred_element_type": jnp.float32}, jnp.bfloat16
    return {}, None


def _conv(x, weight, bias, stride, padding, dilation, groups, n, data_format):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC", "NLC")
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    kernel = None
    pad = _padding(padding, n, stride, dilation, kernel, channel_last)
    dn_str = _dim_numbers(n, channel_last)

    def f(a, w, *bs):
        a, w = maybe_cast_compute(a, w)
        dn = jax.lax.conv_dimension_numbers(a.shape, w.shape, dn_str)
        # groups > 1 (grouped / depthwise) maps straight onto
        # feature_group_count — with the OI* weight spec this is the
        # native XLA fast path in both layouts, no reshapes needed
        pet, back = _accum_kwargs(a, w)
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups, **pet)
        if back is not None:
            out = out.astype(back)
        if bs:
            b = bs[0].astype(out.dtype)
            shape = [1] * out.ndim
            shape[-1 if channel_last else 1] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    args = (x, weight) + (() if bias is None else (bias,))
    return apply(f, *args)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    df = "NWC" if data_format == "NLC" else "NCW"
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1, df)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3, data_format)


def _conv_transpose(x, weight, bias, stride, padding, output_padding, groups,
                    dilation, n, data_format, output_size=None):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC", "NLC")
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pads = _padding(padding, n, stride, dilation, None, channel_last)
    opad = _norm_tuple(output_padding, n)

    def f(a, w, *bs):
        a, w = maybe_cast_compute(a, w)
        # transposed conv == conv with lhs_dilation=stride on a spatially
        # flipped, in/out-swapped kernel. paddle weight: [in_c, out_c/g, *k]
        kshape = w.shape[2:]
        pad_cfg = []
        for i in range(n):
            eff_k = dilation[i] * (kshape[i] - 1) + 1
            lo = eff_k - 1 - pads[i][0]
            hi = eff_k - 1 - pads[i][1] + opad[i]
            pad_cfg.append((lo, hi))
        kern = jnp.flip(w, axis=tuple(range(2, 2 + n)))
        if groups == 1:
            kern = jnp.swapaxes(kern, 0, 1)  # -> [out, in, *k]
        else:
            ic, ocg = w.shape[0], w.shape[1]
            kern = kern.reshape((groups, ic // groups, ocg) + kshape)
            kern = jnp.swapaxes(kern, 1, 2)
            kern = kern.reshape((ocg * groups, ic // groups) + kshape)
        # the kernel is OI* either way, so channels-last activations are
        # consumed natively via dimension numbers (no activation moveaxis)
        dn_str = _dim_numbers(n, channel_last)
        dn = jax.lax.conv_dimension_numbers(a.shape, kern.shape, dn_str)
        pet, back = _accum_kwargs(a, kern)
        out = jax.lax.conv_general_dilated(
            a, kern, window_strides=(1,) * n, padding=pad_cfg,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=groups, **pet)
        if back is not None:
            out = out.astype(back)
        if bs:
            b = bs[0].astype(out.dtype)
            shape = [1] * out.ndim
            shape[-1 if channel_last else 1] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    args = (x, weight) + (() if bias is None else (bias,))
    return apply(f, *args)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1, output_size=None,
                     data_format="NCL", name=None):
    df = "NWC" if data_format == "NLC" else "NCW"
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           groups, dilation, 1, df, output_size)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1, output_size=None,
                     data_format="NCHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           groups, dilation, 2, data_format, output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1, output_size=None,
                     data_format="NCDHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           groups, dilation, 3, data_format, output_size)
