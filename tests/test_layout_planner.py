"""Channels-last layout planner (framework/layout.py): per-op NHWC/NCHW
parity, to_channels_last end-to-end parity, conv+BN folding, the
depthwise fast path, the HLO transpose lint, and plan inheritance by
jit.to_static traces.

Budget note: tier-1 runs close to its wall-clock cap, so the resnet18
pair is built once per module and the heavyweight zoo variants
(mobilenet end-to-end) are marked slow; tools/check_hlo_layout.py
carries the full-size transpose counts (no conv cell in the benchmark:
PERF.md has no timing of this path).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import (
    ChannelsLast, count_hlo_transposes, fold_conv_bn, to_channels_last,
)

RNG = np.random.default_rng(0)


def t(shape, scale=1.0):
    return paddle.to_tensor(
        (RNG.standard_normal(shape) * scale).astype(np.float32))


def to_nhwc(x):
    return paddle.transpose(x, [0, 2, 3, 1])


def back(x):
    return np.asarray(paddle.transpose(x, [0, 3, 1, 2])._data)


@pytest.fixture(scope="module")
def resnet_pair():
    """(nchw_model, channels_last_wrapper) sharing one weight set.

    Read-only for most tests; the fold test (defined last in file
    order, which tier-1's -p no:randomly preserves) mutates weights
    after capturing its own before-output."""
    from paddle_tpu.vision.models import resnet18
    paddle.seed(1)
    m = resnet18(num_classes=10)
    m.eval()
    paddle.seed(1)
    m2 = resnet18(num_classes=10)
    m2.eval()
    m2.set_state_dict(m.state_dict())
    return m, to_channels_last(m2)


class TestPerOpParity:
    """Every layout-aware functional must produce identical values in
    both layouts (same dimension-numbers conv, no transposes)."""

    def setup_method(self, _):
        paddle.seed(0)
        self.x = t((2, 8, 10, 10))
        self.xn = to_nhwc(self.x)

    def test_conv2d(self):
        w, b = t((16, 8, 3, 3)), t((16,))
        ref = np.asarray(F.conv2d(self.x, w, b, stride=2, padding=1)._data)
        out = F.conv2d(self.xn, w, b, stride=2, padding=1,
                       data_format="NHWC")
        np.testing.assert_array_equal(back(out), ref)

    def test_conv2d_strings_and_dilation(self):
        w = t((16, 8, 3, 3))
        for pad in ("SAME", "VALID"):
            ref = np.asarray(F.conv2d(self.x, w, padding=pad, dilation=2)._data)
            out = F.conv2d(self.xn, w, padding=pad, dilation=2,
                           data_format="NHWC")
            np.testing.assert_array_equal(back(out), ref)

    def test_conv2d_full_form_padding_layout(self):
        """The full-rank padding spelling places spatial entries per the
        layout: [..., [ph,ph], [pw,pw]] NCHW vs [..., spatial ..., [0,0]]
        NHWC."""
        w = t((16, 8, 3, 3))
        ref = np.asarray(F.conv2d(
            self.x, w, padding=[[0, 0], [0, 0], [1, 2], [3, 4]])._data)
        out = F.conv2d(self.xn, w,
                       padding=[[0, 0], [1, 2], [3, 4], [0, 0]],
                       data_format="NHWC")
        np.testing.assert_array_equal(back(out), ref)

    def test_depthwise_fast_path(self):
        w = t((8, 1, 3, 3))
        ref = np.asarray(F.conv2d(self.x, w, padding=1, groups=8)._data)
        out = F.conv2d(self.xn, w, padding=1, groups=8, data_format="NHWC")
        np.testing.assert_array_equal(back(out), ref)
        # depthwise-expanding (out = k * in) and grouped variants
        w2 = t((16, 1, 3, 3))
        ref2 = np.asarray(F.conv2d(self.x, w2, padding=1, groups=8)._data)
        out2 = F.conv2d(self.xn, w2, padding=1, groups=8, data_format="NHWC")
        np.testing.assert_array_equal(back(out2), ref2)
        w3 = t((12, 2, 3, 3))
        ref3 = np.asarray(F.conv2d(self.x, w3, padding=1, groups=4)._data)
        out3 = F.conv2d(self.xn, w3, padding=1, groups=4, data_format="NHWC")
        np.testing.assert_array_equal(back(out3), ref3)

    def test_depthwise_emits_no_transposes(self):
        """The NHWC depthwise path keeps the OIHW weight spec: no
        transpose ops in the emitted HLO (the fast-path contract)."""
        paddle.seed(0)
        conv = nn.Conv2D(8, 8, 3, padding=1, groups=8, data_format="NHWC")
        xn = paddle.to_tensor(np.asarray(self.xn._data))
        assert count_hlo_transposes(conv, xn) == 0

    def test_conv2d_transpose(self):
        w = t((8, 4, 3, 3))
        ref = np.asarray(F.conv2d_transpose(
            self.x, w, stride=2, padding=1, output_padding=1)._data)
        out = F.conv2d_transpose(self.xn, w, stride=2, padding=1,
                                 output_padding=1, data_format="NHWC")
        np.testing.assert_array_equal(back(out), ref)

    def test_grouped_conv2d_transpose(self):
        w, b = t((8, 2, 3, 3)), t((4,))
        ref = np.asarray(F.conv2d_transpose(
            self.x, w, b, stride=2, groups=2)._data)
        out = F.conv2d_transpose(self.xn, w, b, stride=2, groups=2,
                                 data_format="NHWC")
        np.testing.assert_array_equal(back(out), ref)

    def test_batch_norm_eval_and_train(self):
        rm1, rv1 = t((8,)), paddle.to_tensor(
            (np.abs(RNG.standard_normal(8)) + 0.5).astype(np.float32))
        rm2 = paddle.to_tensor(np.asarray(rm1._data).copy())
        rv2 = paddle.to_tensor(np.asarray(rv1._data).copy())
        g, b = t((8,)), t((8,))
        ref = np.asarray(F.batch_norm(self.x, rm1, rv1, g, b,
                                      training=False)._data)
        out = F.batch_norm(self.xn, rm2, rv2, g, b, training=False,
                           data_format="NHWC")
        np.testing.assert_allclose(back(out), ref, rtol=1e-6, atol=1e-6)
        # training mode: normalized output AND running-stat updates match
        ref = np.asarray(F.batch_norm(self.x, rm1, rv1, g, b,
                                      training=True)._data)
        out = F.batch_norm(self.xn, rm2, rv2, g, b, training=True,
                           data_format="NHWC")
        np.testing.assert_allclose(back(out), ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rm2._data),
                                   np.asarray(rm1._data), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(rv2._data),
                                   np.asarray(rv1._data), rtol=1e-6)

    def test_pools(self):
        for ref_t, out_t in (
            (F.max_pool2d(self.x, 3, stride=2, padding=1),
             F.max_pool2d(self.xn, 3, stride=2, padding=1,
                          data_format="NHWC")),
            (F.avg_pool2d(self.x, 2, stride=2, exclusive=False),
             F.avg_pool2d(self.xn, 2, stride=2, exclusive=False,
                          data_format="NHWC")),
            (F.avg_pool2d(self.x, 3, stride=1, padding=1),
             F.avg_pool2d(self.xn, 3, stride=1, padding=1,
                          data_format="NHWC")),
            (F.adaptive_avg_pool2d(self.x, (5, 5)),
             F.adaptive_avg_pool2d(self.xn, (5, 5), data_format="NHWC")),
            (F.adaptive_avg_pool2d(self.x, (3, 3)),  # uneven bins
             F.adaptive_avg_pool2d(self.xn, (3, 3), data_format="NHWC")),
            (F.adaptive_avg_pool2d(self.x, (1, 1)),
             F.adaptive_avg_pool2d(self.xn, (1, 1), data_format="NHWC")),
        ):
            np.testing.assert_allclose(back(out_t), np.asarray(ref_t._data),
                                       rtol=1e-6, atol=1e-6)

    def test_conv_grad_parity(self):
        """Gradients flow through the NHWC dimension-numbers conv
        identically to the NCHW one."""
        w1 = t((6, 8, 3, 3))
        w2 = paddle.to_tensor(np.asarray(w1._data).copy())
        w1.stop_gradient = False
        w2.stop_gradient = False
        F.conv2d(self.x, w1, padding=1).sum().backward()
        F.conv2d(self.xn, w2, padding=1, data_format="NHWC").sum().backward()
        np.testing.assert_allclose(np.asarray(w2.grad._data),
                                   np.asarray(w1.grad._data),
                                   rtol=1e-5, atol=1e-5)


def _safe_stack():
    """A small layout-safe conv chain (cheap stand-in for the zoo)."""
    paddle.seed(2)
    return nn.Sequential(
        nn.Conv2D(3, 8, 3, padding=1, bias_attr=False),
        nn.BatchNorm2D(8),
        nn.ReLU(),
        nn.MaxPool2D(2),
        nn.Conv2D(8, 8, 3, padding=1, groups=8),
        nn.AvgPool2D(2),
    )


class TestToChannelsLast:
    def test_resnet18_end_to_end_parity(self, resnet_pair):
        m, cl = resnet_pair
        x = t((2, 3, 32, 32))
        ref = np.asarray(m(x)._data)
        assert isinstance(cl, ChannelsLast)
        assert len(cl.plan.converted) >= 40  # 20 convs + 20 BNs + pools
        np.testing.assert_array_equal(np.asarray(cl(x)._data), ref)

    def test_4d_output_transposed_back(self):
        """A region whose output is 4D gets the exit boundary transpose
        — output returns in NCHW."""
        stack = _safe_stack()
        stack.eval()
        x = t((1, 3, 8, 8))
        ref = np.asarray(stack(x)._data)
        out = np.asarray(to_channels_last(stack, force=True)(x)._data)
        assert out.shape == ref.shape  # NCHW restored
        np.testing.assert_array_equal(out, ref)

    def test_unsafe_model_requires_force(self):
        class Odd(nn.Layer):
            def __init__(self):
                super().__init__()
                self.conv = nn.Conv2D(3, 4, 3)

            def forward(self, x):
                return self.conv(x)

        with pytest.raises(ValueError, match="channels-last-safe"):
            to_channels_last(Odd())

    def test_idempotent(self, resnet_pair):
        _, cl = resnet_pair
        assert to_channels_last(cl) is cl

    def test_zoo_opt_in_markers(self):
        from paddle_tpu.vision.models.mobilenet import (
            MobileNetV1, MobileNetV2, MobileNetV3,
        )
        from paddle_tpu.vision.models.resnet import ResNet
        for cls in (ResNet, MobileNetV1, MobileNetV2, MobileNetV3):
            assert cls._channels_last_safe is True

    @pytest.mark.slow
    def test_mobilenet_end_to_end(self):
        from paddle_tpu.vision.models import mobilenet_v2
        paddle.seed(3)
        m = mobilenet_v2(num_classes=10)
        m.eval()
        paddle.seed(3)
        m2 = mobilenet_v2(num_classes=10)
        m2.eval()
        m2.set_state_dict(m.state_dict())
        x = t((1, 3, 32, 32))
        ref = np.asarray(m(x)._data)
        np.testing.assert_array_equal(
            np.asarray(to_channels_last(m2)(x)._data), ref)


class TestFoldConvBN:
    def test_single_pair_parity(self):
        """The fp32 <=1e-5 folding contract on one conv+BN pair."""
        paddle.seed(3)
        conv = nn.Conv2D(8, 16, 3, padding=1, bias_attr=False)
        bn = nn.BatchNorm2D(16)
        bn._mean._data = t((16,))._data
        bn._variance._data = paddle.to_tensor(
            (np.abs(RNG.standard_normal(16)) + 0.3).astype(np.float32))._data
        bn.weight._data = t((16,))._data
        bn.bias._data = t((16,))._data
        seq = nn.Sequential(conv, bn)
        seq.eval()
        x = t((2, 8, 12, 12))
        before = np.asarray(seq(x)._data)
        folded = fold_conv_bn(seq)
        assert folded == ["1"]
        from paddle_tpu.nn.layer.common import Identity
        assert isinstance(seq._sub_layers["1"], Identity)
        assert conv.bias is not None  # bias materialized by the fold
        after = np.asarray(seq(x)._data)
        assert np.abs(after - before).max() <= 1e-5

    def test_conv_with_bias_folds_in_place(self):
        paddle.seed(4)
        conv = nn.Conv2D(4, 8, 3, padding=1)  # has a bias already
        bn = nn.BatchNorm2D(8)
        bn._mean._data = t((8,))._data
        seq = nn.Sequential(conv, bn)
        seq.eval()
        x = t((1, 4, 9, 9))
        before = np.asarray(seq(x)._data)
        assert fold_conv_bn(seq) == ["1"]
        np.testing.assert_allclose(np.asarray(seq(x)._data), before,
                                   rtol=1e-5, atol=1e-5)

    def test_relu_not_folded(self):
        """conv -> relu -> bn must NOT fold (not adjacent dataflow)."""
        paddle.seed(5)
        seq = nn.Sequential(nn.Conv2D(4, 8, 3), nn.ReLU(), nn.BatchNorm2D(8))
        seq.eval()
        assert fold_conv_bn(seq) == []


class TestHLOLayout:
    def test_resnet18_zero_interior_transposes(self, resnet_pair):
        """The tentpole claim: the channels-last jitted forward emits no
        layout transposes except the entry boundary."""
        _, cl = resnet_pair
        x = t((1, 3, 32, 32))
        xn = to_nhwc(x)
        assert count_hlo_transposes(cl.model, xn) == 0
        assert count_hlo_transposes(cl, x) <= 1

    def test_small_stack_zero_transposes(self):
        paddle.seed(0)
        stack = nn.Sequential(
            nn.Conv2D(3, 8, 3, padding=1, data_format="NHWC"),
            nn.BatchNorm2D(8, data_format="NHWC"),
            nn.ReLU(),
            nn.MaxPool2D(2, data_format="NHWC"),
            nn.AdaptiveAvgPool2D((1, 1), data_format="NHWC"),
        )
        stack.eval()
        xn = t((1, 6, 6, 3))
        assert count_hlo_transposes(stack, xn) == 0


class TestPlanInheritance:
    def test_static_executor_inherits_layout(self):
        """The record/replay Executor replays whatever the converted
        layers emit — the layout plan needs no Program plumbing."""
        from paddle_tpu import static
        stack = _safe_stack()
        stack.eval()
        x_np = RNG.standard_normal((2, 3, 8, 8)).astype(np.float32)
        ref = np.asarray(stack(paddle.to_tensor(x_np))._data)
        cl = to_channels_last(stack, force=True)
        main = static.Program()
        with static.program_guard(main):
            x = static.data('x', [None, 3, 8, 8], 'float32')
            y = cl(x)
        exe = static.Executor()
        out, = exe.run(main, feed={'x': x_np}, fetch_list=[y])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        paddle.disable_static()

    def test_to_static_inherits_layout(self):
        """jit.to_static over a converted region traces the NHWC ops —
        same numbers, no extra plumbing."""
        stack = _safe_stack()
        stack.eval()
        x = t((2, 3, 8, 8))
        ref = np.asarray(stack(x)._data)
        cl = to_channels_last(stack, force=True)
        st = paddle.jit.to_static(cl)
        np.testing.assert_allclose(np.asarray(st(x)._data), ref,
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_accum_policy_eval_only(self):
        """conv_accum_fp32 requests fp32 accumulation for bf16 convs and
        returns bf16; outside the context the dtype chain is untouched."""
        import jax.numpy as jnp

        from paddle_tpu.nn.functional.conv import conv_accum_fp32
        x = t((1, 4, 8, 8)).astype("bfloat16")
        w = t((8, 4, 3, 3)).astype("bfloat16")
        ref = F.conv2d(x, w, padding=1)
        assert ref._data.dtype == jnp.bfloat16
        with conv_accum_fp32():
            out = F.conv2d(x, w, padding=1)
        assert out._data.dtype == jnp.bfloat16
        # fp32 accumulation must stay within bf16 rounding of the ref
        np.testing.assert_allclose(
            np.asarray(out._data, dtype=np.float32),
            np.asarray(ref._data, dtype=np.float32), rtol=0.05, atol=0.05)

    def test_padding_mode_reflect(self):
        """Conv2D padding_mode pre-pads the input (was silently ignored)."""
        paddle.seed(6)
        conv = nn.Conv2D(3, 5, 3, padding=1, padding_mode="reflect")
        x = t((1, 3, 8, 8))
        out = conv(x)
        assert tuple(out.shape) == (1, 5, 8, 8)
        # equals explicit reflect-pad + unpadded conv
        xp = F.pad(x, [1, 1, 1, 1], mode="reflect")
        ref = F.conv2d(xp, conv.weight, conv.bias)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


# defined LAST: mutates the shared resnet_pair weights (fold); tier-1
# runs with -p no:randomly, preserving file order
class TestFoldResnet:
    def test_resnet18_fold_parity(self, resnet_pair):
        m, cl = resnet_pair
        x = t((2, 3, 32, 32))
        before = np.asarray(cl(x)._data)
        folded = fold_conv_bn(cl)
        assert len(folded) == 20  # every BN in resnet18
        out = np.asarray(cl(x)._data)
        # error accumulates through 20 folded layers; relative to the
        # logit scale it stays at the 1e-5 fp32 contract
        np.testing.assert_allclose(out, before, rtol=2e-5, atol=2e-5)
