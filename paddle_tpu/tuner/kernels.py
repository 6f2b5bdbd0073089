"""Built-in kernel registrations: the pallas suite's config spaces.

Each registration pins four things the tuner needs: the enumerable
config space for a shape, a builder that bakes one config into a
jittable callable, the jnp reference the kernel must match in CPU
interpret mode, and the cost-model features the offline ranker scores.

Config-space conventions: spaces are SMALL (tens, not thousands —
exhaustive enumeration is the search strategy), deterministic in order,
and filtered to candidates that are legal at the shape. The registered
``default`` is always the first config the space would yield for the
shape, so default-vs-winner differences are purely the ranker's doing.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..cost_model import VMEM_LIMIT_BYTES, min_tile
from .registry import KernelSpec, register

_LANES = 128
_F32 = 4


def _itemsize(dtype) -> int:
    return int(np.dtype(str(dtype).replace("bfloat16", "float16")).itemsize)


def _sub(dtype) -> int:
    return min_tile(_itemsize(dtype))[0]


def _ceil_div(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------------
# flash attention (fwd+bwd, paddle layout [B, L, H, D])
# ---------------------------------------------------------------------------

def _fa_space(shapes, dtype):
    (B, Lq, H, D), (_, Lk, _, _) = shapes[0], shapes[1]
    out = []
    for bq in (256, 512, 128, 1024):
        if bq > max(Lq, 128):
            continue
        for bk in (512, 256, 1024, 128):
            if bk > max(Lk, 128):
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out or [{"block_q": 256, "block_k": 512}]


def _fa_build(config, interpret):
    from ..ops.pallas.flash_attention import flash_attention

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=config["block_q"],
                               block_k=config["block_k"],
                               interpret=interpret)
    return fn


def _fa_reference(q, k, v):
    import jax
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if qh.shape[1] != kh.shape[1]:          # GQA
        kh = jnp.repeat(kh, qh.shape[1] // kh.shape[1], axis=1)
        vh = jnp.repeat(vh, qh.shape[1] // vh.shape[1], axis=1)
    Lq, Lk = qh.shape[2], kh.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(qh.shape[-1]))
    mask = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


def _fa_features(shapes, dtype, config):
    (B, Lq, H, D), (_, Lk, _, _) = shapes[0], shapes[1]
    bq, bk = config["block_q"], config["block_k"]
    it = _itemsize(dtype)
    vmem = (bq * D + 2 * bk * D) * it \
        + (bq * (2 * _LANES + D)) * _F32 + bq * D * it
    return {"tiles": [(bq, _sub(dtype)), (bk, _sub(dtype)), (D, _LANES)],
            "vmem_bytes": vmem,
            "steps": B * H * _ceil_div(Lq, bq) * _ceil_div(Lk, bk)}


def _fa_demo(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
    return (q, q, q), ((1, 128, 2, 64), (1, 128, 2, 64)), "float32"


register(KernelSpec(
    name="flash_attention",
    space=_fa_space,
    build=_fa_build,
    reference=_fa_reference,
    features=_fa_features,
    default=lambda shapes, dtype: dict(_fa_space(shapes, dtype)[0]),
    demo=_fa_demo,
    shapes_of=lambda args: ((tuple(args[0].shape), tuple(args[1].shape)),
                            str(args[0].dtype)),
    tol=2e-2,   # bf16-typical operand rounding vs the fp32 oracle
    doc="causal flash attention fwd (paddle layout [B, L, H, D])"))


# ---------------------------------------------------------------------------
# int8 MXU matmul with fused rescale epilogue
# ---------------------------------------------------------------------------

def _i8_space(shapes, dtype):
    (M, K), (_, N) = shapes[0], shapes[1]
    out = []
    for bm in (256, 128, 512):
        if bm > max(M, 128):
            continue
        for bn in (256, 128, 512):
            if bn > max(N, 128):
                continue
            out.append({"block_m": bm, "block_n": bn})
    return out or [{"block_m": 256, "block_n": 256}]


def _i8_build(config, interpret):
    from ..ops.pallas.int8_matmul import int8_matmul_rescale

    def fn(xq, xs, wq, ws):
        return int8_matmul_rescale(xq, xs, wq, ws,
                                   out_dtype=jnp.float32,
                                   block_m=config["block_m"],
                                   block_n=config["block_n"],
                                   interpret=interpret)
    return fn


def _i8_reference(xq, xs, wq, ws):
    acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * xs.astype(jnp.float32)
            * ws.astype(jnp.float32))


def _i8_features(shapes, dtype, config):
    (M, K), (_, N) = shapes[0], shapes[1]
    bm, bn = config["block_m"], config["block_n"]
    vmem = bm * K + K * bn + bm * bn * _F32 \
        + (bm + bn) * _F32           # int8 operands + f32 out/scales
    return {"tiles": [(bm, min_tile(1)[0]), (bn, _LANES), (K, _LANES)],
            "vmem_bytes": vmem,
            "steps": _ceil_div(M, bm) * _ceil_div(N, bn)}


def _i8_demo(rng):
    xq = jnp.asarray(rng.integers(-127, 127, (64, 96)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 127, (96, 80)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.01, 0.1, (64, 1)), jnp.float32)
    ws = jnp.asarray(rng.uniform(0.01, 0.1, (1, 80)), jnp.float32)
    return (xq, xs, wq, ws), ((64, 96), (96, 80)), "int8"


register(KernelSpec(
    name="int8_matmul",
    space=_i8_space,
    build=_i8_build,
    reference=_i8_reference,
    features=_i8_features,
    default=lambda shapes, dtype: dict(_i8_space(shapes, dtype)[0]),
    demo=_i8_demo,
    shapes_of=lambda args: ((tuple(args[0].shape), tuple(args[2].shape)),
                            str(args[0].dtype)),
    tol=1e-5,
    doc="int8 x int8 -> int32 MXU matmul, per-channel rescale epilogue"))


# ---------------------------------------------------------------------------
# ragged grouped matmul (ISSUE 14 kernel b)
# ---------------------------------------------------------------------------

def _rg_space(shapes, dtype):
    (G, C, K), (_, _, N) = shapes[0], shapes[1]
    out = []
    for bm in (128, 64, 256, 512):
        if bm > max(C, 64):
            continue
        for bn in (128, 256, 512):
            if bn > max(N, 128):
                continue
            out.append({"block_m": bm, "block_n": bn})
    return out or [{"block_m": 128, "block_n": 128}]


def _rg_build(config, interpret):
    from ..ops.pallas.ragged_matmul import ragged_group_matmul

    def fn(x, w, counts):
        return ragged_group_matmul(x, w, counts,
                                   block_m=config["block_m"],
                                   block_n=config["block_n"],
                                   interpret=interpret)
    return fn


def _rg_reference(x, w, counts):
    from ..ops.pallas.ragged_matmul import ragged_group_matmul_reference
    return ragged_group_matmul_reference(x, w, counts)


def _rg_features(shapes, dtype, config):
    (G, C, K), (_, _, N) = shapes[0], shapes[1]
    bm, bn = config["block_m"], config["block_n"]
    it = _itemsize(dtype)
    vmem = (bm * K + K * bn) * it + bm * bn * _F32
    return {"tiles": [(bm, _sub(dtype)), (bn, _LANES), (K, _LANES)],
            "vmem_bytes": vmem,
            "steps": G * _ceil_div(C, bm) * _ceil_div(N, bn)}


def _rg_demo(rng):
    G, C, K, N = 4, 32, 16, 24
    x = jnp.asarray(rng.standard_normal((G, C, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    counts = jnp.asarray([0, 7, 32, 15], jnp.int32)
    return (x, w, counts), ((G, C, K), (G, K, N)), "float32"


register(KernelSpec(
    name="ragged_matmul",
    space=_rg_space,
    build=_rg_build,
    reference=_rg_reference,
    features=_rg_features,
    default=lambda shapes, dtype: dict(_rg_space(shapes, dtype)[0]),
    demo=_rg_demo,
    shapes_of=lambda args: ((tuple(args[0].shape), tuple(args[1].shape)),
                            str(args[0].dtype)),
    tol=1e-5,
    doc="grouped matmul over per-expert row counts (MoE dispatch, "
        "megablocks-style)"))


# ---------------------------------------------------------------------------
# fused sharded-vocab cross-entropy (ISSUE 14 kernel c)
# ---------------------------------------------------------------------------

def _ce_vmem(H, bn, bv, it):
    """What one grid step of the stats kernel holds in VMEM: the hidden
    and weight tiles are whole in H and double-buffered, plus labels,
    three stats outputs (double-buffered) and scratches one lane tile
    wide, plus the fp32 logits tile and its softmax temporaries."""
    return 2 * (bn * H + H * bv) * it + 11 * bn * _LANES * _F32 \
        + 4 * bn * bv * _F32


def _ce_space(shapes, dtype):
    (N, H), (_, V) = shapes[0], shapes[1]
    it = _itemsize(dtype)
    # the [H, block_v] weight tile grows with H: keep only tiles that
    # leave a quarter of the scoped VMEM to the compiler (at H = 4096
    # bf16 a block_v of 1024 is 8 MB a buffer and is refused)
    budget = VMEM_LIMIT_BYTES * 3 // 4
    out = []
    for bn in (128, 64, 256):
        if bn > max(N, 64):
            continue
        for bv in (1024, 512, 2048, 4096, 256, 128):
            if bv > max(V, 512) or _ce_vmem(H, bn, bv, it) > budget:
                continue
            out.append({"block_n": bn, "block_v": bv})
    return out or [{"block_n": 64, "block_v": 128}]


def _ce_build(config, interpret):
    from ..ops.pallas.fused_ce import fused_ce_loss

    def fn(hidden, w, labels):
        return fused_ce_loss(hidden, w, labels, config["block_n"],
                             config["block_v"], interpret)
    return fn


def _ce_reference(hidden, w, labels):
    from ..ops.pallas.fused_ce import fused_ce_reference
    return fused_ce_reference(hidden, w, labels)


def _ce_features(shapes, dtype, config):
    (N, H), (_, V) = shapes[0], shapes[1]
    bn, bv = config["block_n"], config["block_v"]
    return {"tiles": [(bn, _sub(dtype)), (bv, _LANES), (H, _LANES)],
            "vmem_bytes": _ce_vmem(H, bn, bv, _itemsize(dtype)),
            "steps": _ceil_div(N, bn) * _ceil_div(V, bv)}


def _ce_demo(rng):
    N, H, V = 32, 16, 96
    hidden = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    return (hidden, w, labels), ((N, H), (H, V)), "float32"


register(KernelSpec(
    name="fused_ce",
    space=_ce_space,
    build=_ce_build,
    reference=_ce_reference,
    features=_ce_features,
    default=lambda shapes, dtype: dict(_ce_space(shapes, dtype)[0]),
    demo=_ce_demo,
    shapes_of=lambda args: ((tuple(args[0].shape), tuple(args[1].shape)),
                            str(args[0].dtype)),
    tol=1e-5,
    doc="fused LM-head cross-entropy over vocab tiles (logits never "
        "materialize full-width)"))
