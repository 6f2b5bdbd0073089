"""Every pallas kernel of the main path, compiled (not run) for a
described TPU v5e at Llama-2-7B widths, under the configuration the
program picks when the caller passes none.

The TPU's compiler is installed with jax and compiles for a chip that is
described and not attached, so these cost no chip time and refuse what
the chip would refuse: a block that breaks the (8, 128) tiling rule, a
kernel that needs more VMEM than one may use. Nothing runs; parity with
the jnp references is the interpret-mode suites' job (CPU) and
``chip_smoke.py``'s (chip).

This is the only file that describes a chip. The topology is described
inside a module-scoped fixture — never at import — because one process
at a time may load the TPU's library: under pytest-xdist every worker
imports this file, and only the worker that runs it may make the call.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# Llama-2-7B: 32 heads x 128, hidden 4096, intermediate 11008, vocab 32000
_H, _HD, _HIDDEN, _FF, _VOCAB = 32, 128, 4096, 11008, 32000
_SEQ = 2048
# the engine's pool at n_slots=8, max_len=1024, block_size=16
_SLOTS, _BLOCKS, _BS = 8, 513, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash_attention(grad):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = ((1, _SEQ, _H, _HD), jnp.bfloat16)
    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [qkv] * 3


def _paged_attention(blocks, n_kv, table):
    """The decode kernel at a serving cell's shapes: 16 slots, the pool
    flat over the layers, the window an operand."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    pool = ((blocks, _BS, n_kv, _HD), jnp.bfloat16)
    return paged_attention, [((16, _H, _HD), jnp.bfloat16), pool, pool,
                             ((16, table), jnp.int32), ((16,), jnp.int32),
                             ((), jnp.int32)]


def _chunk_attention():
    """The chunk kernel at the latent cell's shapes: 64 heads x 512 rows,
    192 against 128, one tile of cached lines as the chunk body walks
    them, the running state donated."""
    from paddle_tpu.ops.pallas.chunk_attention import chunk_attention
    from paddle_tpu.text.generation import _LATENT_TILE

    def fold(q, q_shared, lines, w, gpos, first, top, total, acc):
        return chunk_attention(q, q_shared, lines, w, gpos, first,
                               (top, total, acc), scale=0.1446796)

    bf, f32 = jnp.bfloat16, jnp.float32
    return fold, [((64, 512, 128), bf), ((64, 512, 64), bf),
                  ((_LATENT_TILE, 640), bf), ((512, 64, 256), bf),
                  ((512,), jnp.int32), ((), jnp.int32), ((64, 512), f32),
                  ((64, 512), f32), ((64, 512, 128), f32)]


def _fused_ce(grad):
    from paddle_tpu.ops.pallas.fused_ce import fused_ce_loss
    fn = jax.grad(fused_ce_loss, argnums=(0, 1)) if grad else fused_ce_loss
    return fn, [((_SEQ, _HIDDEN), jnp.bfloat16),
                ((_HIDDEN, _VOCAB), jnp.bfloat16), ((_SEQ,), jnp.int32)]


def _int8_linear():
    from paddle_tpu.ops.pallas.int8_matmul import int8_linear
    return int8_linear, [((_SEQ, _HIDDEN), jnp.bfloat16),
                         ((_HIDDEN, _FF), jnp.int8),
                         ((1, _FF), jnp.float32)]


def _ragged_group_matmul():
    from paddle_tpu.ops.pallas.ragged_matmul import ragged_group_matmul
    return ragged_group_matmul, [((8, 256, 2048), jnp.bfloat16),
                                 ((8, 2048, 1024), jnp.bfloat16),
                                 ((8,), jnp.int32)]


def _grouped_swiglu(T, held, h, f):
    """The routed layer's kernel with its layout around it, as a TPU runs
    ``routed_ffn``: 64 experts of 2304 x 896 (a whole expert a block), or
    12 held of 7168 x 2048 (blocks of 256 of the width), top-8."""
    from paddle_tpu.nn.routed_ffn import grouped
    return grouped, [((T, h), jnp.bfloat16), ((T, 8), jnp.int32),
                     ((T, 8), jnp.float32), ((held, h, f), jnp.bfloat16),
                     ((held, h, f), jnp.bfloat16), ((held, f, h), jnp.bfloat16)]


def _stochastic(name):
    from paddle_tpu.nn import quant
    return (functools.partial(getattr(quant, name), seed=7),
            [((_HIDDEN, _HIDDEN), jnp.float32)])


_CASES = {
    "flash_attention-fwd": functools.partial(_flash_attention, False),
    "flash_attention-grad": functools.partial(_flash_attention, True),
    # deepseek-llm-7b at depth 6, 16 x 2048; mellum2 at depth 8, 16 x 8192
    "paged_attention-32_kv_heads": functools.partial(
        _paged_attention, 6 * 2049, 32, 128),
    "paged_attention-4_kv_heads": functools.partial(
        _paged_attention, 8 * 8193, 4, 512),
    # kimi-k2.6 as one chip of 32: a chunk of 512 rows against 2048 lines
    "chunk_attention-64_heads": _chunk_attention,
    # mellum2 and kimi-k2.6 as one chip of 32: a decode step, a chunk
    "grouped_swiglu-64_experts_16_rows": functools.partial(
        _grouped_swiglu, 16, 64, 2304, 896),
    "grouped_swiglu-12_held_512_rows": functools.partial(
        _grouped_swiglu, 512, 12, 7168, 2048),
    "fused_ce_loss-fwd": functools.partial(_fused_ce, False),
    "fused_ce_loss-grad": functools.partial(_fused_ce, True),
    "int8_linear": _int8_linear,
    "ragged_group_matmul": _ragged_group_matmul,
    "stochastic_round": functools.partial(_stochastic, "stochastic_round"),
    "quantize_int8_stochastic": functools.partial(
        _stochastic, "quantize_int8_stochastic"),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, shapes = _CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if case.startswith("paged_attention"):
        # the pool is read where it lies: nothing of its size is planned
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    if case.startswith("chunk_attention"):
        # neither the scores nor the lines' expansion is an array
        assert "chunk_attention" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 22


def _over_jit(mesh):
    """The GSPMD train step: sdpa_raw wraps the kernel in a shard_map of
    its own, batch over "sharding", heads over "tp"."""
    from paddle_tpu.nn.functional.attention import sdpa_raw
    return (lambda q, k, v: sdpa_raw(q, k, v, causal=True),
            P("sharding", None, "tp", None))


def _over_full_manual(mesh):
    """The comm-opt, DGC and compressed-allreduce steps: the body is
    per-device already (per-device batch 2 still divides by "sharding"),
    and the kernel runs where it is."""
    from paddle_tpu.distributed.mesh import shard_map
    from paddle_tpu.nn.functional.attention import sdpa_raw
    spec = P("sharding", None, "tp", None)
    return (shard_map(lambda q, k, v: sdpa_raw(q, k, v, causal=True),
                      mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                      check_vma=False), spec)


def _over_ulysses(mesh):
    """Sequence parallelism inside the GSPMD step: manual over "sep" only,
    the kernel's own shard_map takes the axes that are left."""
    from paddle_tpu.ops.ulysses_attention import ulysses_attention
    return (lambda q, k, v: ulysses_attention(q, k, v, causal=True),
            P(None, "sep", "tp", None))


def _over_ring(mesh):
    """Ring attention inside the GSPMD step: its chunk kernel (forward
    only; the ring has a backward of its own) is placed the same way."""
    from paddle_tpu.ops.ring_attention import ring_attention
    return (lambda q, k, v: ring_attention(q, k, v, causal=True),
            P(None, "sep", "tp", None))


def _over_pipeline(mesh):
    """The pipeline's stages: manual over "pp" only, the kernel inside the
    schedule's scan, differentiated through it."""
    from paddle_tpu.nn.functional.attention import sdpa_raw
    from paddle_tpu.ops.pipeline import spmd_pipeline

    def stage(p, x):
        return sdpa_raw(x * p["scale"][0], x, x, causal=True)

    return (lambda q, k, v: spmd_pipeline(
        stage, {"scale": jnp.ones((2, 1), q.dtype)}, q + k + v),
            P(None, None, "tp", None))


def _over_own_mesh(mesh):
    """A shard_map over a mesh of the caller's own, whose axes the global
    mesh does not have."""
    from paddle_tpu.ops.ulysses_attention import ulysses_attention
    own = Mesh(mesh.devices.reshape(4), ("ring",))
    return (lambda q, k, v: ulysses_attention(q, k, v, mesh=own,
                                              axis_name="ring", causal=True),
            P("sharding", None, "tp", None))


# context -> (the 2x2 chips as pp, dp, sharding, sep, tp; its builder)
_CONTEXTS = {"jit": ((1, 1, 2, 1, 2), _over_jit),
             "full_manual": ((1, 1, 2, 1, 2), _over_full_manual),
             "ulysses": ((1, 1, 1, 2, 2), _over_ulysses),
             "ring": ((1, 1, 1, 2, 2), _over_ring),
             "pipeline": ((2, 1, 1, 1, 2), _over_pipeline),
             "own_mesh": ((1, 1, 2, 1, 2), _over_own_mesh)}


@pytest.mark.parametrize("context", sorted(_CONTEXTS))
def test_flash_attention_over_a_2x2_mesh(context, topo, no_compile_cache,
                                         monkeypatch):
    """A Mosaic kernel is legal only where every mesh axis is manual, and
    a nested shard_map may only name axes that are not manual yet: the
    flash kernels must be placed rightly from every context that reaches
    them, fwd and grad."""
    from paddle_tpu.distributed import mesh as mesh_mod

    shape, build = _CONTEXTS[context]
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), mesh_mod.AXES)
    # jax.default_backend() is the CPU here: take the kernel's branch
    monkeypatch.setenv("PADDLE_TPU_ATTENTION", "flash")
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh)
    attn, spec = build(mesh)

    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((4, _SEQ, _H, _HD), jnp.bfloat16,
                               sharding=NamedSharding(mesh, spec))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if context in ("jit", "full_manual"):
        # attention is independent across batch and heads: nothing to reduce
        assert "all-reduce" not in text and "all-gather" not in text


# the serving cell's engine (deepseek-llm-7b at depth 6, 16 slots x 2048,
# block 16, chunk 256): its pool, its tables and its widths, which are the
# Llama-2-7B ones above with 32 KV heads
_LAYERS, _POOL_BLOCKS, _ENGINE_SLOTS, _TABLE, _CHUNK = 6, 2049, 16, 128, 256
_POOL = (_LAYERS, _POOL_BLOCKS, _BS, _H, _HD)


def _paged_program(kind):
    """A paged program of the engine as the engine jits it (pools
    donated), the shapes of its arguments and its statics."""
    from paddle_tpu.serving import engine as E

    L, S, V = _LAYERS, _ENGINE_SLOTS, _VOCAB
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    w = {"wq": (L, _HIDDEN, _HIDDEN), "wk": (L, _HIDDEN, _HIDDEN),
         "wv": (L, _HIDDEN, _HIDDEN), "wo": (L, _HIDDEN, _HIDDEN),
         "wg": (L, _HIDDEN, _FF), "wu": (L, _HIDDEN, _FF),
         "wd": (L, _FF, _HIDDEN), "ln1": (L, _HIDDEN), "ln2": (L, _HIDDEN),
         "embed": (V, _HIDDEN), "norm": (_HIDDEN,), "head": (_HIDDEN, V)}
    w = {k: (s, bf) for k, s in w.items()}
    pool, scalar = (_POOL, bf), ((), i32)
    slots, keys = ((S,), i32), ((S, 2), jnp.uint32)
    statics = dict(arch="llama", n_heads=_H, n_kv=_H, eps=1e-6, theta=1e4,
                   do_sample=False, top_k=0, top_p=1.0, block_size=_BS)
    if kind == "decode":
        return (E._PAGED_DECODE_DONATED,
                [w, pool, pool, ((S, _TABLE), i32), slots, slots,
                 ((S,), jnp.bool_), keys, ((S,), f32), ((S, V), jnp.int8)],
                statics)
    row, temp, vmask = ((_TABLE,), i32), ((1,), f32), ((V,), jnp.int8)
    if kind == "chunk":
        return (E._PAGED_CHUNK_DONATED,
                [w, pool, pool, slots, slots, keys, ((1, _CHUNK), i32),
                 scalar, scalar, scalar, row, scalar, scalar, scalar, scalar,
                 temp, vmask], statics)
    return (E._SPEC_VERIFY_DONATED,
            [w, pool, pool, keys, ((1, 5), i32), scalar, scalar, row, scalar,
             temp, vmask], statics)


@pytest.mark.parametrize("kind", ("decode", "chunk", "verify"))
def test_paged_program_keeps_the_pool_in_place(kind, one_chip,
                                               no_compile_cache):
    """The pool rides the layer loop as a carry, so the compiler writes
    each layer's rows into the donated buffers: no second pool among the
    temporaries, and no whole pool or layer of it copied, sliced out or
    written back (the six operations that were 29.6 ms of a 55 ms decode
    step while the pool went in as the scan's xs and came back as ys)."""
    fn, shapes, statics = _paged_program(kind)
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    compiled = fn.lower(*args, **statics).compile()
    pool_elements = int(np.prod(_POOL))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * pool_elements      # one bf16 pool
    assert mem.alias_size_in_bytes >= 2 * 2 * pool_elements  # both donated
    moved = []
    for shape, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)"
            r"\(", compiled.as_text()):
        n = int(np.prod([int(d) for d in shape.split(",")]))
        if n in (pool_elements, pool_elements // _LAYERS):
            moved.append((op, shape))
    assert not moved
    if kind == "decode":
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert not re.search(r"= bf16\[16,\d+,32,128\]\S* gather\(", text)


def _banks_seen(compiled, bank):
    """The calls of the grouped kernel in ``compiled`` as a device trace
    names an operation (``benchmarks/trace_reduce.short_name``: the
    instruction with its operands' shapes, without layouts, cut at 160
    characters), each of which must name the whole bank ``[E, h, f]``
    there: the trace's readers find a routed layer's time by it."""
    from jax._src.lib import xla_client as xc

    from benchmarks import trace_reduce
    options = xc._xla.HloPrintOptions()
    options.print_operand_shape = True
    options.print_metadata = False
    text = "\n".join(m.to_string(options)
                     for m in compiled.runtime_executable().hlo_modules())
    calls = [trace_reduce.short_name(line) for line in re.findall(
        r"^\s*(?:ROOT )?(%grouped_swiglu[.\d]* = .*)$", text, re.M)]
    mark = "[" + ",".join(map(str, bank)) + "]"
    assert all(mark in call for call in calls), calls
    return calls


# the routed serving cell's engine: 64 experts of 2304 x 896, 32 query and
# 4 KV heads of 128 under a hidden size of 2304, a 98304-row head, depth 8
# (three window layers of 1024 to one full layer, twice), 16 slots x 8192,
# block 16, chunk 512
_R_LAYERS, _R_HIDDEN, _R_KV, _R_EXPERTS, _R_FF, _R_VOCAB = 8, 2304, 4, 64, \
    896, 98304
_R_BLOCKS, _R_TABLE, _R_CHUNK, _R_WINDOW = 8193, 512, 512, 1024
_R_POOL = (_R_LAYERS, _R_BLOCKS, _BS, _R_KV, _HD)


def _routed_program(kind):
    """A paged program of the engine for a model of layer kinds with
    routed experts, as the engine jits it: the shapes of its arguments
    (the expert banks a tuple of the layers' own arrays, the counters
    last) and its statics."""
    from paddle_tpu.serving import engine as E

    L, S, V, h = _R_LAYERS, _ENGINE_SLOTS, _R_VOCAB, _R_HIDDEN
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    w = {"wq": (L, h, _H * _HD), "wk": (L, h, _R_KV * _HD),
         "wv": (L, h, _R_KV * _HD), "wo": (L, _H * _HD, h),
         "wr": (L, h, _R_EXPERTS), "ln1": (L, h), "ln2": (L, h),
         "embed": (V, h), "norm": (h,), "head": (h, V)}
    w = {k: (s, bf) for k, s in w.items()}
    w.update(rope_inv=((L, _HD // 2), f32), rope_scale=((L,), f32))
    for name, shape in (("wg", (_R_EXPERTS, h, _R_FF)),
                        ("wu", (_R_EXPERTS, h, _R_FF)),
                        ("wd", (_R_EXPERTS, _R_FF, h))):
        w[name] = [(shape, bf)] * L
    pool, scalar = (_R_POOL, bf), ((), i32)
    slots, keys = ((S,), i32), ((S, 2), jnp.uint32)
    moe = {"expert_tokens": ((L, _R_EXPERTS), i32), "experts_hit": ((L,), i32),
           "decode_calls": ((), i32)}
    statics = dict(arch="llama", n_heads=_H, n_kv=_R_KV, eps=1e-6, theta=0.0,
                   do_sample=False, top_k=0, top_p=None, block_size=_BS,
                   kinds=(("sliding_attention",) * 3 + ("full_attention",))
                   * (L // 4),
                   window=_R_WINDOW, moe_k=8)
    row, temp, vmask = ((_R_TABLE,), i32), ((), f32), ((V,), f32)
    if kind == "decode":
        return (E._PAGED_DECODE_DONATED,
                [w, pool, pool, ((S, _R_TABLE), i32), slots, slots,
                 ((S,), jnp.bool_), keys, ((S,), f32), ((S, V), f32), moe],
                statics)
    if kind == "chunk":
        return (E._PAGED_CHUNK_DONATED,
                [w, pool, pool, slots, slots, keys, ((1, _R_CHUNK), i32),
                 scalar, scalar, scalar, row, scalar, scalar,
                 ((), jnp.uint32), scalar, temp, vmask, moe], statics)
    return (E._PAGED_PREFILL_DONATED,
            [w, pool, pool, slots, slots, keys, ((1, 512), i32), scalar,
             scalar, ((), jnp.uint32), scalar, temp, row, scalar, vmask, moe],
            statics)


@pytest.mark.parametrize("kind", ("decode", "chunk", "prefill"))
def test_routed_program_compiles_and_copies_neither_pool_nor_banks(
        kind, one_chip, no_compile_cache):
    """At the published widths: a layer's expert banks reach their
    matmuls as the arrays they are (a slice of a stacked bank was copied
    first: 3 GB a decode step at depth 4), the pool with its 4 KV
    heads is written in place and never relaid whole before a gather or
    the decode kernel (gathered by blocks it was: 8 copies of 537 MB a
    step), the decode program holds the paged-attention kernel once a
    layer and no gathered view, and the chunk's
    full layer walks its 8192 keys in tiles (one pass over float32
    ``[4, 8, 512, 8192]`` scores took 47 ms on the chip). Each routed
    layer is one call of the grouped kernel, which a device trace names
    beside the whole banks' shapes."""
    fn, shapes, statics = _routed_program(kind)
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], type(jnp.bfloat16)))
    compiled = fn.lower(*args, **statics).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    pool_elements = int(np.prod(_R_POOL))
    bank_elements = _R_EXPERTS * _R_HIDDEN * _R_FF
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 2 * pool_elements  # both donated
    # under two banks of temporaries, in all three (a pool is four)
    assert mem.temp_size_in_bytes < 4 * bank_elements
    assert not re.search(r"f32\[4,8,512,8192\]", text)
    moved = []
    for shape, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|"
            r"dynamic-update-slice)\(", text):
        n = int(np.prod([int(d) for d in shape.split(",")]))
        if n in (pool_elements, pool_elements // _R_LAYERS, bank_elements):
            moved.append((op, shape))
    assert not moved
    calls = len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text))
    assert len(_banks_seen(compiled, (_R_EXPERTS, _R_HIDDEN, _R_FF))) \
        == _R_LAYERS
    if kind == "decode":
        # window layers and full ones run the same kernel, one a layer,
        # beside the grouped one
        assert calls == 2 * _R_LAYERS
        assert not re.search(r"= bf16\[16,\d+,4,128\]\S* gather\(", text)
    else:
        assert calls == _R_LAYERS


# the latent serving cell's engine: hidden 7168, 64 heads over a latent of
# 512 beside a shared rotary key of 64 (a line padded to 640), q through a
# rank of 1536; one dense layer of 18432 then four routed ones holding 12
# of 384 experts of 2048 beside a shared expert; a 20480-row slice of the
# head; 32 slots x 16384, block 16, chunk 512
_K_LAYERS, _K_HIDDEN, _K_HEADS, _K_HELD, _K_SCORED, _K_VOCAB = 5, 7168, 64, \
    12, 384, 20480
_K_BLOCKS, _K_TABLE, _K_SLOTS, _K_LINE = 32769, 1024, 32, 640
_K_POOL = (_K_LAYERS, _K_BLOCKS, _BS, 1, _K_LINE)


def _latent_program(kind):
    """A paged program of the engine for a latent model, as the engine
    jits it: every per-layer leaf a tuple of the layers' own arrays (None
    where a layer has no such leaf), one pool and None for the V pool, the
    counters of a share last."""
    from paddle_tpu.serving import engine as E

    L, S, V, h = _K_LAYERS, _K_SLOTS, _K_VOCAB, _K_HIDDEN
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    every = {"ln1": (h,), "wqa": (h, 1536), "qln": (1536,),
             "wqb": (1536, _K_HEADS * 192), "wkva": (h, 576), "kvln": (512,),
             "wkvb": (512, _K_HEADS * 256), "wo": (_K_HEADS * 128, h),
             "ln2": (h,)}
    w = {k: [(s, bf)] * L for k, s in every.items()}
    w.update(rope_inv=[((32,), f32)] * L, rope_scale=[((), f32)] * L)
    dense = {"wg": (h, 18432), "wu": (h, 18432), "wd": (18432, h)}
    routed = {"wg": (_K_HELD, h, 2048), "wu": (_K_HELD, h, 2048),
              "wd": (_K_HELD, 2048, h), "wr": (h, _K_SCORED),
              "rb": (_K_SCORED,), "sg": (h, 2048), "su": (h, 2048),
              "sd": (2048, h)}
    for k, s in routed.items():
        w[k] = [(dense[k], bf) if k in dense else None] + [(s, bf)] * (L - 1)
    w.update(embed=((V, h), bf), norm=((h,), bf), head=((h, V), bf))
    pool, scalar = (_K_POOL, bf), ((), i32)
    slots, keys = ((S,), i32), ((S, 2), jnp.uint32)
    moe = {"expert_tokens": ((L - 1, _K_HELD), i32),
           "experts_hit": ((L - 1,), i32), "decode_calls": ((), i32),
           "picks": ((), i32)}
    statics = dict(arch="latent", n_heads=_K_HEADS, n_kv=1, eps=1e-5,
                   theta=0.0, do_sample=False, top_k=0, top_p=None,
                   block_size=_BS, kinds=("dense",) + ("routed",) * (L - 1),
                   moe_k=8, attn_scale=0.14467962580268923,
                   router=(("first", 0), ("scale", 2.827),
                           ("scoring", "sigmoid")))
    row, temp, vmask = ((_K_TABLE,), i32), ((), f32), ((V,), f32)
    if kind == "decode":
        return (E._PAGED_DECODE_DONATED,
                [w, pool, None, ((S, _K_TABLE), i32), slots, slots,
                 ((S,), jnp.bool_), keys, ((S,), f32), ((S, V), f32), moe],
                statics)
    if kind == "chunk":
        return (E._PAGED_CHUNK_DONATED,
                [w, pool, None, slots, slots, keys, ((1, 512), i32),
                 scalar, scalar, scalar, row, scalar, scalar,
                 ((), jnp.uint32), scalar, temp, vmask, moe], statics)
    return (E._PAGED_PREFILL_DONATED,
            [w, pool, None, slots, slots, keys, ((1, 512), i32), scalar,
             scalar, ((), jnp.uint32), scalar, temp, row, scalar, vmask, moe],
            statics)


@pytest.mark.parametrize("kind", ("decode", "chunk", "prefill"))
def test_latent_program_compiles_and_moves_neither_pool_nor_banks(
        kind, one_chip, no_compile_cache):
    """At the published widths and the cell's sizes: the one pool (3.36
    GB) is donated and written in place, never copied or relaid whole, no
    layer's held banks are copied, the three programs fit beside 10.4 GB
    of weights and pool, the decode program holds the latent kernel once
    a layer and no ``[slots, max_len, ...]`` view of the lines, and the
    chunk program holds the chunk kernel once a layer and neither the
    float32 scores ``[heads, chunk rows, tile]`` nor a tile's expansion
    ``[tile, heads, nope + v]`` as a buffer: it plans less beside its
    arguments than the 0.43 GB the plain form did."""
    fn, shapes, statics = _latent_program(kind)
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], type(jnp.bfloat16)))
    compiled = fn.lower(*args, **statics).compile()
    text = compiled.as_text()
    pool_elements = int(np.prod(_K_POOL))
    bank_elements = _K_HELD * _K_HIDDEN * 2048
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_elements       # donated
    assert mem.temp_size_in_bytes < 2.5e9
    moved = []
    for shape, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|"
            r"dynamic-update-slice)\(", text):
        n = int(np.prod([int(d) for d in shape.split(",")]))
        if n in (pool_elements, pool_elements // _K_LAYERS, bank_elements):
            moved.append((op, shape))
    assert not moved
    calls = len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text))
    # each routed layer's held experts: one call of the grouped kernel
    assert len(_banks_seen(compiled, (_K_HELD, _K_HIDDEN, 2048))) \
        == _K_LAYERS - 1
    if kind == "decode":
        assert calls == _K_LAYERS + _K_LAYERS - 1
        assert "paged_latent_attention" in text
        assert not re.search(r"\[32,16384,", text)
    if kind == "chunk":
        from paddle_tpu.text.generation import _LATENT_TILE
        assert calls == _K_LAYERS + _K_LAYERS - 1
        assert "chunk_attention" in text
        assert not re.search(rf"\[{_K_HEADS},512,{_LATENT_TILE}\]", text)
        assert not re.search(rf"\[{_LATENT_TILE},{_K_HEADS},256\]", text)
        assert mem.temp_size_in_bytes < 0.43e9


# the hybrid serving cell's engine: 32 layers of five kinds at hidden 2560
# (9 recurrent of d_inner 5120 x state 16, 8 window of 512, one full, 7 gated
# units, 7 query-only), 40 query / 20 KV heads of 64 (a KV pair's line 128
# wide), a tied 200064-row head, 64 slots x 8192, block 16, chunk 512
_S_HIDDEN, _S_FF, _S_INNER, _S_STATE, _S_RANK, _S_VOCAB = 2560, 10240, 5120, \
    16, 160, 200064
_S_SLOTS, _S_TABLE, _S_WINDOW, _S_PAIRS = 64, 512, 512, 10
_S_POOL = (1, _S_SLOTS * _S_TABLE + 1, _BS * _S_PAIRS, _HD)
_S_RINGS = (8, _S_SLOTS * _S_WINDOW // _BS + 1, _BS * _S_PAIRS, _HD)


def _sambay_program(kind):
    """A program of ``serving/sambay_programs.py`` as the engine jits it:
    every per-layer leaf a tuple of the layers' own arrays (None where a
    layer has no such leaf), the pool of ONE layer and the rings folded
    (lines and KV pairs of a block one run of rows), the state last."""
    from paddle_tpu.serving import sambay_programs as sp
    from paddle_tpu.text.models.phi4flash import Phi4FlashConfig

    kinds = Phi4FlashConfig().layer_kinds()
    h, f, di, ds, r, V, S = (_S_HIDDEN, _S_FF, _S_INNER, _S_STATE, _S_RANK,
                             _S_VOCAB, _S_SLOTS)
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    lam = {k: ((64,), f32) for k in ("lq1", "lk1", "lq2", "lk2")}
    every = {"ln1w": ((h,), bf), "ln1b": ((h,), bf), "ln2w": ((h,), bf),
             "ln2b": ((h,), bf), "wgu": ((h, 2 * f), bf), "wd": ((f, h), bf)}
    own = {
        "mamba": {"win": ((h, 2 * di), bf), "convw": ((4, di), bf),
                  "convb": ((di,), bf), "wx": ((di, r + 2 * ds), bf),
                  "wdt": ((r, di), bf), "bdt": ((di,), f32),
                  "alog": ((di, ds), f32), "dskip": ((di,), f32),
                  "wout": ((di, h), bf)},
        "gmu": {"gin": ((h, di), bf), "gout": ((di, h), bf)},
        "sliding_attention": dict(lam, wqkv=((h, 2 * h), bf),
                                  wo=((h, h), bf), subln=((128,), bf)),
        "cross_attention": dict(lam, wq=((h, h), bf), wo=((h, h), bf),
                                subln=((128,), bf))}
    own["full_attention"] = own["sliding_attention"]
    names = dict.fromkeys(n for d in own.values() for n in d)
    w = {n: tuple(own[k].get(n) for k in kinds) for n in names}
    w.update({n: (sd,) * len(kinds) for n, sd in every.items()})
    w.update(embed=((V, h), bf), normw=((h,), bf), normb=((h,), bf))
    state = {"wk": (_S_RINGS, bf), "wv": (_S_RINGS, bf),
             "ssm": ((9, S, ds, di), f32), "conv": ((9, S, 3, di), bf)}
    statics = dict(arch="sambay", theta=0.0, do_sample=False, top_k=0,
                   top_p=None, block_size=_BS, kinds=kinds, n_heads=40,
                   n_kv=20, eps=1e-5, window=_S_WINDOW)
    pool, scalar = (_S_POOL, bf), ((), i32)
    slots, keys = ((S,), i32), ((S, 2), jnp.uint32)
    row, temp, vmask = ((_S_TABLE,), i32), ((), f32), ((V,), f32)
    if kind == "decode":
        return (sp.DECODE_DONATED,
                [w, pool, pool, ((S, _S_TABLE), i32), slots, slots,
                 ((S,), jnp.bool_), keys, ((S,), f32), ((S, V), f32), state],
                statics)
    if kind == "chunk":
        return (sp.CHUNK_DONATED,
                [w, pool, pool, slots, slots, keys, ((1, 512), i32), scalar,
                 scalar, scalar, row, scalar, scalar, ((), jnp.uint32),
                 scalar, temp, vmask, state], statics)
    return (sp.PREFILL_DONATED,
            [w, pool, pool, slots, slots, keys, ((1, 512), i32), scalar,
             scalar, ((), jnp.uint32), scalar, temp, row, scalar, vmask,
             state], statics)


@pytest.mark.parametrize("kind", ("decode", "chunk", "prefill"))
def test_hybrid_program_compiles_and_moves_no_pool(kind, one_chip,
                                                   no_compile_cache):
    """At the published widths and the cell's sizes, uncut: the pool's one
    layer (2.68 GB), the window layers' rings (1.34 GB) and the recurrent
    states are donated and written in place, none copied or relaid whole
    (held ``[.., 16, 10, 128]`` the chip's compiler relaid the pools,
    padded, around every scatter: 4.9 GB of temporaries); the three
    programs fit beside 12.0 GB of weights and state; the decode program
    holds the paged kernel once an attention layer (8 window layers over
    the rings, the full layer and 7 query-only layers over the pool)."""
    fn, shapes, statics = _sambay_program(kind)
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], type(jnp.bfloat16)))
    compiled = fn.lower(*args, **statics).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    held = 2 * 2 * (int(np.prod(_S_POOL)) + int(np.prod(_S_RINGS)))
    assert mem.alias_size_in_bytes >= held                    # donated
    assert mem.argument_size_in_bytes < 12.1e9
    assert mem.temp_size_in_bytes < 0.4e9
    sizes = {int(np.prod(_S_POOL)), int(np.prod(_S_RINGS)),
             int(np.prod(_S_RINGS)) // 8}
    # (a line's scatter is a dynamic-update-slice of the donated pool, in
    # place: a copy of a pool would show among the temporaries above)
    moved = [(op, shape) for shape, op in re.findall(
        r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice)\(", text)
        if int(np.prod([int(d) for d in shape.split(",")])) in sizes]
    assert not moved
    kernels = len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                             text))
    assert kernels == (16 if kind == "decode" else 0)
    if kind == "decode":
        assert "paged_attention" in text
        assert not re.search(r"\[64,8192,", text)       # no gathered view


_LOWER = """
import json, os, sys
root = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, root)
import jax
import jax.numpy as jnp
from paddle_tpu.serving import engine as E


def build(x):
    if isinstance(x, dict):
        return {k: build(v) for k, v in x.items()}
    if len(x) == 2 and isinstance(x[1], str):
        return jax.ShapeDtypeStruct(tuple(x[0]), jnp.dtype(x[1]))
    return [build(v) for v in x]


spec = json.load(open(os.path.join(root, "decode.json")))
statics = {k: tuple(v) if isinstance(v, list) else v
           for k, v in spec["statics"].items()}
lowered = E._PAGED_DECODE_DONATED.trace(
    *build(spec["args"]), **statics).lower(lowering_platforms=("tpu",))
open(os.path.join(root, "decode.txt"), "w").write(lowered.as_text())
"""


def test_decode_program_text_is_the_same_from_any_checkout(tmp_path):
    """jax's compile-cache key holds a program's text without locations,
    but a Pallas kernel's payload is embedded whole, locations included:
    with absolute paths in it, the routed cell's decode program (the
    kernel once a layer) would be compiled again by every checkout that
    is not at the path that wrote the entry. Lowered for a TPU (no chip,
    no TPU library: two child processes) from two copies of the package
    at different depths, the texts are equal byte for byte."""
    import json
    import os
    import shutil
    import subprocess
    import sys

    import paddle_tpu

    _, shapes, statics = _routed_program("decode")
    named = jax.tree.map(
        lambda sd: [list(sd[0]), jnp.dtype(sd[1]).name], shapes,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], type(jnp.bfloat16)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    roots, children = [tmp_path / "a", tmp_path / "b" / "deeper"], []
    for root in roots:
        shutil.copytree(os.path.dirname(paddle_tpu.__file__),
                        root / "paddle_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (root / "decode.json").write_text(json.dumps(
            {"args": named, "statics": statics}))
        (root / "lower.py").write_text(_LOWER)
        children.append(subprocess.Popen(
            [sys.executable, str(root / "lower.py")], cwd=root, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    for child in children:
        out, _ = child.communicate(timeout=600)
        assert child.returncode == 0, out[-2000:]
    first, second = ((root / "decode.txt").read_text() for root in roots)
    assert first.count("tpu_custom_call") >= _R_LAYERS
    assert str(tmp_path) not in first
    assert first == second
