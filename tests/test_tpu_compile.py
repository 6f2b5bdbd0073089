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


def _flash_decode(n_kv):
    from paddle_tpu.ops.pallas.flash_decode import flash_decode
    pool = ((_BLOCKS, _BS, n_kv, _HD), jnp.bfloat16)
    return flash_decode, [((_SLOTS, _H, _HD), jnp.bfloat16), pool, pool,
                          ((_SLOTS, (_BLOCKS - 1) // _SLOTS), jnp.int32),
                          ((_SLOTS,), jnp.int32)]


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


def _stochastic(name):
    from paddle_tpu.nn import quant
    return (functools.partial(getattr(quant, name), seed=7),
            [((_HIDDEN, _HIDDEN), jnp.float32)])


_CASES = {
    "flash_attention-fwd": functools.partial(_flash_attention, False),
    "flash_attention-grad": functools.partial(_flash_attention, True),
    "flash_decode-n_kv32": functools.partial(_flash_decode, 32),
    "flash_decode-n_kv8": functools.partial(_flash_decode, 8),
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
