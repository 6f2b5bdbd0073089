"""Where sdpa_raw's flash branch puts the kernel, in each context a trace
can reach it from: plain jit on the global mesh, a full-manual shard_map
(the comm-opt, DGC and compressed-allreduce train steps), a partial-manual
one (the pipeline's "pp", the sequence-parallel "sep"), and a shard_map
over a mesh of the caller's own. A Mosaic kernel is legal only where every
mesh axis is manual, and a nested shard_map may only name axes that are
not manual yet.

The forward and backward kernels are stubbed by the XLA composite here
(they do not run on the CPU), so everything around them is what runs on
the chip; tests/test_tpu_compile.py compiles the same contexts with the
real kernels for a described 2x2 mesh.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.nn.functional import attention
from paddle_tpu.ops.pallas import flash_attention as flash_mod

B, L, H, D = 8, 128, 4, 32


@pytest.fixture
def flash(monkeypatch):
    """Take the flash branch on the CPU, with the composite in place of
    the forward and the backward kernel; returns, for each kernel call,
    the manual axes and all axes of the context it was traced in, and the
    shape it saw ([B, H, L, D])."""
    seen = []

    def fwd(q, k, v, causal, scale, *_):
        ctx = jax.sharding.get_abstract_mesh()
        seen.append((frozenset(ctx.manual_axes), frozenset(ctx.axis_names),
                     q.shape))
        k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1)     # GQA
                for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v)
        return out.astype(q.dtype), lse

    def bwd(q, k, v, out, lse, do, causal, scale, *_):
        return jax.vjp(lambda *a: fwd(*a, causal, scale)[0], q, k, v)[1](do)

    monkeypatch.setenv("PADDLE_TPU_ATTENTION", "flash")
    monkeypatch.setattr(flash_mod, "_fwd", fwd)
    monkeypatch.setattr(flash_mod, "_bwd", bwd)
    return seen


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
                 for _ in range(3))


def _global(monkeypatch, **degrees):
    mesh = mesh_mod.build_mesh(
        devices=jax.devices()[:int(np.prod(list(degrees.values())))],
        **degrees)
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh)
    return mesh


def _loss(q, k, v):
    return attention.sdpa_raw(q, k, v, causal=True).sum()


def _want(qkv):
    return jax.grad(lambda *a: attention._xla_sdpa(*a, causal=True).sum(),
                    argnums=(0, 1, 2))(*qkv)


def _close(got, want):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)


def test_plain_jit_wraps_the_kernel_over_every_axis(flash, qkv, monkeypatch):
    mesh = _global(monkeypatch, dp=2, sharding=2, tp=2)
    got = jax.jit(jax.grad(_loss, argnums=(0, 1, 2)))(*qkv)
    _close(got, _want(qkv))
    manual, names, shape = flash[0]
    assert manual == names == frozenset(mesh.axis_names)
    assert shape == (B // 4, H // 2, L, D)


def test_full_manual_body_calls_the_kernel_where_it_is(flash, qkv,
                                                       monkeypatch):
    """Per-device batch 4 divides by dp: the case that once re-split it."""
    mesh = _global(monkeypatch, dp=2, tp=2)
    spec = P("dp", None, "tp", None)

    def body(q, k, v):
        return jax.grad(_loss, argnums=(0, 1, 2))(q, k, v)

    fn = mesh_mod.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=(spec,) * 3, check_vma=False)
    _close(jax.jit(fn)(*qkv), _want(qkv))
    assert {m for m, _, _ in flash} == {frozenset(mesh.axis_names)}
    assert {s for _, _, s in flash} == {(B // 2, H // 2, L, D)}


def test_partial_manual_body_wraps_the_rest(flash, qkv, monkeypatch):
    """Fwd and grad: jax 0.9.0 cannot differentiate a shard_map nested in
    another, so the two kernels are placed one by one."""
    mesh = _global(monkeypatch, dp=2, sep=2, tp=2)
    fn = mesh_mod.partial_manual(       # manual over "sep" only
        lambda q, k, v: attention.sdpa_raw(q, k, v, causal=True), mesh,
        {"sep"}, in_specs=(P("sep"),) * 3, out_specs=P("sep"))
    got = jax.jit(jax.grad(lambda *a: fn(*a).sum(), argnums=(0, 1, 2)))(*qkv)
    _close(got, _want(qkv))
    assert {(m, n) for m, n, _ in flash} == {(frozenset(mesh.axis_names),) * 2}
    assert {s for _, _, s in flash} == {(B // 2 // 2, H // 2, L, D)}


def test_ring_chunks_are_placed_too(flash, qkv, monkeypatch):
    """The ring calls the forward kernel alone, chunk pair by chunk pair,
    inside its shard_map over "sep"."""
    from paddle_tpu.ops.ring_attention import ring_attention

    mesh = _global(monkeypatch, dp=2, sep=2, tp=2)
    got = jax.jit(jax.grad(
        lambda *a: ring_attention(*a, causal=True).sum(),
        argnums=(0, 1, 2)))(*qkv)
    _close(got, _want(qkv))
    assert {(m, n) for m, n, _ in flash} == {(frozenset(mesh.axis_names),) * 2}
    assert {s for _, _, s in flash} == {(B // 2, H // 2, L // 2, D)}


@pytest.mark.parametrize("axes", [("tp",), ("x", "tp")])
def test_a_mesh_of_the_callers_own(flash, qkv, monkeypatch, axes):
    """ulysses_attention(mesh=...) and ring_attention(mesh=...) trace
    sdpa_raw under a mesh whose axes the global one may not have."""
    _global(monkeypatch, dp=8)
    own = Mesh(np.asarray(jax.devices()[:2 ** len(axes)]).reshape(
        (2,) * len(axes)), axes)
    spec = P(None, None, "tp", None)

    def body(q, k, v):
        return attention.sdpa_raw(q, k, v, causal=True)

    fn = mesh_mod.partial_manual(body, own, {"tp"}, in_specs=(spec,) * 3,
                                 out_specs=spec)
    got = jax.jit(fn)(*qkv)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention._xla_sdpa(*qkv, causal=True)),
        rtol=2e-5, atol=2e-5)
    manual, names, _ = flash[0]
    assert manual == names == frozenset(axes)


def test_comm_opt_step_on_a_llama(flash, monkeypatch):
    """The comm-opt step is one full-manual shard_map over (dp, tp); with a
    per-device batch that divides by dp the kernel must still see it whole."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

    dp, seqlen = 4, 128
    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=1)
    s = DistributedStrategy()
    s.hybrid_configs.update(dp_degree=dp, mp_degree=2)
    s.comm_opt = True
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    model = fleet.distributed_model(LlamaForCausalLM(cfg))
    opt = fleet.distributed_optimizer(
        optim.AdamW(learning_rate=1e-3, parameters=model.parameters()),
        strategy=s)
    step = opt.make_train_step(model, lambda m, i, l: m(i, labels=l))
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (dp * dp, seqlen)).astype(np.int32))
    losses = [float(np.asarray(step(ids, ids)._data)) for _ in range(3)]
    assert losses[-1] < losses[0]
    assert flash and {m for m, _, _ in flash} == {frozenset(mesh_mod.AXES)}
    assert {s[0] for _, _, s in flash} == {dp}
