"""CPU interpret-mode parity for the serving and MoE kernels (paged
decode attention, ragged MoE matmul, fused sharded-vocab CE) and the
engine-level token-identity contract of the paged-attention kernel
through prefix sharing, preemption and adopt() replay.

Kept slim for the tier-1 budget: tiny shapes, one module-scope model,
config sweeps marked slow.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.fused_ce import (fused_ce_loss,
                                            fused_ce_reference,
                                            sharded_vocab_ce)
from paddle_tpu.ops.pallas.ragged_matmul import (
    ragged_dot, ragged_group_matmul, ragged_group_matmul_reference)
from paddle_tpu.serving import Engine
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# paged decode attention: the kernel (interpret mode) against the gathered
# form
# ---------------------------------------------------------------------------

_BS, _MB, _NB, _HD = 4, 7, 30, 16      # lines a block, table width, pool


def _pa_case(heads, *, pos=None, window=0, layers=1, tail=None, seed=0):
    """``(q, kc, vc, tables, write_pos, window)`` in float32: a pool flat
    over ``layers`` whose block 0 of each layer is its trash block, every
    slot's table drawn from the other blocks of the LAST layer (ids moved
    by ``i * nb``, as the engine's layer loop moves them)."""
    H, n_kv = heads
    rng = np.random.default_rng(seed)
    S = 3 if pos is None else len(pos)
    q = rng.standard_normal((S, H, _HD))
    kc, vc = (rng.standard_normal((layers * _NB, _BS, n_kv, _HD))
              for _ in range(2))
    tables = rng.integers(1, _NB, (S, _MB))
    pos = rng.integers(0, _MB * _BS, (S,)) if pos is None else np.asarray(pos)
    if tail is not None:
        # entries past a slot's last live block point at the trash block,
        # which holds ``tail``: a walk that reads it shows it
        live = np.arange(_MB)[None, :] <= (pos // _BS)[:, None]
        tables = np.where(live, tables, 0)
        kc[(layers - 1) * _NB], vc[(layers - 1) * _NB] = tail, tail
    tables = tables + (layers - 1) * _NB
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return (f32(q), f32(kc), f32(vc), jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.int32(window))


_PA_CASES = {
    "heads_32_of_32": dict(heads=(32, 32)),
    "heads_32_of_4": dict(heads=(32, 4)),
    "heads_32_of_8": dict(heads=(32, 8)),
    "window_shorter_than_the_sequence": dict(heads=(8, 2), window=6,
                                             pos=[27, 13, 5, 6]),
    "window_longer_than_the_sequence": dict(heads=(8, 2), window=1000),
    "first_line_block_end_and_whole_table": dict(
        heads=(4, 2), pos=[0, _BS - 1, _MB * _BS - 1]),
    "a_slot_that_sees_nothing": dict(heads=(4, 2), pos=[-1, 9, -1]),
    "blocks_a_step_not_dividing_the_table": dict(heads=(4, 2), step=3,
                                                 pos=[27, 11, 3]),
    "one_block_a_step": dict(heads=(4, 1), step=1, window=9),
    "table_tails_at_the_trash_block": dict(heads=(8, 4), tail=np.nan,
                                           pos=[17, 3, 22]),
    "pool_flat_over_layers": dict(heads=(8, 2), layers=3, window=10),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_PA_CASES))
def test_paged_attention_kernel_against_the_gathered_form(case, dtype,
                                                          monkeypatch):
    """In float32 the kernel is the gathered form to 2e-5; in bf16 it is
    no further from a float32 evaluation of the same bf16 inputs than the
    gathered form is. A slot that sees nothing returns zeros, and a trash
    block full of NaN behind the live blocks is never read."""
    kw = dict(_PA_CASES[case])
    step, tail = kw.pop("step", None), kw.get("tail")
    args = _pa_case(**kw)
    H, n_kv = kw["heads"]
    if step is not None:
        monkeypatch.setattr(pa, "_STEP_BYTES", step * _BS * n_kv * _HD
                            * jnp.dtype(dtype).itemsize)
    # the oracle never multiplies by what the kernel may not read
    clean = _pa_case(**dict(kw, tail=0.0)) if tail is not None else args
    blind = np.asarray(args[4]) < 0

    def cast(a):
        return tuple(x.astype(dtype) for x in a[:3]) + tuple(a[3:])

    got = np.asarray(pa.paged_attention(*cast(args), interpret=True),
                     np.float32)
    assert np.all(got[blind] == 0.0)
    exact = np.asarray(pa.gathered(*cast(clean)[:3], *clean[3:])
                       if dtype == "float32" else
                       pa.gathered(*(x.astype(dtype).astype(jnp.float32)
                                     for x in clean[:3]), *clean[3:]))
    if dtype == "float32":
        np.testing.assert_allclose(got[~blind], exact[~blind], atol=2e-5,
                                   rtol=2e-5)
        return
    plain = np.asarray(pa.gathered(*cast(clean)), np.float32)
    err, plain_err = (np.abs(x[~blind] - exact[~blind]) for x in (got, plain))
    assert err.mean() <= plain_err.mean()
    assert err.max() <= plain_err.max() * 1.01 + 1e-6


# ---------------------------------------------------------------------------
# ragged grouped matmul
# ---------------------------------------------------------------------------

def test_ragged_matmul_parity_and_tile_skip():
    """Counts of 0 / partial / full per group, unaligned C and N."""
    rng = np.random.default_rng(0)
    G, C, K, N = 4, 19, 8, 13
    x = jnp.asarray(rng.standard_normal((G, C, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    counts = jnp.asarray([0, 5, 19, 12], jnp.int32)
    got = ragged_group_matmul(x, w, counts, block_m=8, block_n=8,
                              interpret=True)
    ref = ragged_group_matmul_reference(x, w, counts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # rows past the count are exactly zero (not just close)
    assert not np.asarray(got)[0].any()
    assert not np.asarray(got)[1, 5:].any()


def test_ragged_dot_grads_match_masked_einsum():
    rng = np.random.default_rng(1)
    G, C, K, N = 2, 8, 4, 6
    x = jnp.asarray(rng.standard_normal((G, C, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    counts = jnp.asarray([3, 8], jnp.int32)
    gx, gw = jax.grad(lambda x, w: ragged_dot(x, w, counts, True).sum(),
                      argnums=(0, 1))(x, w)
    rx, rw = jax.grad(
        lambda x, w: ragged_group_matmul_reference(x, w, counts).sum(),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-5)


def test_moe_layer_ragged_kernel_matches_einsum():
    from paddle_tpu.nn.moe import MoELayer
    paddle.seed(0)
    m_e = MoELayer(16, 32, 4, k=2, dispatch_mode="sparse",
                   expert_kernel="einsum")
    paddle.seed(0)
    m_r = MoELayer(16, 32, 4, k=2, dispatch_mode="sparse",
                   expert_kernel="ragged")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((2, 8, 16)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(m_e(x)._data),
                               np.asarray(m_r(x)._data), atol=1e-5)


# ---------------------------------------------------------------------------
# fused sharded-vocab CE
# ---------------------------------------------------------------------------

def _ce_case(rng, N, H, V):
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.2, jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    return h, w, lab


def test_fused_ce_value_and_grads():
    rng = np.random.default_rng(0)
    h, w, lab = _ce_case(rng, 24, 16, 103)   # V not a tile multiple
    got = fused_ce_loss(h, w, lab, 8, 32, True)
    ref = fused_ce_reference(h, w, lab)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    gf = jax.grad(lambda h, w: fused_ce_loss(h, w, lab, 8, 32, True),
                  argnums=(0, 1))(h, w)
    gr = jax.grad(lambda h, w: fused_ce_reference(h, w, lab),
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gr[0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gr[1]),
                               atol=2e-5)


def test_sharded_vocab_ce_ring_psum_free():
    """4-way vocab shard under shard_map: value + grads match the dense
    reference and the lowered HLO carries NO all-reduce (ppermute ring
    only — the PR-11 machinery)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng(1)
    N, H, V, tp = 16, 8, 64, 4
    h, w, lab = _ce_case(rng, N, H, V)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))

    def f(h, w):
        return shard_map(
            lambda h, w, l: sharded_vocab_ce(h, w, l, "tp", tp, 8, 16,
                                             True),
            mesh=mesh, in_specs=(P(), P(None, "tp"), P()),
            out_specs=P(), check_rep=False)(h, w, lab)

    np.testing.assert_allclose(float(f(h, w)),
                               float(fused_ce_reference(h, w, lab)),
                               rtol=1e-5)
    gs = jax.jit(jax.grad(f, argnums=(0, 1)))(h, w)
    gr = jax.grad(lambda h, w: fused_ce_reference(h, w, lab),
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gs[0]), np.asarray(gr[0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gs[1]), np.asarray(gr[1]),
                               atol=2e-5)
    hlo = jax.jit(f).lower(h, w).as_text()   # StableHLO spelling
    assert "all_reduce" not in hlo and "all-reduce" not in hlo
    assert "collective_permute" in hlo or "collective-permute" in hlo


@pytest.mark.slow
def test_fused_ce_config_sweep():
    rng = np.random.default_rng(2)
    h, w, lab = _ce_case(rng, 40, 24, 257)
    ref = float(fused_ce_reference(h, w, lab))
    for bn in (8, 16, 64):
        for bv in (32, 128, 512):
            got = float(fused_ce_loss(h, w, lab, bn, bv, True))
            np.testing.assert_allclose(got, ref, rtol=1e-5)


# ---------------------------------------------------------------------------
# the kernel inside the engine: token identity through the serving paths
# ---------------------------------------------------------------------------

@pytest.fixture
def kernel_in_decode(monkeypatch):
    """Decode programs traced while this holds run the paged-attention
    kernel through the interpreter (on a TPU every decode program runs
    it; here the platform would pick the gathered form). The engine's
    jitted decode programs are module-level, so their traces are dropped
    on the way in and on the way out."""
    from paddle_tpu.serving import engine as E

    def forget():
        E._PAGED_DECODE.clear_cache()
        E._PAGED_DECODE_DONATED.clear_cache()

    traced, kernel = [], pa.paged_attention

    def interpreted(q, *args):
        traced.append(q.shape)
        return kernel(q, *args, interpret=True)

    forget()
    monkeypatch.setattr(pa, "paged_attention", interpreted)
    yield traced       # the query shapes of the kernel calls traced so far
    forget()


def _served(model, prompts, sample=False, **kw):
    if sample:
        kw.update(do_sample=True, top_k=8)
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8, **kw)
    hs = eng.generate_all(prompts, max_new_tokens=6,
                          **({"temperature": 0.9, "seed": 11}
                             if sample else {}))
    return eng, [h.result().tolist() for h in hs]


@pytest.mark.parametrize("sample", [False, True], ids=["greedy", "sampled"])
def test_engine_kernel_token_identical_with_prefix_sharing(model, sample,
                                                           request):
    """Kernel against gathered decode attention: same tokens over a
    shared-prefix workload — prefix sharing, block tables and the PRNG
    chains are untouched by what computes attention."""
    sys_p = _prompts([12], seed=7)[0]
    prompts = [np.concatenate([sys_p, t]) for t in _prompts([4, 6], seed=8)]
    _, plain = _served(model, prompts, sample)
    traced = request.getfixturevalue("kernel_in_decode")
    eng, got = _served(model, prompts, sample)
    assert got == plain
    assert eng.stats()["prefix_hit_tokens"] > 0
    # one decode program, whose scan over the layers holds one kernel
    assert traced == [(2, CFG.num_attention_heads,
                       CFG.hidden_size // CFG.num_attention_heads)]


def test_engine_kernel_preempt_and_adopt_replay(model, kernel_in_decode):
    """The replay machinery over the kernel: pool exhaustion preempts
    and replays token-identically, and a fresh engine adopt()s
    mid-flight handles to the same tokens as an uninterrupted run."""
    prompts = _prompts([12, 12], seed=4)

    def baseline(p, n):
        out = model.generate(paddle.to_tensor(p[None]), max_new_tokens=n)
        return np.asarray(out._data)[0, len(p):]

    # preemption: pool sized below the combined worst case
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8, n_blocks=6, prefix_sharing=False)
    h1 = eng.submit(prompts[0], max_new_tokens=16)
    h2 = eng.submit(prompts[1], max_new_tokens=16)
    eng.drain()
    assert eng.stats()["preemptions"] >= 1
    np.testing.assert_array_equal(np.asarray(h1.tokens, np.int32),
                                  baseline(prompts[0], 16))
    np.testing.assert_array_equal(np.asarray(h2.tokens, np.int32),
                                  baseline(prompts[1], 16))

    # adopt(): decode a few tokens, migrate the live handle to a fresh
    # engine, finish there — tokens equal the uninterrupted run
    src = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8)
    h = src.submit(prompts[0], max_new_tokens=10)
    for _ in range(4):
        src.step()
    assert 0 < len(h.tokens) < 10
    src._condemned = True
    dst = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8)
    dst.adopt(h)
    dst.drain()
    np.testing.assert_array_equal(np.asarray(h.tokens, np.int32),
                                  baseline(prompts[0], 10))
    assert len(kernel_in_decode) == 2       # two pools: two programs


def test_engine_counts_the_lines_its_decode_calls_see(model):
    """``stats()["decode_lines_seen"]``: a request of ``p`` prompt tokens
    and ``n`` new ones makes ``n - 1`` decode calls that see ``p + 1`` ..
    ``p + n - 1`` lines; a model without a window sees as many inside
    one."""
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8)
    eng.generate_all(_prompts([9]), max_new_tokens=5)
    seen = eng.stats()["decode_lines_seen"]
    assert seen == {"calls": 4, "lines": 10 + 11 + 12 + 13,
                    "in_window": 10 + 11 + 12 + 13}
