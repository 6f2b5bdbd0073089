"""Tensor-parallel serving decode (paddle_tpu.serving, ``tp`` axis).

The TP contract: sharding the fused engine programs over a ``tp`` mesh
axis (column-parallel qkv/gate-up, row-parallel o-/down-proj, sharded
vocab head, kv-heads-split paged pool) must be invisible in the tokens —
greedy AND sampled output stays token-identical to the single-device
engine through prefix sharing, chunked prefill, pool preemption and
supervisor rebuild/adopt — while the compile budget stays at exactly
buckets + decode (+ chunk), one shard_map SPMD program each, and the
decode HLO carries ONLY overlapped collective-matmuls (ppermute rings;
the ``unoverlapped-collective`` rule reports 0 high findings). Fast set
kept lean for the tier-1 budget: one tiny module model, geometry shared
with test_serving_paged so single-device programs are warm in-process;
the TP=8 sweep/soak is marked slow. The compile-count/mesh contract CLI
lives in tools/check_serving_compiles.py --mesh N.
"""
import dataclasses

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.serving import Engine
from paddle_tpu.text.models.gpt import GPT_TINY, GPTForCausalLM
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
GEO = dict(n_slots=2, max_len=64, min_prompt_bucket=4, block_size=8)

needs4 = pytest.mark.skipif(len(jax.devices()) < 4,
                            reason="needs >= 4 virtual devices")
needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs >= 8 virtual devices")


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def models(model):
    gpt = GPTForCausalLM(GPT_TINY)
    gpt.eval()
    return {"llama": model, "gpt": gpt}


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _tokens(handles):
    return [list(h.tokens) for h in handles]


# ---------------------------------------------------------------------------
# one program, traced with tp == 1 or inside shard_map
# ---------------------------------------------------------------------------

_COLLECTIVES = ("collective_permute", "collective-permute", "all_gather",
                "all-gather", "all_reduce", "all-reduce")


def _lowered(eng, kind):
    """The text of one of the engine's programs, lowered from the
    operands and statics the engine calls it with."""
    for k, _, jitted, args, statics, _ in eng._aot_probe_specs(buckets=[8]):
        if k == kind:
            return jitted.lower(*args, **statics).as_text()
    raise KeyError(kind)


@pytest.mark.parametrize("kind", ("prefill", "decode", "chunk"))
@pytest.mark.parametrize("arch", ("llama", "gpt"))
def test_one_device_program_holds_no_collective(models, arch, kind):
    """``tp`` is a static of the paged programs: an engine on one device
    passes none, and what it traces holds no collective; the same
    function inside ``shard_map`` with ``tp=2`` holds the rings."""
    one = Engine(models[arch], prefill_chunk=8, **GEO)
    assert "tp" not in one._paged_statics
    text = _lowered(one, kind)
    assert not [op for op in _COLLECTIVES if op in text]
    two = Engine(models[arch], tp=2, prefill_chunk=8, **GEO)
    assert dict(two._tp_statics_items)["tp"] == 2
    assert "collective_permute" in _lowered(two, kind)


@pytest.mark.parametrize("arch", ("llama", "gpt"))
def test_tp2_prefill_writes_the_one_device_pool_lines(models, arch):
    """The sharded prefill writes the pool the one-device way, as rows
    of the pool flat over layers: gathered over the mesh, the lines a
    prompt wrote are the one-device pool's, and no other line outside
    the trash block was touched."""
    prompt = _prompts((11,), seed=4)[0]          # bucket 16: 5 pad rows
    pools = {}
    for tp in (1, 2):
        eng = Engine(models[arch], tp=tp, **GEO)
        h = eng.submit(prompt, max_new_tokens=2)     # prefills inside
        j = np.arange(len(prompt))
        rows = (eng.cache.block_tables[h.slot][j // eng.block_size]
                * eng.block_size + j % eng.block_size)
        flat = (eng.cache.n_layers, -1, eng.cache.kv_heads,
                eng.cache.head_dim)
        pools[tp] = (rows, np.asarray(eng.cache.kc).reshape(flat),
                     np.asarray(eng.cache.vc).reshape(flat))
    rows, k1, v1 = pools[1]
    rows2, k2, v2 = pools[2]
    assert np.array_equal(rows, rows2)
    untouched = np.setdiff1d(np.arange(GEO["block_size"], k1.shape[1]), rows)
    for one, two in ((k1, k2), (v1, v2)):
        assert one[:, rows].any(axis=(2, 3)).all()
        # np.allclose's rtol as elsewhere in this file; the ring sums a
        # row-parallel product in another order, so values near zero
        # differ by a float32 rounding of the largest term, not of theirs
        assert np.allclose(two[:, rows], one[:, rows], atol=1e-5)
        assert not one[:, untouched].any() and not two[:, untouched].any()


# ---------------------------------------------------------------------------
# construction contract
# ---------------------------------------------------------------------------

def test_tp_validation(model):
    with pytest.raises(TypeError, match="flash_decode"):
        Engine(model, flash_decode=True, **GEO)   # an arm that is gone
    with pytest.raises(ValueError, match="does not divide"):
        Engine(model, tp=3, **GEO)        # 8 heads / 4 kv not divisible
    with pytest.raises(ValueError, match="mesh"):
        Engine(model, mesh=object(), **GEO)   # mesh= needs tp > 1
    e = Engine(model, **GEO)
    assert e.tp == 1 and e.tp_geometry() is None
    assert "mesh" not in e.stats() and e.stats()["tp"] == 1


# ---------------------------------------------------------------------------
# TP=4 token parity: greedy + sampled + adopt (the acceptance set)
# ---------------------------------------------------------------------------

@needs4
def test_tp4_greedy_parity_vs_single_device_and_generate(model):
    prompts = _prompts((3, 5, 4))
    single = Engine(model, **GEO)
    tp4 = Engine(model, tp=4, compile_budget=3, **GEO)
    want = _tokens(single.generate_all(prompts, max_new_tokens=6))
    got = _tokens(tp4.generate_all(prompts, max_new_tokens=6))
    assert got == want
    # ... and both match batch generate() on the same prompt
    out = model.generate(paddle.to_tensor(prompts[0][None]),
                         max_new_tokens=6)
    assert got[0] == list(np.asarray(out._data)[0, len(prompts[0]):])
    # compile budget unchanged: 2 prefill buckets + ONE decode, each a
    # single shard_map SPMD program — the budget rule stays green
    rep = analysis.audit_engine(tp4)
    assert not [f for f in rep.findings
                if f.rule_id == "compile-budget"
                and f.severity == "high"]


@needs4
def test_tp4_sampled_parity_including_adopt(model):
    prompts = _prompts((3, 4, 2), seed=1)     # one bucket: lean compiles
    kw = dict(GEO, do_sample=True, top_k=8)
    single = Engine(model, **kw)
    tp4 = Engine(model, tp=4, **kw)
    want = _tokens(single.generate_all(prompts, max_new_tokens=6,
                                       temperature=0.9, seed=123))
    got = _tokens(tp4.generate_all(prompts, max_new_tokens=6,
                                   temperature=0.9, seed=123))
    assert got == want
    # mid-flight adopt() onto a rebuilt TP engine: the PRNG-chain
    # fast-forward keeps even sampled replay token-identical
    eng_a = Engine(model, tp=4, **kw)
    h = eng_a.submit(prompts[0], max_new_tokens=6, temperature=0.9,
                     seed=123)
    for _ in range(3):
        eng_a.step()
    assert 0 < len(h.tokens) < 6
    eng_a._condemned = True
    eng_b = Engine(model, tp=4, **kw)
    eng_b.adopt(h)
    h.result()
    assert list(h.tokens) == want[0]


# ---------------------------------------------------------------------------
# TP=2: chunked prefill + prefix sharing + pool preemption + supervisor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shared_prompts():
    rng = np.random.default_rng(7)
    sysp = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    return [np.concatenate(
        [sysp, rng.integers(0, CFG.vocab_size, (4,)).astype(np.int32)])
        for _ in range(4)]


TP2_KW = dict(GEO, prefill_chunk=16, n_blocks=16)


def test_tp2_chunked_sharing_preemption_parity(model, shared_prompts):
    single = Engine(model, **TP2_KW)
    tp2 = Engine(model, tp=2, **TP2_KW)
    want = _tokens(single.generate_all(shared_prompts, max_new_tokens=5))
    got = _tokens(tp2.generate_all(shared_prompts, max_new_tokens=5))
    assert got == want
    # the TP run exercised the full paged machinery, not a degenerate
    # path: chunked prefill ran, the radix shared the system prefix,
    # and the sharded pool stayed refcount-consistent
    assert tp2.metrics.chunk_steps > 0
    assert tp2.metrics.prefix_hit_tokens > 0
    assert tp2.cache.check_refcounts()
    assert tp2.chunk_used


def test_tp2_supervisor_rebuild_token_identical(model, shared_prompts):
    from paddle_tpu.resilience.chaos import ChaosMonkey
    from paddle_tpu.serving.resilience import EngineSupervisor

    want = _tokens(Engine(model, tp=2, **TP2_KW).generate_all(
        shared_prompts[:2], max_new_tokens=6, seed=11))
    chaos = ChaosMonkey(seed=3, at={2: "decode-raise"})
    sup = EngineSupervisor(model, chaos=chaos, tp=2, **TP2_KW)
    handles = [sup.submit(p, max_new_tokens=6, seed=11)
               for p in shared_prompts[:2]]
    sup.drain()
    assert sup.rebuilds == 1
    assert _tokens(handles) == want
    assert sup.engine.tp == 2         # the rebuilt incarnation is TP too


# ---------------------------------------------------------------------------
# geometry visibility + overlap evidence
# ---------------------------------------------------------------------------

@needs4
def test_tp_stats_audit_and_overlapped_decode_hlo(model):
    tp4 = Engine(model, tp=4, **GEO)
    tp4.generate_all(_prompts((3,)), max_new_tokens=2)
    st = tp4.stats()
    assert st["tp"] == 4
    mesh = st["mesh"]
    assert mesh["kv_pool_bytes_per_device"] * 4 == st["kv_cache_bytes"]
    assert mesh["kv_heads_per_device"] == CFG.num_key_value_heads // 4
    assert mesh["collectives_per_decode_step"] > 0
    assert len(mesh["devices"]) == 4
    # snapshot/profiler plumbing sees the geometry too
    snap = tp4.metrics.snapshot()
    assert snap["tp"] == 4 and snap["collectives_per_decode_step"] == \
        mesh["collectives_per_decode_step"]
    from paddle_tpu.serving.metrics import global_counters
    assert global_counters()["tp_max"] >= 4
    # the REAL lowered TP decode: ppermute rings only — 0 findings from
    # the unoverlapped-collective rule, no serial collective after a dot
    rep = analysis.audit_engine(tp4)
    uo = [f for f in rep.findings
          if f.rule_id == "unoverlapped-collective"]
    assert uo == []
    m = rep.metrics["unoverlapped-collective"]
    assert m["collective_permutes"] > 0 and m["serial_after_dot"] == 0


@needs4
def test_unoverlapped_collective_rule_catches_seeded_serial():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.collective_matmul import (
        ring_rowparallel_matmul, serial_rowparallel_matmul)

    mesh = mesh_mod.build_mesh(tp=4)
    x = np.zeros((4, 16), np.float32)
    w = np.zeros((16, 32), np.float32)
    serial = shard_map(
        lambda a, b: serial_rowparallel_matmul(a, b, "tp"), mesh=mesh,
        in_specs=(P(None, "tp"), P("tp", None)), out_specs=P(),
        check_rep=False)
    rep = analysis.audit(serial, x, w, name="seeded-serial")
    assert any(f.rule_id == "unoverlapped-collective"
               and f.severity == "high" for f in rep.findings)
    ring = shard_map(
        lambda a, b: ring_rowparallel_matmul(a, b, "tp", 4), mesh=mesh,
        in_specs=(P(None, "tp"), P("tp", None)), out_specs=P(),
        check_rep=False)
    rep2 = analysis.audit(ring, x, w, name="overlapped-ring")
    assert not [f for f in rep2.findings
                if f.rule_id == "unoverlapped-collective"]
    # numerically both forms equal the unsharded product
    full = np.asarray(jax.jit(serial)(x, w))
    assert np.allclose(full, x @ w)


# ---------------------------------------------------------------------------
# TP=8 sweep + soak (slow: full-mesh compiles)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.slow
def test_tp8_sweep_and_soak():
    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32",
                              num_hidden_layers=2,
                              num_attention_heads=8,
                              num_key_value_heads=8)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (3 + i % 9,)).astype(
        np.int32) for i in range(12)]
    geo = dict(n_slots=4, max_len=64, min_prompt_bucket=4, block_size=8)
    want = None
    for tp in (1, 2, 4, 8):
        eng = Engine(m, **geo) if tp == 1 else Engine(m, tp=tp, **geo)
        got = _tokens(eng.generate_all(prompts, max_new_tokens=8))
        if want is None:
            want = got
        assert got == want, f"tp={tp} diverged"
        assert eng.cache.check_refcounts()
