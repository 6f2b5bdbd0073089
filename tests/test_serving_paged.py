"""Paged, prefix-shared KV cache + chunked prefill (paddle_tpu.serving).

The paging contract: block-table indirection must be invisible in the
tokens — the engine stays token-identical to batch ``generate()``
through sharing, chunking, pool preemption, cancellation and supervisor
replay, while memory-per-request is ``ceil(len/block_size)`` blocks and
not worst-case ``max_len`` lines, with full-block prefix dedup. Kept
slim for the tier-1 budget: one tiny module-scope model, block_size=8
geometry shared across tests, the soak marked slow; what the engine
measures under load is in PERF.md (the serve cells).
"""
import dataclasses
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (BlockPool, Engine, PagedKVCache,
                                PriorityScheduler, RadixIndex)
from paddle_tpu.serving.kv_cache import TRASH_BLOCK
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
GEO = dict(n_slots=2, max_len=64, min_prompt_bucket=4, block_size=8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _want(model, prompt, n, **kw):
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=n, **kw)
    return np.asarray(out._data)[0, len(prompt):]


# ---------------------------------------------------------------------------
# host-side allocator + radix unit behavior
# ---------------------------------------------------------------------------

def test_block_pool_refcounts_and_trash():
    p = BlockPool(4)
    assert p.n_free == 3                       # block 0 reserved
    a, b, c = p.alloc(), p.alloc(), p.alloc()
    assert {a, b, c} == {1, 2, 3} and p.alloc() is None
    p.ref(a)
    p.deref(a)
    assert p.n_free == 0                       # still referenced
    p.deref(a)
    assert p.n_free == 1 and p.alloc() == a    # reuse
    with pytest.raises(ValueError):
        p.deref(b), p.deref(b), p.deref(b)     # double free
    p.deref(TRASH_BLOCK)                       # no-op: pinned
    assert p.refcount[TRASH_BLOCK] == 1
    with pytest.raises(ValueError):
        BlockPool(1)


def test_radix_match_insert_evict():
    pool = BlockPool(8)
    r = RadixIndex(block_size=4)
    toks = np.arange(12, dtype=np.int32)       # 3 full blocks
    blocks = [pool.alloc() for _ in range(3)]
    assert r.insert(toks, blocks, pool) == 3
    assert r.match(toks) == blocks             # full match
    assert r.match(toks[:9]) == blocks[:2]     # partial: full blocks only
    assert r.match(np.arange(100, 104, dtype=np.int32)) == []
    # same-prefix reinsert keeps the existing nodes
    other = [pool.alloc() for _ in range(2)]
    assert r.insert(toks[:8], other, pool) == 0
    # refcount: 1 (alloc) + 1 (index) per indexed block
    assert all(pool.refcount[b] == 2 for b in blocks)
    for b in blocks:                           # producers release
        pool.deref(b)
    assert pool.n_free == 2                    # index keeps 3 resident
    assert r.evictable_blocks(pool) == 3
    assert r.evict(pool, need=2) == 2          # leaves first
    assert pool.n_free == 4 and r.n_nodes == 1
    r.clear(pool)
    assert pool.n_free == 5


def test_paged_cache_admit_and_free_invariants():
    c = PagedKVCache(n_layers=2, n_slots=2, max_len=32, line=(2, 4),
                     dtype=np.float32, block_size=8)
    assert c.max_blocks == 4 and c.pool.n_blocks == 9
    s = c.alloc("r0")
    toks = np.arange(11, dtype=np.int32)
    n_shared, cow = c.admit(s, toks, 12)       # 2 blocks, nothing cached
    assert n_shared == 0 and not cow
    assert c.ensure(s, 15) and c.ensure(s, 16)  # grow into block 3
    assert list(c.block_tables[s][:3]) != [0, 0, 0]
    c.commit_prefix(s, toks)                   # 1 full block -> radix
    assert c.radix.n_nodes == 1
    c.free(s)
    assert c.check_refcounts()
    assert c.pool.n_free + c.radix.n_nodes == c.pool.n_blocks - 1
    # a second occupant shares the committed block, tail is copy-on-write
    s2 = c.alloc("r1")
    n_shared, cow = c.admit(s2, toks, 12)
    assert n_shared == 8 and cow
    c.free(s2)
    assert c.check_refcounts()


def test_scheduler_free_tokens_watermark_and_requeue():
    class _H:
        _n = 0

        def __init__(self, n, new=4):
            self.n_prompt, self.max_new_tokens = n, new
            self.tokens = []
            self.priority = 0
            self.deadline = None
            self.request_id = _H._n
            _H._n += 1

    s = PriorityScheduler(token_budget=1000, max_queue=2)
    big, small = _H(20), _H(3)
    s.enqueue(big)
    s.enqueue(small)
    # head needs prompt+1 = 21 immediate lines; only 16 free -> it WAITS
    # and nothing overtakes it (free blocks, not slots, gate admission)
    assert s.pop_admissible(free_slots=2, free_tokens=16) == []
    got = s.pop_admissible(free_slots=2, free_tokens=30)
    assert got == [big, small]                 # 21 + 4 <= 30
    # requeue bypasses max_queue (preempted work was already admitted)
    s.enqueue(_H(2))
    s.enqueue(_H(2))
    s.requeue(big)
    assert s.queue_depth == 3


# ---------------------------------------------------------------------------
# engine: parity, sharing, chunking, preemption, churn
# ---------------------------------------------------------------------------

def test_paged_greedy_parity_staggered(model):
    """The engine is token-identical to generate() on a staggered
    workload."""
    prompts = _prompts([5, 9, 5, 9, 5], seed=1)

    def drive(eng):
        hs = [eng.submit(prompts[0], max_new_tokens=4),
              eng.submit(prompts[1], max_new_tokens=4)]
        eng.step()
        eng.step()
        for p in prompts[2:]:
            hs.append(eng.submit(p, max_new_tokens=4))
            eng.step()
        eng.drain()
        return [list(h.tokens) for h in hs]

    for p, toks in zip(prompts, drive(Engine(model, **GEO))):
        np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                      _want(model, p, 4))


def test_prefix_sharing_dedups_blocks_token_identical(model):
    """Requests sharing a system prompt alias its full blocks (refcounts
    + radix index), recompute only the partial tail (copy-on-write), and
    still emit exactly what a dedicated generate() would."""
    rng = np.random.default_rng(2)
    sys_p = rng.integers(0, CFG.vocab_size, (18,)).astype(np.int32)
    reqs = [np.concatenate(
        [sys_p, rng.integers(0, CFG.vocab_size, (k,)).astype(np.int32)])
        for k in (3, 4, 5)]
    eng = Engine(model, **GEO)
    hs = [eng.submit(p, max_new_tokens=4) for p in reqs]
    shared_live = eng.cache.shared_live_blocks()
    assert shared_live                       # 2 full blocks alias NOW
    eng.drain()
    for p, h in zip(reqs, hs):
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32),
                                      _want(model, p, 4))
    st = eng.stats()
    # 2 sharers x 2 full blocks x 8 tokens served from the radix
    assert st["prefix_hit_tokens"] == 32
    assert st["cow_copies"] == 2 and st["radix_nodes"] >= 2
    assert st["prefix_hit_rate"] == pytest.approx(
        32 / sum(len(p) for p in reqs), abs=1e-3)
    assert eng.cache.check_refcounts()


def test_chunked_prefill_coscheduled_with_decode(model):
    """A long prompt prefills in block-aligned chunks through ONE extra
    program while a short request keeps decoding every step (bounded
    ITL), and both outputs are token-identical to generate()."""
    rng = np.random.default_rng(3)
    long_p = rng.integers(0, CFG.vocab_size, (29,)).astype(np.int32)
    short_p = rng.integers(0, CFG.vocab_size, (5,)).astype(np.int32)
    eng = Engine(model, **GEO, prefill_chunk=8)
    short_progress = []
    hshort = eng.submit(
        short_p, max_new_tokens=8,
        on_token=lambda h, t: short_progress.append(len(h.tokens)))
    hlong = eng.submit(
        long_p, max_new_tokens=4,
        on_token=lambda h, t: short_progress.append(("long", len(
            hshort.tokens))))
    eng.drain()
    np.testing.assert_array_equal(np.asarray(hlong.tokens, np.int32),
                                  _want(model, long_p, 4))
    np.testing.assert_array_equal(np.asarray(hshort.tokens, np.int32),
                                  _want(model, short_p, 8))
    st = eng.stats()
    assert st["chunked_prefills"] == 1 and st["chunk_steps"] == 4
    assert st["chunk_program"] and st["prefill_buckets"] == [8]
    # co-scheduling: the short request decoded >= 3 tokens while the
    # long prompt was still chunking (its first token marks the end)
    first_long = next(x for x in short_progress if isinstance(x, tuple))
    assert first_long[1] >= 3


def test_pool_exhaustion_preempts_and_replays_token_identical(model):
    """Pool sized below the combined worst case: the engine preempts the
    newest request mid-decode (blocks freed, request re-queued) and its
    later replay — prompt + emitted tokens, PRNG fast-forward — still
    finishes token-identical."""
    prompts = _prompts([12, 12], seed=4)
    eng = Engine(model, **GEO, n_blocks=6, prefix_sharing=False)
    h1 = eng.submit(prompts[0], max_new_tokens=16)
    h2 = eng.submit(prompts[1], max_new_tokens=16)
    eng.drain()
    np.testing.assert_array_equal(np.asarray(h1.tokens, np.int32),
                                  _want(model, prompts[0], 16))
    np.testing.assert_array_equal(np.asarray(h2.tokens, np.int32),
                                  _want(model, prompts[1], 16))
    st = eng.stats()
    assert st["preemptions"] >= 1
    assert eng.cache.pool.n_free == 5 and eng.cache.check_refcounts()


def test_cancel_and_timeout_mid_chunk_free_all_blocks(model):
    """The churn bugfix: cancelling (or deadline-expiring) a request
    mid-chunked-prefill releases every already-written block and its
    radix refcounts — the pool returns to baseline every cycle."""
    rng = np.random.default_rng(5)
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8, prefill_chunk=8, prefix_sharing=False)
    base_free = eng.cache.pool.n_free
    for i in range(3):
        lp = rng.integers(0, CFG.vocab_size, (25,)).astype(np.int32)
        if i < 2:
            h = eng.submit(lp, max_new_tokens=6)
            eng.step()                     # one chunk written, mid-prefill
            assert not h.finished and h.slot is not None
            assert eng.cache.pool.n_free < base_free
            assert eng.cancel(h)
        else:
            h = eng.submit(lp, max_new_tokens=6, max_time_s=1e-4)
            eng.step()                     # first chunk
            time.sleep(0.01)
            eng.step()                     # deadline fires mid-prefill
            assert h.finish_reason == "timeout"
        assert eng.cache.pool.n_free == base_free, i
        assert eng.cache.check_refcounts()
    assert not eng._chunking and eng.cache.n_active == 0


def test_supervisor_heals_corrupted_shared_block(model):
    """Chaos kv-corrupt on a paged engine poisons a SHARED prefix block;
    the probe walks live blocks only, the rebuild re-admits every sharer
    through a fresh radix, and all of them finish token-identical to the
    uninterrupted run with consistent refcounts."""
    from paddle_tpu.resilience import ChaosMonkey
    from paddle_tpu.serving import EngineSupervisor

    rng = np.random.default_rng(6)
    sys_p = rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
    reqs = [np.concatenate(
        [sys_p, rng.integers(0, CFG.vocab_size, (k,)).astype(np.int32)])
        for k in (3, 4)]
    kw = dict(n_slots=2, max_len=64, min_prompt_bucket=4, block_size=8,
              do_sample=True, top_k=8)
    gen = [dict(max_new_tokens=6, temperature=0.8, seed=11),
           dict(max_new_tokens=6, temperature=1.2, seed=7)]

    def drive(server):
        hs = [server.submit(p, **g) for p, g in zip(reqs, gen)]
        while any(not h.finished for h in hs):
            server.step()
        return hs

    want = [list(h.tokens) for h in drive(Engine(model, **kw))]
    chaos = ChaosMonkey(seed=0, at={2: "kv-corrupt"})
    sup = EngineSupervisor(model, chaos=chaos, kv_probe_interval=1, **kw)
    got = drive(sup)
    assert sup.kv_corruptions == 1 and sup.rebuilds == 1
    assert [list(h.tokens) for h in got] == want
    assert sup.engine.cache.check_refcounts()
    assert sup.engine.metrics.prefix_hit_tokens > 0    # re-shared on replay


# ---------------------------------------------------------------------------
# lint rules, counters, validation
# ---------------------------------------------------------------------------

def test_paged_lint_rules_pos_neg(model):
    from paddle_tpu import analysis

    bad = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=12)
    rep = analysis.audit_engine(bad, lower_decode=False)
    pads = [f for f in rep.findings if f.rule_id == "padding-waste"]
    assert any("block_size=12" in f.message and f.severity == "medium"
               for f in pads)
    assert any("multiple of block_size" in f.message for f in pads)

    good = Engine(model, **GEO, prefill_chunk=16, compile_budget=4)
    good.submit(_prompts([5], seed=7)[0], max_new_tokens=2)
    good.submit(_prompts([20], seed=7)[0], max_new_tokens=2)
    good.drain()
    rep2 = analysis.audit_engine(good, lower_decode=False)
    m = rep2.metrics["compile-budget"]
    # paged budget: buckets + decode + ONE chunk program (block tables
    # are runtime operands — no per-length lowerings)
    assert m["chunk_program"] is True
    assert m["programs"] == len(m["prefill_buckets"]) + 2 <= 4
    assert not [f for f in rep2.findings
                if f.rule_id in ("compile-budget", "padding-waste")
                and f.severity in ("high", "medium")]
    # per-length sprawl beyond the chunk threshold is flagged high
    good.buckets_seen.add(64)
    rep3 = analysis.audit_engine(good, lower_decode=False)
    assert [f for f in rep3.findings if f.rule_id == "compile-budget"
            and "per-length" in f.message and f.severity == "high"]


def test_paged_counters_in_profiler_plumbing(model, capsys):
    import paddle_tpu.profiler as profiler

    before = profiler.serving_counters()
    rng = np.random.default_rng(8)
    sys_p = rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
    eng = Engine(model, **GEO)
    for k in (3, 4):
        eng.submit(np.concatenate(
            [sys_p,
             rng.integers(0, CFG.vocab_size, (k,)).astype(np.int32)]),
            max_new_tokens=2)
    eng.drain()
    after = profiler.serving_counters()
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 8
    assert after["cow_copies"] - before["cow_copies"] == 1
    assert after["prompt_tokens"] > before["prompt_tokens"]
    assert after["peak_active"] >= 2
    assert after["pool_low_watermark"] is not None
    st = eng.stats()
    assert st["pool_occupancy"] > 0 and st["pool_low_watermark"] >= 0
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.step()
    prof.stop()
    prof.summary()
    out = capsys.readouterr().out
    assert "prefix_hit_rate=" in out and "pool_low_watermark=" in out
    assert "cow=" in out and "preempt=" in out


def test_paged_validation_errors(model):
    with pytest.raises(ValueError):
        Engine(model, **GEO, prefill_chunk=12)      # not block-aligned
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 block_size=8, n_blocks=3)          # 16-token pool
    with pytest.raises(ValueError):
        eng.submit(np.zeros((10,), np.int32), max_new_tokens=8)
    # within pool capacity but above it only transiently is fine
    h = eng.submit(np.zeros((5,), np.int32), max_new_tokens=4)
    eng.drain()
    assert h.finished


# ---------------------------------------------------------------------------
# soak (slow): sharing + chunking + preemption under random arrivals
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_paged_sharing_chunking_preemption(model):
    rng = np.random.default_rng(9)
    sys_p = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    reqs = []
    for i in range(24):
        tail = rng.integers(0, CFG.vocab_size,
                            (int(rng.integers(2, 14)),)).astype(np.int32)
        p = np.concatenate([sys_p, tail]) if i % 2 else tail
        reqs.append((p, int(rng.integers(2, 8)),
                     int(rng.integers(0, 1 << 30))))
    eng = Engine(model, n_slots=6, max_len=64, min_prompt_bucket=4,
                 block_size=8, n_blocks=24, prefill_chunk=16,
                 do_sample=True, top_k=8)
    handles = []
    for i, (p, m, s) in enumerate(reqs):
        handles.append(eng.submit(p, max_new_tokens=m, seed=s,
                                  temperature=0.9))
        for _ in range(int(i % 3)):
            eng.step()
    eng.drain()
    for (p, m, s), h in zip(reqs, handles):
        np.testing.assert_array_equal(
            np.asarray(h.tokens, np.int32),
            _want(model, p, m, do_sample=True, top_k=8, temperature=0.9,
                  seed=s))
    assert eng.cache.check_refcounts()
    st = eng.stats()
    assert st["prefix_hit_tokens"] > 0

    # GPT arch over the paged pool incl. its chunk program
    from paddle_tpu.text.models.gpt import GPT_TINY, GPTForCausalLM
    paddle.seed(0)
    gpt = GPTForCausalLM(GPT_TINY)
    gpt.eval()
    ge = Engine(gpt, n_slots=2, max_len=64, min_prompt_bucket=4,
                block_size=8, prefill_chunk=8)
    gp = [rng.integers(0, GPT_TINY.vocab_size, (n,)).astype(np.int32)
          for n in (5, 21, 7)]
    ghs = ge.generate_all(gp, max_new_tokens=5)
    for p, h in zip(gp, ghs):
        want = np.asarray(gpt.generate(paddle.to_tensor(p[None]),
                                       max_new_tokens=5)._data)[0, len(p):]
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), want)
    assert ge.stats()["chunk_steps"] >= 3 and ge.cache.check_refcounts()
