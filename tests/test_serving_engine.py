"""Continuous-batching serving engine (paddle_tpu.serving).

Token-for-token parity between the slot-KV Engine and batch generate()
is the core contract: requests arrive staggered (mid-stream admission,
eviction, slot reuse) and every request must decode exactly what a
dedicated batch call would have produced. Kept slim for the tier-1
budget: one tiny module-scope model, few tokens, shared engine geometry
so the jit cache is hit across tests; the soak is marked slow.
"""
import dataclasses

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (Engine, EngineOverloaded, FIFOScheduler,
                                ledger)
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
RNG = np.random.default_rng(0)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _prompts(lens, rng=None):
    rng = rng or RNG
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _want(model, prompt, n, **kw):
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=n, **kw)
    return np.asarray(out._data)[0, len(prompt):]


def test_greedy_parity_staggered_admission_and_slot_reuse(model):
    """5 requests through 2 slots: queueing, mid-stream admission after
    evictions, and slot reuse — each request token-identical to batch
    generate() on its own prompt. (Two prompt lengths / one max_new so
    the batch-generate parity references stay at 2 jit signatures.)"""
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4)
    prompts = _prompts([5, 9, 5, 9, 5], np.random.default_rng(1))
    handles = [eng.submit(prompts[0], max_new_tokens=4),
               eng.submit(prompts[1], max_new_tokens=4)]
    eng.step()
    eng.step()   # staggered arrivals: later submits land in reused slots
    for p in prompts[2:]:
        handles.append(eng.submit(p, max_new_tokens=4))
        eng.step()
    eng.drain()
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(
            np.asarray(h.tokens, np.int32), _want(model, p, 4))
        assert h.finished and h.finish_reason == "length"
    st = eng.stats()
    assert st["requests_completed"] == 5
    assert st["active"] == 0 and st["queue_depth"] == 0
    # slots were reused: more requests than slots, all through 2 slots
    assert st["prefills"] == 5 and eng.n_slots == 2


def test_per_request_determinism_under_cobatch(model):
    """Sampled output is a function of (prompt, seed, kwargs) only:
    identical whether the request runs alone or co-batched with
    different traffic — and equal to batch generate(seed) for B=1."""
    p = _prompts([6], np.random.default_rng(2))[0]
    kw = dict(do_sample=True, top_k=8)

    eng_a = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4, **kw)
    h_alone = eng_a.submit(p, max_new_tokens=5, temperature=0.8, seed=11)
    eng_a.drain()

    eng_b = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4, **kw)
    noise = _prompts([4, 7], np.random.default_rng(3))
    eng_b.submit(noise[0], max_new_tokens=7, temperature=1.4, seed=99)
    h_mixed = eng_b.submit(p, max_new_tokens=5, temperature=0.8, seed=11)
    eng_b.step()
    eng_b.submit(noise[1], max_new_tokens=3, temperature=0.6, seed=5)
    eng_b.drain()

    assert h_alone.tokens == h_mixed.tokens
    np.testing.assert_array_equal(
        np.asarray(h_alone.tokens, np.int32),
        _want(model, p, 5, do_sample=True, top_k=8, temperature=0.8,
              seed=11))


def test_eos_evicts_and_matches_generate(model):
    """EOS frees the slot early; emitted tokens equal generate()'s
    prefix through the eos position."""
    p = _prompts([5], np.random.default_rng(4))[0]
    ref = _want(model, p, 4)
    eos = int(ref[2])        # 3rd generated token plays eos
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 eos_token_id=eos)
    h = eng.submit(p, max_new_tokens=4)
    eng.drain()
    assert h.finish_reason == "eos"
    np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref[:3])
    assert eng.cache.n_active == 0


def test_scheduler_backpressure_and_token_budget(model):
    """Queue-depth backpressure raises EngineOverloaded; the token
    watermark keeps the queue head waiting until in-flight tokens
    drain (strict FIFO, still completes)."""
    # budget fits exactly one request (prompt 4 + new 4 = 8 tokens)
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4,
                 token_budget=8, max_queue=2)
    prompts = _prompts([4, 4, 4, 4], np.random.default_rng(5))
    h1 = eng.submit(prompts[0], max_new_tokens=4)
    h2 = eng.submit(prompts[1], max_new_tokens=4)
    assert h1.slot is not None          # admitted immediately
    assert h2.slot is None              # watermarked out despite free slot
    h3 = eng.submit(prompts[2], max_new_tokens=4)
    with pytest.raises(EngineOverloaded):
        eng.submit(prompts[3], max_new_tokens=4)
    assert eng.metrics.requests_rejected == 1
    eng.drain()
    for p, h in zip(prompts[:3], (h1, h2, h3)):
        np.testing.assert_array_equal(
            np.asarray(h.tokens, np.int32), _want(model, p, 4))

    # pure scheduler unit check: head blocks, nothing overtakes it
    class _H:
        def __init__(self, n):
            self.n_prompt, self.max_new_tokens = n, 0
    s = FIFOScheduler(token_budget=10, max_queue=4)
    s.enqueue(_H(8))
    s.enqueue(_H(3))
    first = s.pop_admissible(free_slots=2)
    assert [h.n_prompt for h in first] == [8]   # 8+3 > 10: head only
    s.release(first[0])
    assert [h.n_prompt for h in s.pop_admissible(2)] == [3]


def test_streaming_callbacks_and_metrics_ledger(model):
    """on_token streams in decode order (first token during prefill =
    TTFT); request/engine metrics and the profiler plumbing agree."""
    import paddle_tpu.profiler as profiler

    before = profiler.serving_counters()
    seen = []
    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4)
    p = _prompts([5], np.random.default_rng(6))[0]
    h = eng.submit(p, max_new_tokens=4,
                   on_token=lambda hh, t: seen.append((hh.request_id, t)))
    assert len(seen) == 1               # first token streams at prefill
    eng.drain()
    assert [t for _, t in seen] == h.tokens
    assert h.metrics.ttft is not None and h.metrics.ttft >= 0
    assert h.metrics.n_tokens == 4
    assert len(h.metrics.inter_token_latencies) == 3
    assert h.metrics.tokens_per_sec > 0
    led = ledger([h])
    assert led["requests"] == 1 and led["total_new_tokens"] == 4
    for k in ("ttft_ms_p50", "ttft_ms_p95", "itl_ms_p50", "itl_ms_p95",
              "tokens_per_sec"):
        assert led[k] >= 0
    after = profiler.serving_counters()
    assert after["tokens_generated"] - before["tokens_generated"] == 4
    assert after["requests_completed"] - before["requests_completed"] == 1


def test_submit_validation(model):
    eng = Engine(model, n_slots=2, max_len=16, min_prompt_bucket=4)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=13)  # 4+13>16
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 3), np.int32))                   # 2-D


def test_llm_predictor_artifact_roundtrip(model, tmp_path):
    """save_lm -> create_llm_predictor serves the artifact through the
    engine with identical greedy tokens."""
    from paddle_tpu import inference, serving

    path = str(tmp_path / "lm")
    serving.save_lm(model, path)
    pred = inference.create_llm_predictor(
        inference.Config(path + ".pdmodel"), n_slots=2, max_len=64,
        min_prompt_bucket=4)
    p = _prompts([5], np.random.default_rng(7))[0]
    h = pred.submit(p, max_new_tokens=4)
    pred.drain()
    np.testing.assert_array_equal(
        np.asarray(h.tokens, np.int32), _want(model, p, 4))
    assert pred.stats()["requests_completed"] == 1


@pytest.mark.slow
def test_soak_many_requests_random_arrivals(model):
    """Long mixed workload: random arrivals/lengths across buckets, full
    parity for every request (includes GPT arch)."""
    rng = np.random.default_rng(8)
    eng = Engine(model, n_slots=4, max_len=64, min_prompt_bucket=4)
    reqs = [(rng.integers(0, CFG.vocab_size, (int(n),)).astype(np.int32),
             int(m))
            for n, m in zip(rng.integers(4, 17, 40), rng.integers(2, 9, 40))]
    handles = []
    for i, (p, m) in enumerate(reqs):
        handles.append(eng.submit(p, max_new_tokens=m))
        for _ in range(int(rng.integers(0, 3))):
            eng.step()
    eng.drain()
    for (p, m), h in zip(reqs, handles):
        np.testing.assert_array_equal(
            np.asarray(h.tokens, np.int32), _want(model, p, m))

    from paddle_tpu.text.models.gpt import GPT_TINY, GPTForCausalLM
    paddle.seed(0)
    gpt = GPTForCausalLM(GPT_TINY)
    gpt.eval()
    ge = Engine(gpt, n_slots=2, max_len=64, min_prompt_bucket=4)
    gp = [rng.integers(0, GPT_TINY.vocab_size, (n,)).astype(np.int32)
          for n in (5, 7, 4)]
    ghs = ge.generate_all(gp, max_new_tokens=5)
    for p, h in zip(gp, ghs):
        want = np.asarray(gpt.generate(paddle.to_tensor(p[None]),
                                       max_new_tokens=5)._data)[0, len(p):]
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), want)


def test_deadline_frees_slot_and_raises_timeout(model):
    """Graceful degradation: a request whose max_time_s expires mid-
    decode frees its KV slot at the next step and result() raises
    RequestTimeout instead of occupying the engine forever."""
    from paddle_tpu.serving import RequestTimeout

    eng = Engine(model, n_slots=2, max_len=64, min_prompt_bucket=4)
    p = _prompts([5], np.random.default_rng(9))[0]
    h = eng.submit(p, max_new_tokens=40, max_time_s=1e-4)
    assert h.slot is not None
    import time as _time
    _time.sleep(0.01)                  # let the deadline lapse
    eng.step()
    assert h.finished and h.finish_reason == "timeout"
    with pytest.raises(RequestTimeout):
        h.result()
    assert eng.cache.n_free == eng.n_slots          # slot reclaimed
    assert eng.stats()["requests_timed_out"] == 1
    # the engine keeps serving: a healthy request still completes
    h2 = eng.submit(p, max_new_tokens=3)
    np.testing.assert_array_equal(
        np.asarray(h2.result()[len(p):], np.int32), _want(model, p, 3))


def test_deadline_expires_queued_request_without_slot(model):
    """A deadline can lapse while the request is still queued: it drops
    out of the FIFO without ever holding a slot or budget share."""
    from paddle_tpu.serving import RequestTimeout

    eng = Engine(model, n_slots=1, max_len=64, min_prompt_bucket=4)
    rng = np.random.default_rng(10)
    p = _prompts([5], rng)[0]
    hog = eng.submit(p, max_new_tokens=8)           # owns the only slot
    waiting = eng.submit(p, max_new_tokens=8, max_time_s=1e-4)
    assert waiting.slot is None
    import time as _time
    _time.sleep(0.01)
    eng.step()
    assert waiting.finished and waiting.finish_reason == "timeout"
    assert eng.scheduler.queue_depth == 0
    with pytest.raises(RequestTimeout):
        waiting.result()
    hog.result()                                    # unaffected
    assert hog.finish_reason == "length"


def test_overload_message_carries_retry_after_hint(model):
    """EngineOverloaded carries a retry-after estimate once the engine
    has decode-latency history (live ITL x shortest active request)."""
    eng = Engine(model, n_slots=1, max_len=64, min_prompt_bucket=4,
                 max_queue=1)
    rng = np.random.default_rng(11)
    p = _prompts([5], rng)[0]
    eng.submit(p, max_new_tokens=6)
    eng.step()                                      # ITL history exists
    eng.submit(p, max_new_tokens=6)                 # fills the queue
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(p, max_new_tokens=6)
    assert ei.value.retry_after_s is not None and ei.value.retry_after_s > 0
    assert "retry after" in str(ei.value)
    assert eng.metrics.itl_estimate() is not None
