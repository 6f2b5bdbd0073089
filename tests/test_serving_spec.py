"""Speculative decoding (paddle_tpu.serving.speculative).

The speculative contract: draft-verify may only change SPEED, never
tokens — the engine with ``speculative=SpecConfig(...)`` is
token-identical to the non-speculative engine (and batch ``generate()``)
for greedy AND sampled decoding, through prefix sharing, pool
preemption, adopt() replay and supervisor rebuild, for both draft modes
(host n-gram lookahead and a same-family draft model). Acceptance is
the token-identical specialization of rejection sampling: each position
is re-sampled with exactly the PRNG split the non-speculative chain
would have consumed.

Random tiny weights produce non-repetitive text, so n-gram proposals
are forced deterministically through the constrained-decoding rider
(``submit(logit_mask=...)``): masking the vocab to one or two tokens
makes the emitted stream repeat, which is exactly the traffic
prompt-lookup speculation feeds on. The k sweep is marked slow.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import Engine, EngineSupervisor, SpecConfig
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
GEO = dict(n_slots=2, max_len=64, min_prompt_bucket=4, block_size=8)
V = CFG.vocab_size


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _mask(*allowed):
    m = np.zeros(V, bool)
    m[list(allowed)] = True
    return m


def _drive(engine, reqs, stagger=True):
    """Submit (prompt, kwargs) pairs with interleaved steps, drain,
    return the per-request token lists."""
    handles = []
    for i, (p, kw) in enumerate(reqs):
        if stagger and i:
            engine.step()
        handles.append(engine.submit(p, **kw))
    engine.drain()
    return [list(h.tokens) for h in handles]


def _mixed_reqs(seed=0, max_new=10):
    """Two vocab-masked repetitive requests (verify fires) + two plain
    random ones (decode fallback fires)."""
    rng = np.random.default_rng(seed)
    return [
        (np.full((9,), 7, np.int32),
         dict(max_new_tokens=max_new, logit_mask=_mask(7))),
        (rng.integers(0, V, (6,)).astype(np.int32),
         dict(max_new_tokens=max_new)),
        (np.asarray([11, 13] * 5, np.int32),
         dict(max_new_tokens=max_new, logit_mask=_mask(11, 13))),
        (rng.integers(0, V, (5,)).astype(np.int32),
         dict(max_new_tokens=max_new - 2)),
    ]


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_spec_validation(model):
    with pytest.raises(ValueError):
        SpecConfig(k=0)
    with pytest.raises(ValueError):
        SpecConfig(ngram_min=0)
    with pytest.raises(TypeError):
        Engine(model, speculative=4, **GEO)
    with pytest.raises(ValueError, match="tp > 1"):
        Engine(model, speculative=SpecConfig(), tp=2, **GEO)
    eng = Engine(model, **GEO)
    with pytest.raises(ValueError):          # wrong mask shape
        eng.submit(np.asarray([1, 2, 3], np.int32),
                   logit_mask=np.ones(V + 1, bool))
    with pytest.raises(ValueError):          # mask allows nothing
        eng.submit(np.asarray([1, 2, 3], np.int32),
                   logit_mask=np.zeros(V, bool))


# ---------------------------------------------------------------------------
# token identity: greedy + sampled, ngram + model draft
# ---------------------------------------------------------------------------

def test_greedy_token_identity_ngram(model):
    reqs = _mixed_reqs()
    base = _drive(Engine(model, **GEO), reqs)
    spec = Engine(model, speculative=SpecConfig(draft="ngram", k=4),
                  **GEO)
    got = _drive(spec, reqs)
    assert got == base
    assert spec.verify_used                 # speculation actually ran
    assert spec.metrics.spec_accepted_tokens > 0
    # unmasked requests also match batch generate()
    for i in (1, 3):
        p, kw = reqs[i]
        want = np.asarray(model.generate(
            paddle.to_tensor(p[None]),
            max_new_tokens=kw["max_new_tokens"])._data)[0, len(p):]
        assert np.array_equal(np.asarray(got[i], np.int32), want)
    # masked requests only ever emit allowed tokens (prefill included)
    assert set(got[0]) <= {7}
    assert set(got[2]) <= {11, 13}


def test_sampled_token_identity_ngram(model):
    reqs = [(p, dict(kw, temperature=0.9 + 0.2 * i, seed=40 + i))
            for i, (p, kw) in enumerate(_mixed_reqs(seed=2))]
    kw = dict(GEO, do_sample=True, top_k=8)
    base = _drive(Engine(model, **kw), reqs)
    spec = Engine(model, speculative=SpecConfig(draft="ngram", k=4),
                  **kw)
    got = _drive(spec, reqs)
    assert got == base
    assert spec.verify_used


def test_model_draft_token_identity_and_step_ratio(model):
    """Self-draft = the high-acceptance proxy: acceptance ~1 for
    greedy, so target steps per emitted token collapse toward
    1/(k+1)."""
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32),
             dict(max_new_tokens=12)) for n in (6, 9, 11)]
    base = _drive(Engine(model, **GEO), reqs)
    spec = Engine(model, speculative=SpecConfig(draft=model, k=4), **GEO)
    got = _drive(spec, reqs)
    assert got == base
    m = spec.metrics
    assert m.acceptance_rate() > 0.5
    assert m.decode_steps / m.tokens_generated < 0.6
    assert spec.draft_decode_used and spec.draft_buckets_seen
    st = spec.stats()["speculative"]
    assert st["draft"] == "model" and st["verify_used"]


def test_model_draft_pool_stays_inside_its_slots_blocks(model):
    """The draft's pool has no allocator: slot ``s`` owns the run of
    blocks ``1 + s*mb .. (s+1)*mb``. Requests that run to ``max_len``
    in slots 0 and 1 (the draft's k+1 steps would write past it) leave
    slot 2's run and everything past a slot's own last line as zero."""
    geo = dict(GEO, n_slots=3, max_len=32)
    k, bs, mb = 4, geo["block_size"], 32 // geo["block_size"]
    spec = Engine(model, speculative=SpecConfig(draft=model, k=k), **geo)
    draft = spec._spec
    assert draft.kc.shape[1] == 1 + 3 * mb
    assert draft.tables.tolist() == [
        list(range(1 + s * mb, 1 + (s + 1) * mb)) for s in range(3)]
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32),
             dict(max_new_tokens=32 - n)) for n in (6, 21)]
    base = _drive(Engine(model, **geo), reqs, stagger=False)
    assert _drive(spec, reqs, stagger=False) == base
    assert spec.draft_decode_used
    for pool in (np.asarray(draft.kc), np.asarray(draft.vc)):
        lines = pool.reshape(pool.shape[0], -1, *pool.shape[3:])
        written = lines.any(axis=(0, 2, 3))[bs:].reshape(3, mb * bs)
        assert written[0].any() and written[1].any()
        assert not written[2].any()
        # slot 1's request wrote lines 0..31 of its own run and no line
        # of slot 0's: both runs are whole where the sequences ended
        assert written[0][:31].all() and written[1][:31].all()


def test_zero_accept_worst_case(model):
    """Adversarial draft (proposes a token the mask forbids): every
    verify emits exactly ONE token, so the request degrades to exactly
    the non-speculative target-step count — never below it."""

    class Hostile:
        def propose(self, ctx, k):
            return np.full(k, 9, np.int32)   # mask allows only 7

    max_new = 12
    req = [(np.full((9,), 7, np.int32),
            dict(max_new_tokens=max_new, logit_mask=_mask(7)))]
    base = _drive(Engine(model, **GEO), req, stagger=False)
    spec = Engine(model, speculative=SpecConfig(draft=Hostile(), k=4),
                  **GEO)
    got = _drive(spec, req, stagger=False)
    assert got == base
    assert spec.metrics.spec_accepted_tokens == 0
    # 1 token from prefill + (max_new - 1) one-token target steps
    # (verify steps; the remaining==1 tail uses the decode fallback)
    assert spec.metrics.decode_steps == max_new - 1
    # every verify emitted exactly its corrective token, nothing more
    assert spec.metrics.spec_emitted_tokens == spec.metrics.spec_steps


# ---------------------------------------------------------------------------
# prefix sharing + pool preemption + migration
# ---------------------------------------------------------------------------

def test_prefix_sharing_and_preemption_replay(model):
    """A tight block pool forces preemption mid-speculation; replay
    re-admits through the skip-PRNG machinery and the final streams
    stay identical to an unconstrained-pool non-speculative engine.
    The two masked requests share a full-block prefix (radix hit)."""
    shared = np.full((8,), 7, np.int32)          # exactly one block
    reqs = [
        (np.concatenate([shared, np.asarray([7, 7], np.int32)]),
         dict(max_new_tokens=14, logit_mask=_mask(7), seed=3)),
        (np.concatenate([shared, np.asarray([7], np.int32)]),
         dict(max_new_tokens=14, logit_mask=_mask(7), seed=9)),
    ]
    kw = dict(GEO, do_sample=True, top_k=8)
    base = _drive(Engine(model, **kw), reqs)
    spec = Engine(model, speculative=SpecConfig(draft="ngram", k=4),
                  n_blocks=5, **kw)
    got = _drive(spec, reqs)
    assert got == base
    assert spec.metrics.prefix_hit_tokens > 0
    assert spec.metrics.preemptions > 0
    assert spec.verify_used
    assert spec.cache.check_refcounts()


def test_adopt_across_spec_modes(model):
    """The model fingerprint excludes the speculative config: a
    speculative engine's in-flight handle adopts onto a NON-speculative
    engine (and vice versa) and finishes byte-equal — acceptance only
    ever changed speed."""
    prompt = np.full((9,), 7, np.int32)
    kw = dict(max_new_tokens=12, logit_mask=_mask(7), seed=5)
    base_eng = Engine(model, do_sample=True, top_k=8, **GEO)
    base = list(base_eng.generate_all([prompt], **kw)[0].tokens)

    for src_spec, dst_spec in ((SpecConfig(k=4), None),
                               (None, SpecConfig(k=3))):
        a = Engine(model, do_sample=True, top_k=8,
                   speculative=src_spec, **GEO)
        h = a.submit(prompt, **kw)
        for _ in range(3):
            a.step()
        assert 0 < len(h.tokens) < 12
        a._condemned = True
        b = Engine(model, do_sample=True, top_k=8,
                   speculative=dst_spec, **GEO)
        b.adopt(h)
        h.result()
        assert list(h.tokens) == base


def test_supervisor_rebuild_preserves_tokens_and_counters(model):
    from paddle_tpu.resilience import ChaosMonkey

    reqs = _mixed_reqs(seed=4)
    kw = dict(GEO, do_sample=True, top_k=8)
    base = _drive(Engine(model, **kw), reqs)
    chaos = ChaosMonkey(seed=0, at={5: "decode-raise"})
    sup = EngineSupervisor(model, chaos=chaos, kv_probe_interval=1,
                           speculative=SpecConfig(draft="ngram", k=4),
                           **kw)
    handles = []
    for i, (p, skw) in enumerate(reqs):
        if i:
            sup.step()
        handles.append(sup.submit(p, **skw))
    while any(not h.finished for h in handles):
        sup.step()
    assert [list(h.tokens) for h in handles] == base
    assert sup.rebuilds == 1
    # the condemned incarnation's acceptance history survived
    assert sup.spec_totals["spec_steps"] > 0
    total = sup.spec_counters()
    assert total["spec_steps"] >= sup.spec_totals["spec_steps"]
    assert sup.stats()["spec_counters_total"] == total
    assert sup.verify_used_total or sup.engine.verify_used


# ---------------------------------------------------------------------------
# metrics: per-emitted-token ITL
# ---------------------------------------------------------------------------

def test_itl_records_per_emitted_token_intervals():
    from paddle_tpu.serving.metrics import EngineMetrics

    # k>1: a 0.4s step that emitted 4 tokens must read as 4 x 0.1s
    # intervals, not one 0.4s outlier (brownout p95 + retry_after hint)
    m = EngineMetrics()
    m.mark_decode(0.4, tokens=4)
    assert m.decode_steps == 1
    assert m.itl_hist.count == 4
    assert abs(m.itl_hist.sum - 0.4) < 1e-9
    assert m.itl_estimate() is not None and m.itl_estimate() < 0.2
    assert m.itl_p95() < 0.2
    # k=0 / non-speculative: the default is bit-unchanged
    m2 = EngineMetrics()
    m2.mark_decode(0.4)
    assert m2.decode_steps == 1
    assert m2.itl_estimate() > 0.2


def test_engine_itl_observation_count_matches_tokens(model):
    """Engine-level regression: the histogram holds one observation per
    token emitted by a step (spec multi-token steps included)."""
    spec = Engine(model, speculative=SpecConfig(draft="ngram", k=4),
                  **GEO)
    _drive(spec, [(np.full((9,), 7, np.int32),
                   dict(max_new_tokens=12, logit_mask=_mask(7)))],
           stagger=False)
    m = spec.metrics
    # tokens 2..max_new come out of decode/verify steps; token 1 is the
    # prefill sample (not a decode observation)
    assert m.itl_hist.count == m.tokens_generated - m.prefills
    assert m.spec_emitted_tokens + (
        m.decode_steps - m.spec_steps) == m.tokens_generated - m.prefills


# ---------------------------------------------------------------------------
# compile budget + audit + CLI smoke (the tier-1 wiring)
# ---------------------------------------------------------------------------

def test_spec_compile_budget_and_audit():
    """Fresh weight shapes (1-layer config unique to this test): the
    speculative engine cold-compiles EXACTLY buckets + decode + verify,
    the audit meta carries the spec config + acceptance ledger, and the
    compile-budget rule counts the verify program."""
    from paddle_tpu import analysis

    cfg1 = dataclasses.replace(LLAMA_TINY, dtype="float32",
                               num_hidden_layers=1, hidden_size=48)
    paddle.seed(1)
    m1 = LlamaForCausalLM(cfg1)
    m1.eval()
    counter = analysis.CompileEventCounter().install()
    reqs = [(np.full((9,), 7, np.int32),
             dict(max_new_tokens=8, logit_mask=_mask(7))),
            (np.arange(10, 15, dtype=np.int32),
             dict(max_new_tokens=6))]
    budget = 2 + 1 + 1          # buckets {8, 16} + decode + verify
    eng = Engine(m1, speculative=SpecConfig(draft="ngram", k=4),
                 compile_budget=budget, **GEO)
    counter.reset()
    _drive(eng, reqs)
    if counter.available:
        assert counter.count == budget
    assert eng.verify_used and ("decode",) in eng._aot
    rep = analysis.audit_engine(eng)
    meta_spec = rep.metrics["compile-budget"]
    assert meta_spec["verify_program"] is True
    assert meta_spec["programs"] == budget
    assert not [f for f in rep.findings
                if f.rule_id == "compile-budget"
                and f.severity == "high"]
    # under-declaring by one (the verify program) must be caught
    rep2 = analysis.audit_engine(eng, compile_budget=budget - 1)
    assert [f for f in rep2.findings
            if f.rule_id == "compile-budget" and f.severity == "high"]


def test_chaos_serve_spec_cli_smoke(capsys):
    import json

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import chaos_serve
    finally:
        sys.path.pop(0)
    rc = chaos_serve.main(["--spec", "--fault", "raise", "--step", "5",
                           "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"]
    assert out["token_identical"] and out["spec_counters_survived_rebuild"]
    assert out["spec_counters_total"]["spec_steps"] > 0


# ---------------------------------------------------------------------------
# k sweep (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_k_sweep_token_identity(model):
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32),
             dict(max_new_tokens=10)) for n in (5, 8, 12)]
    base = _drive(Engine(model, **GEO), reqs)
    for k in (1, 2, 3, 5, 6):
        spec = Engine(model, speculative=SpecConfig(draft=model, k=k),
                      **GEO)
        assert _drive(spec, reqs) == base
        assert spec.verify_used
