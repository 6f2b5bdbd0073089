"""The serving engine's own stamps (always on) and the span tracer that
reports them (when on).

* every ``Engine.step()`` with decode-active rows leaves a ``StepRecord``
  whose stamps are monotone and whose four phases sum to its length, and
  every program launch a ``LaunchRecord``; ``kind`` is ``admit`` exactly
  when a prefill or chunk launch carries the step's index;
* the stamps cover the token fetch: a result that is slow to reach the
  host shows in ``itl_estimate()``, in the fetch phase and in
  ``serving.prefill`` (all three read ~0 on the parent of this change,
  which closed its spans and took ``mark_decode`` before the fetch: on a
  chip, where dispatch is asynchronous, they timed the enqueue);
* the rings are bounded, fill with the tracer off, and with it on the
  same stamps are ``serving.step`` with its phases as children;
* a live ``tracing.span`` and ``profiler.RecordEvent`` are in a
  ``jax.profiler`` trace by name;
* the host thread accounts for itself: ``admitted`` and ``chunked`` split
  the schedule phase into admission, chunk and capacity, which sum to it
  exactly and take what a slow radix match, eviction or first-token wait
  costs; every admission leaves an ``AdmitRecord``; a step's and a
  submit's thread CPU lies within its wall time; a collection inside a
  step shows on the step's record.

One tiny module-scope model at the geometry the other serving suites use
(shared jit programs).
"""
import dataclasses
import gc
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Engine, SpecConfig
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
GEO = dict(n_slots=2, max_len=64, min_prompt_bucket=4, block_size=8)
LAYOUTS = {
    "paged": GEO,
    "paged_chunked": dict(GEO, prefill_chunk=8),
}
SLOW = 0.02


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _churn(eng, seed=3, max_new=5):
    """Four requests through two slots, one prompt long enough to be
    chunked where the engine chunks: admissions inside ``submit()`` and
    inside steps, decode-only steps, and steps beside a prefill."""
    handles = [eng.submit(p, max_new_tokens=max_new)
               for p in _prompts([5, 21, 6, 9], seed)]
    eng.drain()
    assert all(h.finish_reason == "length" for h in handles)
    return handles


class _SlowToHost:
    """A program result that takes ``SLOW`` seconds to reach the host:
    what a device still computing looks like to ``np.asarray`` / ``int``
    after an asynchronous dispatch has returned at once."""

    def __init__(self, value):
        self.value = value

    def __array__(self, dtype=None, copy=None):
        time.sleep(SLOW)
        return np.asarray(self.value, dtype)

    def __int__(self):
        time.sleep(SLOW)
        return int(self.value)


def _slow_results(eng, kind, index):
    """Wrap ``eng._run_program``: output ``index`` of every ``kind``
    program comes back slow to fetch."""
    run = eng._run_program

    def wrapped(k, hkey, jitted, args, *rest):
        # the slow token vector is also the next program's argument
        args = tuple(a.value if isinstance(a, _SlowToHost) else a
                     for a in args)
        out = run(k, hkey, jitted, args, *rest)
        if k != kind:
            return out
        out = list(out)
        out[index] = _SlowToHost(out[index])
        return tuple(out)

    eng._run_program = wrapped


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_decoding_step_leaves_a_monotone_record(model, layout):
    eng = Engine(model, **LAYOUTS[layout])
    decoded = []
    step = eng.step
    eng.step = lambda: decoded.append(step()) or decoded[-1]
    _churn(eng)
    m = eng.metrics
    steps = list(m.steps)
    # one record for each step() that decoded, none for the others
    assert [r.n_active for r in steps] == [n for n in decoded if n]
    assert [r.index for r in steps] == [i for i, n in enumerate(decoded)
                                        if n]
    for r in steps:
        stamps = r[2:7]
        assert list(stamps) == sorted(stamps)
        assert sum(r.phases().values()) == pytest.approx(r.end - r.begin,
                                                         abs=1e-9)
        assert r.kind in ("decode", "admit")
    # the cumulative seconds are those of the records, phase by phase
    snap = eng.stats()
    assert snap["steps_recorded"] == len(steps) == m.steps_recorded
    for phase, seconds in snap["step_phase_seconds"].items():
        assert seconds == pytest.approx(
            sum(r.phases()[phase] for r in steps), abs=1e-5)
    assert sum(snap["step_phase_seconds"].values()) == pytest.approx(
        sum(r.end - r.begin for r in steps), abs=1e-5)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kind_is_admit_exactly_when_a_prefill_carries_the_step(model,
                                                               layout):
    eng = Engine(model, **LAYOUTS[layout])
    handles = _churn(eng)
    launches = list(eng.metrics.launches)
    prefilled = {r.step for r in launches
                 if r.program.startswith(("prefill:", "chunk"))}
    kinds = {r.index: r.kind for r in eng.metrics.steps}
    assert set(kinds.values()) == {"decode", "admit"}
    for index, kind in kinds.items():
        assert (kind == "admit") == (index in prefilled), (index, kind)
    # the first two prompts found a free slot inside submit(): no step,
    # and the launch lies inside that call's own record
    assert None in prefilled
    submits = {r.request_id: r for r in eng.metrics.submits}
    assert sorted(submits) == sorted(h.request_id for h in handles)
    for r in launches:
        if r.step is None:
            call = submits[r.request_id]
            assert call.begin <= r.called and r.fetched <= call.end
    assert eng.stats()["submit_seconds"] == pytest.approx(
        sum(r.end - r.begin for r in submits.values()), abs=1e-5)
    # every decoding step launched the decode program once, inside it
    assert [r.step for r in launches if r.program == "decode"] \
        == sorted(kinds)
    for r in launches:
        assert r.called <= r.dispatched
        final_less_chunk = r.program == "chunk" and r.fetched is None
        assert final_less_chunk or r.dispatched <= r.fetched
    # each request was prefilled once, and its launch says for whom
    bucket = [r for r in launches if r.program.startswith("prefill:")]
    final = [r for r in launches
             if r.program == "chunk" and r.fetched is not None]
    assert sorted(r.request_id for r in bucket + final) \
        == sorted(h.request_id for h in handles)
    if layout == "paged_chunked":
        assert len(final) == 2      # the prompts of 21 and 9 tokens
        chunks = [r for r in launches if r.program == "chunk"]
        assert len(chunks) > len(final) and all(
            r.step is not None for r in chunks)
        assert sum(r.tokens for r in chunks) == 21 + 9


def test_a_slow_token_fetch_shows_in_itl_and_in_the_fetch_phase(model):
    """Read ~0 before: ``mark_decode`` and ``serving.decode_step`` closed
    before ``np.asarray(nxt)``."""
    eng = Engine(model, **GEO)
    _churn(eng)                  # compiles: a first call dispatches slowly
    warm = eng.metrics.steps_recorded
    assert eng.metrics.itl_estimate() < SLOW
    tracing.enable()
    _slow_results(eng, "decode", 0)
    for _ in range(33):          # fills the rolling window's two halves
        eng.submit(_prompts([5], 4)[0], max_new_tokens=3)
        eng.drain()
    steps = list(eng.metrics.steps)[warm:]
    assert len(steps) == 66
    assert all(r.phases()["fetch"] >= SLOW for r in steps)
    assert all(r.phases()["dispatch"] < SLOW for r in steps)
    assert eng.metrics.itl_estimate() >= SLOW
    assert eng.metrics.itl_p95() >= SLOW
    assert eng._retry_after_hint() == eng.default_retry_after_s  # idle
    fetch = obs.spans("serving.fetch")
    assert len(fetch) == 66 and all(s["dur"] >= SLOW for s in fetch)
    assert eng.stats()["step_phase_seconds"]["fetch"] >= 66 * SLOW


@pytest.mark.parametrize("layout,kind,index", [
    ("paged", "prefill", 5),
    ("paged_chunked", "chunk", 5)])
def test_a_slow_first_token_shows_in_the_prefill_span(model, layout, kind,
                                                      index):
    """Read ~0 before: ``serving.prefill`` closed before ``int(tok0)``."""
    eng = Engine(model, **LAYOUTS[layout])
    _churn(eng)                  # compiles: a first call dispatches slowly
    warm = eng.metrics.launches_recorded
    tracing.enable()
    _slow_results(eng, kind, index)
    h = eng.submit(_prompts([12], 5)[0], max_new_tokens=2)
    eng.drain()
    name = "serving.prefill" if kind == "prefill" else \
        "serving.prefill_chunk"
    spans = [s for s in obs.spans(name)
             if s["args"].get("final", True)]
    assert len(spans) == 1 and spans[0]["dur"] >= SLOW
    assert spans[0]["trace"] == h.trace_id
    rec = [r for r in list(eng.metrics.launches)[warm:]
           if r.program.startswith(kind) and r.fetched is not None]
    assert len(rec) == 1 and rec[0].fetched - rec[0].called >= SLOW
    assert rec[0].request_id == h.request_id and rec[0].tokens > 0
    # a chunk that samples nothing is never fetched, so never waited for
    for s in obs.spans("serving.prefill_chunk"):
        assert s["args"]["final"] or s["dur"] < SLOW


def test_rings_stay_bounded(model, monkeypatch):
    monkeypatch.setattr(serving_metrics, "RING", 6)
    eng = Engine(model, **GEO)
    for p in _prompts([5] * 8, 6):
        eng.submit(p, max_new_tokens=9)
        eng.drain()
    m = eng.metrics
    assert m.steps_recorded == 64 and m.launches_recorded == 72
    assert len(m.steps) == len(m.launches) == 6
    assert [r.index for r in m.steps] == list(range(58, 64))
    # what no ring forgets: the cumulative seconds cover every step
    assert sum(m.phase_seconds.values()) > sum(
        r.end - r.begin for r in m.steps)


def test_tracer_off_fills_the_rings_and_no_span(model):
    assert not tracing.enabled()
    eng = Engine(model, **GEO)
    _churn(eng)
    assert obs.spans() == []
    assert eng.metrics.steps and eng.metrics.launches
    assert eng._held_spans == []
    # the accessor finds this engine's rings among the live engines'
    assert any(m is eng.metrics for m in serving_metrics.live_metrics())
    fams = {f["name"]: f for f in obs.REGISTRY.collect()}
    phases = dict((labels["phase"], v) for labels, v in fams[
        "paddle_serving_step_phase_seconds_total"]["samples"])
    assert set(phases) == set(serving_metrics.PHASES)
    assert phases["fetch"] >= eng.metrics.phase_seconds["fetch"] > 0


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_tracer_on_nests_the_phases_under_the_step(model, layout):
    tracing.enable()
    eng = Engine(model, **LAYOUTS[layout])
    handles = _churn(eng)
    steps = obs.spans("serving.step")
    records = list(eng.metrics.steps)
    assert len(steps) == len(records)
    by_parent = {}
    for s in obs.spans():
        by_parent.setdefault(s["parent"], []).append(s)
    for span, rec in zip(steps, records):
        assert span["args"]["step"] == rec.index
        assert span["args"]["kind"] == rec.kind
        kids = [s for s in by_parent[span["span"]]
                if s["name"] != "spec.verify"]
        assert [s["name"] for s in kids] == [
            "serving.schedule", "serving.dispatch", "serving.fetch",
            "serving.emit"]
        # one stamp feeds ring and span: the same floats, not re-read
        assert span["t0"] == rec.begin
        assert [s["t0"] for s in kids] == [rec.begin, rec.scheduled,
                                           rec.dispatched, rec.fetched]
        assert kids[3]["t0"] + kids[3]["dur"] == pytest.approx(rec.end)
        # a prefill that ran inside the step hangs under its schedule
        inside = by_parent.get(kids[0]["span"], [])
        assert (rec.kind == "admit") == bool(inside)
        assert all(s["name"] in ("serving.prefill",
                                 "serving.prefill_chunk") for s in inside)
    # a request's spans keep its trace id, inside a step or in submit()
    for h in handles:
        mine = [s for s in obs.spans() if s["trace"] == h.trace_id]
        names = {s["name"] for s in mine}
        assert {"serving.submit", "serving.submit_call", "serving.queue",
                "serving.decode", "serving.finish"} <= names
        assert names & {"serving.prefill", "serving.prefill_chunk"}
    assert not obs.spans("serving.decode_step")
    # and the export nests them by parent_span
    doc = obs.to_chrome_trace()["traceEvents"]
    ids = {s["span"] for s in steps}
    nested = [e for e in doc if e.get("args", {}).get("parent_span") in ids]
    assert len(nested) == 4 * len(steps)


def test_a_speculative_step_is_kind_spec_with_its_launches(model):
    tracing.enable()
    eng = Engine(model, speculative=SpecConfig(draft="ngram", k=4), **GEO)
    only_7 = np.zeros(CFG.vocab_size, bool)
    only_7[7] = True         # a repetitive stream: the n-gram draft hits
    h = eng.submit(np.full((9,), 7, np.int32), max_new_tokens=8,
                   logit_mask=only_7)
    eng.drain()
    assert h.finish_reason == "length" and eng.verify_used
    steps = list(eng.metrics.steps)
    assert steps and {r.kind for r in steps} == {"spec"}
    verifies = [r for r in eng.metrics.launches
                if r.program == "spec.verify"]
    assert verifies and all(r.step is not None and r.request_id
                            == h.request_id for r in verifies)
    for r in steps:
        assert list(r[2:7]) == sorted(r[2:7])
    # verify spans hang under their step and keep the request's trace
    step_ids = {s["span"] for s in obs.spans("serving.step")}
    spans = obs.spans("spec.verify")
    assert len(spans) == len(verifies)
    assert all(s["parent"] in step_ids and s["trace"] == h.trace_id
               for s in spans)
    # per-emitted-token intervals, each covering the verify's fetch
    assert eng.metrics.itl_hist.count == eng.metrics.spec_emitted_tokens \
        + sum(r.program == "decode" for r in eng.metrics.launches)


def test_a_step_that_raises_closes_its_stamps(model):
    tracing.enable()
    eng = Engine(model, n_slots=1, max_len=64, min_prompt_bucket=4,
                 block_size=8)
    first, second = (eng.submit(p, max_new_tokens=2)
                     for p in _prompts([5, 6], 8))
    run = eng._run_program

    def failing(kind, *args):
        if kind == "decode" and second.slot is not None:
            raise RuntimeError("boom")
        return run(kind, *args)

    eng._run_program = failing
    eng.step()                   # first finishes, its slot frees
    with pytest.raises(RuntimeError):
        eng.step()               # admits second, then the decode raises
    assert eng._step is None and eng._held_spans == []
    # the prefill launched inside the failed step still has its span
    # (no step to hang under) and its launch record names the step
    spans = [s for s in obs.spans("serving.prefill")
             if s["trace"] == second.trace_id]
    assert len(spans) == 1 and spans[0]["parent"] is None
    assert [r.step for r in eng.metrics.launches
            if r.request_id == second.request_id] == [1]
    assert [r.index for r in eng.metrics.steps] == [0]


def test_span_event_names_its_parent_and_returns_its_id():
    assert obs.span_event("off", 0.0, 1.0) is None
    tracing.enable()
    outer = obs.span_event("outer", 1.0, 3.0, cat="t", trace_id="T")
    inner = obs.span_event("inner", 1.5, 2.0, parent=outer, k=1)
    assert outer and inner and outer != inner
    a, b = obs.spans()
    assert (a["span"], a["parent"], a["trace"]) == (outer, None, "T")
    assert (b["span"], b["parent"], b["args"]) == (inner, outer, {"k": 1})
    ev = [e for e in obs.to_chrome_trace()["traceEvents"]
          if e["name"] == "inner"]
    assert ev[0]["args"]["parent_span"] == outer


def _names_in_xplane(trace_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    return {ev.name for plane in ProfileData.from_file(paths[0]).planes
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("tracer_on", [True, False])
def test_live_spans_and_record_events_are_in_the_profilers_trace(
        tmp_path, tracer_on):
    import jax

    from paddle_tpu import profiler

    if tracer_on:
        tracing.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("stamps.live", cat="test"):
            tok = obs.begin_span("stamps.begun")
            obs.end_span(tok)
        with profiler.RecordEvent("stamps-range"):
            pass
        profiler.RecordInstantEvent("stamps-ping").begin()
    finally:
        jax.profiler.stop_trace()
    names = _names_in_xplane(str(tmp_path))
    ring = {s["name"] for s in obs.spans()}
    # a RecordEvent is the profiler's with the tracer on or off ...
    assert {"UserDefined::stamps-range", "UserDefined::stamps-ping"} \
        <= names
    # ... a span, live or begun by hand, only while the tracer is on
    assert ({"stamps.live", "stamps.begun"} <= names) == tracer_on
    assert ring == ({"stamps.live", "stamps.begun", "user::stamps-range",
                     "user::stamps-ping"} if tracer_on else set())


def test_stamping_a_step_costs_microseconds():
    """The always-on part of a step: nine clock reads, the thread's
    counters at its begin and its end, a StepRecord and a LaunchRecord
    into the rings. The bound here is loose (a loaded CI core); the
    figures measured on a CPU host and on the chip's host, where the
    thread's counters cost a kernel call each, are in PERF.md."""
    m = serving_metrics.EngineMetrics()
    clock = time.perf_counter
    n = 20_000
    t0 = clock()
    for i in range(n):
        a = clock()
        before = serving_metrics.thread_counters()
        b, c, d, e, f, g, h = (clock(), clock(), clock(), clock(), clock(),
                               clock(), clock())
        m.mark_launch(serving_metrics.LaunchRecord(
            "decode", i, e, f, g, None, 16, 0))
        m.mark_step(serving_metrics.StepRecord(
            i, "decode", a, d, f, g, h, 16, b, c,
            serving_metrics.thread_delta(before)))
    per_step = (clock() - t0) / n
    assert per_step < 50e-6
    assert m.steps_recorded == n and len(m.steps) == serving_metrics.RING
    assert m.host_cpu_seconds["step"] > 0


def _parts_sum_to_schedule(r):
    """The three parts lie inside the schedule phase, in order, and add
    up to it to the last bit (each is a difference of two nearby clock
    reads, exact in floating point, and so is their sum)."""
    assert r.begin <= r.admitted <= r.chunked <= r.scheduled
    parts = r.parts()
    assert list(parts) == list(serving_metrics.PARTS)
    assert sum(parts.values()) == r.phases()["schedule"]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_schedule_phase_splits_into_three_parts(model, layout):
    eng = Engine(model, **LAYOUTS[layout])
    _churn(eng)
    steps = list(eng.metrics.steps)
    for r in steps:
        _parts_sum_to_schedule(r)
        # the four phases keep their stamps and their meaning
        assert [p for p, _, _ in r.intervals()] == list(
            serving_metrics.PHASES)
        assert [t for _, t, _ in r.intervals()] == [
            r.begin, r.scheduled, r.dispatched, r.fetched]
    # a step that ran a chunk spent it between admitted and chunked, one
    # that ran a bucket prefill between begin and admitted
    launches = list(eng.metrics.launches)
    for r in steps:
        mine = [x for x in launches if x.step == r.index]
        for x in mine:
            if x.program == "chunk":
                assert r.admitted <= x.called and \
                    (x.fetched or x.dispatched) <= r.chunked
            elif x.program.startswith("prefill:"):
                assert r.begin <= x.called and x.fetched <= r.admitted
    if layout == "paged_chunked":
        assert any(x.program == "chunk" for x in launches)


def _slow(obj, name, seconds=SLOW):
    """Make ``obj.name`` take ``seconds`` longer, in the calling thread
    (a sleep: off the CPU, so wall time and not CPU time)."""
    fn = getattr(obj, name)

    def slow(*args, **kwargs):
        time.sleep(seconds)
        return fn(*args, **kwargs)

    setattr(obj, name, slow)


def test_a_slow_radix_match_lands_in_admission_and_placed(model):
    eng = Engine(model, **GEO)
    _churn(eng)                  # compiles
    warm_steps, warm_admits = (eng.metrics.steps_recorded,
                               len(eng.metrics.admits))
    _slow(eng.cache.radix, "match")
    # two requests fill both slots inside submit(); the third is admitted
    # inside the step after the first finishes
    handles = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(_prompts([5, 6, 7], 9), (2, 6, 3))]
    eng.drain()
    assert all(h.finish_reason == "length" for h in handles)
    admits = list(eng.metrics.admits)[warm_admits:]
    assert [a.request_id for a in admits] == [h.request_id
                                             for h in handles]
    assert [a.step is None for a in admits] == [True, True, False]
    for a in admits:
        assert a.placed - a.begin >= SLOW
        assert a.end - a.placed < SLOW
    steps = list(eng.metrics.steps)[
        -(eng.metrics.steps_recorded - warm_steps):]
    admitting = [r for r in steps if r.index == admits[2].step]
    assert len(admitting) == 1
    r = admitting[0]
    assert r.parts()["admission"] >= SLOW
    assert r.admitted >= admits[2].end >= admits[2].begin >= r.begin
    # the sleep is wall time the thread spent off the CPU
    assert r.thread.cpu <= r.end - r.begin - SLOW / 2
    others = [x for x in steps if x is not r]
    assert others and all(x.parts()["admission"] < SLOW for x in others)
    assert all(x.parts()["capacity"] < SLOW for x in steps)


def test_a_slow_eviction_lands_in_capacity(model):
    eng = Engine(model, **GEO)
    _churn(eng)
    warm = eng.metrics.steps_recorded
    # a decode row that crosses into a new block asks ensure() for it
    _slow(eng.cache, "_alloc_or_evict")
    h = eng.submit(_prompts([6], 10)[0], max_new_tokens=6)
    eng.drain()
    assert h.finish_reason == "length"
    steps = list(eng.metrics.steps)[-(eng.metrics.steps_recorded - warm):]
    slow = [r for r in steps if r.parts()["capacity"] >= SLOW]
    # positions 6 .. 10: the row's next line crosses into block 1 once
    assert len(slow) == 1
    assert all(r.parts()["admission"] < SLOW for r in steps)
    for r in steps:
        _parts_sum_to_schedule(r)


def test_a_final_chunks_token_wait_lands_in_chunk(model):
    eng = Engine(model, **LAYOUTS["paged_chunked"])
    _churn(eng)
    warm = eng.metrics.steps_recorded
    first = eng.submit(_prompts([5], 11)[0], max_new_tokens=12)
    eng.step()                   # first decodes while the next chunks
    _slow_results(eng, "chunk", 5)
    second = eng.submit(_prompts([21], 12)[0], max_new_tokens=2)
    eng.drain()
    assert {first.finish_reason, second.finish_reason} == {"length"}
    steps = list(eng.metrics.steps)[-(eng.metrics.steps_recorded - warm):]
    launches = [x for x in eng.metrics.launches if x.program == "chunk"
                and x.request_id == second.request_id]
    final = [x for x in launches if x.fetched is not None]
    assert len(launches) == 3 and len(final) == 1
    waited = [r for r in steps if r.index == final[0].step]
    assert len(waited) == 1 and waited[0].parts()["chunk"] >= SLOW
    assert waited[0].thread.cpu <= waited[0].end - waited[0].begin - SLOW / 2
    assert all(r.parts()["chunk"] < SLOW for r in steps
               if r is not waited[0])


def test_a_collection_inside_a_step_shows_on_its_record(model):
    eng = Engine(model, **GEO)
    _churn(eng)
    before = eng.stats()
    collected = []

    def on_token(h, token):
        if len(h.tokens) == 3:
            gc.collect()
            collected.append(eng._step)

    eng.submit(_prompts([5], 13)[0], max_new_tokens=5, on_token=on_token)
    eng.drain()
    assert len(collected) == 1 and collected[0] is not None
    by_index = {r.index: r for r in eng.metrics.steps}
    r = by_index[collected[0]]
    assert r.thread.gc_collections >= 1 and r.thread.gc_seconds > 0
    # the pause sits in the emit phase, where the callback ran
    assert r.phases()["emit"] >= r.thread.gc_seconds
    after = eng.stats()
    assert after["gc_collections"] >= before["gc_collections"] + 1
    assert after["gc_pause_seconds"] - before["gc_pause_seconds"] \
        >= r.thread.gc_seconds - 1e-6       # the snapshot rounds to 1 us


# A thread's CPU time never passes its wall time, but the kernel splits
# a thread's CPU into user and system time from scheduler ticks, so over
# a call of a few milliseconds the sum it reports can run past the wall
# clock by up to a tick (0.2 ms over 2.4 ms has been seen): the bound
# keeps a tick of room on each call and a few per cent on their sum,
# which still fails a CPU clock that counts other threads (the runtime's
# workers) or another interval.
TICK = 0.010


def _within_wall(cpu, wall):
    return cpu <= wall * 1.05 + TICK


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_cpu_clock_lies_within_the_wall_clock(model, layout):
    eng = Engine(model, **LAYOUTS[layout])
    _churn(eng)
    m = eng.metrics
    calls = list(m.steps) + list(m.submits)
    for r in calls:
        assert _within_wall(r.thread.cpu, r.end - r.begin)
        assert min(r.thread) >= 0
    assert sum(r.thread.cpu for r in calls) <= 1.05 * sum(
        r.end - r.begin for r in calls) + TICK
    for r in m.admits:
        assert r.begin <= r.placed <= r.end
    snap = eng.stats()
    walls = {"step": sum(snap["step_phase_seconds"].values()),
             "submit": snap["submit_seconds"]}
    assert set(snap["host_cpu_seconds"]) == set(walls)
    for call, cpu in snap["host_cpu_seconds"].items():
        assert 0 < cpu and _within_wall(cpu, walls[call]), call


def test_admissions_are_recorded_in_submit_and_in_steps(model):
    """Each admission leaves one record, inside ``submit()`` (no step) or
    inside a step; a radix hit shows as ``shared``, an eviction as
    ``evicted``, counted as the cache counts them."""
    eng = Engine(model, **dict(GEO, n_blocks=10))
    system = _prompts([8], 14)[0]
    turns = _prompts([12, 13, 14, 15, 16, 17, 12, 13, 14], 15)
    handles = []
    for i in range(0, len(turns), 3):   # three requests for two slots
        handles += [eng.submit(np.concatenate([system, t]),
                               max_new_tokens=4) for t in turns[i:i + 3]]
        eng.drain()
    assert all(h.finish_reason == "length" for h in handles)
    admits = list(eng.metrics.admits)
    # a request the pool could not cover at once is tried again later
    assert set(a.request_id for a in admits) == set(
        h.request_id for h in handles)
    assert {a.step is None for a in admits} == {True, False}
    steps = {r.index for r in eng.metrics.steps}
    assert all(a.step in steps for a in admits if a.step is not None)
    m = eng.metrics
    assert sum(a.shared for a in admits) == m.prefix_hit_tokens > 0
    # the rest of the index's evictions made room for decode lines
    assert 0 < sum(a.evicted for a in admits) <= eng.cache.radix.evicted
    # every admission inside a submit() lies inside that call's record
    calls = {r.request_id: r for r in m.submits}
    for a in admits:
        if a.step is None:
            c = calls[a.request_id]
            assert c.begin <= a.begin <= a.end <= c.end
