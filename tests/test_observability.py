"""paddle_tpu.observability — metrics registry, span tracer, compile
attribution, and the wiring into serving/profiler/lint.

Acceptance contracts covered here:

* registry units + Prometheus text exposition parses + JSON snapshot
  is serializable (collectors included);
* span nesting / trace-id inheritance / bounded ring; the disabled
  path records nothing;
* a full serving request's lifecycle exports as valid Chrome trace
  JSON, and a token-identical replay across an EngineSupervisor
  rebuild carries the ORIGINAL request's trace id;
* compile attribution is consistent with the check_retrace
  CompileEventCounter signal (both zero warm, both nonzero cold, the
  cold compiles attributed to the scoped origin);
* EngineOverloaded.retry_after_s derives from the ITL histogram p95
  with the finite cold-engine default preserved;
* the ``wallclock-in-span`` self-lint rule (pos/neg/allow);
* tools/obs_dump.py --json smoke (the tier-1 wiring).

Kept slim for the tier-1 budget: one module-scope tiny llama shared
with the other serving test modules (same geometry => shared jit
programs).
"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing
from paddle_tpu.resilience import ChaosMonkey
from paddle_tpu.serving import Engine, EngineOverloaded, EngineSupervisor
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
GREEDY = dict(n_slots=2, max_len=64, min_prompt_bucket=4)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts with the tracer off and an empty ring."""
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _obs_dump():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_dump
    finally:
        sys.path.pop(0)
    return obs_dump


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------

def test_counter_gauge_units():
    reg = obs_metrics.MetricsRegistry()
    c = obs_metrics.Counter("t_requests_total", "x",
                            labelnames=("kind",), registry=reg)
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.value == 4
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)          # counters only go up
    with pytest.raises(ValueError):
        c.labels(wrong="a")                 # label names enforced
    with pytest.raises(ValueError):
        c.inc()                             # labeled: must go via labels
    g = obs_metrics.Gauge("t_depth", "x", registry=reg)
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    with pytest.raises(ValueError):
        obs_metrics.Counter("t_depth", "collides", registry=reg)
    with pytest.raises(ValueError):
        obs_metrics.Counter("bad name!", registry=reg)
    fams = {f["name"]: f for f in reg.collect()}
    assert fams["t_requests_total"]["samples"] == [
        ({"kind": "a"}, 3.0), ({"kind": "b"}, 1.0)]


def test_histogram_percentile_window_and_cumulative():
    h = obs_metrics.Histogram("t_lat_seconds", window=64, registry=None)
    assert h.percentile(50) is None and h.percentile(95) is None
    for _ in range(8):
        h.observe(0.5)
    # all-slow window: both quantiles land in the 0.5 bucket
    assert h.percentile(95) > 0.25
    assert h.percentile(50) > 0.25
    # the rolling window forgets: 64 fast observations push the slow
    # ones out entirely (the brownout-exit contract)
    for _ in range(64):
        h.observe(0.001)
    assert h.percentile(95) < 0.01
    # cumulative export never forgets and is monotone with total count
    buckets = h.cumulative()
    assert buckets[-1][0] == float("inf") and buckets[-1][1] == 72
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)
    assert h.count == 72 and abs(h.sum - (8 * 0.5 + 64 * 0.001)) < 1e-9


def test_prometheus_text_parses_and_snapshot_serializable(model):
    # a live engine so the serving collector families have data,
    # including the merged ITL histogram
    eng = Engine(model, **GREEDY)
    eng.submit(_prompts([5], seed=0)[0], max_new_tokens=4)
    eng.drain()
    text = obs.to_prometheus()
    bad = _obs_dump().prom_parses(text)
    assert not bad, f"malformed exposition lines: {bad[:5]}"
    assert "paddle_serving_events_total" in text
    assert "paddle_serving_itl_seconds_bucket" in text
    assert "paddle_xla_compiles_total" in text
    snap = obs.snapshot()
    json.dumps(snap)                     # JSON-serializable end to end
    assert snap["paddle_serving_itl_seconds"]["count"] > 0
    # histogram exposition: le-cumulative counts are monotone
    hist = snap["paddle_serving_itl_seconds"]
    cums = [c for _, c in hist["buckets"]]
    assert cums == sorted(cums)


def test_collector_failure_is_reported_not_fatal():
    reg = obs_metrics.MetricsRegistry()

    def broken():
        raise RuntimeError("scrape me not")
        yield  # pragma: no cover

    reg.collector(broken, "broken")
    fams = {f["name"]: f for f in reg.collect()}
    errs = fams["paddle_collector_errors"]["samples"]
    assert errs and "RuntimeError" in errs[0][0]["error"]


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_ids_and_ring_bound():
    tracing.enable(ring=4)
    try:
        with obs.span("outer") as outer_tok:
            with obs.span("inner"):
                assert tracing.current_trace_id() is not None
        inner, outer = obs.spans()
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["trace"] == outer["trace"]      # inherited
        assert inner["parent"] == outer["span"]
        assert outer["parent"] is None
        assert outer_tok.trace == outer["trace"]
        # ring bound: only the newest 4 survive
        for i in range(10):
            with obs.span(f"s{i}"):
                pass
        names = [s["name"] for s in obs.spans()]
        assert names == ["s6", "s7", "s8", "s9"]
    finally:
        tracing.ring_size(8192)


def test_disabled_tracer_records_nothing():
    assert not tracing.enabled()
    with obs.span("ghost", attr=1) as tok:
        assert tok is None
    obs.instant("ghost-instant")
    obs.span_event("ghost-event", 0.0, 1.0)
    assert obs.spans() == []
    # explicit-trace-id spans still record nothing when disabled
    assert tracing.current_trace_id() is None


def test_chrome_trace_export_shape():
    tracing.enable()
    with obs.span("a", cat="test", k="v"):
        obs.instant("marker", cat="test")
    doc = obs.to_chrome_trace()
    json.loads(json.dumps(doc))
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"                      # process metadata
    xs = [e for e in evs if e["ph"] == "X"]
    ins = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 1 and len(ins) == 1
    assert xs[0]["name"] == "a" and xs[0]["dur"] >= 0
    assert {"ts", "pid", "tid", "args"} <= set(xs[0])
    assert xs[0]["args"]["k"] == "v" and xs[0]["args"]["trace_id"]


# ---------------------------------------------------------------------------
# serving request lifecycle + supervisor rebuild
# ---------------------------------------------------------------------------

def test_serving_request_trace_full_lifecycle(model):
    tracing.enable()
    eng = Engine(model, **GREEDY)
    h = eng.submit(_prompts([5], seed=1)[0], max_new_tokens=4)
    eng.drain()
    by_name = {}
    for s in obs.spans():
        if (s.get("args") or {}).get("request_id") == h.request_id \
                or s["name"] == "serving.step":
            by_name.setdefault(s["name"], []).append(s)
    for phase in ("serving.submit", "serving.queue", "serving.prefill",
                  "serving.decode", "serving.finish"):
        assert phase in by_name, f"missing {phase}"
    assert "serving.step" in by_name
    # every request-scoped phase links to the handle's one trace id
    for phase in ("serving.submit", "serving.queue", "serving.prefill",
                  "serving.decode", "serving.finish"):
        assert by_name[phase][0]["trace"] == h.trace_id
    assert by_name["serving.finish"][0]["args"]["reason"] == "length"
    # and the whole thing exports as loadable Chrome trace JSON
    doc = json.loads(json.dumps(obs.to_chrome_trace()))
    assert any(e.get("args", {}).get("trace_id") == h.trace_id
               for e in doc["traceEvents"])


def test_replay_span_carries_original_trace_id(model):
    """A token-identical replay on a rebuilt engine links to the
    ORIGINAL request's trace: same trace id on both prefills, replay_k
    > 0 on the second, and the rebuild ledger record names both the
    fault's trace id and the replayed request's."""
    tracing.enable()
    chaos = ChaosMonkey(seed=0, at={2: "decode-raise"})
    sup = EngineSupervisor(model, chaos=chaos, **GREEDY)
    h = sup.submit(_prompts([5], seed=2)[0], max_new_tokens=6)
    h.result()
    assert sup.rebuilds == 1 and h.finish_reason == "length"
    prefills = [s for s in obs.spans()
                if s["name"] == "serving.prefill"
                and s["args"]["request_id"] == h.request_id]
    assert len(prefills) == 2
    assert prefills[0]["trace"] == prefills[1]["trace"] == h.trace_id
    assert prefills[0]["args"]["replay_k"] == 0
    assert prefills[1]["args"]["replay_k"] > 0      # PRNG fast-forward
    adopts = [s for s in obs.spans() if s["name"] == "serving.adopt"]
    assert adopts and adopts[0]["trace"] == h.trace_id
    # chaos fault instant + ledger linkage
    fault_spans = [s for s in obs.spans()
                   if s["name"] == "chaos.decode-raise"]
    assert fault_spans and fault_spans[0]["trace"] == chaos.last_trace_id
    rebuilds = [r for r in sup.ledger.to_list() if r["event"] == "rebuild"]
    assert rebuilds[0]["trace_id"] == chaos.last_trace_id
    assert h.trace_id in rebuilds[0]["request_traces"]
    # the full faulted lifecycle still exports as valid Chrome JSON
    doc = json.loads(json.dumps(obs.to_chrome_trace()))
    assert sum(1 for e in doc["traceEvents"]
               if e.get("args", {}).get("trace_id") == h.trace_id) >= 4


# ---------------------------------------------------------------------------
# compile attribution
# ---------------------------------------------------------------------------

def test_compile_attribution_consistent_with_compile_counter():
    """The same contract check_retrace gates on: cold code compiles
    (both the CompileEventCounter and the attributed registry counter
    see it, under the scoped origin), warm code does not (both zero)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import analysis

    counter = analysis.CompileEventCounter().install()
    fn = jax.jit(lambda x: (x * 3 + 1).sum())
    x = jnp.arange(7.0)

    def attributed_total():
        return sum(v["count"] for v in obs.compiles_by_origin().values())

    counter.reset()
    before = attributed_total()
    with obs.compile_scope("test:cold"):
        fn(x)
    cold_attr = attributed_total() - before
    assert cold_attr >= 1
    assert obs.compiles_by_origin()["test:cold"]["count"] >= 1
    assert obs.compiles_by_origin()["test:cold"]["seconds"] > 0
    if counter.available:
        assert counter.count >= 1                # both signals agree
    # warm: neither signal moves (the 0-retrace steady-state contract)
    counter.reset()
    before = attributed_total()
    with obs.compile_scope("test:warm"):
        fn(x)
    assert attributed_total() - before == 0
    assert "test:warm" not in obs.compiles_by_origin()
    if counter.available:
        assert counter.count == 0


def test_compile_span_lands_in_trace():
    import jax
    import jax.numpy as jnp

    tracing.enable()
    with obs.compile_scope("test:span"):
        jax.jit(lambda x: x - 2)(jnp.arange(3.0))
    xs = [s for s in obs.spans() if s["name"] == "xla.compile"]
    assert xs and xs[0]["args"]["origin"] == "test:span"
    assert xs[0]["dur"] > 0


# ---------------------------------------------------------------------------
# ITL histogram -> retry_after / brownout (satellite regression)
# ---------------------------------------------------------------------------

def test_retry_after_hint_histogram_p95_and_cold_default(model):
    eng = Engine(model, n_slots=1, max_len=64, min_prompt_bucket=4,
                 max_queue=1, default_retry_after_s=1.0)
    # cold engine: documented finite default (regression for the cold
    # path now that the hint is histogram-backed)
    assert eng.metrics.itl_p95() is None
    assert eng._retry_after_hint() == 1.0
    h = eng.submit(_prompts([5], seed=3)[0], max_new_tokens=8)
    eng.step()
    eng.step()
    # warm + active: the hint is the rolling p95 x shortest remaining
    p95 = eng.metrics.itl_p95()
    assert p95 is not None and p95 > 0
    remaining = h.max_new_tokens - len(h.tokens)
    assert eng._retry_after_hint() == round(p95 * remaining, 3)
    assert np.isfinite(eng._retry_after_hint())
    eng.submit(_prompts([5], seed=4)[0], max_new_tokens=8)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(_prompts([5], seed=5)[0], max_new_tokens=8)
    assert ei.value.retry_after_s == eng._retry_after_hint()
    eng.drain()


# ---------------------------------------------------------------------------
# train phase spans
# ---------------------------------------------------------------------------

def test_train_phase_spans_cover_the_step():
    tracing.enable()
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4,), np.int64))
    loss = paddle.nn.functional.cross_entropy(net(x), y)
    loss.backward()
    opt.step()
    opt.clear_grad()
    names = {s["name"] for s in obs.spans()}
    assert {"train.forward", "train.backward", "train.optimizer"} <= names
    # ONE forward span per outermost model call, not one per sublayer
    fwd = [s for s in obs.spans() if s["name"] == "train.forward"]
    assert len(fwd) == 1 and fwd[0]["args"]["layer"] == "Sequential"


def test_dataloader_emits_data_spans():
    from paddle_tpu.io import DataLoader, TensorDataset

    tracing.enable()
    ds = TensorDataset([paddle.to_tensor(np.arange(8, dtype=np.float32))])
    loader = DataLoader(ds, batch_size=4)
    n = sum(1 for _ in loader)
    data_spans = [s for s in obs.spans() if s["name"] == "train.data"]
    assert n >= 1 and len(data_spans) >= n


# ---------------------------------------------------------------------------
# profiler surface (satellite: utils / profiler_statistic stubs)
# ---------------------------------------------------------------------------

def test_profiler_utils_and_span_statistic(capsys):
    import paddle_tpu.profiler as profiler
    from paddle_tpu.profiler import profiler_statistic as ps
    from paddle_tpu.profiler import utils as putils

    tracing.enable()
    assert not putils.in_profiler_mode()
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    assert putils.in_profiler_mode()
    with profiler.RecordEvent("custom-range"):
        pass
    profiler.RecordInstantEvent("ping").begin()
    prof.step()
    prof.stop()
    assert not putils.in_profiler_mode()
    stats = ps.gather_span_statistic()
    assert "user::custom-range" in stats
    assert stats["user::custom-range"]["calls"] == 1
    table = ps.build_span_summary(sorted_by=ps.SortedKeys.CPUTotal)
    assert "user::custom-range" in table and "Span Summary" in table
    prof.summary()
    out = capsys.readouterr().out
    assert "Span Summary" in out           # summary prints the ring
    # wrap_optimizers is the reference's optimizer-step RecordEvent
    # patch; here it (idempotently) enables the tracer
    tracing.disable()
    putils.wrap_optimizers()
    assert tracing.enabled()


# ---------------------------------------------------------------------------
# wallclock-in-span lint rule
# ---------------------------------------------------------------------------

_WALL_SRC = '''
import time

def bad_duration():
    t0 = time.time()
    work()
    return time.time() - t0        # flagged: duration from wall clock

def ok_timestamp():
    return {"t": time.time()}      # plain stamp: fine

def ok_monotonic():
    t0 = time.perf_counter()
    return time.perf_counter() - t0

def allowed_cross_process(stamp):
    now = time.time()
    # tpu_lint: allow(wallclock-in-span)
    return now - stamp
'''


def test_wallclock_in_span_rule(tmp_path):
    from paddle_tpu import analysis

    p = tmp_path / "wall.py"
    p.write_text(_WALL_SRC)
    rep = analysis.selflint([str(p)])
    hits = [f for f in rep.findings if f.rule_id == "wallclock-in-span"]
    assert len(hits) == 1
    assert ":7]" in str(hits[0]) or "wall.py:7" in hits[0].location
    assert hits[0].severity == "high"
    # the shipped tree is clean at the tier-1 gate (the 4 pre-existing
    # wall-clock duration sites were converted or allow()-annotated)
    pkg = analysis.selflint([os.path.join(REPO, "paddle_tpu")])
    assert not [f for f in pkg.findings
                if f.rule_id == "wallclock-in-span"]


# ---------------------------------------------------------------------------
# obs_dump CLI smoke (the tier-1 wiring for tools/obs_dump.py)
# ---------------------------------------------------------------------------

def test_obs_dump_cli_smoke(tmp_path, capsys):
    obs_dump = _obs_dump()
    trace_file = str(tmp_path / "trace.json")
    rc = obs_dump.main(["--json", "--trace", trace_file])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rec["ok"]
    assert rec["families"] >= 4 and not rec["prom_malformed_lines"]
    doc = json.load(open(trace_file))
    assert "traceEvents" in doc


# ---------------------------------------------------------------------------
# overhead: the disabled path must stay out of the way
# ---------------------------------------------------------------------------

def test_disabled_overhead_smoke():
    """Not a benchmark (PERF.md has what stamping costs a serving
    step) — just the structural facts: disabled tracing takes
    the one-branch fast path, allocates nothing into the ring, and
    100k guarded checks stay well under a second on the 1-core CI."""
    import time as _time

    assert not tracing.enabled()
    t0 = _time.perf_counter()
    for _ in range(100_000):
        if tracing._ENABLED:          # the instrumentation-site guard
            raise AssertionError("tracer unexpectedly enabled")
    branch_wall = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    for _ in range(10_000):
        with obs.span("noop"):
            pass
    cm_wall = _time.perf_counter() - t0
    assert obs.spans() == []
    assert branch_wall < 1.0 and cm_wall < 2.0
