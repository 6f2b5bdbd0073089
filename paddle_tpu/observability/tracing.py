"""Span tracer: monotonic-clock spans with trace/span ids, a bounded
in-process ring, and Chrome trace-event (perfetto-loadable) export.

Design constraints, in order:

1. **Near-zero overhead when disabled.** Every instrumentation site
   guards on the module flag ``_ENABLED`` (a plain attribute read)
   before building any span machinery, so the disabled path costs one
   branch. ``span()`` itself fast-paths the same way for call sites
   that don't pre-check.
2. **Durations come from ``time.perf_counter()``** — never wall clock
   (the ``wallclock-in-span`` tpu_lint rule enforces this repo-wide).
   Chrome timestamps are microseconds relative to a process-start
   anchor, which is exactly what perfetto wants.
3. **Bounded.** Completed spans land in a ring (``deque(maxlen=...)``);
   a tracer left enabled for weeks cannot eat the host.

Trace ids are process-unique strings minted by :func:`new_trace_id`.
A span opened inside another span inherits its trace id (and records
the parent span id); detached work — a serving request whose lifecycle
crosses many engine steps, or a token-identical replay on a rebuilt
engine — carries its trace id explicitly (``span(trace_id=...)``), so
a request's queue/prefill/decode spans link into one trace even across
an ``EngineSupervisor`` rebuild.

Two kinds of span. A *live* one (``span`` / ``begin_span``) is open
while its work runs: it nests under the thread's open spans and, while
the tracer is enabled, is also a ``jax.profiler.TraceAnnotation`` of the
same name, so a ``jax.profiler`` trace shows it on the device's clock.
A *stamped* one (``span_event``) is written afterwards from two
``perf_counter`` reads the caller already took — the serving engine's
step phases and program launches, whose stamps also feed the always-on
rings of ``serving.metrics.EngineMetrics`` — and names its parent
explicitly (``parent=``, the id an earlier ``span_event`` returned).
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time

__all__ = [
    "enable", "disable", "enabled", "reset", "span", "instant",
    "span_event", "begin_span", "end_span", "new_trace_id",
    "current_trace_id", "spans", "to_chrome_trace", "ring_size",
]

_ENABLED = os.environ.get("PADDLE_TPU_TRACE", "0") not in ("0", "", "false")
_RING_SIZE = 8192
_ring = collections.deque(maxlen=_RING_SIZE)
_tls = threading.local()
_ids = itertools.count(1)
_id_lock = threading.Lock()
# Chrome ts anchor: all exported timestamps are perf_counter deltas
# from process start, in microseconds
_T0 = time.perf_counter()


def new_trace_id():
    """Mint a process-unique trace (or span) id. Cheap enough to call
    unconditionally — request handles carry one whether or not tracing
    is on, so chaos verdicts and ledgers can always reference it."""
    with _id_lock:
        n = next(_ids)
    return f"{os.getpid():x}-{n:x}"


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def enable(ring=None):
    """Turn the tracer on (optionally resizing the ring)."""
    global _ENABLED
    if ring is not None:
        ring_size(ring)
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def enabled():
    return _ENABLED


def ring_size(n):
    """Resize the completed-span ring (drops current contents)."""
    global _ring, _RING_SIZE
    _RING_SIZE = int(n)
    _ring = collections.deque(maxlen=_RING_SIZE)


def reset():
    """Drop all recorded spans (keeps enabled state and ring size)."""
    _ring.clear()


def current_trace_id():
    """Trace id of the innermost open span on this thread, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1][1] if st else None


class _SpanToken:
    __slots__ = ("name", "cat", "trace", "span", "parent", "t0", "args",
                 "ann")

    def __init__(self, name, cat, trace, span_id, parent, t0, args, ann):
        self.name = name
        self.cat = cat
        self.trace = trace
        self.span = span_id
        self.parent = parent
        self.t0 = t0
        self.args = args
        self.ann = ann


def _annotate(name):
    """Enter a ``jax.profiler.TraceAnnotation``: the span as the
    profiler's trace shows it (a no-op a few hundred nanoseconds long
    while no profiler session runs). jax is imported on first use: this
    module loads before everything else."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


def begin_span(name, cat="", trace_id=None, annotation=None, **attrs):
    """Open a span without a context manager (RecordEvent-style begin/
    end pairs). Returns a token for :func:`end_span`, or None when
    tracing is disabled.

    The span is also a ``jax.profiler.TraceAnnotation`` under its own
    name. ``annotation`` gives it another name there, and such a span
    belongs to the profiler's trace whether or not the tracer is on
    (``profiler.RecordEvent``: ``UserDefined::<name>``); only its ring
    entry waits for ``enable()``."""
    if not _ENABLED:
        if annotation is None:
            return None
        return _SpanToken(name, cat, None, None, None, 0.0, None,
                          _annotate(annotation))
    st = _stack()
    parent = st[-1] if st else None
    trace = trace_id or (parent[1] if parent else new_trace_id())
    tok = _SpanToken(name, cat, trace, new_trace_id(),
                     parent[0] if parent else None, 0.0, attrs or None,
                     _annotate(annotation or name))
    st.append((tok.span, trace))
    tok.t0 = time.perf_counter()
    return tok


def end_span(tok, **attrs):
    if tok is None:
        return
    t1 = time.perf_counter()
    tok.ann.__exit__(None, None, None)
    if tok.span is None:       # the profiler's alone: opened tracer-off
        return
    st = _stack()
    if st and st[-1][0] == tok.span:
        st.pop()
    else:                      # out-of-order end: drop it if present
        _tls.stack = [s for s in st if s[0] != tok.span]
    if attrs:
        tok.args = dict(tok.args or {}, **attrs)
    _record(tok.name, tok.cat, tok.trace, tok.span, tok.parent,
            tok.t0, t1 - tok.t0, tok.args)


@contextlib.contextmanager
def span(name, cat="", trace_id=None, **attrs):
    """Record one span around the with-body. Disabled => one branch."""
    if not _ENABLED:
        yield None
        return
    tok = begin_span(name, cat, trace_id, **attrs)
    try:
        yield tok
    finally:
        end_span(tok)


def instant(name, cat="", trace_id=None, annotation=None, **attrs):
    """Zero-duration marker (Chrome phase "i"); with ``annotation``
    also a degenerate range of that name in the profiler's trace,
    tracer on or off (``profiler.RecordInstantEvent``)."""
    if annotation is not None:
        _annotate(annotation).__exit__(None, None, None)
    if not _ENABLED:
        return
    st = getattr(_tls, "stack", None)
    parent = st[-1] if st else None
    _record(name, cat, trace_id or (parent[1] if parent else None),
            new_trace_id(), parent[0] if parent else None,
            time.perf_counter(), 0.0, attrs or None, ph="i")


def span_event(name, t0, t1, cat="", trace_id=None, parent=None, **attrs):
    """Record an already-timed span from two ``perf_counter`` stamps —
    phases whose begin and end live in different calls (a request's
    time in queue, its whole decode phase), or whose stamps the caller
    keeps for its own records (the serving engine's step phases).
    Returns the span's id, for a later call's ``parent=``; None when
    tracing is disabled."""
    if not _ENABLED:
        return None
    sid = new_trace_id()
    _record(name, cat, trace_id, sid, parent, t0, max(0.0, t1 - t0),
            attrs or None)
    return sid


class _ForwardSpan:
    """Span for the OUTERMOST ``nn.Layer.__call__`` on this thread —
    sublayer calls inside it enter a shared no-op instead, so a model
    forward is ONE ``train.forward`` span, not one per sublayer."""

    __slots__ = ("label", "tok")

    def __init__(self, label):
        self.label = label

    def __enter__(self):
        _tls.in_forward = True
        self.tok = begin_span("train.forward", cat="train",
                              layer=self.label)
        return self.tok

    def __exit__(self, *exc):
        _tls.in_forward = False
        end_span(self.tok)


_NULL_CM = contextlib.nullcontext()


def forward_span(label):
    """Instrumentation hook for ``nn.Layer.__call__``: a real span for
    the outermost forward on this thread, a shared nullcontext for
    everything else (including tracing-disabled, which the call site
    pre-checks via ``_ENABLED`` anyway)."""
    if not _ENABLED or getattr(_tls, "in_forward", False):
        return _NULL_CM
    return _ForwardSpan(label)


def _record(name, cat, trace, span_id, parent, t0, dur, args, ph="X"):
    _ring.append({
        "name": name, "cat": cat or "span", "ph": ph,
        "trace": trace, "span": span_id, "parent": parent,
        "t0": t0, "dur": dur, "tid": threading.get_ident(),
        "args": args})


def spans(name=None):
    """Completed spans (oldest first), optionally filtered by name."""
    out = list(_ring)
    if name is not None:
        out = [s for s in out if s["name"] == name]
    return out


def to_chrome_trace():
    """Export the ring as a Chrome trace-event JSON document (load in
    perfetto / chrome://tracing). Timestamps are microseconds since
    process start on the monotonic clock."""
    events = [{
        "name": "process_name", "ph": "M", "pid": os.getpid(), "tid": 0,
        "args": {"name": f"paddle_tpu pid={os.getpid()}"}}]
    for s in sorted(_ring, key=lambda s: s["t0"]):
        args = dict(s["args"] or {})
        if s["trace"]:
            args["trace_id"] = s["trace"]
        if s["parent"]:
            args["parent_span"] = s["parent"]
        ev = {"name": s["name"], "cat": s["cat"], "ph": s["ph"],
              "pid": os.getpid(), "tid": s["tid"],
              "ts": round((s["t0"] - _T0) * 1e6, 3), "args": args}
        if s["ph"] == "X":
            ev["dur"] = round(s["dur"] * 1e6, 3)
        else:
            ev["s"] = "t"      # instant scope: thread
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
