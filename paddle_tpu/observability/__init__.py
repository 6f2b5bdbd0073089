"""paddle_tpu.observability — unified metrics registry, span tracing,
and compile-event attribution across train + serve.

Four pieces, one import:

* **Metrics registry** (``metrics``): typed ``Counter`` / ``Gauge`` /
  ``Histogram`` with labels on a process-wide ``REGISTRY``; the
  pre-existing counter sources (dispatch cache, serving engines,
  resilience ledgers, engine supervisors) are attached as pull-time
  collectors, so one ``snapshot()`` / ``to_prometheus()`` scrape sees
  the whole system with zero hot-path cost.
* **Span tracer** (``tracing``): monotonic-clock spans with trace/span
  ids in a bounded ring, exported as Chrome trace-event JSON
  (``to_chrome_trace()``, perfetto-loadable). Disabled by default —
  every instrumentation site costs one branch until
  ``enable_tracing()`` (or ``PADDLE_TPU_TRACE=1``). Train step phases
  (data / forward / backward / optimizer / checkpoint) and the full
  serving request lifecycle (``serving.submit`` / ``submit_call`` →
  ``queue`` → ``prefill`` / ``prefill_chunk`` → ``decode`` → ``finish``)
  are pre-instrumented; a request's trace id lives on its handle, so a
  token-identical replay on a rebuilt engine links to the original
  request's trace. Engine-wide, each ``serving.step`` is the parent of
  ``serving.schedule``, ``serving.dispatch``, ``serving.fetch`` and
  ``serving.emit`` and of the launches inside it. A live span is also a
  ``jax.profiler.TraceAnnotation`` of its name while the tracer is on;
  ``span_event(..., parent=)`` writes one from clock reads the caller
  already took and returns its id.
* **The serving engine's stamps** (``serving.metrics``, always on, no
  switch): the clock reads behind those spans are kept whether or not
  the tracer is on, as ``StepRecord`` / ``LaunchRecord`` /
  ``SubmitRecord`` / ``AdmitRecord`` tuples in bounded rings on
  ``EngineMetrics`` (``.steps``, ``.launches``, ``.submits``,
  ``.admits``), a step and a submit with its thread's CPU seconds,
  context switches, page faults and collector pauses. Read them through
  ``Engine.stats()["step_phase_seconds"]`` (cumulative seconds by
  phase), ``["host_cpu_seconds"]`` (the thread's CPU seconds in steps
  and in submits), ``["gc_pause_seconds"]``,
  ``serving.metrics.live_metrics()`` (every live engine's rings) or the
  ``paddle_serving_step_phase_seconds_total{phase}``,
  ``paddle_serving_host_cpu_seconds_total{call}`` and
  ``paddle_serving_gc_pause_seconds_total`` families.
* **Compile attribution** (``compile_attr``): every XLA backend
  compile counted + timed under the subsystem that triggered it
  (``compile_scope``), as metrics and (when tracing) ``xla.compile``
  spans.

CLI: ``tools/obs_dump.py`` (``--json`` | ``--prom`` | ``--trace``).
"""
from . import collectors, compile_attr, metrics, tracing  # noqa: F401
from .compile_attr import (  # noqa: F401
    compile_scope, compile_summary, compiles_by_origin,
)
from .metrics import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS, REGISTRY, Counter, Gauge, Histogram,
    MetricsRegistry, counter, gauge, histogram, register_collector,
    snapshot, to_prometheus,
)
from .tracing import (  # noqa: F401
    begin_span, current_trace_id, end_span, instant, new_trace_id,
    span, span_event, spans, to_chrome_trace,
)
from .tracing import enable as enable_tracing  # noqa: F401
from .tracing import disable as disable_tracing  # noqa: F401
from .tracing import enabled as tracing_enabled  # noqa: F401
from .tracing import reset as reset_tracing  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_LATENCY_BUCKETS", "counter", "gauge", "histogram",
    "register_collector", "snapshot", "to_prometheus",
    "span", "instant", "span_event", "begin_span", "end_span",
    "new_trace_id", "current_trace_id", "spans", "to_chrome_trace",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "reset_tracing", "compile_scope", "compile_summary",
    "compiles_by_origin",
]

collectors.install_default_collectors()
compile_attr.install()
