"""What the benchmark observes about a run besides its own clock: programs
built (jax's monitoring events), device memory, versions, host spans that
are also written into the profiler's trace, and the trace itself."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

from benchmarks import trace_reduce


class Builds:
    """Programs built by XLA in this process, from jax's own monitoring
    events: ``count`` programs went through the backend's compile entry,
    of which ``cache_hits`` were read back from the persistent cache.
    (Copied from ``chip_smoke.py``.)"""

    def __init__(self):
        import jax

        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.count = 0
        self.cache_hits = 0


def memory_peak(devices):
    """Peak bytes in use on the fullest device, 0 where the backend
    reports none (the CPU)."""
    peak = 0
    for d in devices:
        st = d.memory_stats()
        if st:
            peak = max(peak, st.get("peak_bytes_in_use", 0))
    return peak


def bytes_limit(device):
    st = device.memory_stats()
    return st.get("bytes_limit") if st else None


def versions():
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


class Spans:
    """Host spans by the benchmark's own clock. Each is also a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>`` (``:<index>``
    appended where given), so that a traced run finds the same span on
    the device's clock."""

    def __init__(self):
        import jax

        self.by_name = {}
        self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name, index=None):
        label = f"bench.{name}" if index is None else f"bench.{name}:{index}"
        with self._annotation(label):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.by_name.setdefault(name, []).append(
                    (t0, time.perf_counter(), index))


class Tracer:
    """Profiles the end of the window, from ``after`` seconds into it
    until the driver calls ``stop()`` at its close (so that writing the
    trace out costs the window nothing), into a temporary directory under
    ``TMPDIR`` that is removed once the events are read. ``tick(since)``
    is called from the measuring loop between two pieces of work."""

    def __init__(self, enabled, after):
        self.after = after
        # what starting the profiler took: the driver keeps its window
        # open that much longer, so that the traced part has its length
        self.extension = 0.0
        self.state = "waiting" if enabled else "done"
        self.dir = None
        self._annotation = None

    def tick(self, since_open):
        import jax

        if self.state == "waiting" and since_open >= self.after:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            t0 = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.extension = time.perf_counter() - t0
            self._annotation = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW)
            self._annotation.__enter__()
            self.state = "tracing"

    def stop(self):
        import jax

        if self.state != "tracing":
            return
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def events(self):
        """The trace's events, or ``None`` where no trace was taken."""
        self.stop()
        if self.dir is None:
            return None
        try:
            path = trace_reduce.find_xplane(self.dir)
            return trace_reduce.events_from_xplane(path) if path else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
