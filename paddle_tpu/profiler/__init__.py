"""Profiler: paddle.profiler API surface over jax.profiler.

Reference: python/paddle/profiler/profiler.py (Profiler, ProfilerTarget,
make_scheduler, export_chrome_tracing) and utils.py (RecordEvent). The
reference's CUPTI/host tracer is replaced by the XLA/TPU profiler:
``start``/``stop`` bracket a ``jax.profiler`` trace whose output
(perfetto/tensorboard trace dir) covers device kernels, XLA fusions, ICI
collectives and host python — strictly more than the reference's op-level
timeline. RecordEvent is a live span of ``observability.tracing``, which
enters a jax.profiler.TraceAnnotation, so custom ranges show up inside the
device trace.
"""
from __future__ import annotations

import os
import time
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

from .timer import Benchmark, benchmark  # noqa: F401

__all__ = [
    "Benchmark", "benchmark", "dispatch_counters", "serving_counters",
    "resilience_counters", "serving_resilience_counters", "aot_counters",
    "fleet_counters",
    "ProfilerState", "ProfilerTarget",
    "make_scheduler", "export_chrome_tracing", "export_protobuf",
    "Profiler", "RecordEvent", "RecordInstantEvent",
    "load_profiler_result", "SortedKeys",
]


def dispatch_counters() -> dict:
    """Eager dispatch fast-path counters (hits / misses / compiles —
    the retrace count — / bypasses), same snapshot as
    ``paddle.framework.dispatch_stats()``. A steady-state eager loop
    should only add hits; anything else is a retrace or a cache bypass
    worth profiling."""
    from ..framework import dispatch_cache

    return dispatch_cache.dispatch_stats()


def serving_counters() -> dict:
    """Aggregate serving-engine counters across every live
    ``paddle_tpu.serving.Engine`` (requests, tokens, prefills, decode
    steps, queue pressure) — same plumbing as dispatch_counters()."""
    from ..serving import metrics as serving_metrics

    return serving_metrics.global_counters()


def aot_counters() -> dict:
    """AOT compile-service snapshot (hits by tier, misses, compiles,
    persist errors, per-store disk bytes) — ``paddle_tpu.aot`` plumbing.
    Zero XLA backend compiles in a warm process shows up here as
    ``disk_exec_hits == hits`` with ``compiled == 0``."""
    from ..aot import aot_stats

    return aot_stats()


def resilience_counters() -> dict:
    """Aggregate flight-ledger event counts across every live
    ``paddle_tpu.resilience`` ledger/supervisor (steps, anomalies,
    saves, restores, rollbacks, aborts). Serving-side supervisors keep
    their own ledgers under scope "serving" — see
    :func:`serving_resilience_counters`."""
    from ..resilience import ledger as resilience_ledger

    return resilience_ledger.global_counters(scope="train")


def serving_resilience_counters() -> dict:
    """Aggregate serving-engine supervisor counters across every live
    ``serving.resilience.EngineSupervisor`` (rebuilds, token-identical
    replays, wedges, KV corruptions, brownout sheds, drains)."""
    from ..serving import resilience as serving_resilience

    return serving_resilience.global_counters()


def fleet_counters() -> dict:
    """Aggregate replica-fleet counters across every live
    ``serving.fleet.ReplicaFleet`` (routing decisions and prefix hits,
    cross-replica migrations, failovers, replica health states)."""
    from ..serving import fleet as serving_fleet

    return serving_fleet.global_counters()


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-phase scheduler, same semantics as the reference."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler: the jax trace dir already contains
    perfetto/chrome-compatible traces; this just records the destination."""
    def handler(prof):
        prof._export_dir = dir_name
    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


#: live started-but-not-stopped Profiler count — utils.in_profiler_mode
_ACTIVE_PROFILERS = 0


class Profiler:
    """paddle.profiler.Profiler over jax.profiler traces.

    Usage matches the reference::

        with profiler.Profiler(targets=[...], on_trace_ready=...) as p:
            for step ...: train(); p.step()
        p.summary()
    """

    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready=None, timer_only=False,
                 record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = list(targets or [ProfilerTarget.CPU,
                                        ProfilerTarget.TPU])
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=max(0, lo), ready=0,
                                            record=hi - lo, repeat=1)
        else:
            self.scheduler = scheduler or _default_scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._export_dir = os.path.join("profiler_log",
                                        time.strftime("%Y%m%d_%H%M%S"))
        self.current_state = ProfilerState.CLOSED
        self._tracing = False
        self._step = 0
        self._step_times = []
        self._t0 = None

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        global _ACTIVE_PROFILERS
        _ACTIVE_PROFILERS += 1
        self.current_state = self.scheduler(self._step)
        self._maybe_toggle()
        self._t0 = time.perf_counter()
        from .timer import benchmark

        benchmark().begin()  # reader_cost/ips collection (timer.py)
        return self

    def stop(self):
        global _ACTIVE_PROFILERS
        _ACTIVE_PROFILERS = max(0, _ACTIVE_PROFILERS - 1)
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        self.current_state = ProfilerState.CLOSED
        from .timer import benchmark

        benchmark().end()
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self._step += 1
        from .timer import benchmark

        benchmark().step(num_samples)  # reference Profiler.step drives it
        self.current_state = self.scheduler(self._step)
        self._maybe_toggle()

    def _maybe_toggle(self):
        want = self.current_state in (ProfilerState.RECORD,
                                      ProfilerState.RECORD_AND_RETURN)
        if want and not self._tracing and not self.timer_only:
            os.makedirs(self._export_dir, exist_ok=True)
            jax.profiler.start_trace(self._export_dir)
            self._tracing = True
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self.on_trace_ready:
                self.on_trace_ready(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- reporting -----------------------------------------------------------

    def step_info(self, unit=None) -> str:
        """Step-time stats plus the Benchmark's reader_cost/batch_cost/
        ips line (reference profiler.py Profiler.step_info)."""
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        t = np.asarray(self._step_times)
        from .timer import benchmark

        bench = benchmark().step_info(unit or "samples")
        return (f"steps: {len(t)}  avg: {t.mean()*1e3:.2f} ms  "
                f"min: {t.min()*1e3:.2f} ms  max: {t.max()*1e3:.2f} ms"
                + (f" |{bench}" if bench else ""))

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        print(self.step_info())
        dc = dispatch_counters()
        print("eager dispatch cache: "
              f"hits={dc['hits']} misses={dc['misses']} "
              f"retraces={dc['compiles']} bypasses={dc['bypasses']} "
              f"entries={dc['entries']}"
              + ("" if dc["enabled"] else " (disabled)"))
        sc = serving_counters()
        if sc["engines"]:
            hr = sc.get("prefix_hit_rate")
            lw = sc.get("pool_low_watermark")
            print("serving: "
                  f"engines={sc['engines']} "
                  f"requests={sc['requests_completed']}/"
                  f"{sc['requests_submitted']} "
                  f"tokens={sc['tokens_generated']} "
                  f"prefills={sc['prefills']} "
                  f"decode_steps={sc['decode_steps']} "
                  f"peak_queue={sc['peak_queue_depth']} "
                  f"peak_active={sc.get('peak_active', 0)} "
                  f"prefix_hit_rate={'-' if hr is None else hr} "
                  f"cow={sc.get('cow_copies', 0)} "
                  f"preempt={sc.get('preemptions', 0)} "
                  f"chunk_steps={sc.get('chunk_steps', 0)} "
                  f"pool_low_watermark={'-' if lw is None else lw}"
                  + (f" tp={sc['tp_max']}"
                     if sc.get("tp_max", 1) > 1 else ""))
        rc = resilience_counters()
        if rc["ledgers"]:
            print("resilience: "
                  f"ledgers={rc['ledgers']} "
                  f"steps={rc.get('step', 0)} "
                  f"anomalies={rc.get('anomaly', 0)} "
                  f"saves={rc.get('save', 0)} "
                  f"restores={rc.get('resume', 0)} "
                  f"rollbacks={rc.get('rollback', 0)} "
                  f"aborts={rc.get('abort', 0)}")
        fc = fleet_counters()
        if fc["fleets"]:
            print("fleet: "
                  f"fleets={fc['fleets']} "
                  f"replicas={fc['replicas']} "
                  f"healthy={fc['healthy']} "
                  f"degraded={fc['degraded']} "
                  f"draining={fc['draining']} "
                  f"condemned={fc['condemned']} "
                  f"routed={fc['routed']} "
                  f"prefix_routed={fc['prefix_routed']} "
                  f"migrations={fc['migrations']} "
                  f"failovers={fc['failovers']} "
                  f"kills={fc['replica_kills']} "
                  f"sheds={fc['fleet_sheds']} "
                  f"backoffs={fc['backoffs']}")
        sv = serving_resilience_counters()
        if sv["supervisors"]:
            print("serving-resilience: "
                  f"supervisors={sv['supervisors']} "
                  f"rebuilds={sv['rebuilds']} "
                  f"replayed={sv['replayed']} "
                  f"wedges={sv['wedges']} "
                  f"step_errors={sv['step_errors']} "
                  f"kv_corruptions={sv['kv_corruptions']} "
                  f"shed={sv['shed']} "
                  f"abandoned={sv['abandoned']} "
                  f"drains={sv['drains']}")
        try:
            from ..distributed.comm_opt import global_comm_stats
            cg = global_comm_stats()
        except Exception:   # tpu_lint: allow(silent-except) — summary
            # line only: an unimportable comm subsystem reads as "no
            # live comm-opt steps", never as a profiler crash
            cg = {"steps": 0}
        if cg["steps"]:
            arms = " ".join(
                f"[{a['grad_compress'] or 'exact'}"
                f"{'+zero1' if a['zero1'] else ''}"
                + (f" tp={a['tp']}" if a['tp'] > 1 else "")
                + f" ratio={a['compression_ratio']}x"
                f" {a['exchange_bytes_per_step']}B/step"
                f" steps={a['steps']}]"
                for a in cg["arms"])
            print(f"comm: arms={cg['steps']} "
                  f"steps={cg['total_steps_run']} {arms}")
        from ..analysis import findings_summary
        fs = findings_summary()
        if fs:
            print(f"tpu_lint: {fs}")
        from ..observability import compile_summary, tracing as _trc
        cs = compile_summary()
        if cs:
            # every XLA compile this process paid, attributed to its
            # origin (eager op / prefill bucket / chunk / decode /
            # static segment) — paddle_tpu.observability.compile_attr
            print(f"compiles: {cs}")
        from ..aot import aot_summary
        ao = aot_summary()
        if ao:
            # executable-cache traffic: how many of those compiles were
            # avoided (deserialized) and what the store holds on disk
            print(f"aot: {ao}")
        if _trc.enabled() and _trc.spans():
            from .profiler_statistic import build_span_summary
            print(build_span_summary(sorted_by=sorted_by,
                                     time_unit=time_unit))
        if self.timer_only:
            return
        try:
            from .statistic import build_summary, load_profiler_result
            result = load_profiler_result(self._export_dir)
            print(build_summary(result, sorted_by=sorted_by,
                                time_unit=time_unit))
        except (FileNotFoundError, ValueError, OSError, EOFError):
            # no recorded steps, or a truncated/corrupt exported trace
            # (json/gzip errors): degrade to the trace-dir message
            pass
        print(f"trace dir: {self._export_dir} "
              f"(tensorboard --logdir or perfetto)")

    def export(self, path: str, format: str = "json"):
        print(f"trace already exported to {self._export_dir}")


class RecordEvent:
    """Custom named range: a live span of the observability tracer.
    It shows in the device trace as ``UserDefined::<name>`` whenever a
    ``jax.profiler`` trace runs, and, when the tracer is on, as a
    ``user::<name>`` span in the in-process ring / Chrome export — so
    RecordEvent works even without an active jax trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span_tok = None

    def begin(self):
        # the UserDefined:: prefix is how the statistic parser routes
        # these into the user-event table (reference groups RecordEvents
        # under TracerEventType.UserDefined) instead of the op summary
        from ..observability import tracing as _trc
        self._span_tok = _trc.begin_span(
            f"user::{self.name}", cat="user",
            annotation=f"UserDefined::{self.name}")

    def end(self):
        from ..observability import tracing as _trc
        _trc.end_span(self._span_tok)
        self._span_tok = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with RecordEvent(self.name):
                return fn(*a, **k)
        return wrapper


class RecordInstantEvent(RecordEvent):
    """Zero-duration marker: an instant event in the observability ring
    plus a degenerate TraceAnnotation range in the device trace."""

    def begin(self):
        from ..observability import tracing as _trc
        _trc.instant(f"user::{self.name}", cat="user",
                     annotation=f"UserDefined::{self.name}")


from .statistic import (ProfilerResult, build_summary,  # noqa: E402
                        load_profiler_result)


class SortedKeys(Enum):
    """Sort order for summary tables (reference
    profiler/profiler_statistic.py SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7
