"""Serving-side fault tolerance: the engine supervisor.

``resilience.Supervisor`` protects the *training* path; this module is
its serving counterpart — all of PR 6's ladder covered train steps, but
a wedged or crashed decode step still took down the Engine and every
in-flight request with it. :class:`EngineSupervisor` wraps an
:class:`~paddle_tpu.serving.engine.Engine` the way the train supervisor
wraps a step:

* **detect** — a decode step that raises, or one that exceeds
  ``step_timeout_s`` (worker-thread join; a hung step),
  or a KV buffer that fails the finiteness probe (``kv_probe_interval``);
* **rebuild** — the condemned engine is replaced by a fresh one (fresh
  KV buffers; the jitted prefill/decode programs are module-level, so a
  warm in-process rebuild adds ZERO new lowerings — a fresh process
  pays only the ordinary re-compile);
* **replay, token-identically** — every surviving in-flight request is
  re-prefilled as ``prompt + tokens_emitted_so_far`` into a fresh slot
  with its admission-seeded PRNG chain fast-forwarded to the correct
  split index (the ``skip`` operand of the prefill program), so the
  resumed request emits exactly the bytes the uninterrupted run would
  have. KV corruption is *healed* by the same mechanism: the replay
  recomputes the slot's KV from the request's own token history.

Graceful degradation under overload rides the same loop:

* **priority + EDF admission** — ``submit(priority=...)`` classes map
  onto :class:`~paddle_tpu.serving.scheduler.PriorityScheduler`
  ordering (lower class first; EDF within a class; FIFO behind that);
* **brownout shedding** — when the rolling decode ITL p95 exceeds
  ``itl_slo_ms``, the lowest-priority queued class is shed each step
  (``result()`` raises ``RequestShed`` with a finite ``retry_after_s``)
  and new low-priority submissions are rejected, while protected
  classes keep decoding;
* **drain** — ``drain()`` stops admission, finishes all in-flight and
  queued work (fault recovery stays active throughout), and returns a
  drained report — the rollout/handover primitive.

Chaos: pass a :class:`~paddle_tpu.resilience.ChaosMonkey` whose plan
uses the serving faults (``decode-stall`` / ``decode-raise`` /
``kv-corrupt`` / ``abandon``); ``tools/chaos_serve.py`` drives each one
to a JSON verdict. Counters surface as the ``serving-resilience:`` line
in ``Profiler.summary()`` via ``profiler.serving_resilience_counters()``.
"""
from __future__ import annotations

import threading
import time
import weakref

import numpy as np

from ..resilience.chaos import ChaosError, StallInjected, corrupt_kv
from ..resilience.ledger import FlightLedger
from ..resilience.supervisor import StepTimeout
from .engine import Engine
from .scheduler import EngineOverloaded

__all__ = ["EngineSupervisor", "ServingAborted", "EngineDraining"]


class ServingAborted(RuntimeError):
    """The rebuild ladder ran out of rungs: ``max_rebuilds`` consecutive
    rebuilds failed to produce a healthy decode step. Carries the
    supervisor's stats snapshot."""

    def __init__(self, message, stats=None):
        super().__init__(message)
        self.stats = stats


class EngineDraining(RuntimeError):
    """submit() was called while the supervisor is draining: admission
    is closed; in-flight work finishes, nothing new starts."""


class EngineSupervisor:
    """Wrap a serving Engine with detect / rebuild / replay plus
    overload degradation (see the module docstring).

    The supervisor OWNS engine construction (it must be able to rebuild
    one): pass the model plus any ``Engine`` kwargs. The public surface
    mirrors the engine — ``submit() -> RequestHandle``, ``step()``,
    ``drain()``, ``stats()`` — and returned handles pump the supervised
    step, so ``handle.result()`` rides through faults transparently.

    ``step_timeout_s`` runs each engine step on a worker thread and
    treats a non-return within the deadline as a wedged step; the thread
    is abandoned and the condemned engine ignores its late emissions.
    ``itl_slo_ms`` arms brownout shedding (classes above
    ``shed_protect_priority`` are shed/rejected while the rolling decode
    ITL p95 exceeds the SLO). ``kv_probe_interval=N`` checks KV
    finiteness every N supervised steps (N=1 in chaos tests; the probe
    syncs the KV buffer to host, so pick a sparse cadence in
    production).
    """

    def __init__(self, model, *, step_timeout_s=None, max_rebuilds=3,
                 retry_backoff_s=0.02, itl_slo_ms=None,
                 shed_protect_priority=0, kv_probe_interval=0,
                 chaos=None, ledger=None, replica_id=None,
                 migrate_hook=None, **engine_kwargs):
        self._model = model
        self._engine_kwargs = dict(engine_kwargs)
        #: fleet identity: stamped onto every engine incarnation (and
        #: through it onto handles + overload exceptions); None when the
        #: supervisor runs standalone
        self.replica_id = replica_id
        #: fleet failover hook: ``hook(supervisor, handles, why) ->
        #: migrated_handles``. Called during rebuild-and-replay with the
        #: surviving in-flight+queued handles BEFORE the local replay;
        #: handles it absorbs (adopted onto healthy peer replicas) are
        #: excluded from the local replay — the faulted replica rebuilds
        #: empty and re-registers while its requests keep decoding
        #: elsewhere. None (standalone) keeps PR-7 local replay.
        self.migrate_hook = migrate_hook
        # one-shot fleet-injected fault (ChaosMonkey fleet plans target a
        # specific replica; the fleet injects here rather than giving
        # every supervisor its own monkey)
        self._pending_fault = None
        self.step_timeout_s = step_timeout_s
        self.max_rebuilds = int(max_rebuilds)
        self.retry_backoff_s = float(retry_backoff_s)
        self.itl_slo_s = None if itl_slo_ms is None else itl_slo_ms / 1e3
        self.shed_protect_priority = int(shed_protect_priority)
        self.kv_probe_interval = int(kv_probe_interval)
        self.chaos = chaos
        self.ledger = (ledger if ledger is not None
                       else FlightLedger(scope="serving"))
        self.engine = self._build()
        # compile ledger across incarnations: a rebuilt engine re-traces
        # nothing in-process (module-level jit cache) but a fresh
        # process pays the union — analysis.audit_engine budgets on it
        self.buckets_seen_total = set()
        self.chunk_used_total = False   # any incarnation traced the
        self.rebuilds = 0               # chunked-prefill program
        # speculative ledger across incarnations: program-usage union
        # (verify/draft lowerings a fresh process would pay) and the
        # acceptance counters of condemned engines — rebuilds must not
        # zero the acceptance history (chaos_serve --spec gates this)
        self.verify_used_total = False
        self.draft_buckets_total = set()
        self.draft_decode_used_total = False
        from .speculative import SPEC_COUNTER_KEYS
        self.spec_totals = {k: 0 for k in SPEC_COUNTER_KEYS}
        self.replayed = 0              # handles re-admitted with tokens
        self.wedges = 0
        self.step_errors = 0
        self.kv_corruptions = 0
        self.shed = 0
        self.abandoned = 0
        self.drains = 0
        self.brownout_steps = 0
        self.draining = False
        self._brownout = False
        self._steps_since_probe = 0
        self._aborted = False
        self._last_fault_trace_id = None
        _register(self)

    def _build(self):
        return Engine(self._model, replica_id=self.replica_id,
                      **self._engine_kwargs)

    def inject(self, fault, trace_id=None):
        """Arm a one-shot serving fault (``decode-stall`` /
        ``decode-raise``) for the next supervised step — the
        ReplicaFleet's chaos channel into a specific replica."""
        self._pending_fault = fault
        if trace_id is not None:
            self._last_fault_trace_id = trace_id

    def rebuild(self, why="requested"):
        """Condemn the current engine incarnation and build a fresh one,
        migrating/replaying survivors exactly like a detected fault —
        the fleet's ``replica-kill`` path (a dead process can't run its
        own ladder; the fleet drives the rebuild from outside)."""
        self._rebuild_and_replay(why=why)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new_tokens=32, *, priority=0, **kw):
        """Engine.submit with supervision: the returned handle's
        ``result()`` pumps the supervised step. Raises
        :class:`EngineDraining` while draining, and rejects
        unprotected-priority work with ``EngineOverloaded`` (finite
        ``retry_after_s``) while brownout is active."""
        if self.draining:
            raise EngineDraining(
                "supervisor is draining: admission closed; retry "
                "against the replacement deployment")
        if self._brownout and priority > self.shed_protect_priority:
            hint = self.engine._retry_after_hint()
            self.engine.metrics.requests_rejected += 1
            self.ledger.record("brownout-reject", priority=priority,
                               retry_after_s=hint)
            raise EngineOverloaded(
                f"brownout: ITL p95 over SLO — priority {priority} "
                f"rejected; retry after ~{hint}s", retry_after_s=hint,
                replica=self.replica_id)
        h = self.engine.submit(prompt, max_new_tokens, priority=priority,
                               **kw)
        h._engine = self      # result() pumps the SUPERVISED step
        return h

    def cancel(self, handle):
        """Client abandoned the stream: frees the slot / queue position
        immediately (Engine.cancel)."""
        return self.engine.cancel(handle)

    # -- the supervised step -----------------------------------------------

    def step(self):
        """One supervised engine iteration. Chaos (if armed) fires its
        planned fault; KV is probed; brownout sheds; then the engine
        steps behind the detect → rebuild → replay ladder."""
        if self._aborted:
            raise ServingAborted("supervisor already aborted",
                                 stats=self.stats())
        fault = self.chaos.take() if self.chaos is not None else None
        if fault is not None:
            # the fault's trace id: anomaly/rebuild ledger records carry
            # it so a chaos run links to its spans (chaos verdicts too)
            self._last_fault_trace_id = self.chaos.last_trace_id
        elif self._pending_fault is not None:
            # fleet-injected one-shot fault (inject() set the trace id)
            fault, self._pending_fault = self._pending_fault, None
        if fault == "kv-corrupt":
            try:
                corrupt_kv(self.engine, seed=self.chaos.seed)
            except ValueError:
                pass   # no active slots: the planned fault is a no-op
            fault = None          # latent — the probe must find it
        elif fault == "abandon":
            self._abandon_one()
            fault = None
        self._probe_kv()
        self._brownout_tick()
        failures = 0
        while True:
            try:
                if fault == "decode-stall":
                    fault = None
                    # chaos is None when the fault was fleet-injected
                    stall = (self.chaos.stall_s if self.chaos is not None
                             else 0.01)
                    time.sleep(stall)
                    raise StallInjected(
                        f"chaos: decode step wedged for {stall}s "
                        f"(replica={self.replica_id})")
                if fault == "decode-raise":
                    fault = None
                    raise ChaosError(
                        f"chaos: decode step failed "
                        f"(replica={self.replica_id})")
                return self._engine_step()
            except Exception as e:
                if isinstance(e, TimeoutError):
                    kind = "wedge"
                    self.wedges += 1
                else:
                    kind = "step-error"
                    self.step_errors += 1
                self.ledger.record("anomaly", kind=kind,
                                   error=f"{type(e).__name__}: {e}",
                                   trace_id=self._last_fault_trace_id)
                failures += 1
                if failures > self.max_rebuilds:
                    self._abort(e)
                self._rebuild_and_replay(why=kind)
                time.sleep(self.retry_backoff_s * failures)

    def _engine_step(self):
        eng = self.engine
        if not self.step_timeout_s:
            return eng.step()
        box = {}

        def run():
            try:
                box["out"] = eng.step()
            except BaseException as e:   # crossing threads: rethrown below
                box["err"] = e

        t = threading.Thread(target=run, daemon=True,
                             name="supervised-decode")
        t.start()
        t.join(self.step_timeout_s)
        if t.is_alive():
            raise StepTimeout(
                f"decode step did not return within "
                f"{self.step_timeout_s}s")
        if "err" in box:
            raise box["err"]
        return box.get("out")

    # -- detect ------------------------------------------------------------

    def _probe_kv(self):
        """Finiteness probe over the live KV state: poisoned state (bit
        flips, a bad DMA — chaos fault ``kv-corrupt``) is caught BEFORE
        the next decode step can consume it, so the rebuild's
        replay-from-tokens stays token-identical. On a paged engine the
        probe walks the LIVE BLOCKS only (blocks referenced by occupied
        slots' block tables — the trash block and radix-only residents
        hold no in-flight request state), so probe cost scales with
        resident tokens, not pool capacity. A corrupted SHARED prefix
        block is healed for every sharer at once: the rebuild re-admits
        all of them through a fresh radix index, and the first
        re-prefill rewrites the prefix bit-identically."""
        if not self.kv_probe_interval:
            return
        self._steps_since_probe += 1
        if self._steps_since_probe < self.kv_probe_interval:
            return
        self._steps_since_probe = 0
        eng = self.engine
        cache = eng.cache
        if hasattr(cache, "live_blocks"):          # paged pool
            where = cache.live_blocks()
            if not where:
                return
            kc = np.asarray(cache.kc)[:, where]
            # a latent cache has one pool: its lines are all there is
            vc = kc if cache.vc is None else np.asarray(cache.vc)[:, where]
        else:
            where = np.nonzero(cache.active)[0]
            if len(where) == 0:
                return
            kc = np.asarray(cache.kc)[:, where]
            vc = np.asarray(cache.vc)[:, where]
        if np.isfinite(kc).all() and np.isfinite(vc).all():
            return
        self.kv_corruptions += 1
        self.ledger.record("anomaly", kind="kv-corrupt",
                           slots=[int(s) for s in where],
                           trace_id=self._last_fault_trace_id)
        self._rebuild_and_replay(why="kv-corrupt")

    # -- rebuild + replay --------------------------------------------------

    def _rebuild_and_replay(self, why):
        """Condemn the broken incarnation, build a fresh engine, and
        re-admit every surviving request: active handles re-prefill
        ``prompt + emitted`` with their PRNG chain fast-forwarded
        (token-identical resume), queued ones re-enqueue untouched.
        With a fleet ``migrate_hook``, survivors are first offered to
        healthy peer replicas — whatever the hook absorbs keeps decoding
        there (same token-identical adopt machinery) and this replica
        rebuilds empty."""
        old = self.engine
        old._condemned = True
        actives = sorted((h for h in old._by_slot
                          if h is not None and not h.finished),
                         key=lambda h: h.request_id)
        queued = [h for h in list(old.scheduler._queue) if not h.finished]
        survivors = actives + queued
        self.buckets_seen_total |= old.buckets_seen
        self.chunk_used_total |= bool(getattr(old, "chunk_used", False))
        self.verify_used_total |= bool(getattr(old, "verify_used",
                                               False))
        self.draft_buckets_total |= set(getattr(old,
                                                "draft_buckets_seen", ()))
        self.draft_decode_used_total |= bool(
            getattr(old, "draft_decode_used", False))
        for k in self.spec_totals:
            self.spec_totals[k] += getattr(old.metrics, k, 0)
        migrated = []
        if self.migrate_hook is not None and survivors:
            migrated = list(self.migrate_hook(self, survivors, why) or ())
            gone = set(map(id, migrated))
            survivors = [h for h in survivors if id(h) not in gone]
        self.engine = self._build()
        self.engine._next_id = old._next_id
        self.rebuilds += 1
        self.ledger.record("rebuild", why=why, replica=self.replica_id,
                           n_active=len(actives),
                           n_queued=len(queued),
                           n_migrated=len(migrated),
                           trace_id=self._last_fault_trace_id,
                           request_traces=[h.trace_id
                                           for h in actives + queued])
        for h in survivors:
            if h.tokens:
                self.replayed += 1
            self.engine.adopt(h)
            h._engine = self
        self.ledger.record("replay", n=len(survivors),
                           migrated=len(migrated))

    def _abandon_one(self):
        """Chaos fault ``abandon``: the longest-running in-flight client
        disconnects mid-stream (deterministic pick: lowest request id)."""
        eng = self.engine
        cand = [h for h in eng._by_slot if h is not None]
        if not cand:
            cand = [h for h in list(eng.scheduler._queue)]
        if not cand:
            return
        target = min(cand, key=lambda h: h.request_id)
        if self.cancel(target):
            self.abandoned += 1
            self.ledger.record("abandon", request_id=target.request_id,
                               tokens=len(target.tokens))

    # -- graceful degradation ----------------------------------------------

    def _brownout_tick(self):
        """Shed/brownout: while the rolling decode ITL p95 exceeds the
        SLO, evict the lowest queued priority class (finite
        retry_after_s) each step and reject new unprotected work;
        protected classes keep decoding untouched."""
        if self.itl_slo_s is None:
            return
        p95 = self.engine.metrics.itl_p95()
        if p95 is None:
            return
        if p95 > self.itl_slo_s:
            if not self._brownout:
                self._brownout = True
                self.ledger.record("brownout-enter",
                                   itl_p95_ms=round(p95 * 1e3, 3))
            self.brownout_steps += 1
            shed = self.engine.shed_queued(self.shed_protect_priority)
            if shed:
                self.shed += len(shed)
                self.ledger.record(
                    "shed", n=len(shed),
                    retry_after_s=shed[0].retry_after_s,
                    priorities=sorted({h.priority for h in shed}))
        elif self._brownout:
            self._brownout = False
            self.ledger.record("brownout-exit",
                               itl_p95_ms=round(p95 * 1e3, 3))

    def drain(self, max_steps=100000):
        """Rollout primitive: stop admission, pump supervised steps
        (fault recovery stays active) until every submitted request has
        finished, and report. Call :meth:`reopen` to accept work again
        (e.g. after a config hot-swap on the same process)."""
        self.draining = True
        self.ledger.record("drain-begin",
                           queued=self.engine.scheduler.queue_depth,
                           active=self.engine.cache.n_active)
        steps = 0
        while (self.engine.scheduler.queue_depth
               or self.engine.cache.n_active) and steps < max_steps:
            self.step()     # self.engine may be rebuilt mid-drain
            steps += 1
        drained = (self.engine.scheduler.queue_depth == 0
                   and self.engine.cache.n_active == 0)
        self.drains += 1
        report = {"drained": drained, "steps": steps,
                  "completed": self.engine.metrics.requests_completed,
                  "rebuilds_during": self.rebuilds}
        self.ledger.record("drain", **report)
        return report

    def reopen(self):
        """Re-open admission after a completed drain."""
        self.draining = False

    # -- observability -----------------------------------------------------

    def counters(self):
        """The serving-resilience profiler counters for this
        supervisor (summed across live supervisors by
        ``profiler.serving_resilience_counters()``)."""
        return {"rebuilds": self.rebuilds, "replayed": self.replayed,
                "wedges": self.wedges, "step_errors": self.step_errors,
                "kv_corruptions": self.kv_corruptions, "shed": self.shed,
                "abandoned": self.abandoned, "drains": self.drains,
                "brownout_steps": self.brownout_steps}

    def spec_counters(self):
        """Speculative acceptance counters summed across every engine
        incarnation this supervisor has owned (condemned + live): the
        counters that must SURVIVE a rebuild."""
        return {k: self.spec_totals[k] + getattr(self.engine.metrics, k,
                                                 0)
                for k in self.spec_totals}

    def stats(self):
        out = {**self.counters(), "replica": self.replica_id,
               "brownout": self._brownout, "draining": self.draining,
               "buckets_seen_total": sorted(
                   self.buckets_seen_total | self.engine.buckets_seen),
               "ledger": self.ledger.counts(),
               "engine": self.engine.stats()}
        if getattr(self.engine, "spec", None) is not None:
            out["spec_counters_total"] = self.spec_counters()
        return out

    def _abort(self, exc):
        self._aborted = True
        stats = self.stats()
        self.ledger.record("abort",
                           exception=f"{type(exc).__name__}: {exc}")
        raise ServingAborted(
            f"serving aborted after {self.rebuilds} rebuilds "
            f"({self.max_rebuilds} consecutive failures): "
            f"{type(exc).__name__}: {exc}", stats=stats) from exc


# ---------------------------------------------------------------------------
# profiler plumbing (the serving-metrics weakref pattern)
# ---------------------------------------------------------------------------

_SUPERVISORS = []    # weakrefs; dead supervisors drop out of the snapshot


def _register(sup):
    _SUPERVISORS.append(weakref.ref(sup))


def global_counters():
    """Summed counters across every live EngineSupervisor — the
    ``serving-resilience:`` line in ``Profiler.summary()``."""
    total = {"supervisors": 0, "rebuilds": 0, "replayed": 0, "wedges": 0,
             "step_errors": 0, "kv_corruptions": 0, "shed": 0,
             "abandoned": 0, "drains": 0, "brownout_steps": 0}
    live = []
    for ref in _SUPERVISORS:
        s = ref()
        if s is None:
            continue
        live.append(ref)
        total["supervisors"] += 1
        for k, v in s.counters().items():
            total[k] = total.get(k, 0) + v
    _SUPERVISORS[:] = live
    return total
