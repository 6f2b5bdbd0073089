"""Deterministic, seeded fault injection for training loops.

Every recovery path in the supervisor must be exercised by test, not by
luck: ``ChaosMonkey`` wraps a train step and fires faults at
deterministically chosen steps, so a CI run with ``seed=7`` reproduces
the exact failure sequence of any previous run with ``seed=7``.

Faults
------

``nan``      the step returns a non-finite loss (poisoned batch / bf16
             overflow analog); the real step is NOT run, matching a loss
             that was computed but useless
``stall``    the step blocks for ``stall_s`` then raises
             :class:`StallInjected` (a hung step); nothing mutates, so a
             retry is safe
``error``    the step raises :class:`ChaosError` (transient RPC failure)
``kill``     SIGKILL to the current process — no atexit, no flushing;
             only a durable checkpoint survives this
``corrupt``  the newest committed checkpoint gets one shard truncated
             (restore must detect the bad checksum and fall back)

Serving faults (consumed by ``serving.resilience.EngineSupervisor`` via
:meth:`ChaosMonkey.take` — the supervisor, not the monkey, performs the
injection because each fault manipulates live engine state):

``decode-stall``   the fused decode step wedges past its deadline then
                   fails (a hung step on the serving path)
``decode-raise``   the decode step raises (transient device/RPC error)
``kv-corrupt``     an active KV slot's attendable lines are poisoned in
                   place (:func:`corrupt_kv`); the supervisor's probe
                   must catch it before the next decode consumes it
``abandon``        a client abandons an in-flight request mid-stream

Schedules are explicit (``at={step: fault}``) or drawn from a seeded RNG
(``p`` per-step probability over ``faults``); both are pure functions of
the constructor arguments.
"""
from __future__ import annotations

import os
import signal
import time

import numpy as np

FAULTS = ("nan", "stall", "error", "kill", "corrupt")
SERVING_FAULTS = ("decode-stall", "decode-raise", "kv-corrupt", "abandon")
#: Consumed by ``serving.fleet.ReplicaFleet`` (one fault per fleet step,
#: injected into a deterministically chosen replica): ``replica-kill``
#: condemns a replica's engine outright (process-death analog; requests
#: migrate to peers), ``route-flap`` randomizes the next few routing
#: decisions (placement must not change tokens), and the decode-* /
#: kv-corrupt serving faults target one replica's engine.
FLEET_FAULTS = ("replica-kill", "route-flap", "decode-stall",
                "decode-raise", "kv-corrupt")


class ChaosError(RuntimeError):
    """Injected transient step failure (RPC-error analog)."""


class StallInjected(TimeoutError):
    """Injected wedged step: blocked past the deadline, then failed."""


class ChaosMonkey:
    """Wrap a train step so faults fire at deterministic steps.

    ``at`` maps 0-based step invocation index -> fault name for an
    explicit plan; alternatively ``p`` > 0 draws a schedule from
    ``numpy.random.default_rng(seed)`` over ``faults`` for ``horizon``
    steps. ``wrap(step_fn)`` returns the chaotic step; the monkey counts
    invocations, so the Nth call fires the fault planned for step N
    (a retried step advances the count — retries meet fresh weather).
    """

    def __init__(self, seed: int = 0, *, at=None, p: float = 0.0,
                 faults=("nan", "stall", "error"), horizon: int = 1024,
                 stall_s: float = 0.25, manager=None):
        self.seed = int(seed)
        self.stall_s = float(stall_s)
        self.manager = manager
        self.calls = 0
        self.fired = []                 # [(step, fault)]
        # observability: every fired fault gets a trace id (minted even
        # with the tracer off) so chaos verdicts/ledgers link a fault to
        # its spans; last_trace_id is the most recent fault's
        self.trace_ids = {}             # step -> trace id
        self.last_trace_id = None
        known = FAULTS + SERVING_FAULTS + FLEET_FAULTS
        for f in tuple(dict(at or {}).values()) + tuple(faults):
            if f not in known:
                raise ValueError(f"unknown fault {f!r} (one of {known})")
        self.plan = {int(k): v for k, v in (at or {}).items()}
        if p > 0.0:
            rng = np.random.default_rng(self.seed)
            for step in range(int(horizon)):
                if step in self.plan:
                    continue
                if rng.random() < p:
                    self.plan[step] = str(rng.choice(list(faults)))

    def schedule(self, n_steps: int):
        """The fault plan restricted to the first ``n_steps`` steps."""
        return {s: f for s, f in sorted(self.plan.items()) if s < n_steps}

    def take(self):
        """Consume one supervised step's planned fault (or None) without
        executing it — the serving EngineSupervisor drives injection
        itself because serving faults manipulate live engine state.
        Counts an invocation exactly like :meth:`wrap`'s chaotic step,
        so the Nth supervised step meets the fault planned for step N."""
        step = self.calls
        self.calls += 1
        fault = self.plan.get(step)
        if fault is not None:
            self.fired.append((step, fault))
            self._mark_fired(step, fault)
        return fault

    def _mark_fired(self, step, fault):
        from ..observability import tracing
        tid = tracing.new_trace_id()
        self.trace_ids[step] = tid
        self.last_trace_id = tid
        tracing.instant(f"chaos.{fault}", cat="chaos", trace_id=tid,
                        step=step, seed=self.seed)

    def wrap(self, step_fn):
        def chaotic_step(*args, **kwargs):
            step = self.calls
            self.calls += 1
            fault = self.plan.get(step)
            if fault is not None:
                self.fired.append((step, fault))
                self._mark_fired(step, fault)
                return self._fire(fault, step_fn, args, kwargs)
            return step_fn(*args, **kwargs)

        chaotic_step.chaos = self
        return chaotic_step

    def _fire(self, fault, step_fn, args, kwargs):
        if fault == "nan":
            return float("nan")
        if fault == "stall":
            time.sleep(self.stall_s)
            raise StallInjected(
                f"chaos: step wedged for {self.stall_s}s (seed={self.seed})")
        if fault == "error":
            raise ChaosError(f"chaos: transient step failure "
                             f"(seed={self.seed})")
        if fault == "kill":
            os.kill(os.getpid(), signal.SIGKILL)
            raise RuntimeError("unreachable: SIGKILL did not fire")
        if fault == "corrupt":
            if self.manager is None:
                raise ValueError(
                    "chaos fault 'corrupt' needs ChaosMonkey(manager=...)")
            corrupt_latest(self.manager, seed=self.seed)
            return step_fn(*args, **kwargs)
        raise ValueError(f"unknown fault {fault!r}")


# ---------------------------------------------------------------------------
# checkpoint corruption helpers (used by chaos 'corrupt' and by tests)
# ---------------------------------------------------------------------------

def corrupt_checkpoint(path, seed: int = 0, mode: str = "truncate"):
    """Damage a committed checkpoint dir in place.

    ``truncate`` halves a deterministically chosen data file; ``flip``
    xors one byte; ``uncommit`` removes the COMMIT marker (simulating a
    kill after rename of a pre-manifest writer). Returns the damaged
    file path (or the marker path for ``uncommit``).
    """
    path = os.path.abspath(path)
    if mode == "uncommit":
        marker = os.path.join(path, "COMMIT")
        os.remove(marker)
        return marker
    files = []
    for root, _dirs, names in os.walk(path):
        for name in names:
            if name == "COMMIT":
                continue
            full = os.path.join(root, name)
            if os.path.getsize(full) > 0:
                files.append(full)
    if not files:
        raise FileNotFoundError(f"no data files to corrupt under {path}")
    files.sort()
    rng = np.random.default_rng(seed)
    victim = files[int(rng.integers(len(files)))]
    size = os.path.getsize(victim)
    if mode == "truncate":
        with open(victim, "rb+") as fh:
            fh.truncate(max(size // 2, 1))
    elif mode == "flip":
        off = int(rng.integers(size))
        with open(victim, "rb+") as fh:
            fh.seek(off)
            b = fh.read(1)
            fh.seek(off)
            fh.write(bytes([b[0] ^ 0xFF]))
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")
    return victim


def corrupt_latest(manager, seed: int = 0, mode: str = "truncate"):
    """Corrupt the newest committed checkpoint of a CheckpointManager."""
    manager.wait()
    step = manager.latest_step()
    if step is None:
        raise FileNotFoundError(
            f"no checkpoints under {manager.directory}")
    return corrupt_checkpoint(
        os.path.join(manager.directory, f"ckpt-{step}"), seed=seed,
        mode=mode)


def corrupt_kv(engine, seed: int = 0, value: float = float("nan")):
    """Serving-side corruption analog (chaos fault ``kv-corrupt``):
    poison deterministically chosen live KV state in place. The
    EngineSupervisor's finiteness probe must catch this BEFORE the next
    decode step consumes it; rebuild-and-replay then *heals* the state
    by recomputing KV from each request's own prompt + emitted-token
    history.

    Slot layout: one active slot's attendable lines are poisoned
    (returns the slot index). Paged layout: one live BLOCK is poisoned —
    preferring a SHARED prefix block (refcount > 1) when one exists, the
    nastiest case: every sharer reads it, so the verdict must show ALL
    of them healed by replay (returns the block id)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    cache = engine.cache
    if hasattr(cache, "live_blocks"):              # paged pool
        shared = cache.shared_live_blocks()
        cand = shared if shared else cache.live_blocks()
        if not cand:
            raise ValueError("no live blocks to corrupt")
        block = int(cand[int(rng.integers(len(cand)))])
        kc = np.asarray(cache.kc).copy()
        kc[:, block] = value
        old_sharding = getattr(cache.kc, "sharding", None)
        if old_sharding is not None and hasattr(old_sharding, "mesh"):
            # tensor-parallel pool: keep the NamedSharding so the poisoned
            # array still matches the SPMD program's operand signature
            import jax
            cache.kc = jax.device_put(kc, old_sharding)
        else:
            cache.kc = jnp.asarray(kc)
        return block
    active = np.nonzero(cache.active)[0]
    if active.size == 0:
        raise ValueError("no active slots to corrupt")
    slot = int(active[int(rng.integers(active.size))])
    lines = max(int(cache.cur_pos[slot]), 1)
    kc = np.asarray(cache.kc).copy()
    kc[:, slot, :lines] = value
    cache.kc = jnp.asarray(kc)
    return slot
