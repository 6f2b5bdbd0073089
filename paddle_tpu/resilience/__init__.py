"""paddle_tpu.resilience — fault-tolerant training.

Reference analogs: fleet/elastic/manager.py (elastic membership and
relaunch) + incubate/checkpoint/auto_checkpoint.py (train-status
auto-resume). This package composes the repo's primitives —
``distributed.checkpoint`` atomic async snapshots, ``distributed.elastic``
membership/resume, ``utils.watchdog`` anomaly detection — into a
training loop that survives the failures a long run actually hits
(a non-finite loss, a step that raises or hangs, a killed process):

* :class:`Supervisor` — escalation ladder around any train step:
  skip non-finite → retry wedged → roll back to durable checkpoint →
  abort with a post-mortem; cadence + emergency checkpointing; exact
  (bitwise) preemption resume via :meth:`Supervisor.resume`.
* :class:`TrainState` / :class:`ResumableLoader` — the snapshot surface:
  params, optimizer moments, PRNG key chain, AMP loss scaler, dataloader
  position.
* :class:`ChaosMonkey` — deterministic seeded fault injection (NaN,
  stall, error, SIGKILL, checkpoint corruption) so every recovery path
  is exercised by test, not by luck. CLI: ``tools/chaos_train.py``.
* :class:`FlightLedger` — bounded black-box JSONL recorder surfaced
  through ``Profiler.summary()``.
"""
from .chaos import (  # noqa: F401
    FAULTS, FLEET_FAULTS, SERVING_FAULTS, ChaosError, ChaosMonkey,
    StallInjected,
    corrupt_checkpoint, corrupt_kv, corrupt_latest,
)
from .ledger import FlightLedger, global_counters  # noqa: F401
from .supervisor import (  # noqa: F401
    ResumableLoader, StepTimeout, Supervisor, SupervisorAborted, TrainState,
)

__all__ = [
    "Supervisor", "SupervisorAborted", "StepTimeout", "TrainState",
    "ResumableLoader", "ChaosMonkey", "ChaosError", "StallInjected",
    "FAULTS", "SERVING_FAULTS", "FLEET_FAULTS", "corrupt_checkpoint",
    "corrupt_kv",
    "corrupt_latest", "FlightLedger", "global_counters",
]
