"""paddle_tpu.serving — continuous-batching LLM serving engine.

Reference pairing: paddle/fluid/inference is the reference deployment
runtime (Config/Predictor over a saved program, one request at a time);
this package is its many-concurrent-requests counterpart: a paged,
prefix-shared KV cache (block pool + radix index) + iteration-level
batching engine whose whole decode step is one fixed-shape jitted XLA
program (see engine.py), with a latency/throughput ledger in metrics.py.

Quick start::

    from paddle_tpu.serving import Engine
    eng = Engine(model, n_slots=8, max_len=256, eos_token_id=2)
    h = eng.submit(prompt_ids, max_new_tokens=64,
                   on_token=lambda h, t: print(t))
    full = h.result()          # pumps the engine until this one finishes

For a saved artifact, ``save_lm(model, path)`` then
``paddle_tpu.inference.create_llm_predictor(path)``.

Production deployments wrap the engine in
``EngineSupervisor`` (serving/resilience.py): wedged/crashed decode
steps rebuild the engine and replay in-flight requests
token-identically; overload degrades gracefully via priority/EDF
admission, brownout shedding and ``drain()``.
"""
from __future__ import annotations

from .engine import (AdoptMismatch, Engine, RequestCancelled,  # noqa: F401
                     RequestHandle, RequestShed, RequestTimeout)
from .fleet import REPLICA_STATES, ReplicaFleet  # noqa: F401
from .kv_cache import BlockPool, PagedKVCache, RadixIndex  # noqa: F401
from .metrics import EngineMetrics, RequestMetrics, ledger  # noqa: F401
from .resilience import (EngineDraining, EngineSupervisor,  # noqa: F401
                         ServingAborted)
from .scheduler import (EngineOverloaded, FIFOScheduler,    # noqa: F401
                        PriorityScheduler)
from .speculative import SpecConfig  # noqa: F401

__all__ = ["Engine", "RequestHandle", "RequestTimeout", "RequestShed",
           "RequestCancelled", "AdoptMismatch", "PagedKVCache", "BlockPool",
           "RadixIndex", "EngineMetrics",
           "RequestMetrics", "ledger", "EngineOverloaded", "FIFOScheduler",
           "PriorityScheduler", "EngineSupervisor", "ServingAborted",
           "EngineDraining", "ReplicaFleet", "REPLICA_STATES", "save_lm",
           "SpecConfig"]


def save_lm(model, path, precompile=None, n_slots=8, max_len=None,
            buckets=None, **engine_kwargs):
    """Save a CausalLM as a servable artifact: jit.save's weight payload
    plus the model config, so inference.create_llm_predictor can rebuild
    the model and serve it through an Engine without the original python
    construction code.

    With ``precompile`` (default: the ``PADDLE_TPU_AOT_PRECOMPILE=1``
    env opt-in), the artifact additionally ships the engine's full
    compiled program set — decode + every prefill bucket (+ chunk) —
    serialized into ``<path>.aot/`` by ``Engine.precompile_aot``, and
    records the engine geometry it was compiled for. A predictor built
    from the artifact on the same backend/jax version then cold-starts
    with ZERO XLA backend compiles for its first token (deserialized
    executables; different toolchains fall back to a normal compile).
    ``n_slots`` / ``max_len`` / ``engine_kwargs`` pin that geometry and
    become the predictor's defaults."""
    import dataclasses
    import os
    import warnings

    from ..jit.serialization import save
    from .engine import Engine, _make_arch

    _, hp, _ = _make_arch(model)      # validates the model type
    if precompile is None:
        precompile = os.environ.get("PADDLE_TPU_AOT_PRECOMPILE",
                                    "0") == "1"
    extra = {}
    if precompile:
        extra["aot_geometry"] = dict(n_slots=int(n_slots),
                                     max_len=max_len, **engine_kwargs)
    out = save(model, path, llm_arch=hp["arch"],
               llm_config=dataclasses.asdict(model.config), **extra)
    if precompile:
        try:
            eng = Engine(model, n_slots=n_slots, max_len=max_len,
                         **engine_kwargs)
            eng.precompile_aot(path + ".aot", buckets=buckets)
        except Exception as e:   # artifact stays valid without programs
            warnings.warn(
                f"save_lm: AOT precompile failed ({type(e).__name__}: "
                f"{e}); artifact carries weights/config only")
    return out
