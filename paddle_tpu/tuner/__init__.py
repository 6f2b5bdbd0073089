"""paddle_tpu.tuner — search-based kernel autotuner (ROADMAP item 3).

CUDA-L2 / FlashFuser-style: searched kernel configs beat hand-picked
defaults, so every pallas kernel registers an enumerable config space
per ``(kernel, shape, dtype, device_kind)`` key and the tuner elects a
winner —

* **measured** on a live accelerator: min-of-batches wall time over the
  PR-9 monotonic span timer;
* **offline** on CPU: the upgraded :mod:`paddle_tpu.cost_model` ranker
  (XLA ``cost_analysis()`` base x tile-alignment / VMEM-footprint
  penalties), deterministic across processes —

and persists BOTH the winning config and its compiled executable
through the PR-10 AOT store under a toolchain-fingerprinted key, so
artifact consumers inherit tuned kernels at zero backend compiles.

Entry points::

    from paddle_tpu import tuner
    tuner.tune("ragged_matmul", args=(x, w, counts))   # search + persist
    tuner.get_config("fused_ce", shapes=..., dtype=...)  # resolve winner
    tuner.call("int8_matmul", xq, xs, wq, ws)          # tuned + AOT-routed

Kernel call sites resolve configs through :func:`get_config`; literal
tile sizes at call sites outside this registry are flagged by the
``untuned-kernel-config`` tpu_lint rule.
"""
from __future__ import annotations

from .registry import KernelSpec, register, get as get_kernel, names  # noqa: F401
from .search import (  # noqa: F401
    TuneResult, call, clear_memory, disable, enable, enabled, get_config,
    status, tune)
from .persist import config_key, load_config, store_config  # noqa: F401

__all__ = [
    "KernelSpec", "register", "get_kernel", "names",
    "TuneResult", "tune", "get_config", "call", "status",
    "enable", "disable", "enabled", "clear_memory",
    "config_key", "load_config", "store_config",
]
