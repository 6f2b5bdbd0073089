"""Device management.

Reference: python/paddle/device/__init__.py (set_device / get_device /
is_compiled_with_*). On TPU the device story is simpler: jax owns placement
and we only track the preferred platform. ``set_device`` accepts paddle-style
strings ("tpu", "tpu:0", "cpu", "gpu:0") and maps them onto jax devices.
"""
from __future__ import annotations

import os
import re

import jax

_current_device: str = "tpu"


def _canonical_source_paths():
    """Lowered programs name source files relative to the directory that
    holds this package, not by the checkout's absolute path.

    jax keeps file names out of its compile-cache key, but a Pallas kernel
    is lowered apart and embedded whole, locations and all, in its
    program: with absolute paths in it, a program that holds a kernel (the
    train step's ``flash_attention``, every decode program's
    ``paged_attention``) is compiled again from every checkout that is not
    at the path that wrote the cache entry. jax's own canonicalisation
    removes the prefix; a caller who has set one keeps theirs."""
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(root + os.sep))


_canonical_source_paths()


def _platform_of(device: str) -> str:
    return device.split(":")[0]


def set_device(device: str) -> str:
    """Select the default device. Accepts "cpu", "tpu", "tpu:<n>", "gpu:<n>".

    "gpu" is accepted for script compatibility and mapped to the best
    available accelerator (tpu if present).
    """
    global _current_device
    plat = _platform_of(device)
    if plat == "gpu":  # compat: run unmodified cuda scripts on tpu
        device = device.replace("gpu", "tpu")
        plat = "tpu"
    if plat not in ("cpu", "tpu"):
        raise ValueError(f"Unsupported device {device!r}; expected cpu/tpu")
    _current_device = device
    return _current_device


def get_device() -> str:
    return _current_device


def get_jax_device(device: str | None = None):
    """Resolve a paddle-style device string to a concrete jax.Device."""
    device = device or _current_device
    plat = _platform_of(device)
    idx = int(device.split(":")[1]) if ":" in device else 0
    try:
        devs = jax.devices(plat if plat != "tpu" else None)
    except RuntimeError:
        devs = jax.devices()
    # jax.devices(None) returns the default backend; filter politely.
    matching = [d for d in devs if plat == "cpu" and d.platform == "cpu"] or devs
    return matching[min(idx, len(matching) - 1)]


def use_compile_cache() -> str:
    """Place jax's persistent compilation cache, before the first compile.

    ``JAX_COMPILATION_CACHE_DIR`` wins: jax reads it itself, so nothing is
    set in code. Otherwise the cache is ``<repo root>/.jax_cache`` as an
    absolute path, whatever the working directory. The directory is no
    part of an entry's key; what did keep a moved checkout from its
    entries was the absolute source paths inside a Pallas kernel's
    payload (``_canonical_source_paths``). Returns the directory in
    use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())


class CPUPlace:
    def __repr__(self):
        return "Place(cpu)"


class TPUPlace:
    def __init__(self, idx: int = 0):
        self.idx = idx

    def __repr__(self):
        return f"Place(tpu:{self.idx})"


# Aliases so scripts doing paddle.CUDAPlace(0) / NPUPlace(0) keep working.
CUDAPlace = TPUPlace
NPUPlace = TPUPlace
XPUPlace = TPUPlace
MLUPlace = TPUPlace
IPUPlace = TPUPlace


class CustomPlace(TPUPlace):
    """Reference: paddle.CustomPlace('device', idx) for plugin devices."""

    def __init__(self, device_type: str = "tpu", idx: int = 0):
        super().__init__(idx)
        self.device_type = device_type

    def __repr__(self):
        return f"Place({self.device_type}:{self.idx})"


def get_cudnn_version():
    """Reference: paddle.get_cudnn_version — None on the TPU build (no
    cuDNN; absence-reporting like the other cuda queries)."""
    return None


class CUDAPinnedPlace:
    """Host pinned memory place (reference: CUDAPinnedPlace). Host arrays
    feed the device through PJRT's own pinned staging on TPU."""

    def __repr__(self):
        return "Place(gpu_pinned)"
