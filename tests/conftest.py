"""Test env: the CPU platform with 8 virtual devices, set before any
backend initialization. No test needs a chip: tests/test_tpu_compile.py
compiles for a described one, and chip_smoke.py is what runs on a real
one."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
