"""Global RNG management.

Reference: python/paddle/framework/random.py (paddle.seed, get/set cuda rng
state). JAX randomness is explicit-key; to present paddle's implicit-RNG API
we keep a process-global key that is split on every draw. The functional/jit
path never touches this: layers and dropout accept explicit keys there
(threaded by the train-step builder), so compiled programs stay pure.
"""
from __future__ import annotations

import contextlib
import threading

import jax

# lazily initialized: creating a PRNGKey at import time would initialize
# the jax backend (and claim the chip) before the user runs anything
_key = None
_seed_value = 0
_tls = threading.local()


def _global_key():
    global _key
    if _key is None:
        _key = jax.random.PRNGKey(_seed_value)
    return _key


def seed(value: int):
    """Seed the global generator (paddle.seed)."""
    global _key, _seed_value
    _seed_value = int(value)
    _key = jax.random.PRNGKey(_seed_value)
    return _key


def get_seed() -> int:
    return _seed_value


def next_key():
    """Return a fresh subkey.

    Inside a ``functional_key`` scope (traced train steps), subkeys are split
    from the explicit key threaded into the compiled program — keeping it
    pure. Otherwise the process-global eager key is split.
    """
    stack = getattr(_tls, "fkeys", None)
    if stack:
        stack[-1], sub = jax.random.split(stack[-1])
        return sub
    global _key
    _key, sub = jax.random.split(_global_key())
    return sub


@contextlib.contextmanager
def functional_key(key):
    """Route next_key() draws to splits of ``key`` (used under jit tracing)."""
    stack = getattr(_tls, "fkeys", None)
    if stack is None:
        stack = _tls.fkeys = []
    stack.append(key)
    try:
        yield
    finally:
        stack.pop()


def get_rng_state():
    return _global_key()


def set_rng_state(state):
    global _key
    _key = state


def swap_key(new_key):
    """Install ``new_key`` as the active key stream; returns the
    previous one (meta_parallel RNG tracker support). Inside a
    functional_key scope (jitted train steps) the TOP OF THE FUNCTIONAL
    STACK is swapped — otherwise the tracker would silently no-op
    exactly where model-parallel dropout isolation matters."""
    stack = getattr(_tls, "fkeys", None)
    if stack:
        prev = stack[-1]
        stack[-1] = new_key
        return prev
    global _key
    prev = _global_key()
    _key = new_key
    return prev


class Generator:
    """Seedable RNG handle (reference fluid/generator.py Generator over
    the C++ generator): manual_seed re-keys the process stream."""

    def __init__(self, place=None):
        self._seed = get_seed()

    def manual_seed(self, new_seed):
        self._seed = int(new_seed)
        seed(self._seed)
        return self

    def initial_seed(self):
        return self._seed

    def seed(self):
        import secrets
        return self.manual_seed(secrets.randbits(32))._seed

    def get_state(self):
        return get_rng_state()

    def set_state(self, state):
        set_rng_state(state)
