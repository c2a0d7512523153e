"""Global random state.

The reference keeps *stateful* per-device RNG (`mshadow::Random`,
`src/resource.cc` kParallelRandom; file-level citation — SURVEY.md caveat).
JAX RNG is counter-based and functional. We bridge the two contracts with a
process-global splittable key stream (SURVEY.md §7.2 "RNG parity"):

  - ``mx.random.seed(n)`` resets the stream deterministically.
  - every stochastic op pulls a fresh subkey via ``new_key()`` — sampling the
    same op twice gives different draws (stateful illusion), while seeding
    replays the exact sequence (reproducibility contract).
  - traced code (hybridized blocks, jitted train steps) must take keys as
    *inputs*; ``new_key()`` returns a concrete array suitable for feeding.
"""

from __future__ import annotations

import threading

import jax
import numpy as _np

__all__ = ["seed", "new_key", "get_state", "set_state"]

# Random bits must not depend on how the consuming array is sharded over
# the mesh: with a non-partitionable threefry lowering the same dropout
# mask computed on a dp2×sp4 vs a dp8 mesh comes out DIFFERENT, so
# data-parallel and model-parallel runs of one seed silently diverge.
jax.config.update("jax_threefry_partitionable", True)

_state = threading.local()
_DEFAULT_SEED = 0


def _ensure():
    if not hasattr(_state, "key"):
        _state.key = jax.random.PRNGKey(_DEFAULT_SEED)
    return _state.key


def seed(seed_state: int, ctx=None):  # ctx accepted for reference parity
    """Seed the global RNG stream (parity: `mx.random.seed`,
    `python/mxnet/random.py`)."""
    _state.key = jax.random.PRNGKey(int(seed_state) & 0x7FFFFFFF)


class _KeyProvider:
    """Trace-scoped key source: inside a traced (hybridized/jitted) region
    the base key is a traced INPUT, so replays draw fresh randomness instead
    of baking one mask into the compiled program."""

    def __init__(self, base):
        self._cur = base

    def __call__(self):
        self._cur, sub = jax.random.split(self._cur)
        return sub


class key_provider:
    """Context manager installing a trace-scoped key provider."""

    def __init__(self, base):
        self._provider = _KeyProvider(base)

    def __enter__(self):
        self._prev = getattr(_state, "provider", None)
        _state.provider = self._provider
        return self._provider

    def __exit__(self, *exc):
        _state.provider = self._prev


def new_key() -> "jax.Array":
    """Split one subkey off the global stream (advances the stream).
    Under an active key_provider (hybridize trace), draws from the traced
    key instead."""
    provider = getattr(_state, "provider", None)
    if provider is not None:
        return provider()
    key = _ensure()
    _state.key, sub = jax.random.split(key)
    return sub


def new_keys(n: int):
    key = _ensure()
    keys = jax.random.split(key, n + 1)
    _state.key = keys[0]
    return keys[1:]


def get_state():
    return _ensure()


def set_state(key):
    _state.key = key


def np_rng() -> _np.random.RandomState:
    """A host-side numpy RNG derived from the stream (for shuffling etc.)."""
    sub = new_key()
    return _np.random.RandomState(int(jax.device_get(sub)[0]) & 0x7FFFFFFF)
