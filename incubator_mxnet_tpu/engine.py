"""Execution-engine shim.

The reference's L2 dependency engine (`src/engine/threaded_engine.{h,cc}`,
`threaded_engine_perdevice.cc`, `naive_engine.cc`; file-level citations —
SURVEY.md caveat) schedules every op as an async closure with read/write
variable sets. In the TPU-native design that engine is XLA's async dispatch:
jnp calls return futures immediately and ordering comes from data
dependencies inside the compiled program (SURVEY.md §1 "key architectural
invariant" + §7.3).

What remains user-visible — and is provided here — is the engine's *control
surface*:

  - ``NaiveEngine`` debug mode (`MXNET_ENGINE_TYPE=NaiveEngine` in the
    reference, selected in `src/engine/engine.cc`): fully synchronous
    execution to bisect scheduling/async bugs. Here ``set_sync(True)`` (or
    env ``MXTPU_ENGINE_TYPE=NaiveEngine``) makes every imperative op call
    ``jax.block_until_ready`` on its outputs, so exceptions surface at the
    faulting op instead of the next sync point (SURVEY.md §5.2).
  - ``wait_all`` — `Engine::WaitForAll` / `mx.nd.waitall`: drain all pending
    async work on every device.
  - op bulking knobs (`MXNET_EXEC_BULK_EXEC_*`): accepted for API parity;
    XLA fuses within a jitted program, so they are no-ops and say so.
"""

from __future__ import annotations

import threading

from .base import getenv_str

__all__ = ["set_sync", "is_sync", "wait_all", "set_bulk_size", "bulk"]

_state = threading.local()
_DEFAULT_SYNC = getenv_str("MXTPU_ENGINE_TYPE", "").lower() == "naiveengine"


def is_sync() -> bool:
    """True when the debug NaiveEngine (synchronous) mode is active."""
    return getattr(_state, "sync", _DEFAULT_SYNC)


def set_sync(sync: bool = True) -> bool:
    """Toggle synchronous execution (parity: ``MXNET_ENGINE_TYPE=NaiveEngine``,
    `src/engine/naive_engine.cc`). Returns the previous setting."""
    prev = is_sync()
    _state.sync = bool(sync)
    return prev


def _maybe_sync(outputs):
    """Called by the imperative front end after each op when sync mode is on."""
    if is_sync():
        import jax

        for o in outputs:
            jax.block_until_ready(o._data if hasattr(o, "_data") else o)


def wait_all() -> None:
    """Block until all async device work is complete (parity:
    `Engine::WaitForAll` via `MXNDArrayWaitAll`)."""
    import jax
    import jax.numpy as jnp

    for dev in jax.devices():
        jax.device_put(jnp.zeros(()), dev).block_until_ready()


_bulk_size = 0


def set_bulk_size(size: int) -> int:
    """Parity no-op for `MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN` / engine op
    bulking: XLA fuses ops inside a jitted program, so bulking is automatic
    under ``hybridize()``. Returns the previous value."""
    global _bulk_size
    prev, _bulk_size = _bulk_size, int(size)
    return prev


class bulk:
    """Context manager parity for ``mx.engine.bulk(size)``; fusion happens in
    XLA, so this only tracks the requested size."""

    def __init__(self, size: int):
        self._size = size
        self._prev = None

    def __enter__(self):
        self._prev = set_bulk_size(self._size)
        return self

    def __exit__(self, *exc):
        set_bulk_size(self._prev)
