"""Base utilities: errors, env-var config, registries.

TPU-native re-design of the reference's base plumbing:
  - ``MXNetError`` mirrors the exception type surfaced through the reference's
    C ABI (`src/c_api/c_api.cc`, `MXGetLastError`; file-level citation — see
    SURVEY.md provenance caveat).
  - ``getenv_*`` mirrors `dmlc::GetEnv` (`3rdparty/dmlc-core/include/dmlc/
    parameter.h`) but under a single ``MXTPU_*`` namespace (SURVEY.md §5.6).

There is no FFI boundary here: JAX/XLA is the native substrate, so the "C API"
layer of the reference collapses into ordinary Python calls that dispatch
straight into XLA's async runtime.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

__all__ = [
    "MXNetError",
    "DeferredInitializationError",
    "getenv_int",
    "getenv_bool",
    "getenv_str",
    "shard_map",
    "string_types",
    "numeric_types",
    "integer_types",
]

from jax import shard_map  # noqa: E402,F401  (re-exported package-wide)


def pcast_varying(x, axes):
    """``lax.pcast(x, axes, to="varying")``: the VMA branding jax requires
    on loop carries inside shard_map."""
    from jax import lax as _lax
    return _lax.pcast(x, axes, to="varying")


class MXNetError(RuntimeError):
    """Default error thrown by framework functions.

    The reference translates C++ exceptions into error codes at the C ABI and
    rethrows ``MXNetError`` in Python (`python/mxnet/base.py`). Here errors
    propagate natively, but we keep the type so user code catching
    ``MXNetError`` keeps working.
    """


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape could be inferred.

    Mirrors `python/mxnet/gluon/parameter.py`'s deferred-init contract.
    """


string_types = (str,)
numeric_types = (float, int, bool)
integer_types = (int,)

_ENV_PREFIXES = ("MXTPU_", "MXNET_")


def _getenv_raw(name: str) -> Optional[str]:
    """Look up ``name`` under the MXTPU_ namespace, falling back to MXNET_
    for compatibility with reference env-var spellings (SURVEY.md §5.6)."""
    for prefix in _ENV_PREFIXES:
        for candidate in (name, prefix + name):
            if candidate.startswith(prefix) or candidate == name:
                val = os.environ.get(candidate)
                if val is not None:
                    return val
    return None


def getenv_str(name: str, default: str = "") -> str:
    val = _getenv_raw(name)
    return default if val is None else val


def getenv_int(name: str, default: int = 0) -> int:
    val = _getenv_raw(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        return default


def getenv_bool(name: str, default: bool = False) -> bool:
    val = _getenv_raw(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


class Registry:
    """A tiny named registry, the analogue of ``dmlc::Registry``
    (`3rdparty/dmlc-core/include/dmlc/registry.h`)."""

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: Dict[str, Any] = {}

    def register(self, name: str, obj: Any = None, *, aliases: tuple = ()):
        def _do(o):
            key = name.lower()
            self._entries[key] = o
            for a in aliases:
                self._entries[a.lower()] = o
            return o

        if obj is None:
            return _do
        return _do(obj)

    def get(self, name: str) -> Any:
        key = name.lower()
        if key not in self._entries:
            raise MXNetError(
                f"{self.kind} '{name}' is not registered. "
                f"Known: {sorted(set(self._entries))}"
            )
        return self._entries[key]

    def __contains__(self, name: str) -> bool:
        return name.lower() in self._entries

    def list(self) -> List[str]:
        return sorted(self._entries)
