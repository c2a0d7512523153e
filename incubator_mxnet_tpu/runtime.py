"""Runtime feature introspection.

Re-design of `src/libinfo.cc` + `python/mxnet/runtime.py` (file-level
citations — SURVEY.md caveat): the reference exposes its compiled feature
flags (`USE_CUDA`, `USE_CUDNN`, `USE_MKLDNN`, `USE_DIST_KVSTORE`, …) through
``mx.runtime.feature_list()`` / ``Features``. The TPU build's "features" are
runtime properties of the JAX/XLA install instead of compile-time #ifdefs,
so they are probed lazily here.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = ["Feature", "Features", "feature_list", "is_enabled"]


class Feature:
    """One named capability flag (parity: `mx.runtime.Feature`)."""

    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _probe() -> Dict[str, bool]:
    import jax

    platforms = {d.platform for d in jax.devices()}
    try:
        import jax.experimental.pallas  # noqa: F401

        has_pallas = True
    except Exception:  # pragma: no cover
        has_pallas = False
    try:
        from .io import _native

        has_native_io = _native.lib() is not None
    except Exception:  # pragma: no cover
        has_native_io = False
    try:
        from .io import _native_image

        has_native_jpeg = _native_image.lib() is not None
    except Exception:  # pragma: no cover
        has_native_jpeg = False
    try:
        import cv2  # noqa: F401

        has_opencv = True
    except Exception:
        has_opencv = False
    return {
        # accelerator backends (reference: CUDA/CUDNN rows)
        "TPU": "tpu" in platforms,
        "GPU": "gpu" in platforms or "cuda" in platforms,
        "CPU": True,
        # compiler / kernel paths (reference: MKLDNN/TENSORRT/NVRTC rows)
        "XLA": True,
        "PALLAS": has_pallas,
        # distribution (reference: DIST_KVSTORE/NCCL rows)
        "DIST_KVSTORE": True,  # jax.distributed + XLA collectives, always in
        "ICI_COLLECTIVES": "tpu" in platforms,
        # IO (reference: OPENCV/LIBJPEG rows)
        "OPENCV": has_opencv,
        "NATIVE_RECORDIO": has_native_io,
        "NATIVE_JPEG": has_native_jpeg,
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "PROFILER": True,
        "AMP": True,
    }


class Features:
    """Mapping of feature name → :class:`Feature` (parity:
    ``mx.runtime.Features``, backed by `MXLibInfoFeatures`)."""

    def __init__(self):
        self._features = {k: Feature(k, v) for k, v in _probe().items()}

    def __getitem__(self, name: str) -> Feature:
        return self._features[name]

    def __contains__(self, name: str) -> bool:
        return name in self._features

    def keys(self):
        return self._features.keys()

    def values(self):
        return self._features.values()

    def is_enabled(self, name: str) -> bool:
        return self._features[name].enabled

    def __repr__(self):
        return ", ".join(repr(f) for f in self._features.values())


def feature_list() -> List[Feature]:
    """Parity: ``mx.runtime.feature_list()``."""
    return list(Features().values())


def is_enabled(name: str) -> bool:
    return Features().is_enabled(name)
