"""Device-mesh construction and multi-host bootstrap.

Replaces the reference's launcher/tracker bootstrap (`tools/launch.py` +
dmlc tracker env `DMLC_ROLE`/`DMLC_PS_ROOT_URI` — SURVEY.md §3.4): there are
no scheduler/server processes; every host runs the same SPMD program and
`jax.distributed.initialize` forms the global device set.

Axis-name convention (used across models/ and spmd.py):
    dp    data parallelism (batch sharding; grads reduced over it)
    fsdp  parameter sharding fused with dp (ZeRO-style)
    tp    tensor/model parallelism (attention heads, MLP hidden)
    sp    sequence/context parallelism (ring attention)
    pp    pipeline stages (reserved)
    ep    expert parallelism (MoE; reserved)
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")

_DEFAULT_MESH: Optional[Mesh] = None


@dataclasses.dataclass
class MeshConfig:
    """Sizes per logical axis; unspecified axes get size 1 and axes set to
    -1 absorb the remaining devices (at most one -1)."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXES}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise MXNetError(f"at most one mesh axis may be -1, got {wild}")
        fixed = 1
        for a, s in sizes.items():
            if s != -1:
                if s <= 0:
                    raise MXNetError(f"mesh axis {a} must be positive or -1")
                fixed *= s
        if wild:
            if n_devices % fixed != 0:
                raise MXNetError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            sizes[wild[0]] = n_devices // fixed
        else:
            if fixed != n_devices:
                raise MXNetError(
                    f"mesh axes product {fixed} != device count {n_devices}")
        return sizes


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence] = None,
               axis_sizes: Optional[Dict[str, int]] = None) -> Mesh:
    """Build a `jax.sharding.Mesh` over ``devices`` (default: all).

    ``axis_sizes`` is shorthand: ``build_mesh(axis_sizes={'dp': 2, 'tp': 4})``.
    Axis order is the canonical ``AXES`` order with size-1 axes kept, so a
    PartitionSpec can always name any logical axis.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if config is None:
        config = MeshConfig(**(axis_sizes or {}))
    sizes = config.resolve(n)
    shape = tuple(sizes[a] for a in AXES)
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(arr, AXES)


def set_default_mesh(mesh: Optional[Mesh]):
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def default_mesh() -> Mesh:
    """The process-default mesh (all devices on ``dp``) unless overridden."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = build_mesh()
    return _DEFAULT_MESH


def current_mesh() -> Optional[Mesh]:
    """The innermost mesh activated via ``with mesh:`` or None."""
    env_mesh = jax.sharding.get_abstract_mesh()
    if not env_mesh.empty:
        return env_mesh
    return _DEFAULT_MESH


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None):
    """Multi-host bootstrap (replaces `tools/launch.py` + dmlc tracker,
    SURVEY.md §3.4). Reads ``MXTPU_COORDINATOR``/``MXTPU_NUM_PROCS``/
    ``MXTPU_PROC_ID`` when args are omitted; no-op when single-process."""
    coordinator_address = coordinator_address or os.environ.get(
        "MXTPU_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("MXTPU_NUM_PROCS", "1"))
    if process_id is None:
        process_id = int(os.environ.get("MXTPU_PROC_ID", "0"))
    if num_processes <= 1 or coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
