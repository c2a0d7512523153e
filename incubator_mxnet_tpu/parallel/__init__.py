"""Parallelism & distribution: device meshes, collectives, fused SPMD
training, and sequence/context parallelism.

TPU-native replacement for the reference's distribution stack
(KVStore comm `src/kvstore/comm.h`, NCCL `kvstore_nccl.h`, ps-lite
`3rdparty/ps-lite/` — SURVEY.md §2.3/§5.8): instead of parameter-server
processes and explicit NCCL calls, a `jax.sharding.Mesh` + `NamedSharding`
annotations let XLA place `psum`/`all_gather`/`reduce_scatter` on ICI
(intra-slice) and DCN (cross-slice) automatically.
"""

from . import mesh
from .mesh import (MeshConfig, build_mesh, current_mesh, default_mesh,
                   set_default_mesh, initialize)
from . import collectives
from .collectives import host_allreduce
from . import spmd
from .spmd import (SPMDTrainer, shard_params, replicate, constrain,
                   activation_sharding_scope, live_trainers)
from . import pipeline
from .pipeline import pipeline_apply, stack_stage_params
from . import moe
from .moe import switch_moe, stack_expert_params
from . import ring_attention
from .ring_attention import ring_self_attention, ring_flash_attention

__all__ = [
    "MeshConfig", "build_mesh", "current_mesh", "default_mesh",
    "set_default_mesh", "initialize", "collectives", "host_allreduce",
    "SPMDTrainer", "shard_params", "replicate", "ring_self_attention",
    "ring_flash_attention", "live_trainers",
]
