"""Device-mesh construction — the TPU-native replacement for process groups.

The reference maintains a registry of torch.distributed process groups
(``deepspeed/utils/groups.py``: data/model/expert(+data) groups). On TPU the
idiomatic equivalent is one ``jax.sharding.Mesh`` with named axes; every
"group" is an axis (or tuple of axes) of that mesh, and XLA emits collectives
over ICI/DCN from sharding annotations.

Axis layout (outer → inner, inner axes most ICI-local):

    pipe    pipeline-parallel stages          (reference: pipe axis, topology.py:243)
    data    pure data parallel / ZeRO shards  (reference: data axis + ZeRO partitions)
    expert  expert parallel, carved OUT OF data parallel exactly as the reference
            carves expert groups from DP ranks (utils/groups.py:109-262): non-expert
            params treat ("data","expert") jointly as the DP axis
    seq     sequence/context parallel (ring attention) — TPU-native addition
    model   tensor parallel (innermost: highest-traffic collectives ride ICI)
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("pipe", "data", "expert", "seq", "model")

# Axes over which ZeRO shards non-expert params/grads/optimizer state. Expert
# params shard over ("data","seq") only (their "DP group" excludes the expert axis).
ZERO_AXES = ("data", "expert", "seq")
EXPERT_ZERO_AXES = ("data", "seq")
# Axes over which the global batch is split.
BATCH_AXES = ("data", "expert")
# Axis over which tensor parallelism splits the attention heads (and the
# projections' wide dim — models/transformer.py's TP rules name it).
TP_AXIS = "model"


class MeshManager:
    """Builds and owns the session's device mesh; answers group-size queries.

    Capability parity with ``deepspeed/utils/groups.py`` accessors
    (_get_data_parallel_group/world_size etc.), rebuilt as mesh-axis queries.
    """

    def __init__(self,
                 devices: Optional[Sequence] = None,
                 pp_size: int = 1,
                 tp_size: int = 1,
                 sp_size: int = 1,
                 ep_size: int = 1,
                 dp_size: Optional[int] = None):
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        denom = pp_size * tp_size * sp_size * ep_size
        if n % denom != 0:
            raise ValueError(
                f"world size {n} not divisible by pipe({pp_size}) * model({tp_size}) "
                f"* seq({sp_size}) * expert({ep_size})")
        inferred_dp = n // denom
        if dp_size is not None and dp_size != inferred_dp:
            raise ValueError(f"dp_size={dp_size} inconsistent with world size {n}")
        self.shape = dict(zip(MESH_AXES, (pp_size, inferred_dp, ep_size, sp_size, tp_size)))
        dev_array = np.asarray(devices).reshape(*self.shape.values())
        self.mesh = Mesh(dev_array, MESH_AXES)

    # -- groups.py-compatible accessors --------------------------------------

    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.shape.values())))

    def get_data_parallel_world_size(self) -> int:
        """DP degree as the reference defines it (includes ranks later carved for EP)."""
        return self.shape["data"] * self.shape["expert"] * self.shape["seq"]

    def get_model_parallel_world_size(self) -> int:
        return self.shape["model"]

    def get_pipe_parallel_world_size(self) -> int:
        return self.shape["pipe"]

    def get_expert_parallel_world_size(self) -> int:
        return self.shape["expert"]

    def get_sequence_parallel_world_size(self) -> int:
        return self.shape["seq"]

    def get_expert_data_parallel_world_size(self) -> int:
        return self.shape["data"] * self.shape["seq"]

    # -- sharding helpers -----------------------------------------------------

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, extra_batch_axes: Tuple[str, ...] = ()) -> NamedSharding:
        """Batch dim split over DP(+EP) axes; extra axes shard subsequent dims
        (e.g. ('seq',) shards dim 1 — the sequence dim — over the seq axis)."""
        return NamedSharding(self.mesh, P(BATCH_AXES, *extra_batch_axes))

    def local_batch_slice(self, global_batch: int) -> int:
        return global_batch // (self.shape["data"] * self.shape["expert"])

    def describe(self) -> str:
        return (f"Mesh(pipe={self.shape['pipe']}, data={self.shape['data']}, "
                f"expert={self.shape['expert']}, seq={self.shape['seq']}, "
                f"model={self.shape['model']})")


_GLOBAL_MESH: Optional[MeshManager] = None


def set_global_mesh(mm: MeshManager) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mm


def get_global_mesh() -> Optional[MeshManager]:
    return _GLOBAL_MESH


def build_mesh_from_config(config, devices: Optional[Sequence] = None) -> MeshManager:
    """Derive mesh axis sizes from a DeepSpeedConfig."""
    mm = MeshManager(
        devices=devices,
        pp_size=config.pipeline.stages,
        tp_size=config.tensor_parallel.tp_size,
        sp_size=config.sequence_parallel.sp_size,
        ep_size=config.moe.ep_size if config.moe.enabled else 1,
    )
    set_global_mesh(mm)
    return mm
