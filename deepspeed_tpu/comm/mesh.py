"""Named-axis device mesh — the TPU-native replacement for process groups.

Replaces (capability-wise) the reference's ``deepspeed/utils/groups.py`` (process
group construction, :544-757), ``runtime/pipe/topology.py`` (``ProcessTopology``,
``PipeModelDataParallelTopology``) and mpu plumbing: all parallel dimensions are
axes of ONE ``jax.sharding.Mesh``; "groups" are axis names, and collectives are
XLA ops over those names, compiled onto ICI/DCN.

Axis layout (outer→inner): ``('data', 'expert', 'pipe', 'seq', 'tensor')``.
``tensor`` innermost so TP collectives ride the fastest ICI links; ``data``
outermost so DP/FSDP traffic can span DCN across slices. ZeRO/FSDP shards over
the compound ``('data','expert','seq')`` axes (the reference's "DP group" is
exactly its data×expert×seq product; Ulysses ranks are DP ranks for parameters,
mirroring ``deepspeed/sequence`` semantics where sp ranks hold identical params).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.logging import log_dist

MESH_AXES: Tuple[str, ...] = ("data", "zero_shard", "expert", "pipe", "seq",
                              "tensor")

# parameter/optimizer-state sharding for ZeRO rides the full DP product.
# 'zero_shard' (size 1 unless MiCS/hpZ is on) is the data sub-axis that
# carves the reference's MiCS shard group / ZeRO++ secondary partition
# (runtime/zero/mics.py:63, zero_hpz_partition_size) out of plain data
# parallelism: with MiCS, ZeRO shards over it and REPLICATES over 'data'.
ZERO_AXES: Tuple[str, ...] = ("data", "zero_shard", "expert", "seq")
# batch (micro-batch leading dim) sharding
BATCH_AXES: Tuple[str, ...] = ("data", "zero_shard", "expert")

_global_mesh: Optional["MeshManager"] = None


def _arrange_devices(devices: Sequence[jax.Device],
                     sizes: Sequence[int]) -> Tuple[np.ndarray, Optional[str]]:
    """Physical-topology-aware device→mesh assignment.

    The mesh analog of the reference's rank-mapping layer
    (``deepspeed/utils/groups.py:544``, ``runtime/pipe/topology.py:12``): axis
    ORDER alone does not put 'tensor' on nearest-neighbor ICI, because
    ``jax.devices()`` is process-tiled (z,y,x, core) order — a naive reshape
    of a v5p pod can land the innermost axis across hosts. On TPU,
    ``mesh_utils.create_device_mesh`` solves the logical→physical-torus
    assignment so inner axes ride contiguous ICI rings; for multi-slice jobs
    ``create_hybrid_device_mesh`` confines exactly one (outermost feasible,
    preferably 'data') axis to DCN and keeps every other axis inside a slice.
    CPU / single-device meshes keep the plain reshape (virtual devices have
    no topology, and tests depend on deterministic device order).

    Returns ``(device_array, dcn_axis_name)`` — the second element names the
    mesh axis confined to DCN on a multi-slice job (None when every axis
    rides ICI), feeding the CommsTelemetry link-class tagging.
    """
    if len(devices) == 1 or getattr(devices[0], "platform", "cpu") != "tpu":
        return np.asarray(devices).reshape(sizes), None
    from jax.experimental import mesh_utils

    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    n_slices = len(slice_ids)
    dcn_axis = None
    if n_slices > 1:
        # one axis spans DCN; scan outer→inner so 'data' wins when it can
        for i in range(len(sizes)):
            if sizes[i] >= n_slices and sizes[i] % n_slices == 0:
                dcn_axis = i
                break
        else:
            raise ValueError(
                f"no mesh axis divisible by slice count {n_slices}: "
                f"{dict(zip(MESH_AXES, sizes))}")
    dcn_name = MESH_AXES[dcn_axis] if dcn_axis is not None else None
    # create_device_mesh either knows the topology or the mesh would be
    # wrong (inner-axis collectives crossing hosts): a failure raises
    if dcn_axis is not None:
        dcn = [1] * len(sizes)
        dcn[dcn_axis] = n_slices
        per_slice = list(sizes)
        per_slice[dcn_axis] //= n_slices
        return mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices), dcn_name
    return mesh_utils.create_device_mesh(sizes, devices=devices), None


@dataclass
class MeshManager:
    """Owns the Mesh plus axis bookkeeping.

    The reference's ``groups._get_data_parallel_world_size()`` etc. become
    properties here; its ``new_group`` / rank enumeration disappears (XLA's SPMD
    partitioner owns rank enumeration).
    """

    mesh: Mesh
    # axes whose collectives cross the slow (DCN) tier: auto-detected on
    # multi-slice TPU jobs from the hybrid-mesh assignment; set explicitly
    # (set_dcn_axes) to model a 2-level topology elsewhere — the hpZ/MiCS
    # zero_shard carve designates 'data' as cross-island. Feeds the
    # CommsTelemetry per-collective link-class tag.
    dcn_axes: Tuple[str, ...] = ()

    @classmethod
    def create(cls, axis_sizes: Dict[str, int],
               devices: Optional[Sequence[jax.Device]] = None) -> "MeshManager":
        devices = list(devices) if devices is not None else jax.devices()
        sizes = [axis_sizes.get(a, 1) for a in MESH_AXES]
        total = int(np.prod(sizes))
        if total != len(devices):
            raise ValueError(f"mesh sizes {dict(zip(MESH_AXES, sizes))} product {total} "
                             f"!= device count {len(devices)}")
        dev_array, dcn_axis = _arrange_devices(devices, sizes)
        mesh = Mesh(dev_array, MESH_AXES)
        log_dist(f"Created mesh {dict(zip(MESH_AXES, sizes))} over {len(devices)} devices "
                 f"({devices[0].platform})")
        return cls(mesh=mesh,
                   dcn_axes=(dcn_axis,) if dcn_axis is not None else ())

    def set_dcn_axes(self, axes: Sequence[str]) -> None:
        """Designate the mesh axes whose collectives cross the slow (DCN)
        tier. Auto-detected for multi-slice TPU meshes; call explicitly to
        model a 2-level topology (the hpZ carve, CPU test meshes)."""
        self.dcn_axes = tuple(axes)

    # --- axis sizes (groups.py parity) ---
    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    @property
    def dp_world_size(self) -> int:
        """Replication degree of the batch == data×expert (reference:
        ``groups._get_data_parallel_world_size``)."""
        return int(np.prod([self.mesh.shape[a] for a in BATCH_AXES]))

    @property
    def zero_world_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in ZERO_AXES]))

    @property
    def mics_shard_size(self) -> int:
        return self.mesh.shape["zero_shard"]

    @property
    def tp_world_size(self) -> int:
        return self.mesh.shape["tensor"]

    @property
    def pp_world_size(self) -> int:
        return self.mesh.shape["pipe"]

    @property
    def sp_world_size(self) -> int:
        return self.mesh.shape["seq"]

    @property
    def ep_world_size(self) -> int:
        return self.mesh.shape["expert"]

    @property
    def world_size(self) -> int:
        return self.mesh.size

    # --- sharding constructors ---
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, extra_seq_axis: bool = False) -> NamedSharding:
        """[batch, seq, ...] sharding: batch over data/expert, optionally the
        sequence dim over 'seq' (Ulysses input layout)."""
        if extra_seq_axis and self.sp_world_size > 1:
            return self.sharding(BATCH_AXES, "seq")
        return self.sharding(BATCH_AXES)

    @contextlib.contextmanager
    def activate(self):
        """Enter the mesh context: bare ``P`` specs resolve inside jit, and
        whatever is traced here can ask which mesh its program is for
        (``jax.sharding.get_abstract_mesh()`` — how a per-device kernel
        learns that it must shard_map itself, ``ops.registry``)."""
        with jax.set_mesh(self.mesh):
            yield self.mesh


def init_mesh(axis_sizes: Dict[str, int],
              devices: Optional[Sequence[jax.Device]] = None) -> MeshManager:
    global _global_mesh
    _global_mesh = MeshManager.create(axis_sizes, devices)
    return _global_mesh


def get_mesh() -> MeshManager:
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = MeshManager.create({"data": len(jax.devices())})
    return _global_mesh


def set_mesh(mm: Optional[MeshManager]) -> None:
    """Install (or with None, reset) the process-global mesh."""
    global _global_mesh
    _global_mesh = mm
