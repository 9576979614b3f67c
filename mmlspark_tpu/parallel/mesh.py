"""Device topology & mesh utilities.

Replaces the reference's cluster-topology discovery + GPU pinning:
``ClusterUtil`` (``core/utils/ClusterUtil.scala:20-126``) and
``ONNXModel.selectGpuDevice`` (``deep-learning/.../onnx/ONNXModel.scala:293-303``).
On TPU the unit of scheduling is the chip within a ``jax.sharding.Mesh``;
partitions of a DataFrame are pinned round-robin to local chips for
embarrassingly-parallel inference, while training shards one global batch
over the mesh with XLA collectives riding ICI.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["local_devices", "device_for_partition", "make_mesh",
           "batch_placement", "feed_placement", "Placement",
           "data_parallel_sharding", "replicated_sharding",
           "MeshContext", "get_default_mesh", "set_default_mesh",
           "mesh_shape"]


def local_devices():
    """Process-local devices of the default backend. A backend that fails
    to come up raises — CPU devices are never handed back in its place."""
    return jax.local_devices()


def device_for_partition(partition_index: int):
    """Pin a data partition to a process-local chip, round-robin.

    TPU-native stand-in for ``TaskContext.resources("gpu")`` pinning
    (``ONNXModel.scala:293-303``).
    """
    devs = local_devices()
    return devs[partition_index % len(devs)]


class Placement(NamedTuple):
    """Where one partition's device feeds go, as one resolved policy.

    ``mesh`` is set for SPMD dispatch (``device`` None), ``device`` for
    chip-pinned dispatch (``mesh`` None), both None for default placement.
    ``shards`` is the multiple the batch's leading dim must pad to; ``put``
    places a host array accordingly. ``key`` is hashable and identifies the
    placement for caching — params caches and warm-up bookkeeping key on it,
    so "warmed for this placement" and "params live on this placement" can
    never disagree about identity.
    """

    mesh: Optional[Mesh]
    device: Optional[object]
    shards: int
    put: object
    key: tuple


def feed_placement(use_mesh: bool, partition_index: int,
                   pin_devices: bool) -> Placement:
    """Resolve where a graph runner's host batches go — the one dispatch
    policy shared by ONNXModel and JaxModel.

    When ``use_mesh`` and a default mesh is installed, batches shard their
    leading axis over the mesh's first axis. Otherwise round-robin chip
    pinning (or default placement), with ``shards == 1``.
    """
    if use_mesh:
        mesh = get_default_mesh()
        if mesh is not None:
            sh = NamedSharding(mesh, P(mesh.axis_names[0]))
            return Placement(mesh, None,
                             int(mesh.shape[mesh.axis_names[0]]),
                             lambda a, _s=sh: jax.device_put(a, _s),
                             ("mesh", mesh))
    device = device_for_partition(partition_index) if pin_devices else None
    if device is not None:
        return Placement(None, device, 1,
                         lambda a, _d=device: jax.device_put(a, _d),
                         ("device", id(device)))
    return Placement(None, None, 1, jax.device_put, ("default",))


def batch_placement(use_mesh: bool, partition_index: int, pin_devices: bool):
    """Back-compat 4-tuple view of :func:`feed_placement`."""
    p = feed_placement(use_mesh, partition_index, pin_devices)
    return p.mesh, p.device, p.shards, p.put


def make_mesh(axis_shapes: Optional[dict] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from {axis_name: size}; -1 means "all remaining devices".

    Default: 1-D data-parallel mesh over every visible device.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not axis_shapes:
        axis_shapes = {"data": len(devices)}
    names, sizes = list(axis_shapes.keys()), list(axis_shapes.values())
    n = len(devices)
    known = int(np.prod([s for s in sizes if s != -1]))
    sizes = [s if s != -1 else max(1, n // known) for s in sizes]
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, "
                         f"have {n}")
    # tpulint: disable=TPU004 — object array of Device handles, not numerics
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))


def data_parallel_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_shape(mesh: Optional[Mesh]) -> str:
    """Canonical string for a mesh's axis layout, e.g. ``"dp4xtp2"``.

    ``"single"`` when ``mesh`` is None. Used to stamp tuning observations
    and decisions so ladders learned on one chip topology are never
    transferred onto another (a dp4xtp2 engine and a single-chip engine
    have different per-tick cost surfaces even at identical batch shapes).
    """
    if mesh is None:
        return "single"
    return "x".join(f"{name}{int(mesh.shape[name])}"
                    for name in mesh.axis_names)


_default_mesh: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _default_mesh


class MeshContext:
    """``with MeshContext({'data': -1}):`` installs a default mesh for stages."""

    def __init__(self, axis_shapes: Optional[dict] = None,
                 devices: Optional[Sequence] = None):
        self.mesh = make_mesh(axis_shapes, devices)
        self._prev: Optional[Mesh] = None

    def __enter__(self) -> Mesh:
        self._prev = get_default_mesh()
        set_default_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_default_mesh(self._prev)
        return False


def get_shard_map():
    """``jax.shard_map`` plus the kwargs that disable its varying-axes
    check, for bodies with per-shard control flow. Returns
    (shard_map_fn, uncheck_kwargs)."""
    return jax.shard_map, {"check_vma": False}
