"""Cluster topology discovery over the JAX runtime.

Parity surface: ``ClusterUtil`` in the reference
(``core/.../core/utils/ClusterUtil.scala:20,107,126``) which asks Spark for
executor/task topology so LightGBM can size its socket ring. Here topology is
a property of the JAX distributed runtime: processes ↔ hosts, local devices ↔
chips, and the global device count is the world size a mesh can span.
"""

from __future__ import annotations

import os
import socket
from typing import List, Optional

__all__ = [
    "num_processes", "process_index", "local_devices", "global_devices",
    "num_tasks", "get_driver_host", "device_for_partition",
]


def num_processes() -> int:
    """World size in hosts (reference: ``ClusterUtil.getExecutors:126``)."""
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def local_devices() -> List:
    """Chips attached to this host (reference: tasks-per-executor,
    ``ClusterUtil.getNumTasksPerExecutor:20``). Shares the implementation
    in ``parallel.mesh``: a backend that fails to come up raises."""
    from ..parallel.mesh import local_devices as _ld
    return _ld()


def global_devices() -> List:
    import jax
    return jax.devices()


def num_tasks(requested: Optional[int] = None) -> int:
    """Number of data-parallel workers a training job should shard into.

    The reference sizes this from executor/task counts
    (``LightGBMBase.scala:447-470``); here it is the global chip count unless
    the caller requests fewer.
    """
    n = len(global_devices())
    if requested is not None and requested > 0:
        return min(requested, n)
    return n


def get_driver_host() -> str:
    """Coordinator address (reference: ``ClusterUtil.getDriverHost:107``).

    Used only to bootstrap ``jax.distributed``; collectives themselves ride
    ICI/DCN, never this address.
    """
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if addr:
        return addr.split(":")[0]
    return socket.gethostbyname(socket.gethostname())


def device_for_partition(part_index: int):
    """Pin a partition to a host-local chip round-robin.

    Replaces the reference's GPU pinning from task resources
    (``ONNXModel.scala:293-303`` — ``selectGpuDevice(TaskContext.resources)``).
    Shares the implementation in ``parallel.mesh``.
    """
    from ..parallel.mesh import device_for_partition as _dfp
    return _dfp(part_index)
