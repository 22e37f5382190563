"""Device-mesh management.

The reference scales by hash-sharding the corpus across nodes and
scatter-gathering searches (`cluster/routing/OperationRouting.java`,
`AbstractSearchAsyncAction.java:214`). The TPU-native analog is a 2-D
`jax.sharding.Mesh`:

  axis "dp"    — query-batch data parallelism (independent searches)
  axis "shard" — corpus partitioning (one Elasticsearch shard ≈ one mesh
                 column's slice of the HBM-resident matrix)

Cross-shard merges ride ICI collectives inside the compiled program instead
of coordinator-side RPC reduces (`SearchPhaseController.mergeTopDocs:221`).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu import telemetry

DP_AXIS = "dp"
SHARD_AXIS = "shard"

# per-device launch locks (lazily created, one per device id): an SPMD
# program's per-device executions must ENQUEUE in a consistent order
# across devices — two threads interleaving enqueues of collective
# programs over overlapping device sets can deadlock the all-gather
# rendezvous (each device stream runs a different program first). The
# guard serializes only the enqueue; execution stays async, and
# launches on DISJOINT device sets (different dp groups) take disjoint
# locks and overlap fully — which is the dp axis's whole point.
_launch_registry_lock = threading.Lock()
_device_launch_locks: Dict[int, threading.Lock] = {}


class _MultiLock:
    """Acquire a list of locks in order (device-id order — globally
    consistent, so overlapping acquirers can't deadlock each other)."""

    __slots__ = ("_locks",)

    def __init__(self, locks):
        self._locks = locks

    def __enter__(self):
        # `mesh.guard_wait`: from asking for the guard to holding it, the
        # time a launch stands behind other launches on its devices
        asked = time.monotonic_ns()
        for lock in self._locks:
            lock.acquire()
        telemetry.stage_done("mesh.guard_wait", asked, time.monotonic_ns())
        return self

    def __exit__(self, *exc):
        for lock in reversed(self._locks):
            lock.release()
        return False


def launch_guard(mesh: Mesh) -> _MultiLock:
    """The enqueue guard for one SPMD dispatch on `mesh` — hold it
    across the `dispatch.call` that launches the program (NOT across
    the sync): per-device locks in device-id order serialize collective
    launches that share devices and let disjoint dp groups launch
    concurrently."""
    ids = sorted(d.id for d in np.asarray(mesh.devices).flat)
    with _launch_registry_lock:
        locks = [_device_launch_locks.setdefault(i, threading.Lock())
                 for i in ids]
    return _MultiLock(locks)


def make_mesh(num_shards: Optional[int] = None, dp: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (dp, shard) mesh over the available devices."""
    devices = list(devices if devices is not None else jax.devices())
    if num_shards is None:
        num_shards = len(devices) // dp
    if dp * num_shards > len(devices):
        raise ValueError(f"mesh {dp}x{num_shards} needs {dp * num_shards} devices, have {len(devices)}")
    grid = np.array(devices[: dp * num_shards]).reshape(dp, num_shards)
    return Mesh(grid, (DP_AXIS, SHARD_AXIS))


def dp_size(mesh: Mesh) -> int:
    return int(mesh.shape[DP_AXIS])


def shard_size(mesh: Mesh) -> int:
    return int(mesh.shape[SHARD_AXIS])


def dp_submeshes(mesh: Mesh):
    """One (dp=1, shard=S) mesh per dp row — the disjoint device groups
    independent dispatches overlap on. Each submesh keeps BOTH axis
    names, so every existing kernel spec (P("dp", ...) queries,
    P("shard", ...) corpus rows) runs unchanged on a group.

    Callers should take groups from `parallel.policy.dp_groups` rather
    than calling this directly: the dispatch cache keys executables on
    mesh IDENTITY, so the router and the warmup grid must share one set
    of group objects per serving mesh."""
    grid = np.asarray(mesh.devices)
    return tuple(Mesh(grid[r:r + 1], mesh.axis_names)
                 for r in range(grid.shape[0]))


def corpus_sharding(mesh: Mesh) -> NamedSharding:
    """Rows of the corpus matrix split across the shard axis."""
    return NamedSharding(mesh, P(SHARD_AXIS, None))


def per_shard_sharding(mesh: Mesh) -> NamedSharding:
    """1-D per-row metadata (norms, scales) split across the shard axis."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def query_sharding(mesh: Mesh) -> NamedSharding:
    """Query batches split across dp, replicated across shards."""
    return NamedSharding(mesh, P(DP_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
