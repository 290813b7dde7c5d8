"""Device mesh construction and snapshot sharding specs."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PODS_AXIS = "pods"
NODES_AXIS = "nodes"


def make_mesh(
    n_devices: Optional[int] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """Mesh with ("pods", "nodes") axes over the first `n_devices` devices.

    The factorization favors the node axis (clusters have more nodes than a
    wave has independent pods-per-shard): nodes gets the larger factor.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    pods_dim = 1
    for cand in range(int(np.sqrt(n)), 0, -1):
        if n % cand == 0:
            pods_dim = cand
            break
    nodes_dim = n // pods_dim
    grid = np.asarray(devices).reshape(pods_dim, nodes_dim)
    return Mesh(grid, (PODS_AXIS, NODES_AXIS))


def make_node_mesh(
    n_devices: Optional[int] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """1-D ("nodes",) mesh for the sharded wave solver: EVERY device on the
    node axis. The wave hot loop's only sharded dimension is the node axis
    (pod-window state is replicated and cheap); a 2-D factorization would
    idle the pods-axis devices during the per-wave ring election."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (NODES_AXIS,))


def pad_to_shards(n: int, n_shards: int) -> int:
    """Smallest multiple of `n_shards` >= n — the mesh-aligned node-axis
    padding rule shared by `dryrun_multichip` and the sharded wave solve
    (padded rows carry zero capacity and node id -1, so they can never win
    a wave election; tests/test_shard_wave.py gates the edge)."""
    return ((n + n_shards - 1) // n_shards) * n_shards


def snapshot_shardings(snap, mesh: Mesh):
    """Sharding pytree for a ClusterSnapshot: node-major arrays shard their
    leading axis over "nodes", pod-major arrays over "pods", side tables
    (gangs/quota/cost matrices) replicate — segment reductions over them ride
    collectives."""

    def spec_for(path, leaf):
        top = path[0].name if path else ""
        if top == "nodes" or top == "numa" or top == "metrics":
            return NamedSharding(mesh, P(NODES_AXIS, *([None] * (leaf.ndim - 1))))
        if top == "pods":
            return NamedSharding(mesh, P(PODS_AXIS, *([None] * (leaf.ndim - 1))))
        if top == "network" and path[-1].name == "placed_node" and leaf.ndim == 2:
            return NamedSharding(mesh, P(None, NODES_AXIS))
        if top == "syscalls":
            name = path[-1].name
            if name in ("host_sets", "counts", "host_pod_count"):
                return NamedSharding(mesh, P(NODES_AXIS, *([None] * (leaf.ndim - 1))))
            return NamedSharding(mesh, P(PODS_AXIS, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())  # replicate side tables

    return jax.tree_util.tree_map_with_path(spec_for, snap)


def shard_snapshot(snap, mesh: Mesh):
    """Place a snapshot on the mesh per `snapshot_shardings`."""
    shardings = snapshot_shardings(snap, mesh)
    return jax.tree.map(jax.device_put, snap, shardings)
