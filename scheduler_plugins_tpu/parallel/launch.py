"""Multi-host launch recipe: jax.distributed + DCN/ICI mesh placement.

The reference scales one Go process with goroutines (SURVEY.md §2.9); the
TPU-native analog is the standard JAX multi-controller runtime — N identical
processes (one per TPU host), each owning its local chips, jitting the SAME
sharded solve over one global mesh. This module packages the launch recipe
docs/SCALING.md describes:

Per host (identical binary, different process_id):

    from scheduler_plugins_tpu.parallel import launch
    launch.initialize()                # reads JAX_COORDINATOR/... env vars,
                                       # or pass explicitly; no-op when alone
    mesh = launch.make_multihost_mesh()

    # host 0 runs the cluster store + event feed; every cycle:
    snap = launch.broadcast_snapshot(snap_or_none)   # host 0 -> everyone
    assignment = launch.distributed_solve(snap, mesh, weights)
    # `assignment` is fully replicated: host 0 applies the bindings

Mesh placement follows docs/SCALING.md "Multi-host (DCN)": the "pods" axis
spans HOSTS (its per-wave work is embarrassingly parallel except log-depth
prefix scans, which tolerate DCN latency), the "nodes" axis stays INSIDE
each host's ICI domain (it carries the frequent small per-wave reductions).
`mesh_utils.create_hybrid_device_mesh` realizes exactly that: the outer
(DCN) factor maps to process granularity, the inner to local chips.

Environment (standard JAX multi-controller):

    JAX_COORDINATOR=host0:8476 JAX_NUM_PROCESSES=4 JAX_PROCESS_ID=k \
        python your_scheduler_host.py

On Cloud TPU pods, `jax.distributed.initialize()` discovers all three
automatically; the env vars are the manual/baremetal path.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from scheduler_plugins_tpu.parallel.mesh import (
    NODES_AXIS,
    PODS_AXIS,
    make_mesh,
)


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """`jax.distributed.initialize` with env-var fallback
    (JAX_COORDINATOR / JAX_NUM_PROCESSES / JAX_PROCESS_ID). Returns True
    when a multi-process runtime was started, False for the single-process
    no-op (local runs, tests, the bench driver)."""
    import os

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR"
    )
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # Cloud TPU pod slice: initialize() autodetects coordinator/count.
        # Must run BEFORE any JAX computation touches the backend (even
        # jax.process_count() would initialize it single-process); a raise
        # here means either "not a managed multi-host environment" or "the
        # backend is already up" (single-process tests) — both single.
        try:
            jax.distributed.initialize()
        except Exception:
            return False
        return jax.process_count() > 1
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def make_multihost_mesh() -> jax.sharding.Mesh:
    """Global ("pods", "nodes") mesh with the pods axis across hosts (DCN)
    and the nodes axis within each host (ICI) — docs/SCALING.md placement.
    Single-process: falls back to `make_mesh` over local devices."""
    n_proc = jax.process_count()
    if n_proc <= 1:
        return make_mesh()
    per_host = jax.local_device_count()
    from jax.experimental import mesh_utils

    try:
        # TPU pod slices: hybrid mesh for the best ICI ordering per host
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1, per_host),  # within a host: all chips on "nodes"
            dcn_mesh_shape=(n_proc, 1),  # across hosts: "pods"
        )
    except ValueError:
        # backends without slice topology info (multi-process CPU — the
        # 2-process test tier): the process boundary IS the DCN boundary
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1, per_host),
            dcn_mesh_shape=(n_proc, 1),
            process_is_granule=True,
        )
    return jax.sharding.Mesh(grid, (PODS_AXIS, NODES_AXIS))


def broadcast_snapshot(snap):
    """Replicate host 0's snapshot to every process (host 0 owns the
    cluster store + feed; the others only compute). Single-process: identity.
    """
    if jax.process_count() <= 1:
        return snap
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(snap)


def distributed_solve(snap, mesh, weights, max_waves: int = 8):
    """Run the sharded batched solve on the global mesh and return the
    (P,) assignment replicated to every host (host 0 binds)."""
    from scheduler_plugins_tpu.parallel.solver import sharded_batch_solve

    assignment, admitted, wait = sharded_batch_solve(
        snap, mesh, weights, max_waves=max_waves
    )
    # replicate across the whole mesh (XLA inserts the all-gather) so every
    # process holds the full (P,) result locally
    from jax.sharding import NamedSharding, PartitionSpec

    with jax.set_mesh(mesh):
        assignment = jax.jit(
            lambda a: a, out_shardings=NamedSharding(mesh, PartitionSpec())
        )(assignment)
    return np.asarray(assignment.addressable_data(0))
