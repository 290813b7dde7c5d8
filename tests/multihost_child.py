"""Child process for the REAL 2-process jax.distributed test
(tests/test_parallel.py TestTwoProcessDistributed).

Usage: python tests/multihost_child.py <process_id> <coordinator_port> <out>

Each process forces a 4-device virtual CPU platform, joins the 2-process
distributed runtime, and runs the docs/SCALING.md multi-host recipe: host 0
owns the (deterministically built) snapshot; host 1 deliberately CORRUPTS
its local copy before the broadcast to prove placements derive from host
0's store, not local state. The replicated assignment is written to <out>.

`build_snapshot()` is importable — the parent test uses the SAME
construction for its single-process reference solve.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 1 << 30


def build_snapshot():
    """Deterministic 8-node / 32-pod problem shared with the parent test."""
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.state.cluster import Cluster

    c = Cluster()
    for i in range(8):
        c.add_node(Node(name=f"n{i}", allocatable={
            CPU: 4000 + 500 * i, MEMORY: 32 * GIB, PODS: 20}))
    for j in range(32):
        c.add_pod(Pod(name=f"p{j}", creation_ms=j, containers=[
            Container(requests={CPU: 700 + 37 * (j % 5), MEMORY: GIB})]))
    pending = sorted(c.pending_pods(), key=lambda p: p.creation_ms)
    return c.snapshot(pending, now_ms=0, pad_nodes=8, pad_pods=32)


def main(proc_id: int, port: str, out_path: str) -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from scheduler_plugins_tpu.parallel import launch

    assert launch.initialize(f"127.0.0.1:{port}", 2, proc_id) is True
    assert jax.process_count() == 2

    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    snap, meta = build_snapshot()
    if proc_id != 0:
        # corrupt the non-owner's copy: the broadcast must win
        snap = snap.replace(pods=snap.pods.replace(req=snap.pods.req * 0 + 1))

    try:
        snap = launch.broadcast_snapshot(snap)
        mesh = launch.make_multihost_mesh()
        assert mesh.devices.size == 8 and jax.process_count() == 2

        weights = jnp.asarray(
            meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
        )
        assignment = launch.distributed_solve(snap, mesh, weights)
    except Exception as exc:  # jaxlib capability gap, not a code bug
        if "Multiprocess computations aren't implemented" in str(exc):
            # older jaxlib CPU backends have no cross-process collectives;
            # exit with the sentinel the parent test maps to pytest.skip
            sys.exit(42)
        raise

    with open(out_path, "w") as f:
        json.dump({
            "process": proc_id,
            "processes": jax.process_count(),
            "devices": int(mesh.devices.size),
            "assignment": [int(a) for a in assignment],
        }, f)


if __name__ == "__main__":
    main(int(sys.argv[1]), sys.argv[2], sys.argv[3])
