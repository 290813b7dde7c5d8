"""The plain references against the program's sequential solve, on the CPU
backend at a small size, on seeded clusters. On the chip the harness makes
the same comparison at full size on a recorded cycle (`harness.checks.probe`).
"""

import json

import numpy as np
import pytest

from harness import checks
from harness import cluster_gen as gen
from harness import spec


def _solve_both(config_name: str, seed: int, n_pods: int):
    import importlib

    import scheduler_plugins_tpu  # noqa: F401  (switches x64 on)
    from scheduler_plugins_tpu.api.config import load_profile
    from scheduler_plugins_tpu.bridge.feed import apply_event
    from scheduler_plugins_tpu.framework import Scheduler
    from scheduler_plugins_tpu.state.cluster import Cluster

    config = spec.Cell(f"{config_name}.steady", rehearse=True).config
    cluster = Cluster()
    nodes = gen.node_specs(config["cluster"], seed)
    for node in nodes:
        apply_event(cluster, json.loads(gen.node_line(node)))
    for name, cpu, mem, node in gen.prefill(config["cluster"], nodes, 150, seed):
        apply_event(cluster, json.loads(gen.pod_line(name, 0, cpu, mem, node)))
    rng = gen.stream(seed, "arrivals")
    for i in range(n_pods):
        cpu, mem = gen.draw_request(rng, config["cluster"]["pod_requests"])
        apply_event(cluster, json.loads(gen.pod_line(f"a-{i:07d}", i, cpu, mem)))
    for side in config["feed_side_events"]:
        apply_event(cluster, json.loads(
            gen.node_metrics_line(nodes, side, seed, 0)
        ))
    scheduler = Scheduler(load_profile(config["profile"]))
    for plugin in scheduler.profile.plugins:
        plugin.configure_cluster(cluster)
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    got = scheduler.solve(snap)
    reference = importlib.import_module(f"references.{config['reference']}")
    want = reference.solve(checks.reference_inputs(snap), config["profile"])
    return got, want


@pytest.mark.parametrize("config_name", ["basic-5000n", "trimaran-5000n"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_sequential_solve(config_name, seed):
    # 700 pods on 48 nodes: the cluster fills, so some pods fit nowhere
    got, want = _solve_both(config_name, seed, 700)
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    placed = int((want["assignment"] >= 0).sum())
    assert 0 < placed < 700


def test_min_bytes_grow_with_the_cluster():
    from references import allocatable, trimaran

    assert allocatable.min_bytes_per_pod(5120, 4) == 5120 * 41 + 32
    assert trimaran.min_bytes_per_pod(5120, 4) > allocatable.min_bytes_per_pod(5120, 4)
