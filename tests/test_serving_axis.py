"""The extended-resource axis through resident state (ISSUE 28, step 2).

The serving engine's resource axis is the canonical four plus the extended
resources its store names when the resident base is built; a node, pod,
PodGroup or quota that names another one later triggers one rebase, which
widens the axis, and is served from then on. Held here, on a cluster of
GPU nodes under gangs and quotas: the engine's snapshot equals a fresh
`build_snapshot`'s leaf for leaf after binds, deletes, a gang rejection and
a node added; each kind of object widens the axis by exactly one rebase;
and `compatible` still refuses every case it refused for another reason.
"""

import dataclasses

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    POD_GROUP_LABEL,
    Container,
    ElasticQuota,
    Node,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PodGroup,
    PreferredSchedulingTerm,
    Taint,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import run_cycle
from scheduler_plugins_tpu.serving import ServeEngine, StreamingServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs
from tests.test_bucketed_axes import gang_scheduler
from tests.test_serving import gib, make_cluster, make_pod, make_scheduler

GPU = "nvidia.com/gpu"
FPGA = "example.com/fpga"


def gpu_node(i, gpus=8):
    return Node(name=f"g{i:03d}", allocatable={
        CPU: 96_000, MEMORY: 1024 * gib, PODS: 110, GPU: gpus,
    })


def gpu_cluster(n_nodes=4):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(gpu_node(i))
    for team, gpus in (("team-a", 16), ("team-b", 4)):
        cluster.add_quota(ElasticQuota(
            name="eq", namespace=team,
            min={CPU: 192_000, MEMORY: 2048 * gib, GPU: 16},
            max={CPU: 384_000, MEMORY: 4096 * gib, GPU: gpus},
        ))
    return cluster


def gpu_pod(name, namespace, now, gang=None, gpus=1, extra=None):
    requests = {CPU: 4000, MEMORY: 16 * gib, GPU: gpus}
    requests.update(extra or {})
    return Pod(
        name=name, namespace=namespace, creation_ms=now,
        labels={POD_GROUP_LABEL: gang} if gang else {},
        containers=[Container(requests=requests)],
    )


def assert_leaf_for_leaf(engine, cluster, now):
    """The engine's snapshot of the store as it stands against a fresh
    `build_snapshot` of it: every leaf of nodes, pods, gangs, quota and
    metrics."""
    pending = cluster.pending_pods()
    refreshed = engine.refresh(cluster, pending, now_ms=now)
    assert refreshed is not None, "the engine fell back"
    mine, meta = refreshed
    fresh, fresh_meta = cluster.snapshot(
        pending, now_ms=now, pad_nodes=engine.npad
    )
    assert meta.index.names == fresh_meta.index.names == engine.index.names
    assert meta.gang_names == fresh_meta.gang_names
    assert set(meta.namespaces) == set(fresh_meta.namespaces)
    for family in ("nodes", "pods", "gangs", "quota", "metrics"):
        got, want = getattr(mine, family), getattr(fresh, family)
        assert (got is None) == (want is None), family
        if got is None:
            continue
        for leaf in dataclasses.fields(got):
            a = np.asarray(getattr(got, leaf.name))
            b = np.asarray(getattr(want, leaf.name))
            assert a.shape == b.shape, (family, leaf.name, a.shape, b.shape)
            np.testing.assert_array_equal(
                a, b, err_msg=f"{family}.{leaf.name}"
            )


@pytest.mark.parametrize("engine_cls", [ServeEngine, StreamingServeEngine])
def test_gpu_state_equals_a_fresh_snapshot_leaf_for_leaf(engine_cls):
    cluster = gpu_cluster()
    engine = engine_cls().attach(cluster)
    sched = gang_scheduler()
    axis0 = obs.metrics.get(obs.SERVE_AXIS_REBASES)

    # binds: plain pods and a whole gang
    for i in range(3):
        cluster.add_pod(gpu_pod(f"solo-{i}", "team-a", 100 + i))
    cluster.add_pod_group(PodGroup(
        name="g1", namespace="team-a", min_member=4, creation_ms=200,
    ))
    for m in range(4):
        cluster.add_pod(gpu_pod(f"g1-{m}", "team-a", 200, gang="g1"))
    report = run_cycle(sched, cluster, now=1000, serve=engine)
    assert len(report.bound) == 7 and not report.failed
    assert engine.index.names[-1] == GPU and engine.rebases == 1
    assert obs.metrics.get(obs.SERVE_AXIS_REBASES) == axis0 + 1
    assert_leaf_for_leaf(engine, cluster, 1500)

    # a gang rejection: team-b's quota admits four GPUs of a gang of six;
    # four members are reserved and wait, two are refused, all six stay
    cluster.add_pod_group(PodGroup(
        name="g2", namespace="team-b", min_member=6, creation_ms=2000,
    ))
    for m in range(6):
        cluster.add_pod(gpu_pod(f"g2-{m}", "team-b", 2000, gang="g2"))
    cluster.add_pod(gpu_pod("solo-9", "team-a", 2100))
    report = run_cycle(sched, cluster, now=3000, serve=engine)
    assert report.rejected_gangs == ["team-b/g2"]
    assert list(report.bound) == ["team-a/solo-9"]
    assert len(report.failed) == 2 and not cluster.reserved
    assert_leaf_for_leaf(engine, cluster, 3500)

    # deletes: a bound plain pod, a bound member, a pending member
    for uid in ("team-a/solo-0", "team-a/g1-3", "team-b/g2-5"):
        cluster.remove_pod(uid)
    assert_leaf_for_leaf(engine, cluster, 4000)

    # a node added (it grows the node bucket's rows, not the axis)
    cluster.add_node(gpu_node(9, gpus=4))
    cluster.add_pod(gpu_pod("solo-10", "team-a", 4100))
    report = run_cycle(sched, cluster, now=5000, serve=engine)
    assert "team-a/solo-10" in report.bound
    assert_leaf_for_leaf(engine, cluster, 5500)

    assert engine.rebases == 1 and engine.gang_fallbacks == 0
    assert engine.antientropy_divergences == 0
    assert engine.verify(cluster) is None
    assert obs.metrics.get(obs.SERVE_AXIS_REBASES) == axis0 + 1


def _node_with_fpga(cluster):
    cluster.add_node(Node(name="f000", allocatable={
        CPU: 8000, MEMORY: 32 * gib, PODS: 32, FPGA: 2,
    }))


def _bound_pod_with_fpga(cluster):
    # in a namespace no quota governs: a quota that does not name a
    # resource its pods ask for refuses everything after them
    pod = gpu_pod("wide", "free", 50, extra={FPGA: 1})
    pod.node_name = "g000"
    cluster.add_pod(pod)


def _pending_pod_with_fpga(cluster):
    cluster.add_pod(gpu_pod("wide", "team-a", 50, extra={FPGA: 1}))


def _pod_group_with_fpga(cluster):
    cluster.add_pod_group(PodGroup(
        name="wide", namespace="team-a", min_member=1, creation_ms=50,
        min_resources={CPU: 1000, FPGA: 1},
    ))


def _quota_with_fpga(cluster):
    cluster.add_quota(ElasticQuota(
        name="eq", namespace="team-c",
        min={CPU: 1000, MEMORY: gib, GPU: 0, FPGA: 1},
        max={CPU: 2000, MEMORY: 2 * gib, GPU: 1, FPGA: 2},
    ))


@pytest.mark.parametrize("name_it", [
    _node_with_fpga, _bound_pod_with_fpga, _pending_pod_with_fpga,
    _pod_group_with_fpga, _quota_with_fpga,
], ids=lambda f: f.__name__.strip("_"))
def test_a_new_resource_name_rebases_once_and_is_then_served(name_it):
    cluster = gpu_cluster()
    engine = ServeEngine().attach(cluster)
    sched = gang_scheduler()
    cluster.add_pod(gpu_pod("first", "team-a", 10))
    run_cycle(sched, cluster, now=1000, serve=engine)
    assert engine.rebases == 1 and FPGA not in engine.index
    axis0 = obs.metrics.get(obs.SERVE_AXIS_REBASES)

    name_it(cluster)
    cluster.add_pod(gpu_pod("second", "team-a", 1100))
    report = run_cycle(sched, cluster, now=2000, serve=engine)
    assert "team-a/second" in report.bound
    assert engine.rebases == 2, "one rebase widens the axis"
    assert engine.index.names[-2:] == (GPU, FPGA)
    assert obs.metrics.get(obs.SERVE_AXIS_REBASES) == axis0 + 1
    assert engine.resident_nodes is not None
    assert_leaf_for_leaf(engine, cluster, 2500)

    # served from then on
    for serial, now in ((3, 3000), (4, 4000)):
        cluster.add_pod(gpu_pod(f"later-{serial}", "team-a", now - 100))
        run_cycle(sched, cluster, now=now, serve=engine)
    assert engine.rebases == 2 and engine.gang_fallbacks == 0
    assert obs.metrics.get(obs.SERVE_AXIS_REBASES) == axis0 + 1
    assert_leaf_for_leaf(engine, cluster, 4500)
    assert engine.verify(cluster) is None


def test_a_checkpoint_carries_the_axis(tmp_path):
    cluster = gpu_cluster()
    engine = ServeEngine().attach(cluster)
    cluster.add_pod(gpu_pod("first", "team-a", 10))
    run_cycle(gang_scheduler(), cluster, now=1000, serve=engine)
    engine.refresh(cluster, [], now_ms=1500)
    path = str(tmp_path / "serve.ckpt")
    assert engine.save_checkpoint(path)
    restored = ServeEngine().attach(cluster)
    assert restored.restore_checkpoint(path)
    assert restored.index.names == engine.index.names
    # the restored state is exact: the verify it forces finds nothing
    assert restored.refresh(cluster, [], now_ms=2000) is not None
    assert restored.rebases == 0 and restored.antientropy_divergences == 0


def _nrt(cluster, engine):
    from scheduler_plugins_tpu.api.objects import NodeResourceTopology

    cluster.nrts["n000"] = NodeResourceTopology(node_name="n000", zones=[])


def _app_group(cluster, engine):
    cluster.app_groups["default/ag"] = object()


def _seccomp(cluster, engine):
    cluster.seccomp_profiles["default/sp"] = object()


def _tainted_node(cluster, engine):
    node = Node(name="n000", allocatable={CPU: 8000, MEMORY: 32 * gib,
                                          PODS: 32})
    node.taints = [Taint(key="k", value="v", effect="NoSchedule")]
    cluster.add_node(node)
    engine.refresh(cluster, [], now_ms=1500)  # classified at the drain


def _gated_nominee(cluster, engine):
    pod = make_pod(70, 1000)
    pod.scheduling_gated = True
    pod.nominated_node_name = "n000"
    cluster.add_pod(pod)


def _reserved_nominee(cluster, engine):
    pod = make_pod(71, 1000)
    cluster.add_pod(pod)
    cluster.reserve(pod.uid, "n001")
    pod.nominated_node_name = "n000"


def _pending(**spec):
    def add(cluster, engine):
        pod = make_pod(72, 1000)
        for key, value in spec.items():
            setattr(pod, key, value)
        cluster.add_pod(pod)
    add.__name__ = "_pending_" + "_".join(spec)
    return add


@pytest.mark.parametrize("refuse", [
    _nrt, _app_group, _seccomp, _tainted_node,
    _gated_nominee, _reserved_nominee,
    _pending(nominated_node_name="n000"),
], ids=lambda f: f.__name__.strip("_"))
def test_compatible_still_refuses_each_of_its_other_cases(refuse):
    cluster = make_cluster(4)
    engine = ServeEngine().attach(cluster)
    cluster.add_pod(make_pod(1, 500))
    run_cycle(make_scheduler(), cluster, now=1000, serve=engine)
    assert engine.compatible(cluster, cluster.pending_pods())
    refuse(cluster, engine)
    pending = cluster.pending_pods()
    assert not engine.compatible(cluster, pending)
    assert engine.refresh(cluster, pending, now_ms=2000) is None


def _ssd_term():
    return NodeSelectorTerm(match_expressions=[
        NodeSelectorRequirement("disk", "In", ("ssd",)),
    ])


@pytest.mark.parametrize("spec", [
    lambda: dict(node_selector={"disk": "ssd"}),
    lambda: dict(node_affinity_required=[_ssd_term()]),
    lambda: dict(node_affinity_preferred=[
        PreferredSchedulingTerm(1, _ssd_term()),
    ]),
], ids=["node_selector", "node_affinity_required", "node_affinity_preferred"])
def test_compatible_no_longer_refuses_a_node_term(spec):
    """ISSUE 38 took these three off the list above: the spec's row over
    the nodes is resident state, the cycle is served, and what it gathers
    is what a fresh build gathers."""
    cluster = make_cluster(4)
    engine = ServeEngine().attach(cluster)
    cluster.add_pod(make_pod(1, 500))
    run_cycle(make_scheduler(), cluster, now=1000, serve=engine)
    _pending(**spec())(cluster, engine)
    pending = cluster.pending_pods()
    assert engine.compatible(cluster, pending)
    mine, _meta = engine.refresh(cluster, pending, now_ms=2000)
    fresh, _ = cluster.snapshot(pending, now_ms=2000, pad_nodes=engine.npad)
    got, want = mine.scheduling, fresh.scheduling
    for table, index in (("node_term_ok", "pod_node_term"),
                         ("pref_score", "pod_pref")):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, table))[
                np.asarray(getattr(got, index))[:len(pending)]],
            getattr(want, table)[getattr(want, index)[:len(pending)]],
        )
    assert engine.rebases == 1 and engine.verify(cluster, 2000) is None


def test_compatible_no_longer_refuses_a_load_watchers_report():
    """ISSUE 29 took `node_metrics` off the list above: the report is
    resident state, the cycle is served and its snapshot carries it."""
    cluster = make_cluster(4)
    engine = ServeEngine().attach(cluster)
    cluster.add_pod(make_pod(1, 500))
    run_cycle(make_scheduler(), cluster, now=1000, serve=engine)
    cluster.node_metrics = {"n000": {"cpu_avg": 50.0}}
    cluster.add_pod(make_pod(2, 1500))
    assert engine.compatible(cluster, cluster.pending_pods())
    assert_leaf_for_leaf(engine, cluster, 2000)
    assert engine.rebases == 1
