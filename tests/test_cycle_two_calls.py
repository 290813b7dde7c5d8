"""`run_cycle` as two calls (ISSUE 31): `cycle_store_stages` (every stage
that reads or writes the store: what the daemon holds the feed lock for)
then `cycle_report_stages` (the report-only epilogue, `Finalize`) leave
exactly what `run_cycle` leaves: the same bound set, the same
`report.quality`, the same `scheduler_placement_quality` gauges and the
same flight-recorder record, on a plain roster, one with a load watcher's
report and one with a gang under a quota, from a fresh snapshot and from
resident state. And the epilogue refuses to run once the serving engine has
refreshed again: it reads the resident node columns in place, which that
refresh donates."""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    Container,
    ElasticQuota,
    Node,
    Pod,
    PodGroup,
    POD_GROUP_LABEL,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.framework.cycle import (
    cycle_report_stages,
    cycle_store_stages,
)
from scheduler_plugins_tpu.plugins import (
    CapacityScheduling,
    Coscheduling,
    LoadVariationRiskBalancing,
    NodeResourcesAllocatable,
    TargetLoadPacking,
)
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import flightrec, observability as obs

gib = 1 << 30


def _nodes(cluster, count=6):
    for i in range(count):
        cluster.add_node(Node(
            name=f"n{i}",
            allocatable={CPU: 8000 + 1000 * i, MEMORY: 32 * gib, PODS: 110},
        ))


def _pod(name, cpu=500, ns="default", **kw):
    return Pod(
        name=name, namespace=ns, creation_ms=1,
        containers=[Container(requests={CPU: cpu, MEMORY: gib})], **kw,
    )


def plain_roster():
    c = Cluster()
    _nodes(c)
    for p in range(7):
        c.add_pod(_pod(f"p{p}", cpu=300 + 200 * p))
    c.add_pod(_pod("huge", cpu=10 ** 9))  # a failure row
    return c, Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))


def metrics_roster():
    c = Cluster()
    _nodes(c)
    c.node_metrics = {
        name: {"cpu_avg": 20.0 + 7 * i, "cpu_std": 1.0 + i,
               "mem_avg": 40.0 + i, "mem_std": 0.5}
        for i, name in enumerate(c.nodes)
    }
    for p in range(7):
        c.add_pod(_pod(f"p{p}", cpu=300 + 200 * p))
    return c, Scheduler(Profile(plugins=[
        TargetLoadPacking(), LoadVariationRiskBalancing(),
    ]))


def gang_quota_roster():
    c = Cluster()
    _nodes(c)
    c.add_quota(ElasticQuota(
        name="q", namespace="team",
        min={CPU: 4000, MEMORY: 16 * gib}, max={CPU: 6000, MEMORY: 24 * gib},
    ))
    c.add_pod_group(PodGroup(name="g", namespace="team", min_member=3,
                             creation_ms=0))
    for p in range(3):
        c.add_pod(_pod(f"g{p}", cpu=1000, ns="team",
                       labels={POD_GROUP_LABEL: "g"}))
    for p in range(5):  # the quota refuses all but the first of these
        c.add_pod(_pod(f"s{p}", cpu=900, ns="team"))
    return c, Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), Coscheduling(), CapacityScheduling(),
    ]))


ROSTERS = {
    "plain": plain_roster,
    "metrics": metrics_roster,
    "gang_quota": gang_quota_roster,
}


def _quality_gauges() -> dict:
    return {
        key: value for key, value in obs.metrics.snapshot().items()
        if key.startswith(obs.PLACEMENT_QUALITY)
    }


def _clear_quality_gauges() -> None:
    for key in _quality_gauges():
        objective = key.split('"')[1]
        obs.metrics.set_gauge(obs.PLACEMENT_QUALITY, -1.0,
                              objective=objective)


def _one_cycle(roster, served: bool, two_calls: bool) -> dict:
    """What one cycle on a new copy of `roster` leaves behind."""
    cluster, scheduler = ROSTERS[roster]()
    serve = ServeEngine().attach(cluster) if served else None
    flightrec.recorder.start(capacity=1)  # the record's number starts over
    _clear_quality_gauges()
    if two_calls:
        ctx = cycle_store_stages(scheduler, cluster, 1000, serve=serve)
        assert ctx.report.quality is None  # the epilogue has not run
        assert not flightrec.recorder.records()[-1].complete
        report = cycle_report_stages(ctx)
        assert report is ctx.report
    else:
        report = run_cycle(scheduler, cluster, now=1000, serve=serve)
    record = flightrec.recorder.records()[-1]
    assert record.complete
    if served:
        assert serve.rebases == 1 and serve.gang_fallbacks == 0
    return {
        "quality": report.quality,
        "gauges": _quality_gauges(),
        "bound": report.bound,
        "reserved": report.reserved,
        "failed": report.failed,
        "failed_by": report.failed_by,
        "store": {uid: pod.node_name for uid, pod in cluster.pods.items()},
        "manifest": record.to_manifest(),
    }


@pytest.fixture
def recorder_off():
    yield
    flightrec.recorder.stop()


@pytest.mark.parametrize("served", [False, True], ids=["fresh", "served"])
@pytest.mark.parametrize("roster", sorted(ROSTERS))
def test_two_calls_leave_what_run_cycle_leaves(roster, served, recorder_off):
    whole = _one_cycle(roster, served, two_calls=False)
    split = _one_cycle(roster, served, two_calls=True)
    assert whole["bound"], "the roster bound nothing: no evidence"
    assert whole["quality"] and whole["gauges"]
    assert set(whole["gauges"]) == {
        f'{obs.PLACEMENT_QUALITY}{{objective="{name}"}}'
        for name in whole["quality"]
    }
    assert min(whole["gauges"].values()) >= 0.0  # every gauge was written
    assert split == whole
    assert split["manifest"]["report"]["quality"] == whole["quality"]
    assert split["manifest"]["digest"] == whole["manifest"]["digest"]


def test_rosters_fail_a_pod_where_they_say_so():
    # the comparison above covers a failure row and a quota refusal only
    # if these rosters really produce them
    cluster, scheduler = plain_roster()
    assert run_cycle(scheduler, cluster, now=1000).failed == ["default/huge"]
    cluster, scheduler = gang_quota_roster()
    report = run_cycle(scheduler, cluster, now=1000)
    assert len(report.bound) == 4 and len(report.failed) == 4
    assert set(report.failed_by.values()) == {"CapacityScheduling"}


class TestFinalizeBeforeTheNextRefresh:
    """The serial engine takes no host copy of the node columns for its
    epilogue: its thread finalizes, then refreshes. Asked the other way
    round (another thread refreshed under the feed lock, as the
    benchmark's resident-state check does) it leaves the quality out; it
    never reads what the refresh donated, and the daemon lives."""

    def _served_cycle(self):
        cluster, scheduler = plain_roster()
        engine = ServeEngine().attach(cluster)
        ctx = cycle_store_stages(scheduler, cluster, 1000, serve=engine)
        assert ctx.served and ctx.serve_generation == engine.generation
        return cluster, scheduler, engine, ctx

    def test_finalize_after_the_engines_next_refresh_skips_quality(
            self, caplog):
        cluster, _scheduler, engine, ctx = self._served_cycle()
        engine.refresh(cluster, [], now_ms=2000)  # the cycle's own binds
        assert engine.generation == ctx.serve_generation + 1
        with caplog.at_level("WARNING"):
            report = cycle_report_stages(ctx)
        assert report.quality is None and report.bound
        assert "next refresh" in caplog.text

    def test_finalize_in_time_reads_the_columns_the_cycle_solved_on(self):
        cluster, scheduler, engine, ctx = self._served_cycle()
        twin, twin_scheduler = plain_roster()
        expected = run_cycle(twin_scheduler, twin, now=1000).quality
        assert cycle_report_stages(ctx).quality == expected
        # and the next cycle, which refreshes, is none the worse for it
        cluster.add_pod(_pod("late"))
        report = run_cycle(scheduler, cluster, now=2000, serve=engine)
        assert "default/late" in report.bound
        assert engine.refresh(cluster, [], now_ms=3000) is not None
        assert engine.verify(cluster) is None

    def test_a_host_copy_outlives_the_refresh(self):
        # the pipelined engine's case: `quality_view` is what lets a
        # deferred finalize run after the donation
        from scheduler_plugins_tpu.framework.cycle import _quality_view

        cluster, _scheduler, engine, ctx = self._served_cycle()
        ctx.quality_view = _quality_view(ctx.snap)
        engine.refresh(cluster, [], now_ms=2000)
        quality = cycle_report_stages(ctx).quality
        assert quality is not None and np.isfinite(list(quality.values())).all()

    def test_an_unserved_cycle_has_nothing_to_guard(self):
        cluster, scheduler = plain_roster()
        ctx = cycle_store_stages(scheduler, cluster, 1000)
        assert not ctx.served and ctx.serve_generation is None
        assert cycle_report_stages(ctx).quality is not None

    def test_a_cycle_with_no_batch_finalizes_nothing(self):
        cluster = Cluster()
        _nodes(cluster)
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        ctx = cycle_store_stages(scheduler, cluster, 1000)
        assert ctx.done
        report = cycle_report_stages(ctx)
        assert report.quality is None and not report.bound
