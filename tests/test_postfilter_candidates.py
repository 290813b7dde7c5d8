"""PostFilter decides whom it searches for before it does anything
O(cluster) (ISSUE 33).

`_run_preemption` takes its candidates first: the failed pods less those
of a gang rejected whole, in queue order. With none left it returns before
the post-bind snapshot, the re-prepare and the hold scan; with one left it
does what it always did. Held here against the order it had before, kept
below as `old_order`: the same report, nominations, victims and counters
in six cases, and on a served gangs + quota roster over five cycles; where
every failed pod belongs to a rejected gang, no snapshot is built and the
plugins stay bound to the cycle's own meta.
"""

import contextlib
from types import SimpleNamespace

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    POD_GROUP_LABEL,
    Container,
    Pod,
    PodGroup,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.framework import cycle as cycle_mod
from scheduler_plugins_tpu.framework.pipeline_cycle import PipelinedCycle
from scheduler_plugins_tpu.framework.preemption import (
    GATED,
    PreemptionEngine,
    PreemptionMode,
    encode_demand,
)
from scheduler_plugins_tpu.obs import ledger as podledger
from scheduler_plugins_tpu.plugins import (
    Coscheduling,
    NodeResourcesAllocatable,
)
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs
from tests.test_bucketed_axes import gang_scheduler
from tests.test_preemption import mknode
from tests.test_serving import gib
from tests.test_serving_axis import gpu_cluster, gpu_pod

def old_order(scheduler, cluster, pending, report, now):
    """`framework.cycle._run_preemption` as it stood before ISSUE 33: the
    snapshot, the re-prepare and the hold scan come first, and the loop
    skips the pods of a rejected gang one by one."""
    engine = scheduler.profile.preemption
    if engine is None or not report.failed:
        return 0
    candidates = 0
    rejected = set(report.rejected_gangs)
    by_uid = {p.uid: p for p in pending}
    failed_pods = [by_uid[uid] for uid in report.failed if uid in by_uid]
    snap, meta = cluster.snapshot(failed_pods, now_ms=now)
    scheduler.prepare(meta, cluster)
    nominated_extra = np.zeros(
        (len(meta.node_names), len(meta.index)), np.int64
    )
    node_pos = {name: i for i, name in enumerate(meta.node_names)}
    for pod in cluster.pods.values():
        if pod.terminating and pod.node_name in node_pos:
            nominated_extra[node_pos[pod.node_name]] -= encode_demand(
                meta.index, pod
            )
    holds = [
        (
            node_pos[pod.nominated_node_name],
            encode_demand(meta.index, pod),
            pod.priority,
            pod.uid,
        )
        for pod in cluster.pods.values()
        if pod.node_name is None
        and not pod.terminating
        and pod.nominated_node_name in node_pos
    ]
    for pod in failed_pods:
        pg = cluster.pod_group_of(pod)
        if pg is not None and pg.full_name in rejected:
            continue
        candidates += 1
        obs.metrics.inc(obs.PREEMPTION_ATTEMPTS)
        extra = nominated_extra.copy()
        for n_, demand_, prio_, uid_ in holds:
            if prio_ >= pod.priority and uid_ != pod.uid:
                extra[n_] += demand_
        result = engine.preempt(
            cluster, scheduler, pod, snap, meta, now,
            extra_reserved=extra,
        )
        if result is GATED:
            continue
        holds = [h for h in holds if h[3] != pod.uid]
        if result is None:
            pod.nominated_node_name = None
            if cluster.delta_sink is not None:
                cluster.delta_sink.note_nomination(pod)
            if podledger.LEDGER.enabled:
                podledger.LEDGER.on_nomination(pod.uid, None)
            continue
        obs.metrics.inc(obs.PREEMPTION_VICTIMS, len(result.victims))
        pod.nominated_node_name = result.nominated_node
        if cluster.delta_sink is not None:
            cluster.delta_sink.note_nomination(pod)
        if podledger.LEDGER.enabled:
            podledger.LEDGER.on_nomination(pod.uid, result.nominated_node)
        n = node_pos[result.nominated_node]
        demand = encode_demand(meta.index, pod)
        victim_freed = np.zeros(len(meta.index), np.int64)
        for victim_uid in result.victims:
            victim = cluster.pods.get(victim_uid)
            if victim is not None:
                cluster.mark_terminating(victim_uid, now)
                victim_freed += encode_demand(meta.index, victim)
        holds.append((n, demand, pod.priority, pod.uid))
        nominated_extra[n] -= victim_freed
        report.preempted[pod.uid] = (result.nominated_node, result.victims)
    return candidates


# -- what a run of PostFilter did, seen from outside -------------------------


@contextlib.contextmanager
def watched(monkeypatch, search):
    """Run cycles with `search` as the PostFilter stage's search. Yields
    what it sees: what the search returned each cycle, and the
    `Cluster.snapshot` and `Scheduler.prepare` calls made from inside it
    (the pending uids of each snapshot, the meta of each prepare)."""
    watch = SimpleNamespace(returned=[], snapshots=[], prepares=[])
    inside = []
    real_snapshot = Cluster.snapshot
    real_prepare = Scheduler.prepare

    def run(scheduler, cluster, pending, report, now):
        inside.append(True)
        try:
            count = search(scheduler, cluster, pending, report, now)
        finally:
            inside.pop()
        watch.returned.append(count)
        return count

    def snapshot(self, pending, *args, **kwargs):
        if inside:
            watch.snapshots.append([p.uid for p in pending])
        return real_snapshot(self, pending, *args, **kwargs)

    def prepare(self, meta, cluster=None):
        if inside:
            watch.prepares.append(meta)
        return real_prepare(self, meta, cluster)

    with monkeypatch.context() as patch:
        patch.setattr(cycle_mod, "_run_preemption", run)
        patch.setattr(Cluster, "snapshot", snapshot)
        patch.setattr(Scheduler, "prepare", prepare)
        yield watch


def store_view(cluster):
    return {
        uid: (pod.node_name, pod.nominated_node_name, pod.terminating)
        for uid, pod in cluster.pods.items()
    }


def report_view(report):
    return {
        "bound": dict(report.bound),
        "reserved": dict(report.reserved),
        "failed": list(report.failed),
        "failed_by": dict(report.failed_by),
        "rejected_gangs": list(report.rejected_gangs),
        "preempted": dict(report.preempted),
    }


def counters():
    return (
        obs.metrics.get(obs.PREEMPTION_ATTEMPTS),
        obs.metrics.get(obs.PREEMPTION_VICTIMS),
    )


# -- the six cases -----------------------------------------------------------


def mkpod(name, cpu=3000, priority=0, node=None, gang=None, created=0):
    pod = Pod(
        name=name, priority=priority, creation_ms=created,
        labels={POD_GROUP_LABEL: gang} if gang else {},
        containers=[Container(requests={CPU: cpu, MEMORY: gib})],
    )
    pod.node_name = node
    return pod


def add_gang(cluster, name, members, min_member, priority=10, created=5):
    cluster.add_pod_group(PodGroup(
        name=name, min_member=min_member, creation_ms=created,
    ))
    for m in range(members):
        cluster.add_pod(mkpod(
            f"{name}-{m}", priority=priority, gang=name, created=created,
        ))


def searching_scheduler():
    return Scheduler(Profile(
        plugins=[NodeResourcesAllocatable(), Coscheduling()],
        preemption=PreemptionEngine(PreemptionMode.DEFAULT),
    ))


def all_in_a_rejected_gang():
    # one member of four fits: the gang is rejected whole
    cluster = Cluster()
    cluster.add_node(mknode("n0"))
    add_gang(cluster, "g", members=4, min_member=4)
    return searching_scheduler(), cluster, [1000]


def rejected_gang_and_a_plain_preemptor():
    cluster = Cluster()
    cluster.add_node(mknode("n0"))
    cluster.add_pod(mkpod("low", priority=1, node="n0"))
    cluster.add_pod(mkpod("high", priority=10, created=1))
    add_gang(cluster, "g", members=4, min_member=4)
    return searching_scheduler(), cluster, [1000]


def gang_short_by_a_tolerated_gap():
    # nine of ten members fit and wait: (10 - 9) / 10 is within the 10 %
    # rejectPercentage, the gang stays, its tenth member is searched for
    cluster = Cluster()
    for i in range(10):
        cluster.add_node(mknode(f"n{i}"))
    cluster.add_pod(mkpod("low", priority=1, node="n9"))
    add_gang(cluster, "g", members=10, min_member=10)
    return searching_scheduler(), cluster, [1000]


def nomination_held_from_an_earlier_cycle():
    # cycle 1: `high` nominates n0 and `low` terminates; cycle 2: `high`
    # fails again while its victim is still terminating (GATED: the hold
    # stays), `later` may not take what it holds, and a gang is rejected
    cluster = Cluster()
    cluster.add_node(mknode("n0"))
    cluster.add_node(mknode("n1", cpu=1000))
    cluster.add_pod(mkpod("low", priority=1, node="n0"))
    cluster.add_pod(mkpod("high", priority=10, created=1))

    def second_cycle(cluster):
        cluster.add_pod(mkpod("later", priority=5, created=1500))
        add_gang(cluster, "g", members=4, min_member=4, created=1600)

    return searching_scheduler(), cluster, [1000, second_cycle, 2000]


def nothing_failed():
    cluster = Cluster()
    cluster.add_node(mknode("n0"))
    cluster.add_pod(mkpod("fits"))
    return searching_scheduler(), cluster, [1000]


def no_engine():
    cluster = Cluster()
    cluster.add_node(mknode("n0"))
    cluster.add_pod(mkpod("low", priority=1, node="n0"))
    cluster.add_pod(mkpod("high", priority=10, created=1))
    sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    return sched, cluster, [1000]


def play(build, pipelined=False):
    """The case's cycles, through `run_cycle` or the pipelined engine
    (which reaches the stage through the same `_cycle_postbind`): the
    report and the store after each."""
    sched, cluster, steps = build()
    pipe = None
    if pipelined:
        pipe = PipelinedCycle(sched, cluster, async_bind=False)
    for step in steps:
        if callable(step):
            step(cluster)
        elif pipe is None:
            yield run_cycle(sched, cluster, now=step), cluster
        else:
            report = pipe.tick(now=step)
            pipe.flush()
            yield report, cluster
    if pipe is not None:
        pipe.close()


def drive(build, monkeypatch, search, pipelined=False):
    """The case's cycles under `search`: the views after each cycle, the
    counters' deltas and what the watch saw."""
    before = counters()
    with watched(monkeypatch, search) as watch:
        views = [
            (report_view(report), store_view(cluster))
            for report, cluster in play(build, pipelined)
        ]
    deltas = tuple(b - a for a, b in zip(before, counters()))
    return views, deltas, watch


#: case -> (returned by each cycle's PostFilter, failed pods of each of
#: its snapshots)
CASES = {
    "a-all-in-a-rejected-gang": (all_in_a_rejected_gang, [0], []),
    "b-rejected-gang-and-a-plain-preemptor": (
        rejected_gang_and_a_plain_preemptor, [1], [5],
    ),
    "c-gang-short-by-a-tolerated-gap": (
        gang_short_by_a_tolerated_gap, [1], [1],
    ),
    "d-nomination-held-from-an-earlier-cycle": (
        nomination_held_from_an_earlier_cycle, [1, 2], [1, 6],
    ),
    "e-nothing-failed": (nothing_failed, [0], []),
    "f-no-engine": (no_engine, [0], []),
}


@pytest.mark.parametrize(
    "pipelined", [False, True], ids=["serial", "pipelined"]
)
@pytest.mark.parametrize("case", sorted(CASES))
def test_new_order_equals_the_old_one(case, pipelined, monkeypatch):
    build, returned, snapshot_sizes = CASES[case]
    new_views, new_deltas, new = drive(
        build, monkeypatch, cycle_mod._run_preemption, pipelined
    )
    old_views, old_deltas, old = drive(
        build, monkeypatch, old_order, pipelined
    )

    assert new_views == old_views
    assert new_deltas == old_deltas
    assert new.returned == old.returned == returned
    # the post-bind snapshot: once in a cycle that searches, over every
    # failed pod (not the candidates alone), and not at all otherwise
    assert [len(uids) for uids in new.snapshots] == snapshot_sizes
    assert len(new.prepares) == len(snapshot_sizes)
    assert new.snapshots == [
        view["failed"] for (view, _), n in zip(new_views, returned) if n
    ]


def test_the_cases_are_the_cases_they_say():
    """What each case is there for did happen: a rejection in (a), (b)
    and (d), a victim in (b), (c) and (d), a kept gang in (c), a gate and
    a refused thief in (d)."""
    def last(build):
        return list(play(build))[-1]

    report, cluster = last(all_in_a_rejected_gang)
    assert report.rejected_gangs == ["default/g"] and not report.preempted
    assert len(report.failed) >= 3 and not cluster.reserved

    report, cluster = last(rejected_gang_and_a_plain_preemptor)
    assert report.rejected_gangs == ["default/g"]
    assert report.preempted == {"default/high": ("n0", ["default/low"])}
    assert cluster.pods["default/low"].terminating

    report, cluster = last(gang_short_by_a_tolerated_gap)
    assert not report.rejected_gangs and len(report.reserved) == 9
    (preemptor, (node, victims)), = report.preempted.items()
    assert preemptor.startswith("default/g-")
    assert (node, victims) == ("n9", ["default/low"])

    report, cluster = last(nomination_held_from_an_earlier_cycle)
    assert report.rejected_gangs == ["default/g"]
    assert cluster.pods["default/high"].nominated_node_name == "n0"
    assert cluster.pods["default/low"].terminating
    assert "default/later" in report.failed and not report.preempted

    report, _ = last(nothing_failed)
    assert not report.failed
    report, _ = last(no_engine)
    assert report.failed == ["default/high"] and not report.preempted


# -- the span, and what stays bound ------------------------------------------


def postfilter_spans(build):
    obs.tracer.start()
    try:
        list(play(build))
        events = obs.tracer.export()["traceEvents"]
    finally:
        obs.tracer.stop()
    spans = [e for e in events if e.get("ph") == "X"]
    stages = [e for e in spans if e["name"].startswith("PostFilter/")]
    inner = [
        [
            e["name"] for e in spans
            if e["name"].startswith("Snapshot")
            and stage["ts"] <= e["ts"] <= stage["ts"] + stage["dur"]
        ]
        for stage in stages
    ]
    return stages, inner


def test_no_snapshot_span_opens_under_a_stage_that_searches_for_nobody():
    (stage,), (inner,) = postfilter_spans(all_in_a_rejected_gang)
    assert stage["name"] == "PostFilter/PreemptionEngine"
    assert stage["args"]["failed"] >= 3
    assert stage["args"]["candidates"] == 0
    assert stage["args"]["searched"] is False
    assert stage["args"]["victims"] == 0
    assert inner == []


def test_the_span_says_when_the_stage_searched():
    (stage,), (inner,) = postfilter_spans(rejected_gang_and_a_plain_preemptor)
    assert stage["args"]["candidates"] == 1
    assert stage["args"]["searched"] is True
    assert stage["args"]["victims"] == 1
    assert "Snapshot/gangs" in inner

    (stage,), (inner,) = postfilter_spans(no_engine)
    assert stage["name"] == "PostFilter/none"
    assert stage["args"]["searched"] is False and inner == []


def test_plugins_stay_bound_to_the_cycles_own_meta(monkeypatch):
    """The search's `scheduler.prepare` rebinds the shared plugins to the
    post-bind snapshot's meta until the next cycle's own prepare; a stage
    that searches for nobody leaves them on the cycle's. Nothing between
    the two reads them on a cycle's path (`Finalize` reads arrays, the
    recorder's commit the report, `report.explain` the aux frozen before
    the stage); a caller that solves on the cycle's snapshot afterwards
    without preparing does, and now reads the meta it solves on."""
    prepared = []
    real_prepare = Scheduler.prepare

    def prepare(self, meta, cluster=None):
        prepared.append(meta)
        return real_prepare(self, meta, cluster)

    monkeypatch.setattr(Scheduler, "prepare", prepare)

    sched, cluster, (now,) = all_in_a_rejected_gang()
    report = run_cycle(sched, cluster, now=now)
    assert report.rejected_gangs and len(prepared) == 1
    assert len(prepared[0].pod_names) == 4  # the cycle's batch

    # where somebody is searched for, the last prepare is the search's:
    # its meta lists the failed pods, not the cycle's batch
    del prepared[:]
    sched, cluster, (now,) = rejected_gang_and_a_plain_preemptor()
    report = run_cycle(sched, cluster, now=now)
    assert len(prepared) == 2
    assert list(prepared[-1].pod_names) == report.failed
    assert len(prepared[0].pod_names) == 5


# -- the served path: a held over-quota gang, five cycles --------------------


def served_run(monkeypatch, search):
    """`test_serving_axis`'s GPU roster under quotas, served from resident
    state: team-b's quota admits four GPUs of a gang of six, so the gang
    is rejected whole every time its back-off lets it try, while plain
    pods arrive and bind."""
    cluster = gpu_cluster()
    engine = ServeEngine().attach(cluster)
    sched = gang_scheduler()
    cluster.add_pod_group(PodGroup(
        name="held", namespace="team-b", min_member=6, creation_ms=50,
    ))
    for m in range(6):
        cluster.add_pod(gpu_pod(f"held-{m}", "team-b", 50, gang="held"))
    views = []
    before = counters()
    with watched(monkeypatch, search) as watch:
        for c in range(5):
            now = 1000 + 20_000 * c
            for i in range(2):
                cluster.add_pod(gpu_pod(f"solo-{c}-{i}", "team-a", now - 10))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            views.append((
                report_view(report), store_view(cluster),
                dict(cluster.reserved),
            ))
    assert engine.gang_fallbacks == 0 and engine.rebases == 1
    assert engine.refresh(cluster, [], now_ms=200_000) is not None
    assert engine.verify(cluster) is None
    deltas = tuple(b - a for a, b in zip(before, counters()))
    return views, deltas, watch


def test_served_cycles_equal_the_old_order_over_five_cycles(monkeypatch):
    new_views, new_deltas, new = served_run(
        monkeypatch, cycle_mod._run_preemption
    )
    old_views, old_deltas, old = served_run(monkeypatch, old_order)
    assert new_views == old_views
    assert new_deltas == old_deltas == (0, 0)
    assert new.returned == old.returned == [0] * 5
    # the gang was on the path and rejected, and only the old order paid
    tried = [v[0]["rejected_gangs"] for v in new_views]
    assert tried.count(["team-b/held"]) >= 2
    assert len(old.snapshots) == tried.count(["team-b/held"])
    assert new.snapshots == [] and new.prepares == []
    assert all(len(v[0]["bound"]) == 2 for v in new_views)
