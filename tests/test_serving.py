"""Resident-state serving engine: delta-equivalence differential + edge paths.

The engine's contract (serving/engine.py): serve mode changes WHERE the
solver input comes from — device-resident node columns maintained by
O(changed) scatter deltas — never what the solver decides. These tests
drive randomized event sequences through the delta path and assert
bit-identical NodeState tensors against a fresh full re-snapshot, and
identical placements against a full-resnapshot baseline run; the edge
tests cover every transition in the docs/SERVING.md classification (grow,
re-base reasons, compatibility fallback and resumption).
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api import events as ev
from scheduler_plugins_tpu.api.objects import (
    REGION_LABEL,
    ZONE_LABEL,
    Container,
    ElasticQuota,
    Node,
    Pod,
    SeccompProfile,
    Taint,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.framework.plugin import BUILTIN_EVENTS
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

gib = 1 << 30

#: every column of the resident NodeState — compared bit-exact
NODE_COLUMNS = (
    "alloc", "capacity", "requested", "nonzero_requested", "limits",
    "mask", "region", "zone", "pod_count", "terminating", "nominated",
)

EXT = "example.com/gpu"


def make_node(i, cpu=8000, unschedulable=False, extra=None):
    alloc = {CPU: cpu, MEMORY: 32 * gib, PODS: 32}
    if extra:
        alloc.update(extra)
    return Node(
        name=f"n{i:03d}",
        allocatable=alloc,
        labels={REGION_LABEL: "r1", ZONE_LABEL: f"z{i % 2}"},
        unschedulable=unschedulable,
    )


def make_cluster(n_nodes=6):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(make_node(i))
    return cluster


def make_pod(serial, now, cpu=500, mem=gib):
    return Pod(
        name=f"p{serial:05d}",
        creation_ms=now + serial,
        containers=[Container(requests={CPU: cpu, MEMORY: mem})],
    )


def make_scheduler():
    return Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))


def trim_pads(snap, meta):
    """`snap` with its gang and quota arrays cut to the rows that PodGroups
    and namespaces hold: the arrays as they were sized before they landed
    on buckets (`G = max(len(gang_pos), 1)`, `Q = max(len(namespaces),
    1)`). A solve of it is the solve without the inert rows."""
    import jax

    out = snap
    if snap.gangs is not None:
        g = max(len(meta.gang_names), 1)
        out = out.replace(gangs=jax.tree.map(lambda a: a[:g], snap.gangs))
    if snap.quota is not None:
        q = max(len(meta.namespaces), 1)
        quota = snap.quota
        out = out.replace(quota=quota.replace(
            min=quota.min[:q], max=quota.max[:q], used=quota.used[:q],
            has_quota=quota.has_quota[:q],
        ))
    return out


def solve_exact_axes(cluster):
    """Make `cluster.snapshot` return gang and quota arrays of exact size,
    so that a cycle on it solves what the program solved before the
    arrays were padded: the twin a padded solve has to equal."""
    padded = cluster.snapshot

    def snapshot(pending, now_ms=0, **kwargs):
        snap, meta = padded(pending, now_ms=now_ms, **kwargs)
        return trim_pads(snap, meta), meta

    cluster.snapshot = snapshot


def assert_resident_matches(engine, cluster, now):
    """Drain the sink (deltas from the cycle's own binds apply at the next
    refresh), then compare the delta-maintained resident columns against a
    fresh full re-snapshot of the same store, bit-exact."""
    refreshed = engine.refresh(cluster, [], now_ms=now)
    assert refreshed is not None, "engine fell back while compatible"
    snap, _ = cluster.snapshot([], now_ms=now, pad_nodes=engine.npad)
    for col in NODE_COLUMNS:
        np.testing.assert_array_equal(
            np.asarray(getattr(engine.resident_nodes, col)),
            np.asarray(getattr(snap.nodes, col)),
            err_msg=f"resident column {col} diverged from fresh snapshot",
        )


class TestDeltaEquivalence:
    """The satellite differential: N randomized event sequences through
    the delta path vs a full re-snapshot every cycle."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_randomized_event_sequences(self, seed):
        rng = np.random.default_rng(seed)
        serve_cluster = make_cluster(6)
        engine = ServeEngine().attach(serve_cluster)
        base_cluster = make_cluster(6)
        serve_sched, base_sched = make_scheduler(), make_scheduler()

        serial = 0
        extra_nodes = 0
        for cycle in range(10):
            now = 1000 * (cycle + 1)
            # one cycle's event batch, resolved against the serve cluster
            # and replayed verbatim on the baseline (identical placements
            # each cycle keep the two stores identical)
            events = []
            for _ in range(int(rng.integers(0, 5))):
                serial += 1
                events.append((
                    "arrive", serial,
                    int(rng.integers(100, 3000)),
                    int(rng.integers(1, 4)) * gib,
                ))
            if rng.random() < 0.3:
                serial += 1
                # pre-bound arrival (feed-replay shape): lands directly in
                # the usage columns without a solve
                events.append((
                    "arrive_bound", serial, int(rng.integers(100, 1000)),
                    gib, f"n{int(rng.integers(0, 6)):03d}",
                ))
            bound = sorted(
                uid for uid, p in serve_cluster.pods.items()
                if p.node_name is not None
            )
            for _ in range(int(rng.integers(0, 3))):
                if not bound:
                    break
                uid = bound.pop(int(rng.integers(0, len(bound))))
                events.append(
                    ("terminate", uid) if rng.random() < 0.3
                    else ("depart", uid)
                )
            if rng.random() < 0.25:
                extra_nodes += 1
                events.append(("node_add", 100 + extra_nodes))
            if rng.random() < 0.2:
                # row overwrite of an existing node (mask flip)
                events.append((
                    "node_update", int(rng.integers(0, 6)),
                    bool(rng.random() < 0.5),
                ))

            for cl in (serve_cluster, base_cluster):
                for e in events:
                    if e[0] == "arrive":
                        cl.add_pod(make_pod(e[1], now, e[2], e[3]))
                    elif e[0] == "arrive_bound":
                        pod = make_pod(e[1], now, e[2], e[3])
                        pod.node_name = e[4]
                        cl.add_pod(pod)
                    elif e[0] == "depart":
                        cl.remove_pod(e[1])
                    elif e[0] == "terminate":
                        cl.mark_terminating(e[1], now)
                    elif e[0] == "node_add":
                        cl.add_node(make_node(e[1]))
                    elif e[0] == "node_update":
                        cl.add_node(make_node(e[1], unschedulable=e[2]))

            serve_report = run_cycle(
                serve_sched, serve_cluster, now=now, serve=engine
            )
            base_report = run_cycle(base_sched, base_cluster, now=now)
            assert serve_report.bound == base_report.bound
            assert serve_report.failed == base_report.failed
            assert_resident_matches(engine, serve_cluster, now)

    def test_steady_state_is_delta_applied_not_rebased(self):
        """After the initial rebase, pure pod churn must never re-base —
        the whole point of the O(changed) path."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(99, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        rebases0 = obs.metrics.get(obs.SERVE_REBASES)
        gen0 = engine.generation
        for cycle in range(5):
            now = 2000 + 1000 * cycle
            cluster.add_pod(make_pod(cycle + 1, now))
            run_cycle(sched, cluster, now=now, serve=engine)
        assert obs.metrics.get(obs.SERVE_REBASES) == rebases0
        assert engine.generation > gen0  # deltas actually applied
        assert_resident_matches(engine, cluster, now)


class TestServeEdgePaths:
    def test_grow_across_padding_bucket(self):
        """Node adds past the padded capacity grow the resident columns
        in place (usage history preserved, no rebase)."""
        cluster = make_cluster(7)  # bucket 8
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.npad == 8
        rebases0 = obs.metrics.get(obs.SERVE_REBASES)
        for i in range(7, 12):  # 12 nodes -> bucket 16
            cluster.add_node(make_node(i))
        cluster.add_pod(make_pod(2, 1500))
        run_cycle(sched, cluster, now=2000, serve=engine)
        assert engine.npad == 16
        assert obs.metrics.get(obs.SERVE_REBASES) == rebases0
        assert_resident_matches(engine, cluster, 2500)

    def test_node_delete_rebases(self):
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        rebases0 = obs.metrics.get(obs.SERVE_REBASES)
        victim = next(iter(cluster.nodes))
        for uid in [
            u for u, p in cluster.pods.items() if p.node_name == victim
        ]:
            cluster.remove_pod(uid)
        cluster.remove_node(victim)
        cluster.add_pod(make_pod(2, 1500))
        report = run_cycle(sched, cluster, now=2000, serve=engine)
        assert report.bound  # still placing
        assert obs.metrics.get(obs.SERVE_REBASES) == rebases0 + 1
        assert_resident_matches(engine, cluster, 2500)

    def test_label_change_rebases(self):
        """Region/zone re-labeling cannot be expressed as a row overwrite
        (codes are first-seen interned) — must re-base, then match."""
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        rebases0 = obs.metrics.get(obs.SERVE_REBASES)
        relabeled = make_node(1)
        relabeled.labels = {REGION_LABEL: "r9", ZONE_LABEL: "z9"}
        cluster.add_node(relabeled)
        cluster.add_pod(make_pod(2, 1500))
        run_cycle(sched, cluster, now=2000, serve=engine)
        assert obs.metrics.get(obs.SERVE_REBASES) == rebases0 + 1
        assert_resident_matches(engine, cluster, 2500)

    def test_extended_resource_node_rebases_once_then_serves(self):
        """A node naming a resource outside the axis triggers ONE rebase
        that widens it; the engine owns the wider state from then on
        (placements equal a fresh-snapshot twin's, no further rebase)."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(90, 500))
        base = make_cluster(4)
        base.add_pod(make_pod(90, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        base_sched = make_scheduler()
        run_cycle(base_sched, base, now=1000)
        assert engine.resident_nodes is not None
        assert EXT not in engine.index
        rebases0 = engine.rebases
        axis0 = obs.metrics.get(obs.SERVE_AXIS_REBASES)
        cluster.add_node(make_node(50, extra={EXT: 4}))
        cluster.add_pod(make_pod(1, 1500))
        base.add_node(make_node(50, extra={EXT: 4}))
        base.add_pod(make_pod(1, 1500))
        serve_report = run_cycle(sched, cluster, now=2000, serve=engine)
        base_report = run_cycle(base_sched, base, now=2000)
        assert serve_report.bound == base_report.bound
        assert serve_report.bound
        assert engine.resident_nodes is not None  # owned, on a wider axis
        assert engine.index.names[-1] == EXT
        assert engine.rebases == rebases0 + 1
        assert obs.metrics.get(obs.SERVE_AXIS_REBASES) == axis0 + 1
        assert_resident_matches(engine, cluster, 2500)
        # served from then on: more churn, no further rebase
        cluster.add_pod(make_pod(2, 2500))
        base.add_pod(make_pod(2, 2500))
        assert (
            run_cycle(sched, cluster, now=3000, serve=engine).bound
            == run_cycle(base_sched, base, now=3000).bound
        )
        assert engine.rebases == rebases0 + 1
        assert_resident_matches(engine, cluster, 3500)
        assert engine.verify(cluster) is None

    def test_extended_resource_pending_pod_rebases_once_then_serves(self):
        """A pending pod naming a new resource is no reason to fall back:
        the refresh that meets it rebases, the axis holds the name after."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(90, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        rebases0 = engine.rebases
        pod = Pod(
            name="gpu-pod", creation_ms=1500,
            containers=[Container(requests={CPU: 100, EXT: 1})],
        )
        cluster.add_pod(pod)
        pending = cluster.pending_pods()
        assert engine.compatible(cluster, pending)
        assert engine._outside_axis(cluster, pending)
        report = run_cycle(sched, cluster, now=2000, serve=engine)
        assert pod.uid in report.failed  # no node declares the resource
        assert engine.rebases == rebases0 + 1
        assert EXT in engine.index
        assert not engine._outside_axis(cluster, cluster.pending_pods())
        cluster.remove_pod(pod.uid)
        # the axis never narrows: a fresh snapshot told to hold the name
        # is what the resident columns equal
        assert engine.refresh(cluster, [], now_ms=2500) is not None
        snap, _ = cluster.snapshot(
            [], now_ms=2500, pad_nodes=engine.npad, extra_resources=(EXT,)
        )
        for col in NODE_COLUMNS:
            np.testing.assert_array_equal(
                np.asarray(getattr(engine.resident_nodes, col)),
                np.asarray(getattr(snap.nodes, col)), err_msg=col,
            )
        assert engine.verify(cluster) is None
        assert engine.rebases == rebases0 + 1

    def test_side_table_fallback_absorbs_deltas(self):
        """While a still-gating side table (a seccomp profile) disqualifies
        serve mode, the cycle falls back to full snapshots but the
        resident columns keep absorbing deltas — serving resumes WITHOUT
        a rebase. (Gang/quota rosters no longer gate — ISSUE 12's
        resident side tables own them, see TestResidentGangQuota; nor
        does the load watcher's report since ISSUE 29, see
        TestResidentMetrics.)"""
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(99, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.resident_nodes is not None
        rebases0 = obs.metrics.get(obs.SERVE_REBASES)
        cluster.add_seccomp_profile(
            SeccompProfile(name="sp", syscalls=frozenset({"read"}))
        )
        assert not engine.compatible(cluster, [])
        for cycle in range(3):
            now = 2000 + 1000 * cycle
            cluster.add_pod(make_pod(cycle + 1, now))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            assert report.bound  # fallback cycles still place
        cluster.seccomp_profiles.clear()
        assert obs.metrics.get(obs.SERVE_REBASES) == rebases0
        assert_resident_matches(engine, cluster, 9000)

    def test_tainted_node_delete_resumes_serving(self):
        """Deleting the only tainted node must clear its compat entry —
        serving resumes instead of pinning fallback forever."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        tainted = make_node(60)
        tainted.taints = [Taint(key="k", value="v")]
        cluster.add_node(tainted)
        # refresh classifies the upsert (tracking the taint) before the
        # gate — the tainted roster falls back to full snapshots
        assert engine.refresh(cluster, [], now_ms=2000) is None
        assert not engine.compatible(cluster, [])
        cluster.remove_node("n060")
        run_cycle(sched, cluster, now=3000, serve=engine)
        assert_resident_matches(engine, cluster, 3500)

    def test_terminating_flip_in_same_drain_window_counts_once(self):
        """Regression: a pod bound in cycle K whose terminating flip lands
        BEFORE cycle K+1's refresh drains the bind event. The flip mutates
        the pod in place AND queues its own +1 delta — the assign row must
        carry the event-time flag (False), not a drain-time re-read, or
        the resident terminating column double-counts until a rebase."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        report = run_cycle(sched, cluster, now=1000, serve=engine)
        (uid,) = report.bound
        # the bind's POD_ASSIGN is still queued; flip terminating now
        cluster.mark_terminating(uid, 1500)
        assert_resident_matches(engine, cluster, 2000)

    def test_reserved_pod_terminating_counts_at_reserved_node(self):
        """Regression: a reserved (permit-held) pod marked terminating —
        e.g. picked as a preemption victim — counts at its RESERVED node
        in the snapshot's assigned view. The delta must fire for the
        held node (binding OR reservation), or the later release
        subtracts a terminating count that was never added and the
        resident column goes permanently negative."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        held = make_pod(2, 600)
        cluster.add_pod(held)
        cluster.reserve(held.uid, "n001")
        cluster.mark_terminating(held.uid, 1500)
        assert_resident_matches(engine, cluster, 2000)
        cluster.release_reservation(held.uid)
        assert_resident_matches(engine, cluster, 3000)

    def test_gated_nominated_pod_falls_back(self):
        """A scheduling-gated pod carrying a NominatedNodeName never
        enters the pending batch, but the full snapshot counts it into
        the nominated column — the sink's sticky tracking must gate."""
        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        run_cycle(sched, cluster, now=1000, serve=engine)
        pod = make_pod(1, 1500)
        pod.scheduling_gated = True
        pod.nominated_node_name = "n000"
        cluster.add_pod(pod)
        assert not engine.compatible(cluster, [])
        cluster.remove_pod(pod.uid)
        assert engine.compatible(cluster, [])
        assert_resident_matches(engine, cluster, 2000)


class TestSinkLifecycle:
    def test_detach_uninstalls_sink(self):
        cluster = make_cluster(3)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        engine.detach()
        assert cluster.delta_sink is None
        assert engine.resident_nodes is None
        # mutators no longer append anywhere
        cluster.add_pod(make_pod(2, 600))
        run_cycle(sched, cluster, now=2000)
        assert engine._sink.events == []

    def test_sink_overflow_forces_rebase_not_corruption(self):
        """An undrained sink past MAX_EVENTS collapses; the next refresh
        must re-base (the surviving window is partial) and still match a
        fresh snapshot bit-exact."""
        from scheduler_plugins_tpu.serving.deltas import DeltaSink

        cluster = make_cluster(3)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        cluster.add_pod(make_pod(1, 500))
        run_cycle(sched, cluster, now=1000, serve=engine)
        rebases0 = engine.rebases
        old_max = DeltaSink.MAX_EVENTS
        DeltaSink.MAX_EVENTS = 4
        try:
            for s in range(2, 9):  # bound arrivals: 7 usage events > cap
                pod = make_pod(s, 1500)
                pod.node_name = "n000"
                cluster.add_pod(pod)
            assert engine._sink.overflowed
        finally:
            DeltaSink.MAX_EVENTS = old_max
        cluster.add_pod(make_pod(50, 1800))  # pending: the cycle refreshes
        run_cycle(sched, cluster, now=2000, serve=engine)
        assert engine.rebases == rebases0 + 1
        assert_resident_matches(engine, cluster, 3000)


class TestEventKindTable:
    """Satellite: the `api.events` table is THE one copy of the kind
    strings — every registration must name a kind the store can emit."""

    def test_builtin_events_are_known(self):
        assert set(BUILTIN_EVENTS) <= ev.EVENT_KINDS

    def test_plugin_registrations_are_known(self):
        from scheduler_plugins_tpu import plugins as P

        checked = 0
        for name in dir(P):
            cls = getattr(P, name)
            if not (isinstance(cls, type) and hasattr(
                    cls, "events_to_register")):
                continue
            try:
                plugin = cls()
            except TypeError:
                continue
            kinds = set(plugin.events_to_register())
            assert kinds <= ev.EVENT_KINDS, name
            checked += 1
        assert checked >= 8  # the mixed roster's worth of plugins

    def test_kind_format(self):
        for kind in ev.EVENT_KINDS:
            resource, _, action = kind.partition("/")
            assert resource and action in {"Add", "Update", "Delete"}, kind

    def test_serve_classification_is_within_the_table(self):
        assert ev.NODE_COLUMN_EVENTS <= ev.EVENT_KINDS
        assert ev.SERVE_REBASE_EVENTS <= ev.EVENT_KINDS


class TestServeFlightRecorder:
    """Satellite: serve-mode cycles are replayable artifacts — the
    assembled snapshot is captured in full (standard replay path) and the
    record additionally carries the serve provenance: resident
    generation, staleness, the base snapshot digest, and the packed
    delta stream that produced this cycle's solver input."""

    def test_serve_cycles_record_replayably(self, tmp_path):
        from scheduler_plugins_tpu.utils import flightrec

        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        flightrec.recorder.start(capacity=4)
        try:
            cluster.add_pod(make_pod(1, 500))
            r1 = run_cycle(sched, cluster, now=1000, serve=engine)
            cluster.add_pod(make_pod(2, 1500))
            r2 = run_cycle(sched, cluster, now=2000, serve=engine)
            recs = flightrec.recorder.records()
            assert [r.manifest["serve"]["mode"] for r in recs] == [
                "rebase", "delta",
            ]
            assert recs[0].manifest["serve"]["base_digest"]
            delta_blk = recs[1].manifest["serve"]
            assert delta_blk["events"] > 0
            assert "deltas" in delta_blk  # the packed scatter batch
            assert delta_blk["generation"] == engine.generation
            summary = flightrec.recorder.save(str(tmp_path))
            assert summary["cycles"] == 2
        finally:
            flightrec.recorder.stop()
        cycles = flightrec.load_bundle(str(tmp_path))
        assert len(cycles) == 2
        for cyc, report in zip(cycles, (r1, r2)):
            assert cyc.digest_ok()
            out = flightrec.replay_cycle(cyc)
            assert out["placements_match"], out.get("mismatches")
            assert out["placed_replayed"] == len(report.bound)
        # the delta stream round-trips: unpacked arrays match the packed
        # usage batch shape (idx + 3 usage vectors + 2 counters)
        spec = cycles[1].manifest["serve"]["deltas"]
        deltas = flightrec.unpack_pytree(spec, cycles[1]._blobs_for(spec))
        assert set(deltas) == {"upserts", "usage"}
        assert deltas["usage"]["idx"].ndim == 1


class TestResidentGangQuota:
    """ISSUE 12: gang/quota rosters serve RESIDENT. Randomized event
    streams (gang arrivals with gated members, quota-scoped churn,
    elastic member deletes) must keep (a) serve-vs-baseline placements
    identical cycle for cycle, (b) the engine-assembled GangState/
    QuotaState tensors BIT-EQUAL to a fresh `cluster.snapshot`'s, and
    (c) the engine off the fallback path entirely (zero gang
    fallbacks)."""

    @staticmethod
    def _gang_quota_cluster():
        from scheduler_plugins_tpu.api.objects import ElasticQuota

        cluster = make_cluster(6)
        cluster.add_quota(ElasticQuota(
            name="eq", namespace="team",
            min={CPU: 24_000, MEMORY: 96 * gib},
            max={CPU: 48_000, MEMORY: 160 * gib},
        ))
        return cluster

    @staticmethod
    def _gang_sched():
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling,
            Coscheduling,
        )

        return Scheduler(Profile(plugins=[
            NodeResourcesAllocatable(),
            Coscheduling(permit_waiting_seconds=5),
            CapacityScheduling(),
        ]))

    def _assert_side_tables_match(self, engine, cluster, now):
        """Engine-assembled snapshot vs a fresh one: every gang/quota
        tensor bit-equal (the namespace-interning tail rows are
        all-default, so tensor equality is exact, not just semantic)."""
        import dataclasses

        pend = cluster.pending_pods()
        refreshed = engine.refresh(cluster, pend, now_ms=now)
        assert refreshed is not None, "gang/quota roster fell back"
        snap, meta = refreshed
        fsnap, fmeta = cluster.snapshot(
            pend, now_ms=now, pad_nodes=engine.npad
        )
        assert fmeta.gang_names == meta.gang_names
        assert set(fmeta.namespaces) == set(meta.namespaces)
        for fam in ("gangs", "quota"):
            mine, fresh = getattr(snap, fam), getattr(fsnap, fam)
            assert (mine is None) == (fresh is None), fam
            if mine is None:
                continue
            for f in dataclasses.fields(mine):
                got = np.asarray(getattr(mine, f.name))
                want = np.asarray(getattr(fresh, f.name))
                assert got.shape == want.shape, (fam, f.name)
                np.testing.assert_array_equal(
                    got, want, err_msg=f"{fam}.{f.name}"
                )

    @pytest.mark.parametrize("baseline_axes", ["padded", "exact"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_randomized_gang_quota_streams(self, seed, baseline_axes):
        """`baseline_axes` "exact": the fresh-snapshot twin solves gang and
        quota arrays of exact size, as before they landed on buckets; the
        served side's are padded. Equal placements cycle for cycle: the
        padded rows are inert."""
        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            PodGroup,
        )

        rng = np.random.default_rng(100 + seed)
        serve_cluster = self._gang_quota_cluster()
        base_cluster = self._gang_quota_cluster()
        if baseline_axes == "exact":
            solve_exact_axes(base_cluster)
        engine = ServeEngine().attach(serve_cluster)
        s_sched, b_sched = self._gang_sched(), self._gang_sched()

        def team_pod(serial, now, cpu, mem_gib, gang=None, gated=False):
            pod = Pod(
                name=f"tp{serial:04d}", namespace="team",
                creation_ms=now + serial,
                labels={POD_GROUP_LABEL: gang} if gang else {},
                containers=[Container(
                    requests={CPU: cpu, MEMORY: mem_gib * gib}
                )],
            )
            pod.scheduling_gated = gated
            return pod

        serial = 0
        for cycle in range(8):
            now = 1000 * (cycle + 1)
            events = []
            for _ in range(int(rng.integers(0, 4))):
                serial += 1
                events.append(("pod", serial, int(rng.integers(200, 2500)),
                               int(rng.integers(1, 4))))
            if cycle % 3 == 1:
                events.append(("gang", cycle, int(rng.integers(2, 4))))
            if cycle % 4 == 2:
                serial += 1
                events.append(("gated", serial, f"g{cycle - 1}"))
            bound = sorted(
                uid for uid, p in serve_cluster.pods.items()
                if p.node_name is not None
            )
            for _ in range(int(rng.integers(0, 2))):
                if bound:
                    events.append((
                        "del", bound.pop(int(rng.integers(0, len(bound))))
                    ))
            for cl in (serve_cluster, base_cluster):
                for e in events:
                    if e[0] == "pod":
                        cl.add_pod(team_pod(e[1], now, e[2], e[3]))
                    elif e[0] == "gang":
                        gname = f"g{e[1]}"
                        cl.add_pod_group(PodGroup(
                            name=gname, namespace="team",
                            min_member=e[2], creation_ms=now,
                        ))
                        for m in range(e[2] + 1):
                            cl.add_pod(Pod(
                                name=f"{gname}-m{m}", namespace="team",
                                creation_ms=now + m,
                                labels={POD_GROUP_LABEL: gname},
                                containers=[Container(requests={
                                    CPU: 1200, MEMORY: 2 * gib,
                                })],
                            ))
                    elif e[0] == "gated":
                        cl.add_pod(team_pod(
                            e[1], now, 500, 1, gang=e[2], gated=True
                        ))
                    elif e[0] == "del":
                        cl.remove_pod(e[1])
            serve_report = run_cycle(
                s_sched, serve_cluster, now=now, serve=engine
            )
            base_report = run_cycle(b_sched, base_cluster, now=now)
            assert serve_report.bound == base_report.bound
            assert serve_report.failed == base_report.failed
            assert serve_report.reserved == base_report.reserved
            assert serve_report.rejected_gangs == base_report.rejected_gangs
            self._assert_side_tables_match(engine, serve_cluster, now + 500)
        assert engine.gang_fallbacks == 0
        assert_resident_matches(engine, serve_cluster, 20_000)

    def test_side_table_anti_entropy_detects_dropped_gang_delta(self):
        """A gang delta that never reaches the side tables (simulated
        in-place corruption) must be caught by the side-table verify and
        healed by the rebase it forces — the node-column anti-entropy
        discipline, extended to the gang/quota aggregates."""
        import jax.numpy as jnp

        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            PodGroup,
        )

        cluster = self._gang_quota_cluster()
        engine = ServeEngine().attach(cluster)
        sched = self._gang_sched()
        cluster.add_pod_group(PodGroup(
            name="g0", namespace="team", min_member=2, creation_ms=100,
        ))
        for m in range(3):
            cluster.add_pod(Pod(
                name=f"g0-m{m}", namespace="team", creation_ms=100 + m,
                labels={POD_GROUP_LABEL: "g0"},
                containers=[Container(
                    requests={CPU: 1000, MEMORY: 2 * gib}
                )],
            ))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.refresh(cluster, [], now_ms=1500) is not None
        # corrupt the resident gang-assigned counter in place
        engine._side = engine._side.replace(
            gang_assigned=engine._side.gang_assigned.at[0].add(jnp.int32(1))
        )
        assert engine._verify_side(cluster) == "side-gang"
        divergences0 = engine.antientropy_divergences
        engine.note_fault("test-side-corruption")
        assert engine.refresh(cluster, [], now_ms=2000) is not None
        assert engine.antientropy_divergences == divergences0 + 1
        # the forced rebase healed the tables
        assert engine._verify_side(cluster) is None
        self._assert_side_tables_match(engine, cluster, 2500)

    def test_reserved_gated_gang_member_counts_both_ways(self):
        """Review regression: a permit-RESERVED gang member that is also
        scheduling-gated counts TWICE in a fresh snapshot — assigned via
        its materialized reserved copy AND gated via the real unbound
        object in `gated_pods()` — and the delta stream mirrors that
        (POD_ASSIGN at reserve + GANG_GATED at upsert). The anti-entropy
        scans must use the same double-count, or a clean resident state
        reads as a spurious 'side-gang' divergence and the post-rebase
        rebuild bakes the undercount into every later GangState."""
        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            PodGroup,
        )

        cluster = self._gang_quota_cluster()
        engine = ServeEngine().attach(cluster)
        sched = self._gang_sched()
        cluster.add_pod_group(PodGroup(
            name="rg", namespace="team", min_member=1, creation_ms=1,
        ))
        cluster.add_pod(Pod(
            name="rg-m0", namespace="team", creation_ms=2,
            labels={POD_GROUP_LABEL: "rg"},
            containers=[Container(requests={CPU: 800, MEMORY: gib})],
        ))
        run_cycle(sched, cluster, now=1000, serve=engine)
        gated = Pod(
            name="rg-held", namespace="team", creation_ms=3,
            labels={POD_GROUP_LABEL: "rg"},
            containers=[Container(requests={CPU: 500, MEMORY: gib})],
        )
        gated.scheduling_gated = True
        cluster.add_pod(gated)          # GANG_GATED +1
        cluster.reserve(gated.uid, "n001")  # POD_ASSIGN (held capacity)
        assert engine.refresh(cluster, [], now_ms=2000) is not None
        # the delta-maintained tables hold assigned=2 (bound member +
        # reserved hold), gated=1 — the scan-based verify must agree
        assert engine._verify_side(cluster) is None, (
            "clean reserved+gated state read as divergence"
        )
        self._assert_side_tables_match(engine, cluster, 2500)

    def test_gang_fallback_metric_decision_table(self):
        """`scheduler_serve_gang_fallbacks_total` decision table: a
        compatible gang roster serves resident (counter unchanged), a
        still-gating side table (NRT) while gangs exist counts one
        fallback per refresh AND exports on the prometheus surface."""
        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            NodeResourceTopology,
            PodGroup,
        )

        cluster = make_cluster(4)
        engine = ServeEngine().attach(cluster)
        sched = make_scheduler()
        counter0 = obs.metrics.get(obs.SERVE_GANG_FALLBACKS) or 0
        cluster.add_pod_group(PodGroup(
            name="pg", namespace="default", min_member=1, creation_ms=1,
        ))
        cluster.add_pod(Pod(
            name="pg-m0", creation_ms=2,
            labels={POD_GROUP_LABEL: "pg"},
            containers=[Container(requests={CPU: 500, MEMORY: gib})],
        ))
        report = run_cycle(sched, cluster, now=1000, serve=engine)
        assert report.bound
        assert engine.gang_fallbacks == 0
        assert (obs.metrics.get(obs.SERVE_GANG_FALLBACKS) or 0) == counter0
        # an NRT gates the engine; with PodGroups present that is a gang
        # fallback, counted and exported
        cluster.add_nrt(NodeResourceTopology(node_name="n000", zones=[]))
        assert engine.refresh(cluster, [], now_ms=2000) is None
        assert engine.gang_fallbacks == 1
        assert obs.metrics.get(obs.SERVE_GANG_FALLBACKS) == counter0 + 1
        text = obs.metrics.prometheus_text()
        assert "scheduler_serve_gang_fallbacks_total" in text
        # without PodGroups the same incompatibility is NOT a gang
        # fallback
        cluster.pod_groups.clear()
        assert engine.refresh(cluster, [], now_ms=3000) is None
        assert engine.gang_fallbacks == 1


# ---------------------------------------------------------------------------
# resident node metrics (ISSUE 29)
# ---------------------------------------------------------------------------


def report_for(names, base=20.0):
    """A load watcher's report naming `names`: cpu and memory averages that
    differ by node, as the feed's `metrics` op would install it."""
    return {
        name: {
            "cpu_avg": base + i, "cpu_std": 1.0 + i,
            "mem_avg": 2 * base + i, "mem_std": 0.5,
        }
        for i, name in enumerate(names)
    }


def bind_new(cluster, serial, node, now, cpu=500):
    pod = make_pod(serial, now, cpu=cpu)
    cluster.add_pod(pod)
    cluster.bind(pod.uid, node, now_ms=now)
    return pod.uid


def assert_metrics_equal_fresh(engine, cluster, now):
    """The engine's `MetricsState` at `now` against the fresh path's at the
    same clock: leaf by leaf, dtype, shape and every value."""
    import dataclasses

    refreshed = engine.refresh(cluster, [], now_ms=now)
    assert refreshed is not None, "engine fell back while compatible"
    mine = refreshed[0].metrics
    fresh = cluster.snapshot([], now_ms=now, pad_nodes=engine.npad)[0].metrics
    if fresh is None:
        assert mine is None
        return
    assert mine is not None
    for leaf in dataclasses.fields(fresh):
        a = np.asarray(getattr(mine, leaf.name))
        b = np.asarray(getattr(fresh, leaf.name))
        assert a.dtype == b.dtype, (leaf.name, now)
        assert a.shape == b.shape, (leaf.name, now)
        np.testing.assert_array_equal(
            a, b, err_msg=f"metrics column {leaf.name} at {now} ms"
        )
    assert engine.verify(cluster) is None


def _case_first_report(c, check):
    check(1000)  # no report yet: no MetricsState on either side
    c.node_metrics = report_for(list(c.nodes))
    check(2000)
    check(3000)


def _case_binds_inside_the_minute(c, check):
    c.node_metrics = report_for(list(c.nodes))
    check(500)
    bind_new(c, 1, "n000", 1000)
    bind_new(c, 2, "n001", 2000, cpu=1500)
    bind_new(c, 3, "n000", 2000)
    check(3000)
    bind_new(c, 4, "n002", 30_000)
    check(30_000)
    check(59_000)


def _case_clock_crosses_sixty_seconds(c, check):
    c.node_metrics = report_for(list(c.nodes))
    bind_new(c, 1, "n000", 1000)
    bind_new(c, 2, "n001", 1001)
    check(1500)
    check(60_999)  # both still unreported
    check(61_000)  # now - ts == 60,000: the first is dropped (>=)
    check(61_001)  # and the second
    check(62_000)


def _case_pod_deleted_inside_the_minute(c, check):
    c.node_metrics = report_for(list(c.nodes))
    uid = bind_new(c, 1, "n000", 1000)
    bind_new(c, 2, "n000", 1000)
    check(2000)
    c.remove_pod(uid)
    check(3000)
    # the uid comes back unbound inside the minute: the fresh path counts
    # its binding again (the entry outlives the pod)
    c.add_pod(make_pod(1, 4000, cpu=700))
    check(5000)


def _case_second_report(c, check):
    names = list(c.nodes)
    c.node_metrics = report_for(names)
    bind_new(c, 1, "n001", 1000)
    check(2000)
    c.node_metrics = {
        # n000 and n002 dropped; n001 gains the two overrides; n003 has a
        # deviation only; n004 says itself what is unreported
        "n001": {"cpu_avg": 30.0, "cpu_tlp": 44.0, "cpu_peaks": 55.0,
                 "mem_avg": 10.0},
        "n003": {"cpu_std": 3.5, "mem_std": 1.25},
        "n004": {"cpu_tlp": 12.0, "missing_cpu_millis": 250},
        "n005": {"cpu_avg": 7.0},
    }
    check(31_000)
    bind_new(c, 2, "n004", 32_000)
    check(33_000)


def _case_node_the_report_does_not_name(c, check):
    c.node_metrics = report_for(["n000", "n001", "ghost"])
    check(1000)
    bind_new(c, 1, "n004", 2000)  # not in the report: counted all the same
    check(3000)


def _case_node_added(c, check):
    c.node_metrics = report_for(list(c.nodes) + ["n006", "n040"])
    bind_new(c, 1, "n002", 1000)
    check(2000)
    c.add_node(make_node(6))  # the report already names it
    bind_new(c, 2, "n006", 3000)
    check(4000)
    for i in range(7, 70):  # past the bucket: the columns grow
        c.add_node(make_node(i))
    check(5000)
    bind_new(c, 3, "n040", 6000)
    check(7000)


def _case_node_deleted(c, check):
    c.node_metrics = report_for(list(c.nodes))
    uid = bind_new(c, 1, "n001", 1000)
    bind_new(c, 2, "n003", 1000)
    check(2000)
    c.remove_pod(uid)
    c.remove_node("n001")  # rebase, or row compaction on the streaming engine
    check(3000)
    bind_new(c, 3, "n005", 3500)
    check(4000)
    # a node deleted under a recent binding takes its share with it
    c.remove_node("n003")
    check(5000)
    check(70_000)


def _case_report_none_and_back(c, check):
    c.node_metrics = report_for(list(c.nodes))
    bind_new(c, 1, "n000", 1000)
    check(2000)
    c.node_metrics = None
    bind_new(c, 2, "n001", 3000)  # no event is sent while there is none
    check(4000)
    c.node_metrics = report_for(list(c.nodes), base=40.0)
    check(5000)
    bind_new(c, 3, "n001", 6000)
    check(7000)


def _case_rebase_mid_sequence(c, check):
    c.node_metrics = report_for(list(c.nodes))
    bind_new(c, 1, "n000", 1000)
    check(2000)
    relabelled = make_node(2)
    relabelled.labels[ZONE_LABEL] = "z9"  # a label change rebases
    c.add_node(relabelled)
    bind_new(c, 2, "n002", 2500)
    check(3000)
    bind_new(c, 3, "n000", 3500)
    check(4000)
    check(61_000)


def _case_pod_replaced_or_rebound(c, check):
    c.node_metrics = report_for(list(c.nodes))
    uid = bind_new(c, 1, "n000", 1000)
    check(2000)
    bigger = make_pod(1, 1000, cpu=2000)  # same uid, another prediction
    bigger.node_name = "n000"
    c.add_pod(bigger)
    check(3000)
    c.add_pod(make_pod(1, 1000, cpu=900))  # a stale echo drops the node
    check(4000)
    c.bind(uid, "n004", now_ms=5000)  # bound again, elsewhere, later
    check(6000)
    check(61_000)  # the first binding's minute is over, the second's is not
    check(65_000)


def _case_clock_set_back_and_prediction_changed(c, check):
    c.node_metrics = report_for(list(c.nodes))
    bind_new(c, 1, "n000", 1000)
    bind_new(c, 2, "n001", 40_000)
    check(62_000)  # the first has expired
    check(50_000)  # a clock set back revives it
    c.tlp_prediction = (2.0, 500)
    check(51_000)


def _case_fallback_interlude(c, check):
    c.node_metrics = report_for(list(c.nodes))
    bind_new(c, 1, "n000", 1000)
    check(2000)
    c.add_seccomp_profile(
        SeccompProfile(name="sp", syscalls=frozenset({"read"}))
    )
    bind_new(c, 2, "n001", 3000)  # absorbed on a cycle that falls back
    c.node_metrics = report_for(list(c.nodes), base=33.0)
    # the engine under test is the one `check` refreshes
    c.seccomp_profiles.clear()
    check(5000)


METRICS_CASES = {
    "first_report": _case_first_report,
    "binds_inside_the_minute": _case_binds_inside_the_minute,
    "clock_crosses_sixty_seconds": _case_clock_crosses_sixty_seconds,
    "pod_deleted_inside_the_minute": _case_pod_deleted_inside_the_minute,
    "second_report": _case_second_report,
    "node_the_report_does_not_name": _case_node_the_report_does_not_name,
    "node_added": _case_node_added,
    "node_deleted": _case_node_deleted,
    "report_none_and_back": _case_report_none_and_back,
    "rebase_mid_sequence": _case_rebase_mid_sequence,
    "pod_replaced_or_rebound": _case_pod_replaced_or_rebound,
    "clock_set_back_and_prediction_changed":
        _case_clock_set_back_and_prediction_changed,
    "fallback_interlude": _case_fallback_interlude,
}


class TestResidentMetrics:
    """The load watcher's report as resident state (ISSUE 29): after every
    step of a scripted sequence the engine's `MetricsState` equals the
    fresh snapshot's at the same clock, and `verify` agrees."""

    @pytest.mark.parametrize("case", sorted(METRICS_CASES))
    @pytest.mark.parametrize("streaming", [False, True],
                             ids=["base", "streaming"])
    def test_resident_metrics_equal_fresh(self, case, streaming):
        from scheduler_plugins_tpu.serving import StreamingServeEngine

        cluster = make_cluster(6)
        engine = (StreamingServeEngine if streaming else ServeEngine)()
        engine.attach(cluster)
        assert engine.refresh(cluster, [], now_ms=0) is not None  # cold build

        def check(now):
            assert_metrics_equal_fresh(engine, cluster, now)

        METRICS_CASES[case](cluster, check)
        assert engine.antientropy_divergences == 0

    def test_one_lowering_per_report(self):
        """`scheduler_serve_metrics_relowers_total` counts reports, not
        cycles: binds and expiries never lower the report again."""
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        engine.refresh(cluster, [], now_ms=0)
        count0 = obs.metrics.get(obs.SERVE_METRICS_RELOWERS) or 0
        cluster.node_metrics = report_for(list(cluster.nodes))
        for cycle in range(50):
            now = 1000 + 2000 * cycle  # 100 s: binds are added and expire
            bind_new(cluster, cycle, f"n{cycle % 6:03d}", now)
            assert engine.refresh(cluster, [], now_ms=now) is not None
        assert obs.metrics.get(obs.SERVE_METRICS_RELOWERS) == count0 + 1
        assert 0 < len(engine._recent) <= 30
        cluster.node_metrics = report_for(list(cluster.nodes), base=50.0)
        assert_metrics_equal_fresh(engine, cluster, 200_000)
        assert obs.metrics.get(obs.SERVE_METRICS_RELOWERS) == count0 + 2
        assert engine.rebases == 1

    def test_no_report_no_events_no_state(self):
        """Where the store holds no report the mechanism is absent: no
        `binding_touched` event, no column, no `MetricsState`."""
        from scheduler_plugins_tpu.serving import deltas as D

        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        engine.refresh(cluster, [], now_ms=0)
        uid = bind_new(cluster, 1, "n000", 1000)
        cluster.remove_pod(uid)
        assert not [
            e for e in engine._sink.events if e[0] == D.BINDING_TOUCHED
        ]
        snap, _ = engine.refresh(cluster, [], now_ms=2000)
        assert snap.metrics is None
        assert engine._metric_cols is None and not engine._recent

    @pytest.mark.parametrize("with_report", [False, True],
                             ids=["no_report", "report"])
    def test_recent_bindings_stay_bounded(self, with_report):
        """1,000 resident cycles with the clock advancing: the store's
        binding cache is pruned where pods are bound (no fresh snapshot is
        ever built here), and the engine's own entries live a minute."""
        cluster = make_cluster(6)
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 0
        if with_report:
            cluster.node_metrics = report_for(list(cluster.nodes))
        peak = 0
        for cycle in range(1000):
            now = 2000 * cycle  # 2 s a cycle, 2,000 s in all
            for j in range(3):
                uid = bind_new(cluster, 3 * cycle + j, f"n{j:03d}", now)
            cluster.remove_pod(uid)  # one departure per cycle
            assert engine.refresh(cluster, [], now_ms=now) is not None
            peak = max(peak, len(cluster.recent_bindings))
        assert engine.rebases == 1
        # 5 minutes of binds at 3 per 2 s and what gathers between two
        # prunings, against 3,000 without
        assert peak <= (
            3 * (cluster.BINDING_CACHE_GC_MS // 2000 + 1)
            + cluster.BINDING_CACHE_PRUNE_EVERY
        )
        if with_report:
            assert len(engine._recent) <= 3 * 30
            assert len(engine._recent_heap) <= 3 * 31
            assert_metrics_equal_fresh(engine, cluster, 2_000_000)


# ---------------------------------------------------------------------------
# one record a pod (ISSUE 37): the delta-equivalence differential, extended
# to the per-pod records the base engine reads
# ---------------------------------------------------------------------------


def assert_records_follow_store(engine, cluster):
    """The record table holds the pods alive and nothing else: every entry
    is the record of the object the store holds under that uid, and every
    pod that holds capacity (bound or reserved) has one."""
    for uid, rec in engine._records.items():
        assert cluster.pods.get(uid) is rec.pod, f"stale record {uid}"
        assert rec.index is engine.index, uid
    held = {
        uid for uid, p in cluster.pods.items()
        if p.node_name is not None or uid in cluster.reserved
    }
    assert held <= set(engine._records)


def assert_columns_equal_fresh(engine, cluster, now):
    """`assert_resident_matches` on whatever axis the engine holds."""
    assert engine.refresh(cluster, [], now_ms=now) is not None
    snap, _ = cluster.snapshot(
        [], now_ms=now, pad_nodes=engine.npad,
        extra_resources=engine._extended(),
    )
    for col in NODE_COLUMNS:
        np.testing.assert_array_equal(
            np.asarray(getattr(engine.resident_nodes, col)),
            np.asarray(getattr(snap.nodes, col)),
            err_msg=f"resident column {col} at {now} ms",
        )


def _records_bound_pod_replaced(c, engine, sched, check):
    uid = bind_new(c, 1, "n000", 1000)
    check(1500)
    first = engine._records[uid]
    bigger = make_pod(1, 1000, cpu=2500, mem=3 * gib)  # same uid
    bigger.node_name = "n000"
    c.add_pod(bigger)
    check(2500)
    assert engine._records[uid] is not first
    assert engine._records[uid].pod is bigger
    moved = make_pod(1, 1000, cpu=700)  # and again, onto another node
    moved.node_name = "n003"
    c.add_pod(moved)
    check(3500)
    c.remove_pod(uid)
    check(4500)
    assert uid not in engine._records


def _records_terminate_flip_between_event_and_drain(c, engine, sched, check):
    pod = make_pod(1, 500)
    c.add_pod(pod)
    c.bind(pod.uid, "n001", now_ms=1000)
    c.mark_terminating(pod.uid, 1200)  # before the assign event is drained
    check(1500)
    record = engine._records[pod.uid]
    other = bind_new(c, 2, "n001", 2000)
    check(2500)
    c.mark_terminating(other, 2600)
    c.remove_pod(other)  # flip and delete inside one window
    check(3500)
    assert engine._records[pod.uid] is record  # a flag is no new spec
    c.remove_pod(pod.uid)
    check(4500)
    assert not engine._records


def _records_axis_widens_mid_run(c, engine, sched, check):
    plain = bind_new(c, 1, "n000", 1000)
    check(1500)
    narrow = engine._records[plain]
    rebases0 = engine.rebases
    c.add_node(make_node(50, extra={EXT: 4}))
    gpu = Pod(
        name="gpu-pod", creation_ms=1600,
        containers=[Container(requests={CPU: 100, EXT: 1})],
    )
    c.add_pod(gpu)
    report = run_cycle(sched, c, now=2000, serve=engine)
    assert gpu.uid in report.bound
    assert engine.rebases == rebases0 + 1 and EXT in engine.index
    check(2500)
    # every record was lowered again on the wider axis
    assert engine._records[plain] is not narrow
    assert len(engine._records[plain].req) == len(engine.index)
    assert engine._records[gpu.uid].req[engine.index.position(EXT)] == 1
    c.remove_pod(gpu.uid)
    c.remove_pod(plain)
    check(3500)
    assert not engine._records


def _records_pod_deleted_while_pending(c, engine, sched, check):
    big = make_pod(1, 500, cpu=64_000)  # fits nowhere: stays pending
    c.add_pod(big)
    c.add_pod(make_pod(2, 600))
    report = run_cycle(sched, c, now=1000, serve=engine)
    assert big.uid in report.failed
    assert engine._records[big.uid].pod is big  # lowered with its batch
    check(1500)
    c.remove_pod(big.uid)  # no column moves: POD_FORGET alone says so
    check(2500)
    assert big.uid not in engine._records
    # replaced while pending: the old object's record goes with it
    waiting = make_pod(3, 2600, cpu=64_000)
    c.add_pod(waiting)
    run_cycle(sched, c, now=3000, serve=engine)
    assert engine._records[waiting.uid].pod is waiting
    smaller = make_pod(3, 2600, cpu=300)
    c.add_pod(smaller)
    check(3500)
    assert waiting.uid not in engine._records
    c.add_node(make_node(7))  # an event, and past backoff: it runs again
    report = run_cycle(sched, c, now=60_000, serve=engine)
    assert smaller.uid in report.bound
    check(61_000)
    assert engine._records[smaller.uid].pod is smaller


def _records_reservation_released(c, engine, sched, check):
    pod = make_pod(1, 500)
    c.add_pod(pod)
    c.reserve(pod.uid, "n002")
    check(1500)
    record = engine._records[pod.uid]
    c.release_reservation(pod.uid)  # unassigned, and still in the store
    check(2500)
    assert engine._records[pod.uid] is record
    c.reserve(pod.uid, "n004")
    c.bind(pod.uid, "n004", now_ms=3000)
    check(3500)
    assert engine._records[pod.uid] is record
    c.remove_pod(pod.uid)
    check(4500)
    assert not engine._records


RECORD_CASES = {
    "bound_pod_replaced_by_upsert": _records_bound_pod_replaced,
    "terminate_flip_between_event_and_drain":
        _records_terminate_flip_between_event_and_drain,
    "axis_widens_mid_run": _records_axis_widens_mid_run,
    "pod_deleted_while_pending": _records_pod_deleted_while_pending,
    "reservation_released": _records_reservation_released,
}


class TestPodRecords:
    """One record a pod object (ISSUE 37): after every step of a scripted
    sequence the resident columns are bit-equal to a fresh snapshot, both
    kinds of anti-entropy check agree that they are, and the record table
    holds exactly the pods alive."""

    @pytest.mark.parametrize("case", sorted(RECORD_CASES))
    @pytest.mark.parametrize("streaming", [False, True],
                             ids=["base", "streaming"])
    def test_records_follow_the_store(self, case, streaming):
        from scheduler_plugins_tpu.serving import StreamingServeEngine

        cluster = make_cluster(6)
        engine = (StreamingServeEngine if streaming else ServeEngine)()
        engine.attach(cluster)
        sched = make_scheduler()
        assert engine.refresh(cluster, [], now_ms=0) is not None  # cold build

        def check(now):
            assert_columns_equal_fresh(engine, cluster, now)
            assert_records_follow_store(engine, cluster)
            assert engine.verify_assigned(cluster) is None
            assert ServeEngine.verify(engine, cluster) is None

        RECORD_CASES[case](cluster, engine, sched, check)
        assert engine.antientropy_divergences == 0

    def test_rebase_primes_the_assigned_population_and_prunes(self):
        cluster = make_cluster(4)
        for serial in range(1, 6):
            bind_new(cluster, serial, f"n00{serial % 4}", 100 * serial)
        cluster.add_pod(make_pod(9, 900))  # pending: no record of it yet
        engine = ServeEngine().attach(cluster)
        assert engine.refresh(cluster, [], now_ms=1000) is not None
        assert len(engine._records) == 5
        assert_records_follow_store(engine, cluster)
        # an entry whose event was lost is gone after the next rebase
        engine._records["default/ghost"] = engine._records["default/p00001"]
        engine._nodes = None
        assert engine.refresh(cluster, [], now_ms=2000) is not None
        assert_records_follow_store(engine, cluster)

    @pytest.mark.parametrize("pod", [
        make_pod(1, 0),
        Pod(name="bare", creation_ms=1),
        Pod(
            name="multi", creation_ms=2, overhead={CPU: 10},
            init_containers=[Container(requests={CPU: 900},
                                       limits={CPU: 900, MEMORY: gib})],
            containers=[
                Container(requests={CPU: 200, MEMORY: gib},
                          limits={CPU: 100}),
                Container(requests={CPU: 300}),
            ],
        ),
    ], ids=["one_container", "no_container", "init_and_overhead"])
    def test_hit_and_cold_lowering_are_byte_identical(self, pod):
        """What a record hands its readers is what each of them lowered
        for itself before: the `PodState` row of `build_pod_state`'s cold
        path, `pod_usage_vectors` and `pod_quota_vector`."""
        from scheduler_plugins_tpu.serving import deltas as D
        from scheduler_plugins_tpu.state.snapshot import (
            _Interner,
            build_pod_state,
        )

        cluster = make_cluster(2)
        engine = ServeEngine().attach(cluster)
        cluster.add_pod(pod)
        assert not engine._outside_axis(cluster, [pod])  # the miss
        record = engine._records[pod.uid]
        hits0 = dict(engine._lookups)
        vectors = engine._pod_vectors(pod)  # a hit
        assert engine._lookups[("classify", "hit")] == 1
        assert hits0 == {("batch", "miss"): 1}
        cold = D.pod_usage_vectors(pod, engine.index) + (
            D.pod_quota_vector(pod, engine.index),
        )
        for mine, theirs in zip(vectors, cold):
            assert mine.dtype == theirs.dtype
            assert mine.tobytes() == theirs.tobytes()
            assert not mine.flags.writeable
        rows = {}
        for name, cache in (("cold", None), ("hit", engine._records)):
            rows[name] = build_pod_state(
                [pod], 4, engine.index, _Interner([]), lambda p: -1,
                cluster.tlp_prediction, row_cache=cache,
            )
        assert engine._records[pod.uid] is record  # read, not replaced
        import dataclasses

        for leaf in dataclasses.fields(rows["cold"]):
            a = np.asarray(getattr(rows["cold"], leaf.name))
            b = np.asarray(getattr(rows["hit"], leaf.name))
            assert a.dtype == b.dtype and a.shape == b.shape, leaf.name
            assert a.tobytes() == b.tobytes(), leaf.name
