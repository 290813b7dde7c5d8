"""Concurrent cycle pipeline (framework.pipeline_cycle) unit tests.

The engine-level equivalence twin lives in
tests/test_differential.py::TestPipelinedCycleEquivalence; this file
covers the pieces: the O(changed) pending index, the conflict-fence
ordering guarantees (preemption nominations and backoff charges fenced to
the cycle that observed the snapshot), binds-as-deltas across the fence,
the streaming serve engine's node-delete compaction and O(assigned)
anti-entropy verify, and the cycle timeline/overlap telemetry.
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    POD_GROUP_LABEL,
    REGION_LABEL,
    ZONE_LABEL,
    Container,
    Node,
    Pod,
    PodGroup,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import (
    PipelinedCycle,
    Profile,
    Scheduler,
    run_cycle,
)
from scheduler_plugins_tpu.framework.pipeline_cycle import CycleTimeline
from scheduler_plugins_tpu.framework.preemption import (
    PreemptionEngine,
    PreemptionMode,
)
from scheduler_plugins_tpu.plugins import (
    Coscheduling,
    NodeResourcesAllocatable,
)
from scheduler_plugins_tpu.serving import ServeEngine, StreamingServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster

gib = 1 << 30


def mknode(name, cpu=16_000):
    return Node(
        name=name, allocatable={CPU: cpu, MEMORY: 64 * gib, PODS: 110}
    )


def mkpod(name, cpu=500, priority=0, node=None, created=0, **kw):
    p = Pod(
        name=name, creation_ms=created, priority=priority,
        containers=[Container(requests={CPU: cpu, MEMORY: gib})], **kw,
    )
    p.node_name = node
    return p


def small_cluster(n_nodes=4, n_bound=6):
    c = Cluster()
    for i in range(n_nodes):
        c.add_node(mknode(f"n{i}"))
    for i in range(n_bound):
        c.add_pod(mkpod(f"b{i}", node=f"n{i % n_nodes}", created=i))
    return c


class TestPendingIndex:
    def test_randomized_parity_with_scan(self):
        """The maintained index must yield the SAME pod list (order
        included) as the O(pods) scan after any mutator sequence."""
        rng = np.random.default_rng(11)

        def fresh():
            c = Cluster()
            for i in range(3):
                c.add_node(mknode(f"n{i}"))
            return c

        indexed, scan = fresh(), fresh()
        serial = 0

        def add_both():
            nonlocal serial
            serial += 1
            for c in (indexed, scan):
                c.add_pod(mkpod(f"p{serial}", created=serial))

        for _ in range(10):
            add_both()
        indexed.enable_pending_index()
        for step in range(600):
            r = rng.random()
            pend = [
                p.uid for p in indexed.pods.values() if p.node_name is None
            ]
            if r < 0.35:
                add_both()
            elif r < 0.55 and pend:
                u = pend[int(rng.integers(len(pend)))]
                indexed.bind(u, "n1", 5)
                scan.bind(u, "n1", 5)
            elif r < 0.7 and indexed.pods:
                u = list(indexed.pods)[int(rng.integers(len(indexed.pods)))]
                indexed.remove_pod(u)
                scan.remove_pod(u)
            elif r < 0.8 and pend:
                u = pend[int(rng.integers(len(pend)))]
                if u not in indexed.reserved:
                    indexed.reserve(u, "n2")
                    scan.reserve(u, "n2")
            elif r < 0.9 and indexed.reserved:
                u = list(indexed.reserved)[
                    int(rng.integers(len(indexed.reserved)))
                ]
                indexed.release_reservation(u)
                scan.release_reservation(u)
            elif pend:
                u = pend[int(rng.integers(len(pend)))]
                indexed.mark_terminating(u, 5)
                scan.mark_terminating(u, 5)
            a = [p.uid for p in indexed.pending_pods()]
            b = [p.uid for p in scan.pending_pods()]
            assert a == b, (step, a[:4], b[:4])

    #: what each churn mixes into a stream of plain arrivals, and the
    #: profile it runs under (ISSUE 25: the daemon's serial engine reads
    #: the index too)
    SERIAL_CHURNS = {
        "gates_lifted": lambda: Profile(plugins=[NodeResourcesAllocatable()]),
        "gangs_reserve_and_release": lambda: Profile(plugins=[
            NodeResourcesAllocatable(),
            Coscheduling(permit_waiting_seconds=2, reject_percentage=100),
        ]),
        "nominations": lambda: Profile(
            plugins=[NodeResourcesAllocatable()],
            preemption=PreemptionEngine(PreemptionMode.DEFAULT),
        ),
        "delete_and_readd": lambda: Profile(
            plugins=[NodeResourcesAllocatable()]
        ),
    }

    @staticmethod
    def _scan(c):
        """`pending_pods()` as the store answers it with the index off."""
        idx, c._pending_idx = c._pending_idx, None
        try:
            return c.pending_pods()
        finally:
            c._pending_idx = idx

    @pytest.mark.parametrize("churn", sorted(SERIAL_CHURNS))
    def test_serial_engine_index_equals_scan(self, churn):
        """Seeded churn through `run_cycle` on an indexed store: after
        every cycle the index yields the scan's list, order included."""
        rng = np.random.default_rng(25)
        c = Cluster()
        for i in range(2):
            c.add_node(mknode(f"n{i}", cpu=4000))
        c.enable_pending_index()
        sched = Scheduler(self.SERIAL_CHURNS[churn]())
        gains = []
        c.on_pending_gain = lambda: gains.append(1)
        serial = 0

        def name():
            nonlocal serial
            serial += 1
            return f"p{serial}"

        def some(uids):
            uids = list(uids)
            return uids[int(rng.integers(len(uids)))] if uids else None

        seen = {"bound": 0, "reserved": 0, "released": 0, "nominated": 0,
                "lifted": 0, "readded": 0}
        for step in range(40):
            now = 1000 * (step + 1)
            if len(self._scan(c)) < 5:
                c.add_pod(mkpod(name(), cpu=500, created=step))
            gated = [p.uid for p in c.gated_pods()]
            bound = [u for u, p in c.pods.items() if p.node_name]
            r = rng.random()
            if churn == "gates_lifted":
                if r < 0.5:
                    c.add_pod(mkpod(name(), created=step,
                                    scheduling_gated=True))
                elif gated:
                    # the gate lifts as the feed applies it: an upsert
                    old = c.pods[some(gated)]
                    c.add_pod(mkpod(old.name, created=old.creation_ms))
                    seen["lifted"] += 1
            elif churn == "gangs_reserve_and_release":
                if step % 8 == 0:
                    # three members of 1,500 m: two reserve where there is
                    # room, the third waits; the timer releases them
                    gang = f"g{step}"
                    c.add_pod_group(PodGroup(
                        name=gang, namespace="default", min_member=3,
                    ))
                    for _ in range(3):
                        c.add_pod(mkpod(
                            name(), cpu=1500, created=step,
                            labels={POD_GROUP_LABEL: gang},
                        ))
            elif churn == "nominations":
                if r < 0.4:
                    c.add_pod(mkpod(name(), cpu=3000, created=step,
                                    priority=int(rng.integers(1, 50))))
                for uid in [u for u, p in c.pods.items() if p.terminating]:
                    c.remove_pod(uid)  # the victim's delete arrives
            elif churn == "delete_and_readd":
                uid = some(c.pods)
                if uid is not None and r < 0.7:
                    old = c.pods[uid]
                    c.remove_pod(uid)
                    if r < 0.5:
                        c.add_pod(mkpod(old.name, created=old.creation_ms))
                        seen["readded"] += 1
            if bound and rng.random() < 0.5:
                c.remove_pod(some(bound))  # departures make room again
            before = set(c.reserved)
            report = run_cycle(sched, c, now=now)
            seen["bound"] += len(report.bound)
            seen["reserved"] += len(report.reserved)
            seen["released"] += len(before - set(c.reserved) - set(report.bound))
            seen["nominated"] += len(report.preempted)
            want = [p.uid for p in self._scan(c)]
            assert [p.uid for p in c.pending_pods()] == want, (churn, step)
            assert c.pending_count() == len(want)
        # the churn did what its name says, and the hook fired for every
        # pod that entered the set (at least once per pod ever bound)
        assert seen["bound"] > 5 and len(gains) >= seen["bound"]
        wanted = {"gates_lifted": "lifted",
                  "gangs_reserve_and_release": "released",
                  "nominations": "nominated",
                  "delete_and_readd": "readded"}[churn]
        assert seen[wanted] > 0, seen
        if churn == "gangs_reserve_and_release":
            assert seen["reserved"] > 0, seen

    def test_inplace_flip_needs_reindex(self):
        """In-place eligibility flips bypass the mutators (the delta
        sink's blind spot too) — `reindex_pod` is the supported hook."""
        c = Cluster()
        c.add_node(mknode("n0"))
        c.add_pod(mkpod("a"))
        c.enable_pending_index()
        pod = c.pods["default/a"]
        pod.scheduling_gated = True
        # the index is stale until told
        assert [p.uid for p in c.pending_pods()] == ["default/a"]
        c.reindex_pod("default/a")
        assert c.pending_pods() == []
        pod.scheduling_gated = False
        c.reindex_pod("default/a")
        assert [p.uid for p in c.pending_pods()] == ["default/a"]

    def test_readd_lands_at_queue_end_like_the_dict(self):
        c = Cluster()
        c.add_node(mknode("n0"))
        for name in ("a", "b", "c"):
            c.add_pod(mkpod(name))
        c.enable_pending_index()
        c.remove_pod("default/a")
        c.add_pod(mkpod("a"))
        assert [p.uid for p in c.pending_pods()] == [
            "default/b", "default/c", "default/a"
        ]


class TestCycleTimeline:
    def test_overlap_and_bubble_math(self):
        tl = CycleTimeline(3)
        tl.overlap_ms = 3.0
        tl.fence_wait_ms = 1.0
        assert tl.pipeline_bubble_ms == 1.0
        assert tl.overlap_efficiency == pytest.approx(0.75)
        d = tl.as_dict()
        assert d["cycle"] == 3 and d["overlap_efficiency"] == 0.75

    def test_empty_envelope_counts_as_fully_overlapped(self):
        tl = CycleTimeline(0)
        assert tl.overlap_efficiency == 1.0


class TestPipelinedTickBasics:
    def test_tick_matches_run_cycle_plain(self):
        def build():
            c = small_cluster()
            for i in range(5):
                c.add_pod(mkpod(f"p{i}", created=10 + i))
            c.add_pod(mkpod("huge", cpu=10**9, created=99))
            return c

        serial_c, pipe_c = build(), build()
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        want = run_cycle(sched, serial_c, now=1000)
        pipe = PipelinedCycle(sched, pipe_c)
        got = pipe.tick(now=1000)
        pipe.flush()
        assert got.bound == want.bound
        assert got.failed == want.failed
        assert got.failed_by == want.failed_by
        # quality is part of the deferred finalize — flushed above
        assert got.quality is not None
        assert got.quality == pytest.approx(want.quality)
        pipe.close()

    def test_report_finalized_in_next_ticks_overlap_window(self):
        c = small_cluster()
        c.add_pod(mkpod("p0", created=10))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        pipe = PipelinedCycle(sched, c)
        r0 = pipe.tick(now=1000)
        assert r0.quality is None  # deferred into the overlap window
        c.add_pod(mkpod("p1", created=20))
        pipe.tick(now=2000)
        assert r0.quality is not None  # finalized while solve 1 in flight
        pipe.close()

    def test_inflight_and_depth_introspection(self):
        c = small_cluster()
        c.add_pod(mkpod("p0"))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        pipe = PipelinedCycle(sched, c)
        assert pipe.depth == 2 and pipe.inflight == 0
        pipe.tick(now=1000)
        assert pipe.inflight >= 1  # deferred finalize (+ maybe bind flush)
        pipe.flush()
        assert pipe.inflight == 0
        pipe.close()


class TestConflictFence:
    def test_nomination_attributed_to_observing_cycle(self):
        """Satellite regression (the latent ordering hazard): a
        preemption nomination landing mid-overlap must be attributed to
        the cycle that observed the snapshot — report k carries
        `preempted`, the nomination is visible to cycle k+1's snapshot,
        and both match the serial engine exactly."""
        def build():
            c = Cluster()
            c.add_node(Node(
                name="n0",
                allocatable={CPU: 4000, MEMORY: 32 * gib, PODS: 110},
            ))
            c.add_pod(mkpod("low", cpu=3000, priority=1, node="n0"))
            c.add_pod(mkpod("high", cpu=3000, priority=10))
            return c

        profile = lambda: Profile(  # noqa: E731
            plugins=[NodeResourcesAllocatable()],
            preemption=PreemptionEngine(PreemptionMode.DEFAULT),
        )
        serial_c, pipe_c = build(), build()
        s_sched, p_sched = Scheduler(profile()), Scheduler(profile())
        want0 = run_cycle(s_sched, serial_c, now=1000)
        pipe = PipelinedCycle(p_sched, pipe_c)
        got0 = pipe.tick(now=1000)
        pipe.fence()
        # the nomination belongs to cycle 0's report, fenced BEFORE any
        # later ingest — not to whatever cycle is running when the
        # deferred finalize executes
        assert got0.preempted == want0.preempted
        assert pipe_c.pods["default/high"].nominated_node_name == "n0"
        assert pipe_c.pods["default/low"].terminating
        # cycle 1 observes the nomination identically in both engines
        serial_c.remove_pod("default/low")
        pipe_c.remove_pod("default/low")
        want1 = run_cycle(s_sched, serial_c, now=2000)
        got1 = pipe.tick(now=2000)
        pipe.flush()
        assert got1.bound == want1.bound == {"default/high": "n0"}
        assert got0.preempted and not got1.preempted
        pipe.close()

    def test_backoff_charged_with_observing_cycles_clock(self):
        """`mark_unschedulable` runs on the flusher thread, possibly
        after the wall clock moved on — the backoff window must still be
        charged with the OBSERVING cycle's `now`."""
        def build():
            c = Cluster()
            c.add_node(mknode("n0", cpu=1000))
            c.add_pod(mkpod("big", cpu=50_000))
            return c

        serial_c, pipe_c = build(), build()
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        run_cycle(sched, serial_c, now=7000)
        pipe = PipelinedCycle(sched, pipe_c)
        pipe.tick(now=7000)
        pipe.flush()
        assert (
            pipe_c.pod_backoff_until_ms["default/big"]
            == serial_c.pod_backoff_until_ms["default/big"]
        )
        assert (
            pipe_c.unschedulable_since["default/big"]
            == serial_c.unschedulable_since["default/big"]
        )
        pipe.close()

    def test_late_bind_is_an_ordinary_delta(self):
        """A bind landing AFTER a refresh's ingest boundary reaches the
        resident columns as an ordinary DeltaSink delta (the PR 6
        classification): the next refresh absorbs it and the anti-entropy
        digest stays clean."""
        c = small_cluster(n_nodes=4, n_bound=4)
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        run_cycle(sched, c, now=1000, serve=engine)  # builds the base
        # a "late" bind: lands through the store mutators after the
        # cycle's drain boundary, as the async flusher would
        c.add_pod(mkpod("late", created=50))
        c.bind("default/late", "n2", 1500)
        # the delta sits in the sink; the NEXT refresh absorbs it
        snap_meta = engine.refresh(c, [], now_ms=2000)
        assert snap_meta is not None
        assert engine.verify(c) is None  # resident state byte-exact


class TestStreamingServeEngine:
    def _churny(self, n_nodes=5, n_bound=8):
        c = small_cluster(n_nodes=n_nodes, n_bound=n_bound)
        return c, StreamingServeEngine().attach(c)

    def test_node_delete_compacts_without_rebase(self):
        c, engine = self._churny()
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))  # non-empty batch: the
        # first cycle must actually refresh (and build the base)
        run_cycle(sched, c, now=1000, serve=engine)
        rebases0 = engine.rebases
        assert rebases0 == 1  # the initial base build
        # drain-then-delete (the kubectl drain shape)
        victim = "n3"
        for uid in [
            u for u, p in c.pods.items() if p.node_name == victim
        ]:
            c.remove_pod(uid)
        c.remove_node(victim)
        c.add_pod(mkpod("after", created=60))
        report = run_cycle(sched, c, now=2000, serve=engine)
        assert engine.rebases == rebases0  # compacted, no rebase
        assert engine.compactions == 1
        assert "default/after" in report.bound
        # drain the cycle's own bind deltas, then byte-compare
        assert engine.refresh(c, [], now_ms=2500) is not None
        assert engine.verify(c) is None
        # row order matches the store's surviving order
        assert engine._names == list(c.nodes)

    def test_compaction_matches_base_engine_placements(self):
        """Same delete-heavy stream through the streaming engine vs the
        base (rebase-on-delete) engine: identical placements and final
        state."""
        def run(engine_cls):
            c = small_cluster(n_nodes=6, n_bound=10)
            engine = engine_cls().attach(c)
            sched = Scheduler(
                Profile(plugins=[NodeResourcesAllocatable()])
            )
            placements = {}
            serial = 0
            for cycle in range(8):
                now = 1000 * (cycle + 1)
                serial += 1
                c.add_pod(mkpod(f"arr{serial}", created=now + serial))
                if cycle in (2, 5):
                    victim = next(iter(c.nodes))
                    for uid in [
                        u for u, p in c.pods.items()
                        if p.node_name == victim
                    ]:
                        c.remove_pod(uid)
                    c.remove_node(victim)
                r = run_cycle(sched, c, now=now, serve=engine)
                placements.update(r.bound)
            state = {u: p.node_name for u, p in c.pods.items()}
            return placements, state, engine

        base_pl, base_state, base_engine = run(ServeEngine)
        st_pl, st_state, st_engine = run(StreamingServeEngine)
        assert st_pl == base_pl
        assert st_state == base_state
        assert st_engine.compactions == 2
        assert st_engine.rebases < base_engine.rebases

    def test_fast_verify_expectation_matches_fresh_snapshot(self):
        """The O(assigned) expectation must be BYTE-identical to the
        base engine's fresh-snapshot columns — on a roster with regions,
        zones, reservations and terminating pods."""
        c = Cluster()
        for i in range(5):
            c.add_node(Node(
                name=f"n{i}",
                allocatable={CPU: 16_000, MEMORY: 64 * gib, PODS: 110},
                labels={
                    REGION_LABEL: "r0" if i < 3 else "r1",
                    ZONE_LABEL: f"z{i % 2}",
                },
            ))
        for i in range(9):
            c.add_pod(mkpod(f"b{i}", node=f"n{i % 5}", created=i))
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))
        run_cycle(sched, c, now=1000, serve=engine)
        assert engine.npad > 0  # resident base built
        c.reserve(list(c.pending_pods())[0].uid, "n1") \
            if c.pending_pods() else None
        c.mark_terminating("default/b3", 1500)
        expected, _side = engine._expected_columns(c, list(c.nodes))
        fresh, _meta = c.snapshot([], now_ms=0, pad_nodes=engine.npad)
        for key, arr in expected.items():
            ref = np.asarray(getattr(fresh.nodes, key))
            assert arr.dtype == ref.dtype, key
            assert np.array_equal(arr, ref), key

    def test_fast_verify_detects_corruption_like_base(self):
        c, engine = self._churny()
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))
        run_cycle(sched, c, now=1000, serve=engine)
        assert engine.refresh(c, [], now_ms=1500) is not None
        assert engine.verify(c) is None
        nodes = engine._nodes
        engine._nodes = nodes.replace(
            requested=nodes.requested.at[1, 0].add(17)
        )
        assert engine.verify(c) == "column-digest"
        # row-order divergence too
        engine._names = list(reversed(engine._names))
        assert engine.verify(c) == "row-order"

    @pytest.mark.parametrize("column", ["cpu_tlp_valid", "missing_cpu_millis"])
    def test_fast_verify_covers_the_metrics_columns(self, column):
        """The expectation of the resident metrics columns comes from the
        store's own merge at the clock of the last refresh: clean state
        verifies, one flipped cell does not, and the base engine's
        fresh-snapshot verify says the same of the same state."""
        c, engine = self._churny()
        c.node_metrics = {f"n{i}": {"cpu_avg": 20.0 + i} for i in range(4)}
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))
        run_cycle(sched, c, now=1000, serve=engine)
        assert engine.refresh(c, [], now_ms=1500) is not None
        assert c.recent_bindings  # the seed's bind is unreported
        assert engine.verify(c) is None
        assert ServeEngine.verify(engine, c) is None
        assert engine.verify(c, now_ms=70_000) == "metrics-digest"  # expired
        state = engine._metrics_state
        cell = np.asarray(getattr(state, column)).copy()
        cell[4] = not cell[4] if cell.dtype == bool else cell[4] + 1
        engine._metrics_state = state.replace(**{column: cell})
        assert engine.verify(c) == "metrics-digest"
        assert ServeEngine.verify(engine, c) == "metrics-digest"

    def test_row_cache_is_bit_identical(self):
        from scheduler_plugins_tpu.serving import deltas as D
        from scheduler_plugins_tpu.state.snapshot import (
            _Interner,
            build_pod_state,
        )

        pods = [mkpod(f"p{i}", cpu=100 * (i + 1), created=i)
                for i in range(7)]
        pods.append(Pod(
            name="multi", creation_ms=50,
            init_containers=[Container(requests={CPU: 50})],
            containers=[Container(requests={CPU: 200, MEMORY: gib}),
                        Container(requests={CPU: 300})],
        ))
        cache: dict = {}
        cold = build_pod_state(
            pods, 16, D.CANON_INDEX, _Interner([]), lambda p: -1
        )
        warm1 = build_pod_state(
            pods, 16, D.CANON_INDEX, _Interner([]), lambda p: -1,
            row_cache=cache,
        )
        warm2 = build_pod_state(
            pods, 16, D.CANON_INDEX, _Interner([]), lambda p: -1,
            row_cache=cache,
        )
        for field in ("req", "limits", "predicted_cpu_millis",
                      "container_req", "container_is_init",
                      "container_mask", "priority", "ns", "gang", "qos",
                      "mask", "creation_ms", "gated"):
            a = np.asarray(getattr(cold, field))
            assert np.array_equal(a, np.asarray(getattr(warm1, field))), field
            assert np.array_equal(a, np.asarray(getattr(warm2, field))), field

    def test_usage_vector_memo_invalidates_on_new_pod_object(self):
        c, engine = self._churny(n_nodes=2, n_bound=0)
        pod = mkpod("x", cpu=700)
        v1 = engine._pod_vectors(pod)
        assert engine._pod_vectors(pod)[0] is v1[0]  # memo hit
        replacement = mkpod("x", cpu=900)  # same uid, new object
        v2 = engine._pod_vectors(replacement)
        assert v2[0][0] == 900
        assert v2[3][0] == 900  # the quota vector rides the same memo
        # final release drops the entry
        engine._pod_vectors(replacement, final=True)
        assert "default/x" not in engine._vec_cache


class TestReviewRegressions:
    def test_add_then_delete_same_window_leaves_no_ghost_row(self):
        """A node added AND removed within one drain window (a flap):
        the delete's slot only exists after the same window's upserts
        apply — resolving the slot first would discard the delete and
        leave a ghost resident row for a node the store no longer has."""
        c = Cluster()
        for i in range(4):
            c.add_node(mknode(f"n{i}"))
        for i in range(5):
            c.add_pod(mkpod(f"b{i}", node=f"n{i % 4}", created=i))
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))
        run_cycle(sched, c, now=1000, serve=engine)
        # flap within ONE window: add nx, then remove it (undrained)
        c.add_node(mknode("nx"))
        c.remove_node("nx")
        assert engine.refresh(c, [], now_ms=2000) is not None
        assert engine._names == list(c.nodes)  # no ghost row
        assert engine.verify(c) is None

    def test_late_bind_counter_fires_on_external_drain(self):
        """A bind flush overtaken by an EXTERNAL sink drain is counted
        as a late bind and absorbed as an ordinary delta of the next
        window — resident state stays exact."""
        import threading

        from scheduler_plugins_tpu.utils import observability as obs

        c = small_cluster()
        c.add_pod(mkpod("p0", created=10))
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        pipe = PipelinedCycle(sched, c, serve=engine)
        before = obs.metrics.snapshot().get(obs.CYCLE_LATE_BINDS, 0)
        gate = threading.Event()
        # stall the flusher so this tick's bind job runs AFTER the
        # external drain below
        pipe._flusher.submit(gate.wait)
        pipe.tick(now=1000)
        engine.refresh(c, [], now_ms=1500)  # external drain boundary
        gate.set()
        pipe.flush()
        assert obs.metrics.snapshot()[obs.CYCLE_LATE_BINDS] == before + 1
        assert pipe.timelines[-1].late_bind
        # the late bind is an ordinary delta of the NEXT window
        assert engine.refresh(c, [], now_ms=2000) is not None
        assert engine.verify(c) is None
        pipe.close()

    def test_extended_resource_fallback_verify_counts_once(self):
        """The extended-resource fallback delegates to the base verify
        BEFORE opening the fast path's span/counter — one check must
        count exactly once."""
        from scheduler_plugins_tpu.utils import observability as obs

        c = small_cluster(n_nodes=3, n_bound=3)
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        c.add_pod(mkpod("seed", created=40))
        run_cycle(sched, c, now=1000, serve=engine)
        assert engine.refresh(c, [], now_ms=1500) is not None
        # an extended-resource pod lands BOUND in the store (outside the
        # canonical axis): the fast expectation cannot be built
        ext = Pod(
            name="gpu", creation_ms=50,
            containers=[Container(requests={CPU: 100, "example.com/gpu": 1})],
        )
        ext.node_name = "n0"
        c.add_pod(ext)
        before = obs.metrics.snapshot().get(obs.ANTIENTROPY_CHECKS, 0)
        engine.verify(c)
        assert obs.metrics.snapshot()[obs.ANTIENTROPY_CHECKS] == before + 1


class TestPipelinedObservability:
    def test_overlap_gauges_and_tracer_rows(self):
        from scheduler_plugins_tpu.utils import observability as obs
        from tools.trace_smoke import validate_trace

        c = small_cluster()
        for i in range(4):
            c.add_pod(mkpod(f"p{i}", created=10 + i))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        engine = StreamingServeEngine().attach(c)
        pipe = PipelinedCycle(sched, c, serve=engine)
        obs.tracer.start(clear=True)
        try:
            pipe.tick(now=1000)
            c.add_pod(mkpod("p9", created=30))
            pipe.tick(now=2000)
            pipe.flush()
        finally:
            obs.tracer.stop()
            pipe.close()
        gauges = obs.metrics.snapshot()
        assert obs.CYCLE_OVERLAP_EFFICIENCY in gauges
        assert obs.CYCLE_PIPELINE_BUBBLE in gauges
        trace = obs.tracer.export()
        assert validate_trace(trace) == []
        rows = {
            e["args"]["name"]
            for e in trace["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        for row in ("Cycle/ingest", "Cycle/solve", "Cycle/finalize",
                    "Cycle/bind"):
            assert row in rows, (row, rows)
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        assert "ingest cycle 0" in names and "solve cycle 1" in names
        tls = [t.as_dict() for t in pipe.timelines]
        assert len(tls) == 2
        assert all(0.0 <= t["overlap_efficiency"] <= 1.0 for t in tls)
