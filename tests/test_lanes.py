"""K-lane optimistic-concurrency decision tables (parallel.lanes,
framework.laned_cycle — ISSUE 17).

The engine-level differential lives in
tests/test_differential.py::TestLanedCycleEquivalence; this file covers
the fence's decision tables on tiny, purpose-built shapes: two lanes
bidding one node's last capacity commit in serial-order priority,
cross-lane quota contention re-resolving exactly, the gang-whole
partition invariant, late lane-flusher binds absorbed as ordinary
deltas, and the deterministic (PYTHONHASHSEED-independent) partition.
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    POD_GROUP_LABEL,
    Container,
    ElasticQuota,
    Node,
    Pod,
    PodGroup,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import (
    LanedCycle,
    Profile,
    Scheduler,
    run_cycle,
)
from scheduler_plugins_tpu.parallel.lanes import (
    LaneSolver,
    fence_exact,
    lane_key,
    lane_of,
    partition_lanes,
)
from scheduler_plugins_tpu.plugins import (
    CapacityScheduling,
    Coscheduling,
    NodeResourcesAllocatable,
)
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

gib = 1 << 30


def mknode(name, cpu=16_000, mem=64 * gib):
    return Node(name=name, allocatable={CPU: cpu, MEMORY: mem, PODS: 110})


def mkpod(name, cpu, ns="default", created=0, labels=None):
    return Pod(
        name=name, namespace=ns, creation_ms=created, labels=labels or {},
        containers=[Container(requests={CPU: cpu, MEMORY: gib})],
    )


def distinct_lane_namespaces(k, count):
    """`count` namespace names that land on pairwise-distinct lanes at
    `k` — found by deterministic search (the partition is a stable
    blake2b hash, so the same names work on every run/host)."""
    chosen, lanes = [], set()
    i = 0
    while len(chosen) < count:
        ns = f"ns{i}"
        lane = lane_of("ns:" + ns, k)
        if lane not in lanes:
            lanes.add(lane)
            chosen.append(ns)
        i += 1
        assert i < 1000
    return chosen


class TestPartition:
    def test_deterministic_and_order_preserving(self):
        c = Cluster()
        pods = [mkpod(f"p{i}", 100, ns=f"t{i % 5}", created=i)
                for i in range(40)]
        for p in pods:
            c.add_pod(p)
        for k in (1, 2, 4, 8):
            for mode in ("namespace", "hash"):
                lanes = partition_lanes(pods, c, k, mode)
                again = partition_lanes(pods, c, k, mode)
                assert lanes == again
                flat = sorted(i for lane in lanes for i in lane)
                assert flat == list(range(len(pods)))
                for lane in lanes:
                    assert lane == sorted(lane)  # subsequence of order

    def test_hash_mode_keys_on_admission_serial(self):
        c = Cluster()
        c.enable_pending_index()
        pods = [mkpod(f"p{i}", 100) for i in range(8)]
        for p in pods:
            c.add_pod(p)
        # same namespace: "namespace" mode collapses to one lane,
        # "hash" mode sprays by admission serial
        ns_lanes = partition_lanes(pods, c, 4, "namespace")
        assert sum(1 for lane in ns_lanes if lane) == 1
        hash_lanes = partition_lanes(pods, c, 4, "hash")
        assert sum(1 for lane in hash_lanes if lane) > 1

    def test_gang_never_splits_across_lanes(self):
        """A PodGroup's members key on the gang name, NEVER the
        namespace/serial — a split gang would let two lanes each count
        a partial quorum."""
        c = Cluster()
        c.enable_pending_index()
        pods = []
        for g in range(3):
            c.add_pod_group(PodGroup(
                name=f"g{g}", namespace=f"t{g}", min_member=3,
            ))
            for m in range(4):
                pod = mkpod(
                    f"g{g}-m{m}", 100, ns=f"t{g}", created=g * 10 + m,
                    labels={POD_GROUP_LABEL: f"g{g}"},
                )
                c.add_pod(pod)
                pods.append(pod)
        for i in range(6):
            pod = mkpod(f"solo{i}", 100, ns=f"t{i % 3}", created=100 + i)
            c.add_pod(pod)
            pods.append(pod)
        for k in (2, 3, 4, 8):
            for mode in ("namespace", "hash"):
                lanes = partition_lanes(pods, c, k, mode)
                for g in range(3):
                    member_lanes = {
                        j
                        for j, lane in enumerate(lanes)
                        for i in lane
                        if pods[i].labels.get(POD_GROUP_LABEL) == f"g{g}"
                    }
                    assert len(member_lanes) == 1, (k, mode, g)

    def test_lpt_balances_skewed_segments(self):
        """Segments pack onto lanes by deterministic LPT, so one huge
        namespace plus many small ones still yields near-equal lane
        sizes — a hash spray would let the big tenant's lane dominate
        the critical path (the longest lane's scan IS the laned solve
        boundary)."""
        from scheduler_plugins_tpu.parallel.lanes import partition_segments

        c = Cluster()
        pods = []
        for i in range(60):  # one tenant with 60 pods...
            pods.append(mkpod(f"big{i}", 100, ns="big", created=i))
        for t in range(30):  # ...and 30 singleton tenants
            pods.append(mkpod(f"s{t}", 100, ns=f"small{t}", created=100 + t))
        for p in pods:
            c.add_pod(p)
        lanes, seg_of_pod, lane_of_seg, seg_keys, fresh = (
            partition_segments(pods, c, 3)
        )
        sizes = sorted(len(lane) for lane in lanes)
        # LPT: big=60 alone on one lane, 30 singletons split 15/15
        assert sizes == [15, 15, 60]
        assert list(fresh) == list(range(len(pods)))
        # segments never split: every pod of a key rides one lane
        for i, p in enumerate(pods):
            assert lane_of_seg[seg_of_pod[i]] == next(
                j for j, lane in enumerate(lanes) if i in lane
            )

    def test_key_cache_steady_state_and_gang_label_holdout(self):
        """The caller-owned key cache memoizes per-pod keys across
        cycles — but a pod wearing a pod-group label whose PodGroup is
        NOT yet registered must never cache (its key flips from `ns:` to
        `gang:` the moment the group appears; a stale entry could split
        the gang across lanes)."""
        from scheduler_plugins_tpu.parallel.lanes import partition_segments

        c = Cluster()
        c.enable_pending_index()
        plain = [mkpod(f"p{i}", 100, ns=f"t{i % 3}", created=i)
                 for i in range(6)]
        orphan = mkpod(
            "orphan", 100, ns="t0", created=50,
            labels={POD_GROUP_LABEL: "late-group"},
        )
        pods = plain + [orphan]
        for p in pods:
            c.add_pod(p)
        cache: dict = {}
        first = partition_segments(pods, c, 2, "namespace", cache)
        # plain pods cached; the unresolved gang label held out
        assert all(p.uid in cache for p in plain)
        assert orphan.uid not in cache
        second = partition_segments(pods, c, 2, "namespace", cache)
        assert first[0] == second[0]  # cache hit changes nothing
        # only the orphan re-keys (every cycle, until its group registers)
        assert list(second[4]) == [pods.index(orphan)]

    def test_key_cache_orphan_rekeys_until_group_registers(self):
        from scheduler_plugins_tpu.parallel.lanes import partition_segments

        c = Cluster()
        c.enable_pending_index()
        orphan = mkpod(
            "orphan", 100, ns="t0", created=0,
            labels={POD_GROUP_LABEL: "late-group"},
        )
        c.add_pod(orphan)
        cache: dict = {}
        _, _, _, keys1, fresh1 = partition_segments(
            [orphan], c, 2, "namespace", cache
        )
        assert keys1[0].startswith("ns:") and list(fresh1) == [0]
        c.add_pod_group(PodGroup(
            name="late-group", namespace="t0", min_member=1,
        ))
        _, _, _, keys2, fresh2 = partition_segments(
            [orphan], c, 2, "namespace", cache
        )
        # the key flipped to the gang key AND is now cacheable
        assert keys2[0].startswith("gang:") and list(fresh2) == [0]
        assert cache[orphan.uid] == keys2[0]

    def test_unknown_modes_rejected(self):
        with pytest.raises(ValueError):
            partition_lanes([], None, 2, "roundrobin")
        with pytest.raises(ValueError):
            LaneSolver(Scheduler(Profile(
                plugins=[NodeResourcesAllocatable()]
            )), k=2, dispatch="fibers")
        with pytest.raises(ValueError):
            LaneSolver(Scheduler(Profile(
                plugins=[NodeResourcesAllocatable()]
            )), k=0)


def _twin_clusters(build):
    a, b = Cluster(), Cluster()
    build(a)
    build(b)
    return a, b


class TestConflictFence:
    def test_last_capacity_commits_in_serial_order(self):
        """Two lanes bid the same node's last capacity slot: the fence
        walks the defined serial order, so the earlier-queued pod wins
        and the later one re-resolves against committed state — exactly
        the serial outcome, with the conflict and re-resolve counted."""
        ns_a, ns_b = distinct_lane_namespaces(2, 2)

        def build(c):
            c.add_node(Node(
                name="n0", allocatable={CPU: 1000, MEMORY: 8 * gib,
                                        PODS: 110},
            ))
            c.add_pod(mkpod("first", 800, ns=ns_a, created=10))
            c.add_pod(mkpod("second", 800, ns=ns_b, created=20))

        laned_c, serial_c = _twin_clusters(build)
        sched_l = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        sched_s = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        laned = LanedCycle(sched_l, laned_c, k=2)
        ra = laned.tick(now=1000)
        rb = run_cycle(sched_s, serial_c, now=1000)
        assert dict(ra.bound) == dict(rb.bound) == {f"{ns_a}/first": "n0"}
        assert sorted(ra.failed) == sorted(rb.failed) == [f"{ns_b}/second"]
        assert dict(ra.failed_by) == dict(rb.failed_by)
        assert ra.lanes["path"] == "laned"
        assert sum(ra.lanes["conflicts"]) == 1
        assert ra.lanes["re_resolved"] == 1
        laned.close()

    def test_cross_lane_quota_contention_reresolves_exactly(self):
        """Two quota'd namespaces in different lanes contend the shared
        aggregate-Min headroom: each lane's speculative admit passes in
        isolation, the fence detects the second pod's verdict flip
        against committed usage and re-resolves it — the serial
        queue-order quota outcome, bit for bit."""
        ns_a, ns_b = distinct_lane_namespaces(2, 2)

        def build(c):
            c.add_node(mknode("n0"))
            c.add_node(mknode("n1"))
            for ns in (ns_a, ns_b):
                c.add_quota(ElasticQuota(
                    name=f"eq-{ns}", namespace=ns,
                    min={CPU: 1000, MEMORY: 8 * gib},
                    max={CPU: 16_000, MEMORY: 64 * gib},
                ))
            # agg Min = 2000 CPU: the first 1500 fits, the second's
            # 1500 overflows only once the first's usage is committed
            c.add_pod(mkpod("first", 1500, ns=ns_a, created=10))
            c.add_pod(mkpod("second", 1500, ns=ns_b, created=20))

        laned_c, serial_c = _twin_clusters(build)

        def mk_sched():
            return Scheduler(Profile(plugins=[
                NodeResourcesAllocatable(), CapacityScheduling(),
            ]))

        laned = LanedCycle(mk_sched(), laned_c, k=2)
        ra = laned.tick(now=1000)
        rb = run_cycle(mk_sched(), serial_c, now=1000)
        assert dict(ra.bound) == dict(rb.bound)
        assert list(ra.bound) == [f"{ns_a}/first"]
        assert sorted(ra.failed) == sorted(rb.failed) == [f"{ns_b}/second"]
        # the re-resolved pod's attribution names the quota plugin,
        # identically on both engines
        assert dict(ra.failed_by) == dict(rb.failed_by)
        assert ra.failed_by[f"{ns_b}/second"] == "CapacityScheduling"
        assert sum(ra.lanes["conflicts"]) == 1
        assert ra.lanes["re_resolved"] == 1
        laned.close()

    def test_disjoint_tenants_commit_wholesale(self):
        """Fully disjoint per-lane traffic: zero conflicts, every lane
        commits wholesale, no repair dispatch."""
        ns = distinct_lane_namespaces(4, 4)

        def build(c):
            for i in range(4):
                c.add_node(mknode(f"n{i}"))
            for j, n in enumerate(ns):
                for i in range(3):
                    c.add_pod(mkpod(
                        f"{n}-p{i}", 500, ns=n, created=j * 10 + i
                    ))

        laned_c, serial_c = _twin_clusters(build)
        sched_l = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        sched_s = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        laned = LanedCycle(sched_l, laned_c, k=4)
        ra = laned.tick(now=1000)
        rb = run_cycle(sched_s, serial_c, now=1000)
        assert dict(ra.bound) == dict(rb.bound)
        assert len(ra.bound) == 12
        assert ra.lanes["path"] == "laned"
        assert sum(ra.lanes["conflicts"]) == 0
        assert ra.lanes["re_resolved"] == 0
        assert ra.lanes["sizes"] == [3, 3, 3, 3]
        laned.close()

    def test_conflict_metrics_fire(self):
        ns_a, ns_b = distinct_lane_namespaces(2, 2)
        c = Cluster()
        c.add_node(Node(
            name="n0", allocatable={CPU: 1000, MEMORY: 8 * gib, PODS: 110},
        ))
        c.add_pod(mkpod("first", 800, ns=ns_a, created=10))
        c.add_pod(mkpod("second", 800, ns=ns_b, created=20))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        laned = LanedCycle(sched, c, k=2)
        before = obs.metrics.snapshot()
        conflicts0 = sum(
            v for k_, v in before.items()
            if k_.startswith(obs.LANE_CONFLICTS)
        )
        rr0 = before.get(obs.LANE_RERESOLVES, 0)
        laned.tick(now=1000)
        after = obs.metrics.snapshot()
        conflicts1 = sum(
            v for k_, v in after.items()
            if k_.startswith(obs.LANE_CONFLICTS)
        )
        assert conflicts1 == conflicts0 + 1
        assert after[obs.LANE_RERESOLVES] == rr0 + 1
        laned.close()


class TestSerialFallbackGate:
    def test_nominees_reject_the_gate(self):
        """Preemption nominees couple the built-in fit to the cross-lane
        placed_mask carry — the gate must route such snapshots to the
        sequential parity solve, counted as a fallback."""
        c = Cluster()
        c.add_node(mknode("n0"))
        nominee = mkpod("nom", 500, created=5)
        nominee.nominated_node_name = "n0"
        c.add_pod(nominee)
        c.add_pod(mkpod("p0", 500, created=10))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        snap, _ = c.snapshot(c.pending_pods(), now_ms=1000)
        ok, reason = fence_exact(sched, snap)
        assert not ok and reason == "nominees"

    def test_gang_quota_tables_pass_the_gate(self):
        """Gang + quota side state is exactly what the fence's host
        twins model — the gate must NOT reject it (the empty padded
        quota-nominee row is inert)."""
        c = Cluster()
        c.add_node(mknode("n0"))
        c.add_quota(ElasticQuota(
            name="eq", namespace="team",
            min={CPU: 4000, MEMORY: 16 * gib},
            max={CPU: 8000, MEMORY: 32 * gib},
        ))
        c.add_pod_group(PodGroup(name="g", namespace="team", min_member=1))
        c.add_pod(mkpod(
            "m0", 500, ns="team", labels={POD_GROUP_LABEL: "g"},
        ))
        sched = Scheduler(Profile(plugins=[
            NodeResourcesAllocatable(),
            Coscheduling(),
            CapacityScheduling(),
        ]))
        snap, _ = c.snapshot(c.pending_pods(), now_ms=1000)
        ok, reason = fence_exact(sched, snap)
        assert ok, reason

    def test_fallback_cycle_still_matches_serial(self):
        """Gate-rejected cycles are still bit-identical — they run THE
        parity solve — and the fallback is attributed on the report."""
        def build(c):
            c.add_node(mknode("n0"))
            nominee = mkpod("nom", 500, created=5)
            nominee.nominated_node_name = "n0"
            c.add_pod(nominee)
            c.add_pod(mkpod("p0", 500, created=10))

        laned_c, serial_c = _twin_clusters(build)
        sched_l = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        sched_s = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        laned = LanedCycle(sched_l, laned_c, k=4)
        ra = laned.tick(now=1000)
        rb = run_cycle(sched_s, serial_c, now=1000)
        assert dict(ra.bound) == dict(rb.bound)
        assert ra.lanes["path"] == "serial"
        assert ra.lanes["serial_fallback_reason"] == "nominees"
        assert laned.serial_fallbacks == 1
        laned.close()

    def test_packing_profiles_rejected_at_construction(self):
        sched = Scheduler(Profile(
            plugins=[NodeResourcesAllocatable()], solve_mode="packing",
        ))
        with pytest.raises(ValueError):
            LanedCycle(sched, Cluster(), k=2)


class TestLaneDispatchModes:
    def test_threads_dispatch_matches_fused(self):
        ns = distinct_lane_namespaces(2, 2)

        def build(c):
            for i in range(3):
                c.add_node(mknode(f"n{i}"))
            for j, n in enumerate(ns):
                for i in range(3):
                    c.add_pod(mkpod(
                        f"{n}-p{i}", 700, ns=n, created=j * 10 + i
                    ))

        fused_c, threads_c = _twin_clusters(build)
        sched_f = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        sched_t = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        fused = LanedCycle(sched_f, fused_c, k=2, dispatch="fused")
        threads = LanedCycle(sched_t, threads_c, k=2, dispatch="threads")
        ra = fused.tick(now=1000)
        rb = threads.tick(now=1000)
        assert dict(ra.bound) == dict(rb.bound)
        assert len(ra.bound) == 6
        fused.close()
        threads.close()


class TestLateLaneBinds:
    def test_late_flusher_bind_absorbed_as_delta(self):
        """A lane flush overtaken by an EXTERNAL sink drain is counted
        late and absorbed as an ordinary delta of the next window — the
        resident serving state stays byte-exact (the PR 6 classification,
        shared with the pipelined engine's flusher)."""
        import threading

        from scheduler_plugins_tpu.serving import StreamingServeEngine

        c = Cluster()
        for i in range(3):
            c.add_node(mknode(f"n{i}"))
        c.add_pod(mkpod("p0", 500, created=10))
        engine = StreamingServeEngine().attach(c)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        laned = LanedCycle(sched, c, k=2, serve=engine, async_bind=True)
        before = obs.metrics.snapshot().get(obs.CYCLE_LATE_BINDS, 0)
        gate = threading.Event()
        # stall the flusher so this tick's bind job runs AFTER the
        # external drain below
        laned._flusher.submit(gate.wait)
        laned.tick(now=1000)
        engine.refresh(c, [], now_ms=1500)  # external drain boundary
        gate.set()
        laned.flush()
        assert obs.metrics.snapshot()[obs.CYCLE_LATE_BINDS] == before + 1
        # the late bind is an ordinary delta of the NEXT window
        assert engine.refresh(c, [], now_ms=2000) is not None
        assert engine.verify(c) is None
        laned.close()


class TestLaneChurnMicro:
    """Zoned-tenant churn with an adversarial contended tail, solved by
    the defined serial order AND by `LaneSolver` at K in {1, 2} on the same
    snapshot every cycle: assignment, admitted and wait bit-equal on every
    cycle, clean hard-constraint audit, no serial fallback, and real
    cross-lane conflicts re-resolved through the fence on the tail."""

    #: 8 tenants over 4 zone resources on 8 deep nodes that never fill,
    #: plus one node whose scarce `hot` resource 4 distinct-tenant bidders
    #: race for 2 slots of on the contended cycle
    N, ZONES, TENANTS, ARRIVE, DEPART = 8, 4, 8, 64, 64
    HOT_SLOTS, HOT_BIDDERS, CYCLES = 2, 4, 4

    def _cluster(self, rng):
        c = Cluster()
        for i in range(self.N):
            c.add_node(Node(
                name=f"node-{i:04d}",
                allocatable={CPU: 256_000, MEMORY: 1024 * gib, PODS: 1024,
                             f"example.com/zone-{i % self.ZONES}": 100_000},
            ))
        c.add_node(Node(
            name="node-hot",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 512,
                         "example.com/hot": self.HOT_SLOTS},
        ))
        for i in range(32):
            j = i % self.N
            pod = Pod(
                name=f"bound-{i:06d}", creation_ms=i,
                namespace=f"tenant-{i % self.TENANTS:03d}",
                containers=[Container(requests={
                    CPU: int(rng.integers(100, 900)), MEMORY: gib,
                    f"example.com/zone-{j % self.ZONES}": 1,
                })],
            )
            pod.node_name = f"node-{j:04d}"
            c.add_pod(pod)
        c.enable_pending_index()
        return c

    def test_identity_conflicts_and_no_fallback(self):
        from scheduler_plugins_tpu.tuning.gates import hard_violations

        rng = np.random.default_rng(1)
        cluster = self._cluster(rng)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        solvers = {
            k: LaneSolver(sched, k=k, partition="namespace",
                          dispatch="sequential")
            for k in (1, 2)
        }
        serial_no = 0

        def arrive(n, hot=False):
            nonlocal serial_no
            for _ in range(n):
                serial_no += 1
                t = serial_no % self.TENANTS
                extra = ("example.com/hot" if hot
                         else f"example.com/zone-{t % self.ZONES}")
                cluster.add_pod(Pod(
                    name=f"{'hot' if hot else 'arr'}-{serial_no:06d}",
                    namespace=f"tenant-{t:03d}",
                    creation_ms=1_000_000 + serial_no,
                    containers=[Container(requests={
                        CPU: int(rng.integers(100, 900)), MEMORY: gib,
                        extra: 1,
                    })],
                ))

        conflicts = re_resolved = 0
        for cycle in range(self.CYCLES):
            now = 1000 * (cycle + 1)
            contended = cycle == self.CYCLES - 1
            arrive(self.ARRIVE - (self.HOT_BIDDERS if contended else 0))
            if contended:
                arrive(self.HOT_BIDDERS, hot=True)
            bound = sorted(u for u, p in cluster.pods.items()
                           if p.node_name is not None)
            for i in sorted(rng.choice(len(bound), replace=False,
                                       size=min(self.DEPART, len(bound)))):
                cluster.remove_pod(bound[int(i)])
            pending = cluster.pending_pods()
            P = len(pending)
            snap, meta = cluster.snapshot(pending, now_ms=now)
            sched.prepare(meta, cluster)
            res = sched.solve(snap, mode="sequential")
            serial = tuple(np.asarray(x) for x in (
                res.assignment, res.admitted, res.wait))
            a_ser, ok_ser, w_ser = serial
            assert hard_violations(snap, a_ser, w_ser)["total"] == 0
            for k, solver in solvers.items():
                *laned, _codes, st = solver.solve(
                    snap, pending, cluster, meta=meta
                )
                for got, want in zip(laned, serial):
                    np.testing.assert_array_equal(
                        np.asarray(got)[:P], want[:P], err_msg=f"{cycle} {k}"
                    )
                if k > 1:
                    assert st.path != "serial", (cycle, st)
                    if contended:
                        conflicts += sum(st.conflicts or [])
                        re_resolved += st.re_resolved
            for i, pod in enumerate(pending):
                if ok_ser[i] and a_ser[i] >= 0:
                    cluster.bind(pod.uid, meta.node_names[int(a_ser[i])],
                                 now_ms=now)
        for solver in solvers.values():
            solver.close()
        # the contended tail really collides, and the fence re-resolves
        assert conflicts > 0 and re_resolved > 0
        hot_bound = [p for p in cluster.pods.values()
                     if p.name.startswith("hot-") and p.node_name]
        assert len(hot_bound) == self.HOT_SLOTS
