"""Decision tables for the rank-aware gang placement engine (ISSUE 10).

Mirrors the reference's NetworkOverhead/Coscheduling unit-table style for
the COMPOSED path the reference never built: block-first packing, spill
ordering by cost (not index), quorum-fail leaving zero partial ranks,
quota caps, elastic shrink releasing highest-cost ranks first, elastic
growth anchoring on the resident block — plus the cycle/serving/recorder
seams (docs/GANGS.md)."""

import numpy as np

from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.framework.plugin import SolverState
from scheduler_plugins_tpu.gangs import (
    GangPhase,
    RankGangState,
    gang_cost_stats,
    gang_solve_np,
    shrink_select_np,
)
from scheduler_plugins_tpu.models import rank_gang_scenario
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable

I64 = np.int64
I32 = np.int32
GIB = 1 << 30


def make_state(n_nodes, n_blocks, rank_cpu_rows, min_ranks,
               block_cost=None, node_block=None, prev=None,
               quota_max_cpu=None, gang_ns=None):
    """Hand-built RankGangState: resource axis = (cpu, pods)."""
    G = len(rank_cpu_rows)
    M = max(len(r) for r in rank_cpu_rows)
    R = 2
    rank_req = np.zeros((G, M, R), I64)
    rank_mask = np.zeros((G, M), bool)
    for g, row in enumerate(rank_cpu_rows):
        for m, cpu in enumerate(row):
            rank_req[g, m] = (cpu, 1)
            rank_mask[g, m] = True
    if node_block is None:
        node_block = np.array(
            [i % n_blocks for i in range(n_nodes)], I32
        )
    if block_cost is None:
        block_cost = np.full((n_blocks, n_blocks), 10, I32)
        np.fill_diagonal(block_cost, 1)
    if prev is None:
        prev = np.full((G, M), -1, I32)
    quota_max = np.full((1, R), np.iinfo(I64).max, I64)
    quota_has = np.zeros(1, bool)
    if quota_max_cpu is not None:
        quota_max[0, 0] = quota_max_cpu
        quota_has[0] = True
    return RankGangState(
        rank_req=rank_req, rank_mask=rank_mask, prev_assigned=prev,
        min_ranks=np.asarray(min_ranks, I32),
        gang_ns=(np.asarray(gang_ns, I32) if gang_ns is not None
                 else np.full(G, -1, I32)),
        gang_mask=np.ones(G, bool),
        node_block=np.asarray(node_block, I32),
        block_cost=np.asarray(block_cost, I32),
        quota_max=quota_max, quota_has=quota_has,
    )


def solve(gangs, free_cpu_per_node, pods_per_node=8):
    N = len(free_cpu_per_node)
    # synthetic (cpu, pods) axis local to these tables (not CANONICAL —
    # the gang solve is axis-order agnostic)
    free0 = np.zeros((N, 2), I64)
    free0[:, 0] = free_cpu_per_node  # graft-lint: ignore[GL005]
    free0[:, 1] = pods_per_node  # graft-lint: ignore[GL005]
    eq0 = np.zeros((gangs.quota_max.shape[0], 2), I64)
    return gang_solve_np(gangs, free0, eq0, np.ones(N, bool))


class TestTopologyDecisionTables:
    def test_block_first_packing(self):
        # blocks 0/1/2 over 6 nodes round-robin; block 1 has the most
        # capacity -> the whole gang lands in block 1 (nodes 1 and 4)
        gangs = make_state(
            6, 3, [[1000] * 4], [4],
        )
        free = [1000, 4000, 1000, 1000, 4000, 1000]
        rank_nodes, admitted, placed, *_ = solve(gangs, free)
        assert admitted[0]
        assert placed[0] == 4
        chosen = rank_nodes[0, :4]
        assert set(np.asarray(gangs.node_block)[chosen]) == {1}
        # lowest-index node of the block fills first (sequential twin
        # tie-break), then the next node of the SAME block
        assert list(chosen) == [1, 1, 1, 1] or list(chosen) == [1, 1, 1, 4]

    def test_spill_ordered_by_cost_not_index(self):
        # all blocks pack 2 of the 4 ranks (equal packed capacity ->
        # primary = block 0, lowest index); the spill must go to block 2
        # (cost 3 from block 0), NOT block 1 (cost 30, lower index)
        block_cost = np.array([
            [1, 30, 3],
            [30, 1, 5],
            [3, 5, 1],
        ], I32)
        gangs = make_state(
            3, 3, [[1000] * 4], [4], block_cost=block_cost,
            node_block=[0, 1, 2],
        )
        free = [2000, 2000, 2000]
        rank_nodes, admitted, placed, *_ = solve(gangs, free)
        assert admitted[0]
        blocks = np.asarray(gangs.node_block)[rank_nodes[0, :4]]
        assert list(blocks) == [0, 0, 2, 2]
        max_cost, _ = gang_cost_stats(
            rank_nodes, gangs.rank_mask, gangs.node_block, gangs.block_cost
        )
        assert max_cost[0] == 3

    def test_quorum_fail_leaves_zero_partial_ranks(self):
        # capacity fits only 2 of min 4 -> NOTHING places, free untouched
        gangs = make_state(2, 2, [[1000] * 4], [4], node_block=[0, 1])
        free = [1000, 1000]
        rank_nodes, admitted, placed, free_out, _ = solve(gangs, free)
        assert not admitted[0]
        assert placed[0] == 0
        assert (rank_nodes == -1).all()
        assert (free_out[:, 0] == [1000, 1000]).all()

    def test_elastic_prefix_above_quorum_is_kept(self):
        # min 2 of 4 ranks; capacity fits 3 -> prefix of 3 places (the
        # elastic partial-width case), 4th retries later
        gangs = make_state(1, 1, [[1000] * 4], [2], node_block=[0])
        free = [3000]
        rank_nodes, admitted, placed, *_ = solve(gangs, free)
        assert admitted[0]
        assert placed[0] == 3
        assert list(rank_nodes[0]) == [0, 0, 0, -1]

    def test_quota_cap_rejects_whole_gang(self):
        # namespace max 2500 cpu < gang demand 4000 -> quota kills rank 3
        # below quorum -> whole gang rejected, zero partial ranks
        gangs = make_state(
            2, 1, [[1000] * 4], [4], node_block=[0, 0],
            quota_max_cpu=2500, gang_ns=[0],
        )
        free = [8000, 8000]
        rank_nodes, admitted, placed, free_out, eq_out = solve(gangs, free)
        assert not admitted[0]
        assert (rank_nodes == -1).all()
        assert (eq_out == 0).all()

    def test_heterogeneous_launcher_rank(self):
        # rank 0 (the launcher) wants 2x. Block totals would fit the gang
        # (7500 <= 8000) but PER-NODE granularity cannot (3000 + 1500 >
        # 4000): the launcher takes node 0, two workers pack node 2 (the
        # block's next node, exact first-fit), and the last worker —
        # which no block-0 node can hold any more — spills across blocks.
        gangs = make_state(
            4, 2, [[3000, 1500, 1500, 1500]], [4],
            node_block=[0, 1, 0, 1],
        )
        free = [4000, 4000, 4000, 4000]
        rank_nodes, admitted, placed, *_ = solve(gangs, free)
        assert admitted[0]
        assert list(rank_nodes[0]) == [0, 2, 2, 1]
        max_cost, _ = gang_cost_stats(
            rank_nodes, gangs.rank_mask, gangs.node_block, gangs.block_cost
        )
        assert max_cost[0] == 10  # the one cross-block pair

    def test_growth_anchors_on_resident_block(self):
        # gang has 2 residents in block 1; block 0 has MORE free capacity
        # but growth must anchor on the resident block
        prev = np.full((1, 4), -1, I32)
        prev[0, 0] = 1  # resident on node 1 (block 1)
        prev[0, 1] = 3  # resident on node 3 (block 1)
        gangs = make_state(
            4, 2, [[1000] * 4], [2], node_block=[0, 1, 0, 1], prev=prev,
        )
        free = [8000, 2000, 8000, 2000]
        rank_nodes, admitted, placed, *_ = solve(gangs, free)
        assert admitted[0]
        assert placed[0] == 2
        grown = rank_nodes[0, 2:4]
        assert set(np.asarray(gangs.node_block)[grown]) == {1}

    def test_shrink_releases_highest_cost_ranks_first(self):
        # ranks 0-2 packed in block 0, rank 3 stranded in a cost-50
        # block -> the outlier releases first; among equals the HIGHEST
        # index goes (the launcher, rank 0, leaves last)
        block_cost = np.array([[1, 50], [50, 1]], I32)
        node_block = np.asarray([0, 0, 1], I32)
        rank_nodes = np.asarray([[0, 0, 1, 2]], I32)
        live = np.ones((1, 4), bool)
        release = shrink_select_np(
            rank_nodes, live, node_block, block_cost,
            np.asarray([1], I32),
        )
        assert list(release[0]) == [False, False, False, True]
        release2 = shrink_select_np(
            rank_nodes, live, node_block, block_cost,
            np.asarray([2], I32),
        )
        # all remaining ranks tie at max cost 50 (each pairs with the
        # outlier)... after the outlier, ties release highest index first
        assert list(release2[0]) == [False, False, True, True]


class TestGangPhaseCycle:
    SHAPE = dict(n_nodes=16, n_regions=2, zones_per_region=2, n_mpi=2,
                 mpi_ranks=4, n_dl=1, dl_min=2, dl_desired=3, dl_max=5)

    def _arm(self, **kw):
        cluster = rank_gang_scenario(seed=0, **{**self.SHAPE, **kw})
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        return cluster, scheduler, GangPhase(check_twin=True)

    def test_phase_binds_whole_gangs_and_consumes_members(self):
        cluster, scheduler, phase = self._arm()
        report = run_cycle(scheduler, cluster, now=10_000, gangs=phase)
        assert report.rank_gangs, "phase produced no gang stats"
        for name, row in report.rank_gangs.items():
            assert row["admitted"], name
            pg = cluster.pod_groups[name]
            bound = [
                p for p in cluster.gang_members(pg)
                if p.node_name is not None
            ]
            assert len(bound) >= pg.min_member
        # drift 0.0: jit and numpy twin bit-agree on the real cycle
        assert phase.last_drift == 0.0
        # no rank pod leaked into the per-pod solve or stayed pending
        assert not cluster.pending_pods()
        # events rode the shared kind table (no literal strings)
        from scheduler_plugins_tpu.api import events as ev

        assert set(cluster.event_last) <= ev.EVENT_KINDS
        assert ev.POD_UPDATE in cluster.event_last  # the binds

    def test_rank_aware_placement_costs_less_than_quorum_only(self):
        """The same scenario through the gang phase and through quorum-only
        Coscheduling, both audited with the solver's own yardstick
        (`block_cost_view` + `gang_cost_stats`): the rank-aware arm's worst
        inter-rank pair is strictly cheaper, with every gang admitted."""
        from scheduler_plugins_tpu import plugins as P
        from scheduler_plugins_tpu.gangs.phase import block_cost_view

        shape = dict(n_nodes=48, n_regions=2, zones_per_region=2, n_mpi=4,
                     mpi_ranks=6, n_dl=2, dl_min=2, dl_desired=3, dl_max=6)

        def worst_pair(phase):
            cluster = rank_gang_scenario(seed=0, **shape)
            scheduler = Scheduler(Profile(plugins=[
                NodeResourcesAllocatable(), P.Coscheduling(),
                P.CapacityScheduling(),
            ]))
            for cycle in range(8):
                run_cycle(scheduler, cluster, now=10_000 * (cycle + 1),
                          gangs=phase)
                if not cluster.pending_pods():
                    break
            node_pos, zones, block_cost = block_cost_view(cluster)
            gangs = [pg for _, pg in sorted(cluster.pod_groups.items())
                     if pg.rank_aware]
            placed = [
                [node_pos[p.node_name] for p in cluster.gang_members(pg)
                 if p.node_name in node_pos]
                for pg in gangs
            ]
            assert all(len(b) >= pg.min_member
                       for b, pg in zip(placed, gangs))
            M = max(len(b) for b in placed)
            rank_nodes = np.full((len(placed), M), -1, I32)
            for g, b in enumerate(placed):
                rank_nodes[g, :len(b)] = b
            max_cost, _ = gang_cost_stats(
                rank_nodes, rank_nodes >= 0, zones, block_cost
            )
            return int(max_cost.max())

        phase = GangPhase(check_twin=True)
        assert worst_pair(phase) < worst_pair(None)
        assert phase.last_drift == 0.0

    def test_quorum_fail_parks_all_members_with_backoff(self):
        # a fleet too small for one gang: every member parks, none binds
        cluster, scheduler, phase = self._arm()
        # shrink the fleet to 1 tiny node so nothing fits
        for name in list(cluster.nodes):
            cluster.remove_node(name)
        from scheduler_plugins_tpu.api.objects import Node

        cluster.add_node(Node(name="tiny", allocatable={"cpu": 100}))
        report = run_cycle(scheduler, cluster, now=10_000, gangs=phase)
        assert not report.bound
        assert report.rejected_gangs
        for uid in report.failed:
            assert uid in cluster.unschedulable_since
            assert report.failed_by[uid] == "RankGangPlacement"
        for pg in cluster.pod_groups.values():
            bound = sum(
                1 for p in cluster.gang_members(pg)
                if p.node_name is not None
            )
            assert bound == 0  # zero partial ranks

    def test_elastic_grow_and_shrink_converge(self):
        cluster, scheduler, phase = self._arm()
        run_cycle(scheduler, cluster, now=10_000, gangs=phase)
        dl = next(
            pg for pg in cluster.pod_groups.values()
            if pg.desired_replicas is not None
        )

        def live():
            return [
                p for p in cluster.gang_members(dl)
                if p.node_name is not None
            ]

        assert len(live()) == 3
        dl.desired_replicas = 5
        cluster.add_pod_group(dl)  # PodGroup/Update
        run_cycle(scheduler, cluster, now=20_000, gangs=phase)
        assert len(live()) == 5, "grow did not converge in one cycle"
        # shrink back to the quorum floor: highest-cost ranks leave first
        before = {p.uid for p in live()}
        dl.desired_replicas = 2
        cluster.add_pod_group(dl)
        run_cycle(scheduler, cluster, now=30_000, gangs=phase)
        survivors = {p.uid for p in live()}
        assert len(survivors) == 2
        assert survivors <= before
        # the survivors sit in ONE block (the released ranks were the
        # topology outliers by construction of the selection keys)
        zones = {
            cluster.nodes[p.node_name].zone for p in live()
        }
        assert len(zones) == 1

    def test_host_twin_mode_places_identically(self):
        a = self._arm()
        b_cluster, b_sched, _ = self._arm()
        run_cycle(a[1], a[0], now=10_000, gangs=a[2])
        run_cycle(b_sched, b_cluster, now=10_000,
                  gangs=GangPhase(host_twin=True))
        place_a = {
            u: p.node_name for u, p in a[0].pods.items() if p.node_name
        }
        place_b = {
            u: p.node_name for u, p in b_cluster.pods.items() if p.node_name
        }
        assert place_a == place_b

    def test_wave_mode_places_identically_with_zero_drift(self):
        """ISSUE 12: a `GangPhase(wave=True)` cycle — the wave-batched
        solve — binds the SAME placements as the sequential phase, and
        with `check_twin` the numpy twin cross-check reports drift 0.0
        on the real cycle (the bit-identity claim, at phase level)."""
        a = self._arm()
        b_cluster, b_sched, _ = self._arm()
        run_cycle(a[1], a[0], now=10_000, gangs=a[2])
        wave_phase = GangPhase(check_twin=True, wave=True, wave_width=4)
        run_cycle(b_sched, b_cluster, now=10_000, gangs=wave_phase)
        place_a = {
            u: p.node_name for u, p in a[0].pods.items() if p.node_name
        }
        place_b = {
            u: p.node_name for u, p in b_cluster.pods.items() if p.node_name
        }
        assert place_a == place_b
        assert wave_phase.max_drift == 0.0


class TestServingSeam:
    def test_gang_roster_serves_resident(self):
        """ISSUE 12: a gang/quota roster no longer degrades the serving
        engine to the O(cluster) full-snapshot fallback — the resident
        gang/quota side tables own it (zero `gang_fallbacks`), and the
        per-gang resident-rank mirror stays maintained O(changed)."""
        from scheduler_plugins_tpu.serving import ServeEngine

        cluster = rank_gang_scenario(
            seed=0, n_nodes=8, n_regions=1, zones_per_region=2, n_mpi=1,
            mpi_ranks=3, n_dl=0,
        )
        engine = ServeEngine().attach(cluster)
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        phase = GangPhase()
        report = run_cycle(
            scheduler, cluster, now=10_000, serve=engine, gangs=phase
        )
        assert report.bound  # the gang placed
        # the roster is compatible: every refresh serves resident
        assert engine.gang_fallbacks == 0
        # the resident-served gang problem places IDENTICALLY to the
        # fresh-snapshot phase (the O(changed) lowering changes WHERE
        # the inputs come from, never what the solve decides)
        control = rank_gang_scenario(
            seed=0, n_nodes=8, n_regions=1, zones_per_region=2, n_mpi=1,
            mpi_ranks=3, n_dl=0,
        )
        control_report = run_cycle(
            Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
            control, now=10_000, gangs=GangPhase(),
        )
        assert report.bound == control_report.bound
        # ...while absorbing the binds into the resident-rank mirror
        gang_name = next(iter(cluster.pod_groups))
        refreshed = engine.refresh(cluster, [], now_ms=20_000)  # drain
        assert refreshed is not None, "gang roster fell back"
        assert gang_name in engine.resident_ranks
        assert set(engine.resident_ranks[gang_name]) == set(report.bound)
        # a member delete leaves the mirror O(changed)
        victim = next(iter(report.bound))
        cluster.remove_pod(victim)
        assert engine.refresh(cluster, [], now_ms=30_000) is not None
        assert victim not in engine.resident_ranks.get(gang_name, {})
        # a still-gating side table (an NRT) forces the fallback AND
        # counts it as a gang fallback while PodGroups exist
        from scheduler_plugins_tpu.api.objects import (
            NodeResourceTopology,
        )

        cluster.add_nrt(NodeResourceTopology(node_name="n000", zones=[]))
        assert engine.refresh(cluster, [], now_ms=40_000) is None
        assert engine.gang_fallbacks == 1
        cluster.remove_nrt("n000")
        # gangs drained away -> plain serving continues
        for uid in list(cluster.pods):
            cluster.remove_pod(uid)
        for name in list(cluster.pod_groups):
            del cluster.pod_groups[name]
        cluster.quotas.clear()
        cluster.app_groups.clear()
        cluster.network_topologies.clear()
        assert engine.compatible(cluster, [])


class TestFlightRecorderSeam:
    def test_recorded_gang_cycle_replays_bit_identically(self):
        from scheduler_plugins_tpu.utils import flightrec
        from scheduler_plugins_tpu.utils.flightrec import unpack_pytree

        cluster = rank_gang_scenario(
            seed=1, n_nodes=12, n_regions=2, zones_per_region=2, n_mpi=2,
            mpi_ranks=3, n_dl=1, dl_min=2, dl_desired=2, dl_max=4,
        )
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        phase = GangPhase()
        flightrec.recorder.start(capacity=4)
        try:
            run_cycle(scheduler, cluster, now=10_000, gangs=phase)
            recs = flightrec.recorder.records()
        finally:
            flightrec.recorder.stop()
        assert recs, "gang cycle was not recorded"
        spec = recs[-1].manifest.get("rank_gangs")
        assert spec is not None, "record carries no gang capture"
        cap = unpack_pytree(spec, recs[-1].blobs)
        gangs = RankGangState(**cap["gangs"])
        rank_nodes, admitted, _, _, _ = gang_solve_np(
            gangs, cap["free0"], cap["eq_used0"], cap["node_mask"]
        )
        assert (rank_nodes == cap["rank_nodes"]).all()
        assert (admitted == cap["admitted"]).all()


class TestReviewRegressions:
    """Regressions for the PR-10 review findings."""

    def test_extended_resource_member_does_not_crash_the_phase(self):
        # the problem snapshot must union the resource axis over EVERY
        # consumed member — a one-pod union KeyError'd encoding the rest
        from scheduler_plugins_tpu.api.objects import (
            Container, Pod, PodGroup, POD_GROUP_LABEL,
        )

        cluster = rank_gang_scenario(
            seed=0, n_nodes=8, n_regions=1, zones_per_region=2, n_mpi=1,
            mpi_ranks=2, n_dl=0,
        )
        cluster.add_pod_group(PodGroup(
            name="gpu-gang", namespace="mpi-team", min_member=2,
            rank_aware=True, creation_ms=50_000,
        ))
        for m in range(2):
            cluster.add_pod(Pod(
                name=f"gpu-gang-r{m}", namespace="mpi-team",
                creation_ms=50_000 + m,
                containers=[Container(
                    requests={"cpu": 500, "nvidia.com/gpu": 1}
                )],
                labels={POD_GROUP_LABEL: "gpu-gang"},
            ))
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        report = run_cycle(
            scheduler, cluster, now=10_000, gangs=GangPhase(check_twin=True)
        )
        # the GPU gang fails cleanly (no node carries the resource) while
        # the plain gang still places
        assert "mpi-team/gpu-gang" in report.rejected_gangs
        assert report.rank_gangs["mpi-team/mpi-000"]["admitted"]

    def test_reconcile_sheds_pending_extras_above_desired(self):
        # desired drops while clones are still pending: the extras are
        # DELETED (newest first), never bound-then-deleted next cycle
        cluster, scheduler, phase = TestGangPhaseCycle()._arm()
        run_cycle(scheduler, cluster, now=10_000, gangs=phase)
        dl = next(
            pg for pg in cluster.pod_groups.values()
            if pg.desired_replicas is not None
        )
        dl.desired_replicas = 5
        cluster.add_pod_group(dl)
        phase.reconcile(cluster, 20_000)  # creates 2 clones, still pending
        pend = [
            p for p in cluster.gang_members(dl) if p.node_name is None
        ]
        assert len(pend) == 2
        dl.desired_replicas = 3
        cluster.add_pod_group(dl)
        report = run_cycle(scheduler, cluster, now=30_000, gangs=phase)
        live = [
            p for p in cluster.gang_members(dl) if p.node_name is not None
        ]
        assert len(live) == 3
        # the clones left without ever binding
        assert not any(uid in report.bound for uid in (p.uid for p in pend))
        assert all(p.uid not in cluster.pods for p in pend)

    def test_elastic_bounds_never_shrink_below_quorum(self):
        from scheduler_plugins_tpu.api.objects import PodGroup
        from scheduler_plugins_tpu.gangs import elastic_bounds

        pg = PodGroup(name="x", min_member=4, rank_aware=True,
                      desired_replicas=6, max_replicas=2)
        lo, desired, hi = elastic_bounds(pg)
        assert (lo, desired, hi) == (4, 4, 4)

    def test_parked_gang_requeues_on_gang_events(self):
        # a gang parked by the phase has no profile plugin registering its
        # events — the gang-phase requeue gate must admit it on
        # GANG_EVENTS kinds (here: a NetworkTopology update)
        from scheduler_plugins_tpu.api.objects import NetworkTopology

        cluster, scheduler, phase = TestGangPhaseCycle()._arm()
        for name in list(cluster.nodes):
            cluster.remove_node(name)
        from scheduler_plugins_tpu.api.objects import Node

        cluster.add_node(Node(name="tiny", allocatable={"cpu": 100}))
        report = run_cycle(scheduler, cluster, now=10_000, gangs=phase)
        assert report.failed
        # no registered event since the failure: the batch stays parked
        # (backoff expired at +20s, the 5-minute flush not yet due)
        r2 = run_cycle(scheduler, cluster, now=30_000, gangs=phase)
        assert not r2.rank_gangs
        assert set(r2.skipped) == set(report.failed)
        # a NetworkTopology update is a GANG_EVENTS kind -> re-admitted
        cluster.add_network_topology(NetworkTopology(weights={}))
        r3 = run_cycle(scheduler, cluster, now=60_000, gangs=phase)
        assert r3.rank_gangs  # the gangs re-entered the phase
