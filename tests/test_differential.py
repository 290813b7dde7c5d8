"""Differential placement gate (BASELINE.md): the jitted sequential solve
must produce BIT-IDENTICAL placements to an independent, reference-shaped
Python implementation of the same semantics (per-pod scan over all nodes:
resource fit -> weighted allocatable score with Go integer division ->
min-max normalize -> argmax with lowest-index tie-break -> commit)."""

import itertools

import jax.numpy as jnp
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
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.state.cluster import Cluster

gib = 1 << 30


def go_div(a, b):
    q = abs(a) // b
    return -q if a < 0 else q


def static_scores(nodes, weights, sign=-1):
    wsum = sum(weights.values())
    return {
        n.name: go_div(
            sum(sign * n.allocatable.get(r, 0) * w for r, w in weights.items()),
            wsum,
        )
        for n in nodes
    }


def place_one(free, raw, node_order, req):
    """The shared per-pod step: fit -> min-max normalize -> argmax with
    lowest-index tie-break -> commit. Returns the chosen node name or None."""
    feasible = [
        name
        for name in node_order
        if free[name].get(PODS, 0) >= 1
        and all(free[name].get(r, 0) >= v for r, v in req.items())
    ]
    if not feasible:
        return None
    lo = min(raw[f] for f in feasible)
    hi = max(raw[f] for f in feasible)
    best, best_score = None, None
    for name in feasible:
        score = 0 if hi == lo else (raw[name] - lo) * 100 // (hi - lo)
        if best_score is None or score > best_score:
            best, best_score = name, score
    for r, v in req.items():
        free[best][r] = free[best].get(r, 0) - v
    free[best][PODS] -= 1
    return best


def reference_loop(nodes, pods, weights, sign=-1):
    """Independent per-pod x per-node implementation (the Go path's shape)."""
    free = {n.name: dict(n.allocatable) for n in nodes}
    for n in nodes:
        free[n.name].setdefault(PODS, 0)
    raw = static_scores(nodes, weights, sign)
    order = [n.name for n in nodes]
    return [place_one(free, raw, order, p.effective_request()) for p in pods]


def random_cluster(rng, n_nodes, n_pods):
    nodes = [
        Node(
            name=f"n{i:03d}",
            allocatable={
                CPU: int(rng.integers(2000, 64_000)),
                MEMORY: int(rng.integers(4, 256)) * gib,
                PODS: int(rng.integers(4, 60)),
            },
        )
        for i in range(n_nodes)
    ]
    pods = [
        Pod(
            name=f"p{j:04d}",
            creation_ms=j,
            containers=[
                Container(
                    requests={
                        CPU: int(rng.integers(50, 8000)),
                        MEMORY: int(rng.integers(1, 16)) * gib,
                    }
                )
            ],
        )
        for j in range(n_pods)
    ]
    return nodes, pods


def reference_loop_quota(nodes, pods, weights, quotas, sign=-1):
    """Reference loop + ElasticQuota admission (over-Max, aggregate-over-Min)
    with usage committed per placement."""
    free = {n.name: dict(n.allocatable) for n in nodes}
    for n in nodes:
        free[n.name].setdefault(PODS, 0)
    raw = static_scores(nodes, weights, sign)
    order = [n.name for n in nodes]
    axis = sorted({r for q in quotas.values() for r in list(q["min"]) + list(q["max"])}
                  | {r for p in pods for r in p.effective_request()}
                  | {CPU, MEMORY, "ephemeral-storage", PODS})
    used = {ns: {r: 0 for r in axis} for ns in quotas}
    placements = []
    for pod in pods:
        req = pod.effective_request()
        ns = pod.namespace
        if ns in quotas:
            q = quotas[ns]
            over_max = any(
                used[ns].get(r, 0) + req.get(r, 0) > q["max"].get(r, 2**63 - 1)
                for r in axis
            )
            agg_used = {r: sum(used[m].get(r, 0) for m in quotas) for r in axis}
            agg_min = {r: sum(quotas[m]["min"].get(r, 0) for m in quotas) for r in axis}
            over_min = any(
                agg_used[r] + req.get(r, 0) > agg_min[r] for r in axis
            )
            if over_max or over_min:
                placements.append(None)
                continue
        best = place_one(free, raw, order, req)
        if best is not None and ns in quotas:
            for r, v in req.items():
                used[ns][r] = used[ns].get(r, 0) + v
        placements.append(best)
    return placements


class TestDifferential:
    def test_bit_identical_placements_random_scenarios(self):
        weights = {CPU: 1 << 20, MEMORY: 1}
        for seed in range(5):
            rng = np.random.default_rng(seed)
            n_nodes = int(rng.integers(3, 40))
            n_pods = int(rng.integers(10, 120))
            nodes, pods = random_cluster(rng, n_nodes, n_pods)

            expected = reference_loop(nodes, pods, weights)

            cluster = Cluster()
            for n in nodes:
                cluster.add_node(n)
            for p in pods:
                cluster.add_pod(p)
            sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
            pending = sched.sort_pending(cluster.pending_pods(), cluster)
            snap, meta = cluster.snapshot(pending, now_ms=0)
            sched.prepare(meta, cluster)
            result = sched.solve(snap)
            got = [
                meta.node_names[int(a)] if int(a) >= 0 else None
                for a in np.asarray(result.assignment)[: len(pods)]
            ]
            assert got == expected, f"seed {seed}: divergence"

    def test_quota_differential(self):
        from scheduler_plugins_tpu.api.objects import ElasticQuota
        from scheduler_plugins_tpu.plugins import CapacityScheduling

        weights = {CPU: 1 << 20, MEMORY: 1}
        for seed in range(3):
            rng = np.random.default_rng(100 + seed)
            nodes, pods = random_cluster(rng, 10, 80)
            namespaces = ["a", "b", "c"]
            for i, pod in enumerate(pods):
                pod.namespace = namespaces[i % 3]
                pod.uid = f"{pod.namespace}/{pod.name}"
            quotas = {
                ns: {
                    "min": {CPU: int(rng.integers(20_000, 60_000)),
                            MEMORY: int(rng.integers(64, 256)) * gib},
                    "max": {CPU: int(rng.integers(60_000, 120_000)),
                            MEMORY: int(rng.integers(256, 512)) * gib},
                }
                for ns in namespaces[:2]  # one namespace stays quota-free
            }
            cluster = Cluster()
            for n in nodes:
                cluster.add_node(n)
            for p in pods:
                cluster.add_pod(p)
            for ns, q in quotas.items():
                cluster.add_quota(
                    ElasticQuota(name=ns, namespace=ns, min=q["min"], max=q["max"])
                )
            sched = Scheduler(
                Profile(plugins=[NodeResourcesAllocatable(), CapacityScheduling()])
            )
            pending = sched.sort_pending(cluster.pending_pods(), cluster)
            snap, meta = cluster.snapshot(pending, now_ms=0)
            sched.prepare(meta, cluster)
            result = sched.solve(snap)
            assignment = np.asarray(result.assignment)
            got = [
                meta.node_names[int(a)] if int(a) >= 0 else None
                for a in assignment[: len(pending)]
            ]
            # the reference loop consumes pods in the solver's queue order
            expected = reference_loop_quota(nodes, pending, weights, quotas)
            assert got == expected, f"seed {seed}: quota divergence"

    def test_multi_cycle_differential(self):
        # three consecutive cycles with churn between them: placements must
        # stay bit-identical against the reference loop replayed per cycle
        weights = {CPU: 1 << 20, MEMORY: 1}
        rng = np.random.default_rng(999)
        nodes, _ = random_cluster(rng, 8, 0)
        cluster = Cluster()
        for n in nodes:
            cluster.add_node(n)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        serial = 0
        for cycle in range(3):
            # arrivals
            _, fresh = random_cluster(rng, 1, 10)
            for p in fresh:
                serial += 1
                p.name = f"c{cycle}-p{serial}"
                p.uid = f"default/{p.name}"
                p.creation_ms = cycle * 1000 + serial
                cluster.add_pod(p)
            pending = sched.sort_pending(cluster.pending_pods(), cluster)
            # reference loop sees nodes with CURRENT usage: model via
            # shrunken allocatable
            assigned = [p for p in cluster.pods.values() if p.node_name]
            used = {n.name: {} for n in nodes}
            for p in assigned:
                for r, v in p.effective_request().items():
                    used[p.node_name][r] = used[p.node_name].get(r, 0) + v
                used[p.node_name][PODS] = used[p.node_name].get(PODS, 0) + 1
            eff_nodes = [
                Node(
                    name=n.name,
                    allocatable={
                        r: n.allocatable.get(r, 0) - used[n.name].get(r, 0)
                        for r in set(n.allocatable) | set(used[n.name])
                    },
                )
                for n in nodes
            ]
            # scores in the real solver use TRUE allocatable; mimic by
            # passing raw scores from the original nodes
            free = {n.name: dict(n.allocatable) for n in eff_nodes}
            for n in eff_nodes:
                free[n.name].setdefault(PODS, 0)
            raw = static_scores(nodes, weights)  # scores use TRUE allocatable
            order = [n.name for n in eff_nodes]
            expected = [
                place_one(free, raw, order, p.effective_request())
                for p in pending
            ]
            snap, meta = cluster.snapshot(pending, now_ms=cycle * 1000)
            sched.prepare(meta, cluster)
            result = sched.solve(snap)
            assignment = np.asarray(result.assignment)
            got = [
                meta.node_names[int(a)] if int(a) >= 0 else None
                for a in assignment[: len(pending)]
            ]
            assert got == expected, f"cycle {cycle}: divergence"
            # apply bindings + random completions
            for p, node in zip(pending, got):
                if node is not None:
                    cluster.bind(p.uid, node)
            bound = [p for p in cluster.pods.values() if p.node_name]
            for p in bound:
                if rng.random() < 0.3:
                    cluster.remove_pod(p.uid)

    def test_most_mode_differential(self):
        weights = {CPU: 1 << 20, MEMORY: 1}
        rng = np.random.default_rng(42)
        nodes, pods = random_cluster(rng, 12, 60)
        expected = reference_loop(nodes, pods, weights, sign=+1)
        cluster = Cluster()
        for n in nodes:
            cluster.add_node(n)
        for p in pods:
            cluster.add_pod(p)
        sched = Scheduler(
            Profile(plugins=[NodeResourcesAllocatable(mode="Most")])
        )
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        result = sched.solve(snap)
        got = [
            meta.node_names[int(a)] if int(a) >= 0 else None
            for a in np.asarray(result.assignment)[: len(pods)]
        ]
        assert got == expected


class TestBatchedNumaGangHardConstraintParity:
    """ISSUE 2 satellite: the rewritten batched NUMA path vs the sequential
    parity path on a cfg-2-shaped cluster (NRT zones + gangs) — hard
    constraints (resource fit, single-NUMA feasibility, gang quorum) must
    hold IDENTICALLY in both modes across >= 3 seeds, with independent
    numpy replay oracles (no jax code on the oracle side)."""

    ZONES = 4

    def _cluster(self, rng, n_nodes=96, n_gangs=6, gang_size=8, n_singles=48):
        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            NodeResourceTopology,
            NUMAZone,
            PodGroup,
            TopologyManagerPolicy,
        )

        cluster = Cluster()
        per_zone_cpu = 16_000 // self.ZONES
        for i in range(n_nodes):
            cluster.add_node(Node(
                name=f"n{i:03d}",
                allocatable={CPU: 16_000, MEMORY: 64 * gib, PODS: 32},
            ))
            cluster.add_nrt(NodeResourceTopology(
                node_name=f"n{i:03d}",
                policy=TopologyManagerPolicy.SINGLE_NUMA_NODE,
                zones=[
                    NUMAZone(
                        numa_id=z,
                        available={CPU: per_zone_cpu, MEMORY: 16 * gib},
                    )
                    for z in range(self.ZONES)
                ],
            ))

        def guaranteed(name, order, cpu, labels=None):
            return Pod(
                name=name, creation_ms=order,
                containers=[Container(
                    requests={CPU: cpu, MEMORY: 1 * gib},
                    limits={CPU: cpu, MEMORY: 1 * gib},
                )],
                labels=labels or {},
            )

        order = 0
        for g in range(n_gangs):
            cluster.add_pod_group(
                PodGroup(name=f"gang-{g}", min_member=gang_size)
            )
            for m in range(gang_size):
                cluster.add_pod(guaranteed(
                    f"gang-{g}-m{m}", order,
                    int(rng.integers(200, per_zone_cpu // 2)),
                    labels={POD_GROUP_LABEL: f"gang-{g}"},
                ))
                order += 1
        for s in range(n_singles):
            cluster.add_pod(guaranteed(
                f"single-{s}", order,
                int(rng.integers(200, per_zone_cpu)),
            ))
            order += 1
        return cluster

    # -- numpy replay oracles (independent of the jax kernels) -----------
    def _fit_ok(self, an, snap):
        req = np.asarray(snap.pods.req)
        alloc = np.asarray(snap.nodes.alloc)
        used = np.zeros_like(alloc)
        for p, n in enumerate(an):
            if n >= 0:
                used[n] += req[p]
                used[n, -1] += 0  # pods slot already in req encoding
        return bool((used <= alloc).all())

    def _numa_ok(self, an, snap):
        """Queue-order pessimistic replay: every placed pod had a fitting
        zone at its own placement time (all-reported-zone deduction)."""
        req = np.asarray(snap.pods.req)
        avail = np.asarray(snap.numa.available).astype(np.int64).copy()
        reported = np.asarray(snap.numa.reported)
        zmask = np.asarray(snap.numa.zone_mask)
        for p in np.argsort(np.arange(len(an))):  # queue order
            n = an[p]
            if n < 0:
                continue
            fit = any(
                zmask[n, z] and all(
                    not (req[p, r] > 0 and reported[n, z, r]
                         and avail[n, z, r] < req[p, r])
                    for r in range(req.shape[1])
                )
                for z in range(avail.shape[1])
            )
            if not fit:
                return False
            avail[n][reported[n]] -= np.broadcast_to(
                req[p][None, :], avail[n].shape
            )[reported[n]]
        return True

    def _gang_quorum_ok(self, an, wait, snap):
        """No gang binds below quorum: members placed WITHOUT a Permit-Wait
        flag only exist when the gang's placed count reaches min_member."""
        gang = np.asarray(snap.pods.gang)
        min_member = np.asarray(snap.gangs.min_member)
        assigned = np.asarray(snap.gangs.assigned)
        placed = an >= 0
        for g in range(len(min_member)):
            members = gang == g
            bound = int((members & placed & ~wait).sum())
            total = int((members & placed).sum()) + int(assigned[g])
            if bound > 0 and total < int(min_member[g]):
                return False
        return True

    def _solve_modes(self, cluster):
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve
        from scheduler_plugins_tpu.plugins import (
            Coscheduling,
            NodeResourceTopologyMatch,
        )

        sched = Scheduler(Profile(plugins=[
            NodeResourceTopologyMatch(), Coscheduling(),
        ]))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        seq = sched.solve(snap)
        a_seq = np.asarray(seq.assignment)
        w_seq = np.asarray(seq.wait)
        a_bat, _, w_bat = profile_batch_solve(sched, snap)
        return snap, a_seq, w_seq, np.asarray(a_bat), np.asarray(w_bat)

    def test_hard_constraint_parity_across_seeds(self):
        for seed in (0, 1, 2):
            rng = np.random.default_rng(seed)
            cluster = self._cluster(rng)
            snap, a_seq, w_seq, a_bat, w_bat = self._solve_modes(cluster)
            for mode, an, wait in (
                ("sequential", a_seq, w_seq), ("batch", a_bat, w_bat)
            ):
                assert self._fit_ok(an, snap), (seed, mode)
                assert self._numa_ok(an, snap), (seed, mode)
                assert self._gang_quorum_ok(an, wait, snap), (seed, mode)
            # completeness parity: the throughput mode must not place fewer
            # pods than the bit-faithful path
            assert int((a_bat >= 0).sum()) >= int((a_seq >= 0).sum()), seed


def _ns_pod(name, cpu, ns="default", created=0, labels=None, mem=gib):
    return Pod(
        name=name, namespace=ns, creation_ms=created, labels=labels or {},
        containers=[Container(requests={CPU: cpu, MEMORY: mem})],
    )


def _deep_node(name, cpu=16_000, mem=64 * gib):
    return Node(name=name, allocatable={CPU: cpu, MEMORY: mem, PODS: 110})


def _allocatable_scheduler():
    return Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))


def _gang_quota_scheduler():
    from scheduler_plugins_tpu.plugins import (
        CapacityScheduling,
        Coscheduling,
    )

    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(),
        Coscheduling(permit_waiting_seconds=5),
        CapacityScheduling(),
    ]))


def _no_node_overcommitted(cluster):
    used = {}
    for p in cluster.pods.values():
        if p.node_name is None:
            continue
        row = used.setdefault(p.node_name, {})
        for r, v in p.effective_request().items():
            row[r] = row.get(r, 0) + v
    for name, row in used.items():
        alloc = cluster.nodes[name].allocatable
        for r, v in row.items():
            assert v <= alloc.get(r, 0), (name, r, v)


def _quota_max_held(cluster):
    used = {}
    for p in cluster.pods.values():
        if p.node_name is not None:
            used[p.namespace] = used.get(p.namespace, 0) + (
                p.effective_request().get(CPU, 0)
            )
    for q in cluster.quotas.values():
        assert used.get(q.namespace, 0) <= q.max[CPU], q.namespace


def _gangs_all_or_nothing(cluster):
    bound = {}
    for p in cluster.pods.values():
        if p.pod_group() and p.node_name is not None:
            key = f"{p.namespace}/{p.pod_group()}"
            bound[key] = bound.get(key, 0) + 1
    for pg in cluster.pod_groups.values():
        n = bound.get(f"{pg.namespace}/{pg.name}", 0)
        assert n == 0 or n >= pg.min_member, (pg.name, n)


def _no_events(cluster, cycle, now, rng, serial):
    pass


class _Scenario:
    """One event stream for the serve-vs-fresh differential: `build(rng)`
    makes the starting cluster, `step(cluster, cycle, now, rng, serial)`
    applies a cycle's events before it runs (`serial` numbers the pods
    it adds), `invariant(cluster)` must hold after
    every cycle, and `check(reports, cluster)` pins the outcome the
    scenario exists for."""

    def __init__(self, build, step, cycles, scheduler=_allocatable_scheduler,
                 seed=0, invariant=_no_node_overcommitted, check=None):
        self.build, self.step, self.cycles = build, step, cycles
        self.scheduler, self.seed = scheduler, seed
        self.invariant, self.check = invariant, check


def _dense():
    from tests.test_serving import make_cluster, make_node, make_pod

    def step(cluster, cycle, now, rng, serial):
        for _ in range(int(rng.integers(1, 4))):
            cluster.add_pod(make_pod(
                next(serial), now, int(rng.integers(200, 2500)), gib
            ))
        if cycle == 3:
            cluster.add_node(make_node(40))
        if cycle == 5:
            bound = sorted(
                u for u, p in cluster.pods.items() if p.node_name is not None
            )
            cluster.remove_pod(bound[0])

    return _Scenario(lambda rng: make_cluster(5), step, 8, seed=11)


def _last_capacity():
    """Two namespaces bid one node's last capacity: the earlier pod in
    queue order wins, the later one fails on the fit."""
    def build(rng):
        c = Cluster()
        c.add_node(_deep_node("n0", cpu=1000, mem=8 * gib))
        c.add_pod(_ns_pod("first", 800, ns="team-a", created=10))
        c.add_pod(_ns_pod("second", 800, ns="team-b", created=20))
        return c

    def check(reports, cluster):
        assert reports[0].bound == {"team-a/first": "n0"}
        assert reports[0].failed == ["team-b/second"]
        assert reports[0].failed_by == {
            "team-b/second": "NodeResourcesFit"
        }
        assert cluster.pods["team-b/second"].node_name is None

    return _Scenario(build, _no_events, 2, check=check)


def _quota_contention():
    """Two quota'd namespaces contend for the aggregate Min, then one of
    them for its own Max: queue order decides the first, and no cycle
    leaves a namespace over its Max."""
    def build(rng):
        c = Cluster()
        c.add_node(_deep_node("n0"))
        c.add_node(_deep_node("n1"))
        for ns, cap in (("team-a", 2500), ("team-b", 16_000)):
            c.add_quota(ElasticQuota(
                name=f"eq-{ns}", namespace=ns,
                min={CPU: 1000, MEMORY: 8 * gib},
                max={CPU: cap, MEMORY: 64 * gib},
            ))
        # aggregate Min 2000: the first 1500 borrows, the second's 1500
        # overflows the aggregate once the first's usage is counted
        c.add_pod(_ns_pod("first", 1500, ns="team-a", created=10))
        c.add_pod(_ns_pod("second", 1500, ns="team-b", created=20))
        return c

    def step(cluster, cycle, now, rng, serial):
        if cycle == 1:
            # 1500 + 1200 would put team-a over its Max of 2500
            cluster.add_pod(_ns_pod("over", 1200, ns="team-a", created=now))

    def check(reports, cluster):
        first = reports[0]
        assert first.bound == {"team-a/first": "n0"}
        assert first.failed == ["team-b/second"]
        assert first.failed_by["team-b/second"] == "CapacityScheduling"
        assert cluster.pods["team-a/over"].node_name is None
        assert any(
            r.failed_by.get("team-a/over") == "CapacityScheduling"
            for r in reports
        )

    def invariant(cluster):
        _no_node_overcommitted(cluster)
        _quota_max_held(cluster)

    return _Scenario(build, step, 3, scheduler=_gang_quota_scheduler,
                     invariant=invariant, check=check)


def _disjoint_tenants():
    """Four tenants with traffic of their own on roomy nodes: every pod
    binds, in the cycle it arrives."""
    tenants = [f"tenant-{t}" for t in range(4)]

    def build(rng):
        c = Cluster()
        for i in range(4):
            c.add_node(_deep_node(f"n{i}"))
        return c

    def step(cluster, cycle, now, rng, serial):
        for j, ns in enumerate(tenants):
            for i in range(3):
                cluster.add_pod(_ns_pod(
                    f"c{cycle}-p{i}", 500, ns=ns, created=now + j * 10 + i,
                ))

    def check(reports, cluster):
        for cycle, r in enumerate(reports):
            assert len(r.bound) == 12 and not r.failed, cycle
        assert all(p.node_name for p in cluster.pods.values())

    return _Scenario(build, step, 2, check=check)


def _nominee_held():
    """A preemption nominee holds its node: the pod ahead of it in queue
    order may not take the nominee's room on the nominated node."""
    def build(rng):
        c = Cluster()
        c.add_node(_deep_node("n0", cpu=1000, mem=8 * gib))
        c.add_node(_deep_node("n1", cpu=1000, mem=8 * gib))
        c.add_pod(_ns_pod("early", 800, created=5))
        nominee = _ns_pod("nom", 800, created=10)
        nominee.nominated_node_name = "n0"
        c.add_pod(nominee)
        return c

    def check(reports, cluster):
        assert reports[0].bound == {
            "default/early": "n1", "default/nom": "n0",
        }

    return _Scenario(build, _no_events, 1, check=check)


def _gang_quota_tables():
    """Gang and quota tables together: a gang the quota cannot hold
    whole binds no member, the one beside it binds whole."""
    def build(rng):
        c = Cluster()
        c.add_node(_deep_node("n0"))
        c.add_quota(ElasticQuota(
            name="eq", namespace="team",
            min={CPU: 4000, MEMORY: 16 * gib},
            max={CPU: 8000, MEMORY: 32 * gib},
        ))
        c.add_pod_group(PodGroup(name="small", namespace="team",
                                 min_member=1))
        c.add_pod(_ns_pod("small-m0", 500, ns="team",
                          labels={POD_GROUP_LABEL: "small"}))
        # three members of 3000 each: 9000 against a Max of 8000
        c.add_pod_group(PodGroup(name="big", namespace="team",
                                 min_member=3))
        for m in range(3):
            c.add_pod(_ns_pod(f"big-m{m}", 3000, ns="team", created=m + 1,
                              labels={POD_GROUP_LABEL: "big"}))
        return c

    def check(reports, cluster):
        assert reports[0].bound == {"team/small-m0": "n0"}
        assert not any(
            cluster.pods[f"team/big-m{m}"].node_name for m in range(3)
        )

    def invariant(cluster):
        _no_node_overcommitted(cluster)
        _quota_max_held(cluster)
        _gangs_all_or_nothing(cluster)

    return _Scenario(build, _no_events, 2,
                     scheduler=_gang_quota_scheduler, invariant=invariant,
                     check=check)


def _orphan_gang_label():
    """A pending pod names a PodGroup that registers cycles later: until
    then it schedules as a plain pod (and finds no room), and once the
    group is there with its quorum the group binds whole, in one cycle."""
    def build(rng):
        c = Cluster()
        c.add_node(_deep_node("n0", cpu=4000))
        c.add_pod(_ns_pod("solo", 500, ns="t0", created=1))
        c.add_pod(_ns_pod("orphan", 4000, ns="t0", created=2,
                          labels={POD_GROUP_LABEL: "late"}))
        return c

    def step(cluster, cycle, now, rng, serial):
        if cycle == 2:
            cluster.add_pod_group(PodGroup(
                name="late", namespace="t0", min_member=2, creation_ms=now,
            ))
            cluster.add_pod(_ns_pod("member", 4000, ns="t0", created=now,
                                    labels={POD_GROUP_LABEL: "late"}))
        if cycle == 3:
            cluster.add_node(_deep_node("n1", cpu=4000))
            cluster.add_node(_deep_node("n2", cpu=4000))

    def check(reports, cluster):
        assert reports[0].bound == {"t0/solo": "n0"}
        assert reports[0].failed_by == {"t0/orphan": "NodeResourcesFit"}
        gang = {"t0/orphan", "t0/member"}
        assert [set(r.bound) & gang for r in reports if set(r.bound) & gang
                ] == [gang]
        assert {cluster.pods[u].node_name for u in gang} == {"n1", "n2"}

    def invariant(cluster):
        _no_node_overcommitted(cluster)
        _gangs_all_or_nothing(cluster)

    return _Scenario(build, step, 6, scheduler=_gang_quota_scheduler,
                     invariant=invariant, check=check)


def _churn_micro():
    """Multi-cycle churn over zoned tenants, with four bidders for a hot
    node's two slots of a scarce resource on the last cycle: the node
    takes two, and no node is ever overcommitted."""
    n_nodes, zones, tenants, arrive, depart = 8, 4, 8, 64, 64
    hot_slots, hot_bidders, cycles = 2, 4, 4

    def build(rng):
        c = Cluster()
        for i in range(n_nodes):
            c.add_node(Node(
                name=f"node-{i:04d}",
                allocatable={CPU: 256_000, MEMORY: 1024 * gib, PODS: 1024,
                             f"example.com/zone-{i % zones}": 100_000},
            ))
        c.add_node(Node(
            name="node-hot",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 512,
                         "example.com/hot": hot_slots},
        ))
        for i in range(32):
            j = i % n_nodes
            pod = Pod(
                name=f"bound-{i:06d}", creation_ms=i,
                namespace=f"tenant-{i % tenants:03d}",
                containers=[Container(requests={
                    CPU: int(rng.integers(100, 900)), MEMORY: gib,
                    f"example.com/zone-{j % zones}": 1,
                })],
            )
            pod.node_name = f"node-{j:04d}"
            c.add_pod(pod)
        return c

    def add(cluster, rng, serial, n, hot=False):
        for _ in range(n):
            s = next(serial)
            t = s % tenants
            extra = ("example.com/hot" if hot
                     else f"example.com/zone-{t % zones}")
            cluster.add_pod(Pod(
                name=f"{'hot' if hot else 'arr'}-{s:06d}",
                namespace=f"tenant-{t:03d}",
                creation_ms=1_000_000 + s,
                containers=[Container(requests={
                    CPU: int(rng.integers(100, 900)), MEMORY: gib, extra: 1,
                })],
            ))

    def step(cluster, cycle, now, rng, serial):
        contended = cycle == cycles - 1
        add(cluster, rng, serial, arrive - (hot_bidders if contended else 0))
        if contended:
            add(cluster, rng, serial, hot_bidders, hot=True)
        bound = sorted(
            u for u, p in cluster.pods.items() if p.node_name is not None
        )
        for i in sorted(rng.choice(len(bound), replace=False,
                                   size=min(depart, len(bound)))):
            cluster.remove_pod(bound[int(i)])

    def check(reports, cluster):
        hot = [p for p in cluster.pods.values() if p.name.startswith("hot-")]
        assert len(hot) == hot_bidders
        assert sum(1 for p in hot if p.node_name) == hot_slots
        assert sum(1 for p in hot if p.uid in reports[-1].failed) == (
            hot_bidders - hot_slots
        )

    return _Scenario(build, step, cycles, seed=1, check=check)


def _plain_roster():
    """The seeded multi-tenant serve roster: arrivals over three
    namespaces, a node added, a pod that fits nowhere, a departure and a
    drained node deleted."""
    from tests.test_serving import make_cluster, make_node

    def step(cluster, cycle, now, rng, serial):
        for _ in range(int(rng.integers(1, 4))):
            s = next(serial)
            cluster.add_pod(Pod(
                name=f"p{s:05d}", namespace=f"ns{s % 3}",
                creation_ms=now + s,
                containers=[Container(requests={
                    CPU: int(rng.integers(200, 2500)), MEMORY: gib,
                })],
            ))
        if cycle == 3:
            cluster.add_node(make_node(40))
        if cycle == 4:
            cluster.add_pod(Pod(
                name="nofit", creation_ms=now + 999,
                containers=[Container(requests={CPU: 10**9})],
            ))
        if cycle == 5:
            bound = sorted(
                u for u, p in cluster.pods.items() if p.node_name is not None
            )
            cluster.remove_pod(bound[0])
        if cycle == 7:
            victim = next(iter(cluster.nodes))
            for uid in [
                u for u, p in cluster.pods.items() if p.node_name == victim
            ]:
                cluster.remove_pod(uid)
            cluster.remove_node(victim)

    def check(reports, cluster):
        assert cluster.pods["default/nofit"].node_name is None
        assert reports[4].failed_by["default/nofit"] == "NodeResourcesFit"
        assert all(
            p.node_name in cluster.nodes
            for p in cluster.pods.values() if p.node_name is not None
        )

    return _Scenario(lambda rng: make_cluster(6), step, 10, seed=23,
                     check=check)


def _gang_quota_roster():
    """The seeded gang+quota roster: priority-mixed arrivals under one
    ElasticQuota, a three-member gang every fifth cycle, random
    departures."""
    def build(rng):
        c = Cluster()
        for i in range(8):
            c.add_node(Node(
                name=f"n{i}",
                allocatable={CPU: 16_000, MEMORY: 64 * gib, PODS: 30},
            ))
        c.add_quota(ElasticQuota(
            name="eq", namespace="team",
            min={CPU: 64_000, MEMORY: 256 * gib},
            max={CPU: 96_000, MEMORY: 384 * gib},
        ))
        return c

    def step(cluster, cycle, now, rng, serial):
        for _ in range(int(rng.integers(0, 5))):
            s = next(serial)
            cluster.add_pod(Pod(
                name=f"p{s:04d}", namespace="team", creation_ms=now + s,
                priority=int(rng.integers(0, 5)),
                containers=[Container(requests={
                    CPU: int(rng.integers(200, 4000)),
                    MEMORY: int(rng.integers(1, 8)) * gib,
                })],
            ))
        if cycle % 5 == 1:
            gname = f"g{cycle}"
            cluster.add_pod_group(PodGroup(
                name=gname, namespace="team", min_member=3, creation_ms=now,
            ))
            for m in range(3):
                next(serial)
                cluster.add_pod(Pod(
                    name=f"{gname}-m{m}", namespace="team",
                    creation_ms=now + m, labels={POD_GROUP_LABEL: gname},
                    containers=[Container(
                        requests={CPU: 2000, MEMORY: 4 * gib}
                    )],
                ))
        for pod in [
            p for p in cluster.pods.values()
            if p.node_name is not None and not p.pod_group()
        ]:
            if rng.random() < 0.15:
                cluster.remove_pod(pod.uid)

    def check(reports, cluster):
        assert sum(len(r.bound) for r in reports) > 0
        assert any(
            p.pod_group() and p.node_name for p in cluster.pods.values()
        )

    def invariant(cluster):
        _no_node_overcommitted(cluster)
        _quota_max_held(cluster)
        _gangs_all_or_nothing(cluster)

    return _Scenario(build, step, 12, scheduler=_gang_quota_scheduler,
                     seed=5, invariant=invariant, check=check)


DELTA_SCENARIOS = {
    "dense": _dense,
    "last_capacity": _last_capacity,
    "quota_contention": _quota_contention,
    "disjoint_tenants": _disjoint_tenants,
    "nominee_held": _nominee_held,
    "gang_quota_tables": _gang_quota_tables,
    "orphan_gang_label": _orphan_gang_label,
    "churn_micro": _churn_micro,
    "plain_roster": _plain_roster,
    "gang_quota_roster": _gang_quota_roster,
}


class TestServeDeltaEquivalence:
    """Serve-mode differential (docs/SERVING.md): the resident-state
    engine's delta-maintained solver input must be BIT-IDENTICAL to a
    fresh full re-snapshot after any event sequence, and serve-mode
    placements identical to full-resnapshot cycles — the engine changes
    where the solver input comes from, never what the solver decides.
    (The randomized per-cycle tensor diff lives in tests/test_serving.py;
    this gate replays each scenario's event stream through BOTH
    `run_cycle` modes, diffs outcomes + final resident tensors, and holds
    the serial result to the outcome the scenario exists for.)"""

    @staticmethod
    def _run(scn, engine_cls):
        rng = np.random.default_rng(scn.seed)
        cluster = scn.build(rng)
        serial = itertools.count(1)
        engine = engine_cls().attach(cluster) if engine_cls else None
        sched = scn.scheduler()
        reports = []
        for cycle in range(scn.cycles):
            now = 1000 * (cycle + 1)
            scn.step(cluster, cycle, now, rng, serial)
            reports.append(run_cycle(sched, cluster, now=now, serve=engine))
            scn.invariant(cluster)
        return cluster, engine, reports

    @pytest.mark.parametrize("scenario", list(DELTA_SCENARIOS))
    def test_delta_path_matches_full_resnapshot(self, scenario):
        from scheduler_plugins_tpu.serving import ServeEngine
        from tests.test_serving import NODE_COLUMNS

        scn = DELTA_SCENARIOS[scenario]()
        outcomes = {}
        for mode, engine_cls in (("serve", ServeEngine), ("baseline", None)):
            cluster, engine, reports = self._run(scn, engine_cls)
            outcomes[mode] = [
                (
                    dict(r.bound), list(r.failed), dict(r.failed_by),
                    dict(r.reserved), list(r.rejected_gangs),
                    dict(r.preempted),
                )
                for r in reports
            ]
            if engine is not None:
                end = 1000 * (scn.cycles + 1)
                assert engine.refresh(cluster, [], now_ms=end) is not None
                resident = engine.resident_nodes
                fresh, _ = cluster.snapshot(
                    [], now_ms=end, pad_nodes=engine.npad
                )
            if scn.check is not None:
                scn.check(reports, cluster)
        assert outcomes["serve"] == outcomes["baseline"]
        for col in NODE_COLUMNS:
            np.testing.assert_array_equal(
                np.asarray(getattr(resident, col)),
                np.asarray(getattr(fresh.nodes, col)),
                err_msg=col,
            )


class TestPipelinedCycleEquivalence:
    """The concurrent-pipeline differential (docs/SCALING.md): N
    pipelined cycles vs the serial `run_cycle` on ONE shared seeded
    event stream must produce identical per-cycle placements
    (bound/reserved/failed/attribution, with conflict-fenced binds
    replayed as ordinary deltas) AND an identical final cluster state —
    on a plain serve-mode roster and on a gang+quota roster (served
    RESIDENT since ISSUE 12: the gang/quota side tables keep both
    engines off the full-snapshot fallback, and the equivalence must
    hold through them). Shapes reuse tests/test_serving's compile
    buckets."""

    def _run_plain(self, pipelined):
        from scheduler_plugins_tpu.framework import run_cycle
        from scheduler_plugins_tpu.framework.pipeline_cycle import (
            PipelinedCycle,
        )
        from scheduler_plugins_tpu.serving import (
            ServeEngine,
            StreamingServeEngine,
        )
        from tests.test_serving import (
            make_cluster,
            make_node,
            make_pod,
            make_scheduler,
        )

        rng = np.random.default_rng(23)
        cluster = make_cluster(6)
        engine = (
            StreamingServeEngine() if pipelined else ServeEngine()
        ).attach(cluster)
        sched = make_scheduler()
        pipe = (
            PipelinedCycle(sched, cluster, serve=engine)
            if pipelined else None
        )
        serial = 0
        reports = []
        for cycle in range(10):
            now = 1000 * (cycle + 1)
            for _ in range(int(rng.integers(1, 4))):
                serial += 1
                cluster.add_pod(make_pod(
                    serial, now, int(rng.integers(200, 2500)), gib
                ))
            if cycle == 3:
                cluster.add_node(make_node(40))
            if cycle == 4:
                # a pod that fits nowhere: failure + attribution rows
                # must match cycle for cycle (the pipelined engine
                # defers the failed_by decode — digested post-flush)
                cluster.add_pod(Pod(
                    name="nofit", creation_ms=now + 999,
                    containers=[Container(requests={CPU: 10**9})],
                ))
            if cycle == 5:
                bound = sorted(
                    u for u, p in cluster.pods.items()
                    if p.node_name is not None
                )
                cluster.remove_pod(bound[0])
            if cycle == 7:
                # drain-then-delete: the serial engine re-bases, the
                # streaming engine row-compacts — placements must agree
                victim = next(iter(cluster.nodes))
                for uid in [
                    u for u, p in cluster.pods.items()
                    if p.node_name == victim
                ]:
                    cluster.remove_pod(uid)
                cluster.remove_node(victim)
            if pipelined:
                report = pipe.tick(now)
                pipe.fence()
            else:
                report = run_cycle(sched, cluster, now=now, serve=engine)
            reports.append(report)
        if pipelined:
            # finalize the last cycle BEFORE digesting: the pipelined
            # engine defers attribution/quality into the next tick's
            # overlap window, so failed_by is complete only post-flush
            pipe.flush()
            pipe.close()
        per_cycle = [
            (
                dict(r.bound), dict(r.reserved),
                list(r.failed), dict(r.failed_by),
            )
            for r in reports
        ]
        final = {u: p.node_name for u, p in sorted(cluster.pods.items())}
        return per_cycle, final

    def test_plain_roster_cycles_identical(self):
        serial_cycles, serial_final = self._run_plain(pipelined=False)
        pipe_cycles, pipe_final = self._run_plain(pipelined=True)
        assert pipe_cycles == serial_cycles
        assert pipe_final == serial_final

    def _run_gang_quota(self, pipelined):
        from scheduler_plugins_tpu.api.objects import (
            ElasticQuota,
            PodGroup,
            POD_GROUP_LABEL,
        )
        from scheduler_plugins_tpu.framework import run_cycle
        from scheduler_plugins_tpu.framework.pipeline_cycle import (
            PipelinedCycle,
        )
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling,
            Coscheduling,
        )
        from scheduler_plugins_tpu.serving import (
            ServeEngine,
            StreamingServeEngine,
        )

        rng = np.random.default_rng(5)
        cluster = Cluster()
        for i in range(8):
            cluster.add_node(Node(
                name=f"n{i}",
                allocatable={CPU: 16_000, MEMORY: 64 * gib, PODS: 30},
            ))
        cluster.add_quota(ElasticQuota(
            name="eq", namespace="team",
            min={CPU: 64_000, MEMORY: 256 * gib},
            max={CPU: 96_000, MEMORY: 384 * gib},
        ))
        engine = (
            StreamingServeEngine() if pipelined else ServeEngine()
        ).attach(cluster)
        sched = Scheduler(Profile(plugins=[
            NodeResourcesAllocatable(),
            Coscheduling(permit_waiting_seconds=5),
            CapacityScheduling(),
        ]))
        pipe = (
            PipelinedCycle(sched, cluster, serve=engine)
            if pipelined else None
        )
        serial = 0
        reports = []
        for cycle in range(12):
            now = 1000 * (cycle + 1)
            for _ in range(int(rng.integers(0, 5))):
                serial += 1
                cluster.add_pod(Pod(
                    name=f"p{serial:04d}", namespace="team",
                    creation_ms=now + serial,
                    priority=int(rng.integers(0, 5)),
                    containers=[Container(requests={
                        CPU: int(rng.integers(200, 4000)),
                        MEMORY: int(rng.integers(1, 8)) * gib,
                    })],
                ))
            if cycle % 5 == 1:
                gname = f"g{cycle}"
                cluster.add_pod_group(PodGroup(
                    name=gname, namespace="team", min_member=3,
                    creation_ms=now,
                ))
                for m in range(3):
                    serial += 1
                    cluster.add_pod(Pod(
                        name=f"{gname}-m{m}", namespace="team",
                        creation_ms=now + m,
                        labels={POD_GROUP_LABEL: gname},
                        containers=[Container(
                            requests={CPU: 2000, MEMORY: 4 * gib}
                        )],
                    ))
            bound = [
                p for p in cluster.pods.values()
                if p.node_name is not None and not p.pod_group()
            ]
            for pod in bound:
                if rng.random() < 0.15:
                    cluster.remove_pod(pod.uid)
            if pipelined:
                report = pipe.tick(now)
                pipe.fence()
            else:
                report = run_cycle(sched, cluster, now=now, serve=engine)
            reports.append(report)
        if pipelined:
            pipe.flush()
            pipe.close()
        per_cycle = [
            (
                dict(r.bound), dict(r.reserved),
                list(r.failed), dict(r.failed_by),
                list(r.rejected_gangs), dict(r.preempted),
            )
            for r in reports
        ]
        final = {u: p.node_name for u, p in sorted(cluster.pods.items())}
        return per_cycle, final

    def test_gang_quota_roster_cycles_identical(self):
        serial_cycles, serial_final = self._run_gang_quota(pipelined=False)
        pipe_cycles, pipe_final = self._run_gang_quota(pipelined=True)
        assert pipe_cycles == serial_cycles
        assert pipe_final == serial_final


class TestShardedWaveHardConstraintParity:
    """ISSUE 7 satellite: the shard_map ring-election wave solver vs the
    sequential parity path — hard constraints (resource fit, queue-order
    quota caps, gang quorum; single-NUMA via the sharded PROFILE solve,
    the other member of the sharded-solve family) must hold IDENTICALLY
    across >= 3 seeds and NON-power-of-two node counts, with independent
    numpy replay oracles. The mesh-padding edge rides through every case:
    node counts that don't divide the 8-way mesh pad with zero-capacity
    rows, and a padded row must never win an election (every placement
    lands on a real, schedulable node)."""

    #: none divide the 8-shard mesh; all pad to the SAME 32-node snapshot
    #: bucket so the three seeds share one compile of each program (the
    #: raw-tensor rank-padding edge is exercised by tests/test_shard_wave)
    NODE_COUNTS = {0: 21, 1: 27, 2: 29}

    def _gang_quota_cluster(self, rng, n_nodes, n_gangs=4, gang_size=6,
                            n_singles=30):
        from scheduler_plugins_tpu.api.objects import (
            POD_GROUP_LABEL,
            ElasticQuota,
            PodGroup,
        )

        nodes, _ = random_cluster(rng, n_nodes, 0)
        cluster = Cluster()
        for n in nodes:
            cluster.add_node(n)
        namespaces = ["team-a", "team-b", "free-ns"]
        for ns in namespaces[:2]:  # one namespace stays quota-free
            cluster.add_quota(ElasticQuota(
                name=ns, namespace=ns,
                min={CPU: int(rng.integers(20_000, 60_000)),
                     MEMORY: int(rng.integers(64, 256)) * gib},
                max={CPU: int(rng.integers(60_000, 120_000)),
                     MEMORY: int(rng.integers(256, 512)) * gib},
            ))

        def add_pod(name, order, labels=None):
            ns = namespaces[order % 3]
            pod = Pod(
                name=name, namespace=ns, creation_ms=order,
                containers=[Container(requests={
                    CPU: int(rng.integers(100, 6000)),
                    MEMORY: int(rng.integers(1, 8)) * gib,
                })],
                labels=labels or {},
            )
            pod.uid = f"{ns}/{name}"
            cluster.add_pod(pod)

        order = 0
        for g in range(n_gangs):
            cluster.add_pod_group(
                PodGroup(name=f"gang-{g}", min_member=gang_size)
            )
            for m in range(gang_size):
                add_pod(f"gang-{g}-m{m}", order,
                        labels={POD_GROUP_LABEL: f"gang-{g}"})
                order += 1
        for s in range(n_singles):
            add_pod(f"single-{s}", order)
            order += 1
        return cluster

    # -- numpy replay oracles (no jax on the oracle side) ----------------
    def _fit_ok(self, an, snap):
        from scheduler_plugins_tpu.api.resources import CANONICAL, PODS as _P

        pods_i = CANONICAL.index(_P)
        req = np.asarray(snap.pods.req)
        alloc = np.asarray(snap.nodes.alloc)
        used = np.zeros_like(alloc)
        for p, n in enumerate(an):
            if n >= 0:
                used[n] += req[p]
                used[n, pods_i] += 1
        return bool((used <= alloc).all())

    def _quota_ok(self, an, snap):
        """Queue-order quota replay: every PLACED pod of a quota namespace
        must fit under its Max and the aggregate Min pool at its own
        admission step (the scan semantics both solvers enforce)."""
        if snap.quota is None:
            return True
        req = np.asarray(snap.pods.req).astype(np.int64)
        ns = np.asarray(snap.pods.ns)
        has_q = np.asarray(snap.quota.has_quota)
        qmax = np.asarray(snap.quota.max).astype(np.int64)
        qmin = np.asarray(snap.quota.min).astype(np.int64)
        used = np.asarray(snap.quota.used).astype(np.int64).copy()
        agg_min = (qmin * has_q[:, None]).sum(axis=0)
        agg_used = (used * has_q[:, None]).sum(axis=0)
        for p in range(len(an)):
            if an[p] < 0 or not has_q[ns[p]]:
                continue
            if (used[ns[p]] + req[p] > qmax[ns[p]]).any():
                return False
            if (agg_used + req[p] > agg_min).any():
                return False
            used[ns[p]] += req[p]
            agg_used += req[p]
        return True

    def _gang_quorum_ok(self, an, wait, snap):
        if snap.gangs is None:
            return True
        gang = np.asarray(snap.pods.gang)
        min_member = np.asarray(snap.gangs.min_member)
        assigned = np.asarray(snap.gangs.assigned)
        placed = an >= 0
        for g in range(len(min_member)):
            members = gang == g
            bound = int((members & placed & ~wait).sum())
            total = int((members & placed).sum()) + int(assigned[g])
            if bound > 0 and total < int(min_member[g]):
                return False
        return True

    def test_wave_hard_constraints_across_seeds(self):
        import jax
        import jax.numpy as jnp

        from scheduler_plugins_tpu.parallel import make_node_mesh
        from scheduler_plugins_tpu.parallel.solver import (
            batch_solve,
            sharded_wave_solve,
        )
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling,
            Coscheduling,
        )

        mesh = make_node_mesh(8)
        # one scheduler + one jitted batch solve across the seeds: the
        # three clusters share padded shapes, so every program compiles
        # exactly once
        sched = Scheduler(Profile(plugins=[
            NodeResourcesAllocatable(), Coscheduling(),
            CapacityScheduling(),
        ]))
        batch_jit = jax.jit(lambda s, w: batch_solve(s, w))
        for seed, n_nodes in self.NODE_COUNTS.items():
            rng = np.random.default_rng(seed)
            cluster = self._gang_quota_cluster(rng, n_nodes)
            pending = sched.sort_pending(cluster.pending_pods(), cluster)
            snap, meta = cluster.snapshot(pending, now_ms=0)
            sched.prepare(meta, cluster)
            weights = jnp.asarray(
                meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
            )

            seq = sched.solve(snap)
            a_seq = np.asarray(seq.assignment)
            w_seq = np.asarray(seq.wait)
            a_wave, _, w_wave = sharded_wave_solve(snap, mesh, weights)
            a_wave, w_wave = np.asarray(a_wave), np.asarray(w_wave)

            for mode, an, wait in (
                ("sequential", a_seq, w_seq), ("sharded-wave", a_wave, w_wave)
            ):
                assert self._fit_ok(an, snap), (seed, mode)
                assert self._quota_ok(an, snap), (seed, mode)
                assert self._gang_quorum_ok(an, wait, snap), (seed, mode)

            # padded ranks and masked/padded snapshot rows never win: every
            # placement lands on a real schedulable node row
            node_mask = np.asarray(snap.nodes.mask)
            placed_nodes = a_wave[a_wave >= 0]
            assert (placed_nodes < len(meta.node_names)).all(), seed
            assert node_mask[placed_nodes].all(), seed
            assert (a_wave >= 0).sum() > 0, seed

            # and the sharded election is BIT-IDENTICAL to the single-device
            # batched wave path on the same snapshot (this scale sits far
            # below the 2^53 cumulative-capacity parity bound)
            a_one, _, _ = batch_jit(snap, weights)
            assert (a_wave == np.asarray(a_one)).all(), seed

    def test_sharded_numa_profile_hard_constraints(self):
        # single-NUMA coverage for the sharded-solve family: the mixed
        # NUMA roster through the sharded PROFILE solve on the 8-way mesh
        # (mesh-aligned snapshot padding), replayed with the established
        # NUMA oracle
        from scheduler_plugins_tpu.parallel import make_mesh
        from scheduler_plugins_tpu.parallel.solver import (
            sharded_profile_batch_solve,
        )
        from scheduler_plugins_tpu.plugins import (
            Coscheduling,
            NodeResourceTopologyMatch,
        )

        helper = TestBatchedNumaGangHardConstraintParity()
        rng = np.random.default_rng(11)
        cluster = helper._cluster(
            rng, n_nodes=14, n_gangs=2, gang_size=4, n_singles=8
        )
        mesh = make_mesh(8)
        pods_dim, nodes_dim = mesh.devices.shape
        sched = Scheduler(Profile(plugins=[
            NodeResourceTopologyMatch(), Coscheduling(),
        ]))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        pad = lambda x, d: ((x + d - 1) // d) * d
        snap, meta = cluster.snapshot(
            pending, now_ms=0,
            pad_nodes=pad(14, nodes_dim), pad_pods=pad(len(pending), pods_dim),
        )
        sched.prepare(meta, cluster)
        a, _, wait = sharded_profile_batch_solve(sched, snap, mesh)
        an, wn = np.asarray(a), np.asarray(wait)
        assert helper._fit_ok(an, snap)
        assert helper._numa_ok(an, snap)
        assert helper._gang_quorum_ok(an, wn, snap)
        assert (an >= 0).sum() > 0


class TestRankGangDifferential:
    """ISSUE 10 oracle discipline: the jit topology-block waterfill
    (`gangs.topology.gang_solve_body`) must bit-match its numpy
    sequential twin across seeds, and an INDEPENDENT numpy replay of the
    placements must prove the hard constraints — fit (no node over
    free0), quota caps (no namespace over ElasticQuota max), and quorum
    (an admitted gang's resident+new ranks >= min; a rejected gang
    places ZERO new ranks)."""

    def _random_problem(self, seed):
        from scheduler_plugins_tpu.gangs.topology import RankGangState

        rng = np.random.default_rng(seed)
        N = int(rng.integers(8, 24))
        B = int(rng.integers(2, 5))
        G = int(rng.integers(2, 6))
        M = int(rng.integers(3, 9))
        R = 3  # cpu, memory, pods-style axis
        Q = int(rng.integers(1, 4))

        node_block = rng.integers(-1, B, size=N).astype(np.int32)
        node_mask = rng.random(N) > 0.1
        block_cost = rng.integers(1, 60, size=(B, B)).astype(np.int32)
        block_cost = np.maximum(block_cost, block_cost.T)
        np.fill_diagonal(block_cost, 1)

        # synthetic 3-slot axis local to this oracle (NOT the CANONICAL
        # layout — the gang solve is axis-order agnostic)
        free0 = np.zeros((N, R), np.int64)
        free0[:, 0] = rng.integers(1_000, 8_000, size=N)  # graft-lint: ignore[GL005]
        free0[:, 1] = rng.integers(4, 64, size=N)  # graft-lint: ignore[GL005]
        free0[:, 2] = rng.integers(2, 10, size=N)  # graft-lint: ignore[GL005]

        rank_req = np.zeros((G, M, R), np.int64)
        rank_mask = np.zeros((G, M), bool)
        prev = np.full((G, M), -1, np.int32)
        min_ranks = np.ones(G, np.int32)
        gang_ns = rng.integers(-1, Q, size=G).astype(np.int32)
        gang_mask = np.ones(G, bool)
        for g in range(G):
            k = int(rng.integers(2, M + 1))
            rank_mask[g, :k] = True
            rank_req[g, :k, 0] = rng.integers(200, 3_000, size=k)
            rank_req[g, :k, 1] = rng.integers(1, 8, size=k)
            rank_req[g, :k, 2] = 1
            min_ranks[g] = int(rng.integers(1, k + 1))
            # some gangs carry residents (elastic growth mid-flight)
            if rng.random() < 0.5:
                n_res = int(rng.integers(1, k))
                prev[g, :n_res] = rng.integers(0, N, size=n_res)

        eq_used0 = np.zeros((Q, R), np.int64)
        quota_max = np.full((Q, R), np.iinfo(np.int64).max, np.int64)
        quota_has = rng.random(Q) > 0.4
        for q in range(Q):
            if quota_has[q]:
                quota_max[q, 0] = int(rng.integers(2_000, 20_000))
                quota_max[q, 1] = int(rng.integers(16, 128))
                quota_max[q, 2] = int(rng.integers(4, 32))
                eq_used0[q, 0] = int(rng.integers(0, 1_000))

        gangs = RankGangState(
            rank_req=rank_req, rank_mask=rank_mask, prev_assigned=prev,
            min_ranks=min_ranks, gang_ns=gang_ns, gang_mask=gang_mask,
            node_block=node_block, block_cost=block_cost,
            quota_max=quota_max, quota_has=quota_has,
        )
        return gangs, free0, eq_used0, node_mask

    def _replay_oracle(self, gangs, free0, eq_used0, node_mask,
                      rank_nodes, admitted, placed_new):
        """Independent numpy audit — written against the CONTRACT, not
        the solver's code paths."""
        G, M, R = gangs.rank_req.shape
        new = (rank_nodes >= 0) & (gangs.prev_assigned < 0) & gangs.rank_mask
        # fit: total newly placed demand per node within free0, and only
        # on schedulable nodes
        used = np.zeros_like(free0)
        for g in range(G):
            for m in range(M):
                if new[g, m]:
                    n = int(rank_nodes[g, m])
                    assert node_mask[n], (g, m, n)
                    used[n] += gangs.rank_req[g, m]
        assert (used <= free0).all(), "node over free capacity"
        # quota caps: per-namespace new demand within max - used0
        for q in range(gangs.quota_max.shape[0]):
            if not gangs.quota_has[q]:
                continue
            dem = np.zeros(R, np.int64)
            for g in range(G):
                if gangs.gang_ns[g] == q:
                    dem += gangs.rank_req[g][new[g]].sum(axis=0)
            assert (eq_used0[q] + dem <= gangs.quota_max[q]).all(), \
                f"namespace {q} over quota max"
        # quorum / zero-partial
        for g in range(G):
            resident = int(
                ((gangs.prev_assigned[g] >= 0) & gangs.rank_mask[g]).sum()
            )
            n_new = int(new[g].sum())
            if admitted[g]:
                assert resident + n_new >= int(gangs.min_ranks[g]), g
                assert n_new == int(placed_new[g]), g
            else:
                assert n_new == 0, f"rejected gang {g} left partial ranks"

    def test_jit_matches_twin_and_oracle_across_seeds(self):
        import jax
        import jax.numpy as jnp

        from scheduler_plugins_tpu.framework.plugin import SolverState
        from scheduler_plugins_tpu.gangs.topology import (
            gang_solve_fn,
            gang_solve_np,
        )

        fn = gang_solve_fn()
        for seed in range(3):
            gangs, free0, eq_used0, node_mask = self._random_problem(
                1000 + seed
            )
            rn_np, adm_np, new_np, free_np, eq_np = gang_solve_np(
                gangs, free0, eq_used0, node_mask
            )
            state0 = SolverState(
                free=jnp.asarray(free0),
                eq_used=jnp.asarray(eq_used0),
                rank_nodes=jnp.asarray(gangs.prev_assigned),
            )
            rn_j, adm_j, new_j, state = fn(
                jax.tree.map(jnp.asarray, gangs), state0,
                jnp.asarray(node_mask),
            )
            assert (np.asarray(rn_j) == rn_np).all(), f"seed {seed}"
            assert (np.asarray(adm_j) == adm_np).all(), f"seed {seed}"
            assert (np.asarray(new_j) == new_np).all(), f"seed {seed}"
            assert (np.asarray(state.free) == free_np).all(), f"seed {seed}"
            assert (np.asarray(state.eq_used) == eq_np).all(), f"seed {seed}"
            self._replay_oracle(
                gangs, free0, eq_used0, node_mask, rn_np, adm_np, new_np
            )

    def test_shrink_selection_jit_matches_twin(self):
        import jax

        from scheduler_plugins_tpu.gangs.elastic import (
            shrink_select,
            shrink_select_np,
        )

        for seed in range(3):
            gangs, free0, _, _ = self._random_problem(2000 + seed)
            rng = np.random.default_rng(seed)
            G, M = gangs.rank_mask.shape
            N = free0.shape[0]
            rank_nodes = np.where(
                gangs.rank_mask, rng.integers(0, N, size=(G, M)), -1
            ).astype(np.int32)
            live = rank_nodes >= 0
            n_release = rng.integers(0, 3, size=G).astype(np.int32)
            got = np.asarray(jax.jit(shrink_select)(
                rank_nodes, live, gangs.node_block, gangs.block_cost,
                n_release,
            ))
            want = shrink_select_np(
                rank_nodes, live, gangs.node_block, gangs.block_cost,
                n_release,
            )
            assert (got == want).all(), f"seed {seed}"
            # contract: exactly min(n_release, live) released, live only
            assert (got <= live).all()
            assert (
                got.sum(axis=1)
                == np.minimum(n_release, live.sum(axis=1))
            ).all()


class TestWaveGangDifferential:
    """ISSUE 12: the wave-batched gang solve (`gangs.waves`) must be
    BIT-IDENTICAL to the numpy sequential twin — and therefore to the
    sequential jit scan it parity-anchors — on every output
    (rank_nodes, admitted, placed_new, AND the final free/eq_used
    carries), across seeds and wave widths (width 2 forces many waves,
    so between-wave host carries and the conflicted-lane host-resolve
    path both exercise), with the independent replay oracle proving the
    hard constraints. Problems reuse `TestRankGangDifferential`'s
    generator seeds, so the two gang differentials share shapes."""

    def test_wave_matches_twin_and_oracle_across_seeds(self):
        from scheduler_plugins_tpu.gangs.topology import gang_solve_np
        from scheduler_plugins_tpu.gangs.waves import wave_gang_solve

        base = TestRankGangDifferential()
        names = ("rank_nodes", "admitted", "placed_new", "free", "eq_used")
        for seed in range(3):
            gangs, free0, eq_used0, node_mask = base._random_problem(
                1000 + seed
            )
            ref = gang_solve_np(gangs, free0, eq_used0, node_mask)
            for wave in (2, 64):
                stats: dict = {}
                out = wave_gang_solve(
                    gangs, free0, eq_used0, node_mask, wave=wave,
                    stats=stats,
                )
                for got, want, name in zip(out, ref, names):
                    assert (np.asarray(got) == np.asarray(want)).all(), (
                        f"seed {seed} wave {wave}: {name} diverged from "
                        "the sequential twin"
                    )
                assert stats["waves"] >= 1
            base._replay_oracle(
                gangs, free0, eq_used0, node_mask, out[0], out[1], out[2]
            )


# ---------------------------------------------------------------------------
# ISSUE 13: Pallas ring-kernel election parity (SPT_PALLAS=1 interpret twins)
# ---------------------------------------------------------------------------


class TestPallasWaveParity:
    """ISSUE 13 acceptance gate: the `SPT_PALLAS=1` interpret-mode sharded
    wave solve — every per-wave collective replaced by the
    `parallel.kernels` Pallas ring programs, the admission-verdict psum
    replaced by replicated math over the election payload — must be
    BIT-IDENTICAL to the lax collectives formulation: placements AND the
    resident rank-free carry, across >= 2 shard counts and 3 seeds.

    The whole class is `slow`: each shard count is its own multi-device
    compile and tier-1 sits AT the 870s runtime cliff (the clean run
    finishes ~855s — teardown alone eats the margin), so the full-solve
    matrix rides `make pallas-smoke` + CI instead; tier-1 keeps the
    kernel-level parity/edge coverage (tests/test_pallas_kernels.py,
    compile-cheap) in-suite."""

    SEEDS = (0, 1, 2)
    #: (pallas?, shards) -> built chunk solver: seeds share one compile
    _solvers: dict = {}

    @staticmethod
    def _problem(seed, n_nodes=24, n_pods=120):
        import jax.numpy as jnp

        from scheduler_plugins_tpu.api.resources import CANONICAL

        rng = np.random.default_rng(seed)
        tight = seed % 2 == 1  # alternate loose/tight so rescue waves and
        # hopeless retirements fire inside the matrix
        cpu_hi = 8_000 if tight else 64_000
        alloc = np.stack([
            rng.integers(2000, cpu_hi, n_nodes),
            rng.integers(4, 64 if tight else 256, n_nodes) * gib,
            np.zeros(n_nodes, np.int64),
            rng.integers(2 if tight else 4, 60, n_nodes),
        ], axis=1).astype(np.int64)[:, :len(CANONICAL)]
        req = np.stack([
            rng.integers(50, 8000, n_pods),
            rng.integers(1, 16, n_pods) * gib,
            np.zeros(n_pods, np.int64),
            np.zeros(n_pods, np.int64),
        ], axis=1).astype(np.int64)[:, :len(CANONICAL)]
        free0 = jnp.asarray(alloc)
        cpu_col = free0[:, CANONICAL.index(CPU)]
        mem_col = free0[:, CANONICAL.index(MEMORY)]
        raw = -(cpu_col * (1 << 20) + mem_col) // ((1 << 20) + 1)
        node_mask = jnp.asarray(rng.random(n_nodes) > 0.1)
        pod_mask = jnp.asarray(rng.random(n_pods) > 0.05)
        return raw, free0, node_mask, jnp.asarray(req), pod_mask

    @classmethod
    def _solver(cls, S, n_nodes, use_pallas):
        from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
        from scheduler_plugins_tpu.parallel.solver import (
            sharded_wave_chunk_solver,
        )

        key = (use_pallas, S, n_nodes)
        if key not in cls._solvers:
            cls._solvers[key] = sharded_wave_chunk_solver(
                make_node_mesh(S), n_nodes, max_waves=8,
                rescue_window=64, lite_window=32,
                use_pallas=use_pallas, pallas_interpret=True,
            )
        return cls._solvers[key]

    def _assert_pair_bitident(self, S, seed):
        from scheduler_plugins_tpu.parallel.solver import rank_order_inputs

        raw, free0, node_mask, req, pod_mask = self._problem(seed)
        node_ids, rank_free0 = rank_order_inputs(raw, free0, node_mask, S)
        outs = {}
        for use_pallas in (False, True):
            solver = self._solver(S, free0.shape[0], use_pallas)
            (a, _stats), rf = solver(
                node_ids, req, pod_mask, jnp.asarray(rank_free0)
            )
            outs[use_pallas] = (np.asarray(a), np.asarray(rf))
        a_lax, f_lax = outs[False]
        a_pk, f_pk = outs[True]
        assert (a_pk == a_lax).all(), (S, seed, "placements diverged")
        assert (f_pk == f_lax).all(), (S, seed, "free carry diverged")
        assert (a_pk >= 0).sum() > 0, (S, seed)

    @pytest.mark.slow
    def test_two_shard_bitident_three_seeds(self):
        for seed in self.SEEDS:
            self._assert_pair_bitident(2, seed)

    @pytest.mark.slow
    @pytest.mark.parametrize("S", [4, 8])
    def test_wider_mesh_bitident_three_seeds(self, S):
        for seed in self.SEEDS:
            self._assert_pair_bitident(S, seed)

    @pytest.mark.slow
    def test_gang_quota_envelope_bitident(self, monkeypatch):
        """The full `sharded_wave_solve` envelope (gang/quota PreFilter +
        queue-order quota prefix + gang quorum Permit) under SPT_PALLAS=1:
        assignment, admitted and wait must match the lax build exactly on
        a gang+quota cluster, and the hard-constraint oracles must hold —
        the env-var wiring path, not just the explicit-flag path."""
        import jax.numpy as jnp

        from scheduler_plugins_tpu.parallel import make_node_mesh
        from scheduler_plugins_tpu.parallel.solver import sharded_wave_solve
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling,
            Coscheduling,
        )

        base = TestShardedWaveHardConstraintParity()
        rng = np.random.default_rng(3)
        cluster = base._gang_quota_cluster(rng, 21)
        sched = Scheduler(Profile(plugins=[
            NodeResourcesAllocatable(), Coscheduling(),
            CapacityScheduling(),
        ]))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        weights = jnp.asarray(
            meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
        )
        mesh = make_node_mesh(4)
        monkeypatch.delenv("SPT_PALLAS", raising=False)
        a0, ad0, w0 = sharded_wave_solve(snap, mesh, weights)
        monkeypatch.setenv("SPT_PALLAS", "1")
        monkeypatch.setenv("SPT_PALLAS_INTERPRET", "1")
        a1, ad1, w1 = sharded_wave_solve(snap, mesh, weights)
        for u, v, name in (
            (a0, a1, "assignment"), (ad0, ad1, "admitted"),
            (w0, w1, "wait"),
        ):
            assert (np.asarray(u) == np.asarray(v)).all(), name
        an, wt = np.asarray(a1), np.asarray(w1)
        assert base._fit_ok(an, snap)
        assert base._quota_ok(an, snap)
        assert base._gang_quorum_ok(an, wt, snap)
