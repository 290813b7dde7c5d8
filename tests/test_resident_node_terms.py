"""NodeAffinity's (spec, node) verdict and score rows as resident state
(ISSUE 38; docs/SERVING.md "Resident node-term rows").

(i) randomized streams on a 48-node cluster of three zones and two pools
(pods with a `nodeSelector`, required terms over all six operators and
`matchFields`, preferred terms with weights, pods with none; nodes added,
relabelled into and out of a term's reach, and deleted mid-stream) through
the resident engine and through a twin that rebuilds `build_scheduling`
every cycle: bit-equal placements, equal gathered rows at every cycle,
`engine.verify` clean; (ii) padded spec axes solve as exact-size ones, 60
cycles of specs that come and go compile no more `solve` shapes than the
bucket pairs crossed, a held row is not evaluated again, and a full axis
releases its idle rows; (iii) `benchmark/references/nodeaffinity.py`
against the sequential solve on seeded 48-node clusters (the tier-1 mirror
of `benchmark/tests/test_config_nodeaffinity.py`), the cell rehearsed once
through the real command, and the two planted faults ending `correct:
false`; (iv) one case per clause `ServeEngine.fallback_reason` still
refuses, asserting the reason's counter; (v) a tampered row is a
`node-terms` divergence for both kinds of check, and heals; (vi) a store
whose pods carry no node term builds no row.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    ZONE_LABEL,
    Container,
    LabelSelector,
    Node,
    NodeSelectorRequirement as Req,
    NodeSelectorTerm as Term,
    Pod,
    PodAffinityTerm,
    PreferredSchedulingTerm as Preferred,
    Taint,
    TopologySpreadConstraint,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.plugins.intree import (
    NodeAffinity,
    PodTopologySpread,
)
from scheduler_plugins_tpu.serving import ServeEngine, node_terms
from scheduler_plugins_tpu.serving.engine import StreamingServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
for path in (os.path.join(BENCH_DIR, "tests"), BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_config_nodeaffinity as by_hand  # noqa: E402

gib = 1 << 30


def pooled_node(i, zone=None, pool=None, cores=None, gpu=False):
    labels = {
        ZONE_LABEL: zone or f"z{i % 3}",
        "pool": pool or "ab"[i % 2],
        "cores": str(cores or 4 * (1 + i % 4)),
    }
    if gpu or i % 8 == 0:
        labels["gpu"] = "yes"
    return Node(
        name=f"n{i:03d}", labels=labels,
        allocatable={CPU: 4000 * (1 + i % 4), MEMORY: 16 * gib, PODS: 110},
    )


def pooled_cluster(n_nodes=48):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(pooled_node(i))
    return cluster


def term(*expressions, fields=()):
    return Term(
        match_expressions=[Req(*r) for r in expressions],
        match_fields=[Req(*r) for r in fields],
    )


#: what a pod may say of its nodes: every operator, `matchFields`, a
#: selector ANDed with an OR of terms, preferred weights, and nothing
SPECS = [
    lambda: {},
    lambda: dict(node_selector={"pool": "a"}),
    lambda: dict(node_selector={"pool": "b", ZONE_LABEL: "z1"}),
    lambda: dict(node_affinity_required=[
        term((ZONE_LABEL, "In", ("z0", "z1"))),
    ]),
    lambda: dict(node_affinity_required=[
        term((ZONE_LABEL, "NotIn", ("z0",))),
    ]),
    lambda: dict(node_affinity_required=[term(("gpu", "Exists"))]),
    lambda: dict(node_affinity_required=[term(("gpu", "DoesNotExist"))]),
    lambda: dict(node_affinity_required=[term(("cores", "Gt", ("4",)))]),
    lambda: dict(node_affinity_required=[term(("cores", "Lt", ("12",)))]),
    lambda: dict(node_affinity_required=[term(fields=[
        ("metadata.name", "In", ("n003", "n007", "n011", "n050")),
    ])]),
    lambda: dict(
        node_selector={"pool": "a"},
        node_affinity_required=[
            term((ZONE_LABEL, "In", ("z2",))),
            term(("cores", "Gt", ("8",)), ("gpu", "DoesNotExist")),
        ],
    ),
    lambda: dict(node_affinity_preferred=[
        Preferred(80, term((ZONE_LABEL, "In", ("z2",)))),
        Preferred(20, term(("pool", "In", ("a",)))),
    ]),
    lambda: dict(
        node_affinity_required=[term((ZONE_LABEL, "NotIn", ("z1",)))],
        node_affinity_preferred=[Preferred(50, term(("gpu", "Exists")))],
    ),
    lambda: dict(node_affinity_required=[
        term((ZONE_LABEL, "In", ("nowhere",))),  # no node admits it
    ]),
]


def spec_pod(serial, now, spec, cpu=300):
    return Pod(
        name=f"p{serial:05d}", creation_ms=now + serial,
        containers=[Container(requests={CPU: cpu, MEMORY: gib // 2})],
        **SPECS[spec](),
    )


def affinity_scheduler(*more):
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), NodeAffinity(), *more,
    ]))


def fallbacks(reason=None) -> int:
    if reason is not None:
        return obs.metrics.get(obs.SERVE_FALLBACKS, reason=reason)
    return sum(
        v for k, v in obs.metrics.snapshot().items()
        if k.startswith(obs.SERVE_FALLBACKS)
    )


def counter(name) -> int:
    return obs.metrics.get(name)


def settled(engine, cluster, now) -> None:
    """Drain the cycle's own binds (they are still in the sink), then both
    kinds of anti-entropy check."""
    assert engine.refresh(cluster, [], now_ms=now) is not None
    assert engine.verify(cluster) is None
    assert engine.verify_assigned(cluster) is None


def gathered(scheduling, n_pods: int, n_nodes: int) -> tuple:
    """The (pods, nodes) verdicts and scores a solve would gather."""
    if scheduling is None:
        return None
    ok = np.asarray(scheduling.node_term_ok)[
        np.asarray(scheduling.pod_node_term)[:n_pods]
    ][:, :n_nodes]
    pref = np.asarray(scheduling.pref_score)[
        np.asarray(scheduling.pod_pref)[:n_pods]
    ][:, :n_nodes]
    return ok, pref


def assert_gathers_as_fresh(engine, cluster, twin, sched, now) -> None:
    """The batch as the engine assembles it against the twin's fresh build:
    the rows each pod gathers, over the real nodes."""
    pending = sched.sort_pending(cluster.pending_pods(), cluster)
    refreshed = engine.refresh(cluster, pending, now_ms=now)
    assert refreshed is not None, engine.fallback_reason(cluster, pending)
    theirs = sched.sort_pending(twin.pending_pods(), twin)
    fresh, _ = twin.snapshot(theirs, now_ms=now)
    assert [p.uid for p in pending] == [p.uid for p in theirs]
    mine = gathered(refreshed[0].scheduling, len(pending), len(cluster.nodes))
    want = gathered(fresh.scheduling, len(theirs), len(twin.nodes))
    assert (mine is None) == (want is None)
    if mine is not None:
        np.testing.assert_array_equal(mine[0], want[0])
        np.testing.assert_array_equal(mine[1], want[1])


class TestRandomizedStreams:
    @pytest.mark.parametrize("engine_class", [ServeEngine,
                                              StreamingServeEngine])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_resident_engine_equals_a_twin_that_rebuilds(self, seed,
                                                         engine_class):
        rng = np.random.default_rng(380 + seed)
        serve_cluster, base_cluster = pooled_cluster(), pooled_cluster()
        engine = engine_class().attach(serve_cluster)
        s_sched, b_sched = affinity_scheduler(), affinity_scheduler()
        fell_back = fallbacks()
        serial = 0
        placed = 0
        for cycle in range(14):
            now = 1000 * (cycle + 1)
            events = []
            for _ in range(int(rng.integers(2, 8))):
                serial += 1
                events.append(("pod", serial, int(rng.integers(len(SPECS))),
                               int(rng.integers(100, 900))))
            bound = sorted(
                uid for uid, p in serve_cluster.pods.items() if p.node_name
            )
            for _ in range(int(rng.integers(0, 4))):
                if bound:
                    events.append((
                        "del", bound.pop(int(rng.integers(0, len(bound))))
                    ))
            if cycle == 2:
                events.append(("node", 48, {}))  # a node arrives
            if cycle == 4:  # out of `pool: a`'s reach, into `gpu`'s
                events.append(("node", 5, dict(pool="b", gpu=True)))
            if cycle == 6:  # n007 leaves z1: a region/zone change rebases
                events.append(("node", 7, dict(zone="z2")))
            if cycle == 8:  # into `cores > 8`'s reach
                events.append(("node", 12, dict(cores=16)))
            if cycle == 10:
                events.append(("gone", "n020"))
            if cycle == 12:
                events.append(("node", 49, dict(zone="z2", pool="a")))
            for cl in (serve_cluster, base_cluster):
                for e in events:
                    if e[0] == "pod":
                        cl.add_pod(spec_pod(e[1], now, e[2], cpu=e[3]))
                    elif e[0] == "del":
                        cl.remove_pod(e[1])
                    elif e[0] == "node":
                        cl.add_node(pooled_node(e[1], **e[2]))
                    elif e[0] == "gone":
                        cl.remove_node(e[1])
            assert_gathers_as_fresh(
                engine, serve_cluster, base_cluster, s_sched, now
            )
            serve_report = run_cycle(
                s_sched, serve_cluster, now=now, serve=engine
            )
            base_report = run_cycle(b_sched, base_cluster, now=now)
            assert serve_report.bound == base_report.bound, cycle
            assert serve_report.failed == base_report.failed, cycle
            placed += len(serve_report.bound)
            # the cycle's own binds are still in the delta sink
            assert engine.refresh(
                serve_cluster, [], now_ms=now + 500
            ) is not None
            assert engine.verify(serve_cluster) is None, cycle
            assert engine.verify_assigned(serve_cluster) is None, cycle
        assert fallbacks() == fell_back
        assert engine.antientropy_divergences == 0
        assert placed > 30
        # the pods no node admits are still pending, and nothing else
        stuck = {p.uid for p in serve_cluster.pods.values()
                 if p.node_name is None}
        assert stuck == {p.uid for p in base_cluster.pods.values()
                         if p.node_name is None}

    def test_a_spread_pod_beside_a_node_term_pod_is_served(self):
        """One batch, a pod with a node term and a pod with a spread
        constraint: neither reads the other's tables, both are resident."""
        clusters = pooled_cluster(12), pooled_cluster(12)
        engine = ServeEngine().attach(clusters[0])
        scheds = [affinity_scheduler(PodTopologySpread()) for _ in clusters]
        fell_back = fallbacks()
        reports = []
        for cycle in range(3):
            now = 1000 * (cycle + 1)
            for cl in clusters:
                cl.add_pod(spec_pod(10 * cycle + 1, now, 3))
                cl.add_pod(dataclasses.replace(
                    spec_pod(10 * cycle + 2, now, 0),
                    labels={"color": "blue"},
                    topology_spread=[TopologySpreadConstraint(
                        max_skew=1, topology_key=ZONE_LABEL,
                        label_selector=LabelSelector(
                            match_labels={"color": "blue"}),
                    )],
                ))
                cl.add_pod(spec_pod(10 * cycle + 3, now, 11))
            assert_gathers_as_fresh(engine, *clusters, scheds[0], now)
            reports = [
                run_cycle(scheds[0], clusters[0], now=now, serve=engine),
                run_cycle(scheds[1], clusters[1], now=now),
            ]
            assert reports[0].bound == reports[1].bound
            assert len(reports[0].bound) == 3
            assert engine.refresh(clusters[0], [], now_ms=now + 500)
            assert engine.verify(clusters[0]) is None
        assert fallbacks() == fell_back and engine.rebases == 1


class TestNodeEventsCostHeldSpecs:
    def _served(self, n_specs=3):
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        for spec in range(1, 1 + n_specs):
            cluster.add_pod(spec_pod(spec, 0, spec))
        run_cycle(sched, cluster, now=1000, serve=engine)
        return cluster, engine, sched

    def test_a_new_and_a_relabelled_node_write_a_column_and_no_more(self):
        cluster, engine, sched = self._served()
        held = engine._node_terms
        rows, columns = (counter(obs.SERVE_NODE_TERM_ROWS),
                         counter(obs.SERVE_NODE_TERM_COLUMNS))
        epoch = held.epoch
        assert held.held == 3
        # a new node in pool a: row `pool: a` admits it from this refresh on
        cluster.add_node(pooled_node(13, pool="a"))
        cluster.add_pod(spec_pod(20, 2000, 1))
        pending = cluster.pending_pods()
        snap, _ = engine.refresh(cluster, pending, now_ms=2000)
        ok, _ = gathered(snap.scheduling, 1, 13)
        assert ok[0, 12] and counter(obs.SERVE_NODE_TERM_COLUMNS) == columns + 1
        # the same node out of the pool's reach: its column, nothing else
        cluster.add_node(pooled_node(13, pool="b"))
        snap, _ = engine.refresh(cluster, pending, now_ms=2100)
        ok, _ = gathered(snap.scheduling, 1, 13)
        assert not ok[0, 12]
        assert counter(obs.SERVE_NODE_TERM_COLUMNS) == columns + 2
        # a node sent again as it was writes nothing
        cluster.add_node(pooled_node(13, pool="b"))
        engine.refresh(cluster, pending, now_ms=2200)
        assert counter(obs.SERVE_NODE_TERM_COLUMNS) == columns + 2
        assert counter(obs.SERVE_NODE_TERM_ROWS) == rows
        assert engine.rebases == 1 and held.epoch == epoch
        assert engine.verify(cluster) is None

    def test_a_node_delete_rebases_and_the_rows_come_back_on_first_use(self):
        cluster, engine, sched = self._served()
        rows = counter(obs.SERVE_NODE_TERM_ROWS)
        cluster.remove_node("n002")
        cluster.add_pod(spec_pod(30, 3000, 2))
        report = run_cycle(sched, cluster, now=3000, serve=engine)
        assert engine.rebases == 2 and len(report.bound) == 1
        # the batch's one spec was evaluated again; the other two were not
        assert counter(obs.SERVE_NODE_TERM_ROWS) == rows + 1
        assert engine._node_terms.held == 1
        assert engine.refresh(cluster, [], now_ms=3500) is not None
        assert engine.verify(cluster) is None

    def test_a_restored_checkpoint_carries_no_row(self):
        cluster, engine, sched = self._served()
        engine.refresh(cluster, [], now_ms=1500)
        data = engine.checkpoint_bytes()
        restored = ServeEngine().attach(cluster)
        assert restored.restore_checkpoint(data)
        assert restored._node_terms.held == 0
        cluster.add_pod(spec_pod(40, 4000, 1))
        report = run_cycle(sched, cluster, now=4000, serve=restored)
        assert len(report.bound) == 1 and restored._node_terms.held == 1
        assert restored.rebases == 0  # the restore was exact: no rebase


class TestPaddedAxesAreInert:
    # 1 spec pads the axis (with its trivial row) to 8; 7 sit on it
    # exactly; 8 pad to 16
    @pytest.mark.parametrize("n_specs", [1, 7, 8])
    def test_padded_solve_equals_exact_solve(self, n_specs):
        cluster = pooled_cluster(24)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        zones = ("z0", "z1", "z2")
        for s in range(n_specs):
            for copy in range(2):
                cluster.add_pod(dataclasses.replace(
                    spec_pod(10 * s + copy, 0, 0),
                    node_affinity_required=[term(
                        (ZONE_LABEL, "In", (zones[s % 3],)),
                        ("cores", "Gt", (str(s),)),
                    )],
                    node_affinity_preferred=[Preferred(
                        1 + s, term(("pool", "In", ("ab"[s % 2],))),
                    )],
                ))
        cluster.add_pod(spec_pod(999, 0, 0))  # and a pod without any
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        padded_snap, meta = engine.refresh(cluster, pending, now_ms=1000)
        exact_snap, _ = cluster.snapshot(
            pending, now_ms=1000, pad_nodes=engine.npad
        )
        mine, theirs = padded_snap.scheduling, exact_snap.scheduling
        assert theirs.node_term_ok.shape[0] == n_specs + 1
        assert mine.node_term_ok.shape[0] == bucket_size(n_specs + 1)
        assert mine.pref_score.shape[0] == bucket_size(n_specs + 1)
        # the trivial rows sit at index 0, and an unused row is one of them
        assert np.asarray(mine.node_term_ok)[0].all()
        assert np.asarray(mine.node_term_ok)[n_specs + 1:].all()
        assert not np.asarray(mine.pref_score)[0].any()
        assert not np.asarray(mine.pref_score)[n_specs + 1:].any()
        assert int(np.asarray(mine.pod_node_term)[len(pending) - 1]) == 0
        assert mine.pend_match is None and theirs.pend_match is None
        sched.prepare(meta, cluster)
        padded = sched.solve(padded_snap)
        exact = sched.solve(exact_snap)
        for name in ("assignment", "admitted", "wait", "failed_plugin"):
            np.testing.assert_array_equal(
                np.asarray(getattr(padded, name)),
                np.asarray(getattr(exact, name)), err_msg=name,
            )
        assert (np.asarray(padded.assignment) >= 0).any()


def _misses(program: str) -> int:
    return sum(
        value for key, value in obs.metrics.snapshot().items()
        if key.startswith(obs.JIT_CACHE_MISS) and f'"{program}"' in key
    )


def numbered_pod(serial, now, number, preferred=False):
    """A pod of workload `number`: its own required spec (and, with
    `preferred`, its own preferred one)."""
    spec = dict(node_affinity_required=[term(
        ("cores", "Gt", (str(number % 12),)), ("pool", "Exists"),
    )])
    if preferred:
        spec["node_affinity_preferred"] = [
            Preferred(1 + number % 50, term(("pool", "In", ("a",)))),
        ]
    return dataclasses.replace(spec_pod(serial, now, 0, cpu=50), **spec)


class TestShapesFollowBuckets:
    def test_specs_that_come_and_go_compile_per_bucket_pair_crossed(self):
        """60 served cycles over a store whose workloads, one spec each, go
        from 1 to 12 and back: the batch stays in one pod bucket, so every
        `solve` shape is a (term bucket, preference bucket) pair; and a row
        is evaluated when its spec is first named, not once a cycle."""
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        solve0 = _misses("solve")
        rows0 = counter(obs.SERVE_NODE_TERM_ROWS)
        pairs, specs = set(), set()
        live: list = []
        serial = 0
        for cycle in range(60):
            now = 1000 * (cycle + 1)
            rising = cycle < 30
            if cycle % 2 == 0:
                if rising and len(live) < 12:
                    live.append(len(live))
                elif not rising and len(live) > 1:
                    live.pop()
            # two replicas of the two newest workloads: the pod bucket
            # never moves, and an old workload's row goes ungathered
            for number in live[-2:]:
                for _ in range(2):
                    serial += 1
                    cluster.add_pod(numbered_pod(
                        serial, now, number, preferred=number >= 4
                    ))
                specs.add(("term", number))
                if number >= 4:
                    specs.add(("pref", number))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            assert len(report.bound) == 2 * len(live[-2:]), cycle
            held = engine._node_terms
            pairs.add((held._term.table.shape[0], held._pref.table.shape[0]))
            for uid in list(report.bound):
                cluster.remove_pod(uid)
        assert max(p[0] for p in pairs) == 16 and len(pairs) >= 2
        assert _misses("solve") - solve0 <= len(pairs)
        # 12 required specs and 8 preferred ones over 60 cycles and 200
        # pods: 20 rows evaluated, each once (nothing was released: no
        # row went ungathered for `IDLE_CYCLES`)
        assert counter(obs.SERVE_NODE_TERM_ROWS) - rows0 == len(specs) == 20
        assert engine.rebases == 1 and fallbacks("node-affinity") == 0
        assert engine.refresh(cluster, [], now_ms=99_000) is not None
        assert engine.verify(cluster) is None

    def test_a_full_axis_releases_its_idle_rows(self, monkeypatch):
        """With the axis full (7 specs and the trivial row on 8), the next
        spec lays the table out again: rows ungathered for `IDLE_CYCLES`
        go, the batch's own and the recent ones stay; with nothing idle
        the axis takes the next bucket."""
        monkeypatch.setattr(node_terms, "IDLE_CYCLES", 3)
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        held = engine._node_terms
        rebases = counter(obs.SERVE_NODE_TERM_REBASES)
        serial = 0

        def cycle(now, numbers):
            nonlocal serial
            for number in numbers:
                serial += 1
                cluster.add_pod(numbered_pod(serial, now, number))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            assert len(report.bound) == len(numbers)

        cycle(1000, range(7))  # the axis is full
        assert held._term.table.shape[0] == 8 and len(held._term.rows) == 7
        for now in range(2000, 7000, 1000):
            cycle(now, [5, 6])  # rows 0-4 go idle
        assert counter(obs.SERVE_NODE_TERM_REBASES) == rebases
        epoch = held.epoch
        rows = counter(obs.SERVE_NODE_TERM_ROWS)
        cycle(7000, [6, 7])  # an eighth spec: 0-4 are released
        assert counter(obs.SERVE_NODE_TERM_REBASES) == rebases + 1
        assert held.epoch == epoch + 1 and len(held._term.rows) == 3
        assert held._term.table.shape[0] == 8
        assert counter(obs.SERVE_NODE_TERM_ROWS) == rows + 1
        settled(engine, cluster, 7500)
        # a released spec that comes back costs one evaluation
        cycle(8000, [0])
        assert counter(obs.SERVE_NODE_TERM_ROWS) == rows + 2
        # eight more specs in one batch (12 + 0 is held), none idle: the
        # next bucket
        cycle(9000, range(8, 17))
        assert held._term.table.shape[0] == 16
        assert len(held._term.rows) == 12
        assert counter(obs.SERVE_NODE_TERM_REBASES) == rebases + 2
        settled(engine, cluster, 9500)
        assert engine.rebases == 1


class TestReferenceAndCell:
    @pytest.mark.parametrize("resident", [False, True])
    @pytest.mark.parametrize("case", by_hand.CASES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_reference_equals_the_sequential_solve(self, case, seed,
                                                   resident):
        by_hand.assert_reference_equals_solve(case, seed, resident)

    def test_the_cell_rehearses_to_a_correct_result(self):
        result, info, stderr = by_hand.rehearse(3, trace=1)
        by_hand.assert_sound(result, info, stderr)
        by_hand.assert_resident(
            {k: v["value"] for k, v in result["metrics"].items()}
        )

    @pytest.mark.parametrize("fault", sorted(by_hand.TAMPERS))
    def test_a_planted_fault_ends_not_correct(self, tmp_path, fault):
        by_hand.assert_planted_fault_is_found(tmp_path, fault)

    def test_the_audit_names_a_pod_its_term_refuses(self):
        by_hand.test_the_audit_names_a_pod_its_term_refuses()

    def test_population_shapes_do_not_depend_on_the_seed(self):
        from harness import spec

        config = spec.Cell(by_hand.CELL, rehearse=True).config
        for seed in (0, 3, 2147483777):
            found = by_hand.population_counts(config, seed, 200)
            assert found["nodes"] == 48 and found["objects"] == 0
            assert len(found["labels"]) == 1
            assert found["templated"] == found["pods"] == 464
            assert found["bound"] == found["uids"] == 200


def _plain_pod(name="q0", **spec):
    return Pod(
        name=name, creation_ms=1,
        containers=[Container(requests={CPU: 100, MEMORY: gib})], **spec
    )


def _bound(pod, node="n000"):
    pod.node_name = node
    return pod


def _spread(**more):
    return [TopologySpreadConstraint(
        max_skew=1, topology_key=ZONE_LABEL,
        label_selector=LabelSelector(match_labels={"color": "blue"}), **more,
    )]


#: reason -> what makes the store, or the batch, fall under that clause
CLAUSES = {
    "nrt": lambda c: c.nrts.update({"n000": object()}),
    "app-group": lambda c: c.app_groups.update({"default/ag": object()}),
    "seccomp": lambda c: c.seccomp_profiles.update({"default/sp": object()}),
    "taints": lambda c: c.add_node(dataclasses.replace(
        pooled_node(0), taints=[Taint(key="dedicated", value="x")],
    )),
    "affinity-namespace-selector": lambda c: c.add_pod(_bound(_plain_pod(
        "carrier", labels={"app": "web"},
        pod_anti_affinity_required=[PodAffinityTerm(
            topology_key=ZONE_LABEL,
            label_selector=LabelSelector(match_labels={"app": "web"}),
            namespace_selector=LabelSelector(match_labels={"team": "a"}),
        )],
    ))),
    "nomination": lambda c: c.add_pod(_plain_pod(
        "nominee", nominated_node_name="n001",
    )),
    # what ISSUE 38 left of `node-affinity`: one pod with a node term AND
    # a spread constraint that honours it
    "spread-node-affinity": lambda c: c.add_pod(_plain_pod(
        "both", labels={"color": "blue"}, node_selector={"pool": "a"},
        topology_spread=_spread(),
    )),
    # a node with the zone key and no hostname key: a pod naming both in
    # one class has its domains counted by node
    "spread-node-counts": lambda c: (
        [c.add_node(dataclasses.replace(
            node, labels=dict(node.labels, host=node.name),
        )) for node in list(c.nodes.values())[1:]],
        c.add_pod(_plain_pod(
            "two-keys", labels={"color": "blue"},
            topology_spread=_spread() + [TopologySpreadConstraint(
                max_skew=3, topology_key="host",
                label_selector=LabelSelector(match_labels={"color": "blue"}),
            )],
        )),
    ),
}


class TestWhatStillFallsBack:
    @pytest.mark.parametrize("reason", sorted(CLAUSES))
    def test_each_remaining_clause_falls_back_under_its_reason(self, reason):
        cluster = pooled_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler(PodTopologySpread())
        cluster.add_pod(spec_pod(1, 0, 3))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.rebases == 1 and engine._node_terms.held == 1
        CLAUSES[reason](cluster)
        before, total = fallbacks(reason), fallbacks()
        cluster.add_pod(spec_pod(2, 2000, 3))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        if reason != "taints":  # seen where the node's event is drained
            assert engine.fallback_reason(cluster, pending) == reason
        assert engine.refresh(cluster, pending, now_ms=2000) is None
        assert fallbacks(reason) == before + 1
        assert fallbacks() == total + 1
        assert fallbacks("node-affinity") == 0  # the clause is gone
        if reason in ("nrt", "app-group", "seccomp"):
            return  # stand-in objects: nothing a fresh build can lower
        # and the cycle is solved all the same, by the fresh build
        report = run_cycle(sched, cluster, now=3000, serve=engine)
        assert "default/p00002" in report.bound

    def test_a_spread_constraint_that_ignores_the_node_term_is_served(self):
        cluster = pooled_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler(PodTopologySpread())
        total = fallbacks()
        cluster.add_pod(_plain_pod(
            "both", labels={"color": "blue"}, node_selector={"pool": "a"},
            topology_spread=_spread(node_affinity_policy="Ignore"),
        ))
        report = run_cycle(sched, cluster, now=1000, serve=engine)
        assert report.bound["default/both"] in ("n000", "n002", "n004")
        assert fallbacks() == total and engine.rebases == 1


class TestAntiEntropy:
    @pytest.mark.parametrize("table", ["node_term_ok", "pref_score"])
    @pytest.mark.parametrize("where", ["host", "staged"])
    def test_a_tampered_row_is_a_divergence_and_heals(self, table, where):
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        for serial, spec in enumerate((3, 11, 12)):
            cluster.add_pod(spec_pod(serial, 0, spec))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.refresh(cluster, [], now_ms=1500) is not None
        assert engine.verify(cluster) is None
        assert engine.verify_assigned(cluster) is None
        held = engine._node_terms
        rows = held._term if table == "node_term_ok" else held._pref
        if where == "host":
            rows.table[1, 2] = not rows.table[1, 2]
        else:
            staged = np.array(held._staged[table])
            staged[1, 2] = not staged[1, 2]
            held._staged[table] = engine._stage_pods(staged)
        assert engine.verify_assigned(cluster) == "node-terms"
        assert engine.verify(cluster) == "node-terms"
        assert engine.antientropy_divergences == 2
        # what `refresh` does with a divergence: rebase, from the store
        engine._rebase(cluster, [], 2000)
        assert held.held == 0 and engine.verify(cluster) is None
        cluster.add_pod(spec_pod(9, 3000, 11))
        report = run_cycle(sched, cluster, now=3000, serve=engine)
        assert len(report.bound) == 1
        settled(engine, cluster, 3500)

    def test_a_record_that_points_at_another_row_is_a_divergence(self):
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        cluster.add_pod(spec_pod(1, 0, 13))  # no node admits it: it stays
        cluster.add_pod(spec_pod(2, 0, 3))
        run_cycle(sched, cluster, now=1000, serve=engine)
        stuck = cluster.pods["default/p00001"]
        assert stuck.node_name is None
        settled(engine, cluster, 1500)
        record = engine._records[stuck.uid]
        epoch, ok_row, pref_row = record.node_rows
        held = engine._node_terms
        pods, term_rows, pref_rows = held._last
        term_rows[pods.index(stuck)] = 3 - ok_row  # the other spec's row
        assert engine.verify(cluster) == "node-terms"


class TestAPlainStoreKeepsNoRow:
    def test_no_row_no_span_and_no_scheduling_state(self):
        cluster = pooled_cluster(12)
        engine = ServeEngine().attach(cluster)
        sched = affinity_scheduler()
        before = {
            name: counter(name) for name in (
                obs.SERVE_NODE_TERM_ROWS, obs.SERVE_NODE_TERM_COLUMNS,
                obs.SERVE_NODE_TERM_REBASES,
            )
        }
        obs.tracer.start()
        try:
            for cycle in range(3):
                now = 1000 * (cycle + 1)
                for serial in range(4):
                    cluster.add_pod(spec_pod(10 * cycle + serial, now, 0))
                pending = sched.sort_pending(cluster.pending_pods(), cluster)
                snap, _ = engine.refresh(cluster, pending, now_ms=now)
                assert snap.scheduling is None
                report = run_cycle(sched, cluster, now=now, serve=engine)
                assert len(report.bound) == 4
                cluster.add_node(pooled_node(12 + cycle))
            spans = {e["name"] for e in obs.tracer.export()["traceEvents"]}
        finally:
            obs.tracer.stop()
        held = engine._node_terms
        assert held.held == 0 and held._term.table is None
        assert held._staged is None
        assert "ServeRefresh/assemble" in spans
        assert "ServeRefresh/node_terms" not in spans
        assert all(r.node_keys is None and r.node_rows is None
                   for r in engine._records.values())
        assert before == {name: counter(name) for name in before}
        settled(engine, cluster, 9000)
