"""InterPodAffinity's terms and carrier counts as resident state (ISSUE 34;
docs/SERVING.md "Resident affinity terms").

(i) seeded streams of add / bind / delete / re-add of carriers and matchers
(hostname and zone anti-affinity, required affinity, preferred terms, plain
and spread pods in between) through the resident engine and through a twin
that rebuilds `build_scheduling` every cycle: bit-equal placements, resident
`track_base`, carrier counts and the derived `exist_anti_base` equal to a
fresh build after every cycle, `engine.verify` clean; the last carrier of a
domain leaving lifts the block, the one before it does not; (ii) padded term
axes solve as exact-size ones, a term arriving beyond its bucket rebases
once and is counted, a bind or a delete never does; (iii) what no longer
falls back, kind by kind, and the one kind that still does, under its
reason, until its pod leaves; (iv) `benchmark/references/antiaffinity.py`
against the sequential solve on the rehearsal cluster (the tier-1 mirror of
`benchmark/tests/test_config_antiaffinity.py`), the population's
arithmetic, the audit, and the cell rehearsed once through the real
command.
"""

import os
import sys

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    ZONE_LABEL,
    Container,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.plugins.intree import (
    InterPodAffinity,
    PodTopologySpread,
)
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.serving.engine import StreamingServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
for path in (os.path.join(BENCH_DIR, "tests"), BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_config_antiaffinity as by_hand  # noqa: E402

gib = 1 << 30
HOSTNAME = "kubernetes.io/hostname"


def host_cluster(n_nodes=12):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(Node(
            name=f"n{i:03d}",
            labels={ZONE_LABEL: f"z{i % 3}", HOSTNAME: f"n{i:03d}"},
            allocatable={CPU: 4000 * (1 + i % 2), MEMORY: 16 * gib,
                         PODS: 110},
        ))
    return cluster


def term(color="green", key=HOSTNAME, **scope):
    return PodAffinityTerm(
        topology_key=key,
        label_selector=LabelSelector(match_labels={"color": color}),
        **scope,
    )


def pod(serial, now, color="green", namespace="default", cpu=300, **spec):
    return Pod(
        name=f"p{serial:05d}", namespace=namespace, creation_ms=now + serial,
        labels={"color": color},
        containers=[Container(requests={CPU: cpu, MEMORY: gib // 2})],
        **spec,
    )


def anti_pod(serial, now, color="green", key=HOSTNAME, **kw):
    return pod(serial, now, color=color,
               pod_anti_affinity_required=[term(color, key)], **kw)


#: the kinds of pod a stream draws from, by name
KINDS = {
    "host_anti": lambda s, t: anti_pod(s, t),
    "zone_anti": lambda s, t: anti_pod(s, t, color="blue", key=ZONE_LABEL),
    "plain_green": lambda s, t: pod(s, t),  # matched by the carriers' term
    "plain_red": lambda s, t: pod(s, t, color="red"),
    "follows_red": lambda s, t: pod(
        s, t, color="red", pod_affinity_required=[term("red", ZONE_LABEL)],
    ),
    # two required terms: the (AT,) escape against the (AT, N) matches
    "follows_red_twice": lambda s, t: pod(
        s, t, color="red", pod_affinity_required=[
            term("red", ZONE_LABEL), term("red", HOSTNAME),
        ],
    ),
    "prefers": lambda s, t: pod(
        s, t, color="red",
        pod_affinity_preferred=[
            WeightedPodAffinityTerm(weight=30, term=term("red", ZONE_LABEL)),
        ],
        pod_anti_affinity_preferred=[
            WeightedPodAffinityTerm(weight=70, term=term("blue", HOSTNAME)),
        ],
    ),
    "spread": lambda s, t: pod(s, t, color="red", topology_spread=[
        TopologySpreadConstraint(
            max_skew=2, topology_key=ZONE_LABEL,
            when_unsatisfiable="DoNotSchedule",
            label_selector=LabelSelector(match_labels={"color": "red"}),
        ),
    ]),
}


def scheduler():
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), PodTopologySpread(), InterPodAffinity(),
    ]))


def fallbacks(reason=None) -> int:
    if reason is not None:
        return obs.metrics.get(obs.SERVE_FALLBACKS, reason=reason)
    return sum(
        v for k, v in obs.metrics.snapshot().items()
        if k.startswith(obs.SERVE_FALLBACKS)
    )


def resident_tables(engine) -> dict:
    """{(kind, key, domain value): count or presence} as the engine's device
    tables have them, decoded through its own rows."""
    held = engine._selectors
    if not held.live:
        return {}
    out = {}
    tables = [("track", held.track_base, [
        (t, held.track_keys[t], k) for (_s, k), t in held.axes.tracks.items()
    ])]
    for kind, table, keys in (("anti", held.anti_count, held.anti_keys),
                              ("sym", held.sym_base, held.sym_keys),
                              ("blocked", held.exist_anti_base,
                               held.anti_keys)):
        if table is not None:
            tables.append((kind, table, [
                (e, key, held._carrier_rows[key][1])
                for e, key in enumerate(keys)
            ]))
    for kind, table, rows in tables:
        host = np.asarray(table)
        for row, key, k in rows:
            for value, code in held.domain_values[k].items():
                if host[row, code]:
                    out[(kind, key, value)] = int(host[row, code])
    return out


def fresh_tables(cluster) -> dict:
    """The same, decoded from a fresh `build_scheduling` over the store
    with every pod of the store's terms in the batch (so that every track
    and term of the registry is on its axes)."""
    from scheduler_plugins_tpu.state import scheduling as S

    nodes = list(cluster.nodes.values())
    assigned = cluster._assigned_pods()
    # one stand-in pending pod per live pod with a term or a constraint
    batch = [
        p for p in cluster.pods.values()
        if p.topology_spread or cluster._has_affinity_terms(p)
    ]
    if not batch:
        return {}
    state = S.build_scheduling(
        nodes, batch, len(nodes), len(batch), assigned=assigned,
    )
    # decode by re-interning as the build did
    axes = S.SelectorAxes()
    S.spread_rows(axes, batch, len(batch))
    S.affinity_rows(axes, batch, len(batch))
    S.assigned_carriers(axes, assigned)
    for p in batch:
        S.pod_sym_rows(axes, p)
    _code, _has, values = S.topology_tables(
        axes.key_names, nodes, len(nodes)
    )

    def selkey(s):
        scope, selector = axes.sel_objs[s]
        return scope, None if selector is None else selector._key()

    out = {}
    registry = cluster.selectors
    for (s, k), t in axes.tracks.items():
        key = selkey(s) + (axes.key_names[k],)
        for value, code in values[k].items():
            if state.track_base[t, code]:
                out[("track", key, value)] = int(state.track_base[t, code])
    for (s, k), e in axes.anti_terms.items():
        key = selkey(s) + (axes.key_names[k],)
        for value, code in values[k].items():
            if state.exist_anti_base[e, code]:
                out[("blocked", key, value)] = 1
    for (s, k, weight, hard), e2 in axes.sym_terms.items():
        key = selkey(s) + (axes.key_names[k], weight, hard)
        for value, code in values[k].items():
            if state.sym_base[e2, code]:
                out[("sym", key, value)] = int(state.sym_base[e2, code])
    assert set(registry.tracks) >= {k[1] for k in out if k[0] == "track"}
    return out


def assert_tables_equal_fresh(engine, cluster):
    mine = resident_tables(engine)
    theirs = fresh_tables(cluster)
    counted = {k: v for k, v in mine.items() if k[0] != "anti"}
    assert counted == theirs
    # the presence the scan reads is `count > 0`, cell for cell
    assert {k[1:] for k in mine if k[0] == "anti"} == {
        k[1:] for k in mine if k[0] == "blocked"
    }


class TestRandomizedStreams:
    @pytest.mark.parametrize("engine_class", [ServeEngine,
                                              StreamingServeEngine])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_resident_engine_equals_a_twin_that_rebuilds(self, seed,
                                                         engine_class):
        rng = np.random.default_rng(340 + seed)
        serve_cluster, base_cluster = host_cluster(), host_cluster()
        engine = engine_class().attach(serve_cluster)
        s_sched, b_sched = scheduler(), scheduler()
        fell_back = fallbacks()
        carried = obs.metrics.get(obs.SERVE_AFFINITY_CARRIER_ROWS)
        kinds = sorted(KINDS)
        serial = 0
        removed: list = []
        for cycle in range(12):
            now = 1000 * (cycle + 1)
            events = []
            for _ in range(int(rng.integers(1, 6))):
                serial += 1
                # the kinds arrive one after another: the set of terms
                # moves for the first cycles, then holds
                kind = kinds[int(rng.integers(0, min(cycle + 2, len(kinds))))]
                events.append(("pod", serial, kind))
            bound = sorted(
                uid for uid, p in serve_cluster.pods.items() if p.node_name
            )
            for _ in range(int(rng.integers(0, 4))):
                if bound:
                    events.append((
                        "del", bound.pop(int(rng.integers(0, len(bound))))
                    ))
            if cycle % 4 == 3 and removed:
                events.append(("readd",) + removed.pop(0))
            if cycle == 9:
                events.append(("node", 12))
            for cl in (serve_cluster, base_cluster):
                for e in events:
                    if e[0] == "pod":
                        cl.add_pod(KINDS[e[2]](e[1], now))
                    elif e[0] == "del":
                        gone = cl.pods[e[1]]
                        cl.remove_pod(e[1])
                        if cl is serve_cluster:
                            kind = next(
                                ev[2] for ev in reversed(events)
                                if ev[0] == "pod"
                            )
                            removed.append((int(gone.name[1:]), kind))
                    elif e[0] == "readd":
                        # the same name comes back, pending, another kind
                        cl.add_pod(KINDS[e[2]](e[1], now))
                    elif e[0] == "node":
                        cl.add_node(Node(
                            name="n012",
                            labels={ZONE_LABEL: "z0", HOSTNAME: "n012"},
                            allocatable={CPU: 4000, MEMORY: 16 * gib,
                                         PODS: 110},
                        ))
            serve_report = run_cycle(
                s_sched, serve_cluster, now=now, serve=engine
            )
            base_report = run_cycle(b_sched, base_cluster, now=now)
            assert serve_report.bound == base_report.bound, cycle
            assert serve_report.failed == base_report.failed, cycle
            # the cycle's own binds are still in the delta sink
            assert engine.refresh(
                serve_cluster, [], now_ms=now + 500
            ) is not None
            assert_tables_equal_fresh(engine, base_cluster)
            assert engine.verify(serve_cluster) is None, cycle
        assert fallbacks() == fell_back
        assert engine.antientropy_divergences == 0 and engine.rebases == 1
        assert obs.metrics.get(obs.SERVE_AFFINITY_CARRIER_ROWS) > carried
        assert any(k[0] == "blocked" for k in resident_tables(engine))

    def test_the_last_carrier_leaving_lifts_the_block_not_the_one_before(
            self):
        """Two carriers of one zone-keyed term in one zone: a matcher is
        kept out of the zone until both are gone."""
        cluster = host_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = scheduler()
        for serial, node in ((1, "n000"), (2, "n003")):  # both in z0
            carrier = pod(serial, 0, color="red", pod_anti_affinity_required=[
                term("green", ZONE_LABEL)
            ])
            carrier.node_name = node
            cluster.add_pod(carrier)
        # fill z1 and z2 so that only z0 has room for a big pod
        serial = 10
        for i in (1, 2, 4, 5):
            serial += 1
            filler = pod(serial, 0, color="red",
                         cpu=4000 * (1 + i % 2) - 100)
            filler.node_name = f"n{i:03d}"
            cluster.add_pod(filler)
        cluster.add_pod(pod(20, 1000, cpu=1000))  # green: the term matches
        report = run_cycle(sched, cluster, now=1000, serve=engine)
        assert "default/p00020" in report.failed
        held = engine._selectors
        z0 = held.domain_values[held.axes.keys[ZONE_LABEL]]["z0"]
        assert int(np.asarray(held.anti_count)[0, z0]) == 2
        # (the cycles are a minute apart: past the failed pod's backoff)
        cluster.remove_pod("default/p00001")
        report = run_cycle(sched, cluster, now=61_000, serve=engine)
        assert "default/p00020" in report.failed  # one carrier is left
        assert int(np.asarray(held.anti_count)[0, z0]) == 1
        assert bool(np.asarray(held.exist_anti_base)[0, z0])
        cluster.remove_pod("default/p00002")
        report = run_cycle(sched, cluster, now=121_000, serve=engine)
        assert report.bound["default/p00020"] in ("n000", "n003")
        assert engine.refresh(cluster, [], now_ms=121_500) is not None
        assert engine.rebases == 1 and engine.verify(cluster) is None

    def test_a_carrier_count_off_by_one_is_a_divergence_and_heals(self):
        cluster = host_cluster()
        engine = ServeEngine().attach(cluster)
        sched = scheduler()
        for serial in range(5):
            cluster.add_pod(anti_pod(serial, 1000))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.refresh(cluster, [], now_ms=1500) is not None
        assert engine.verify(cluster) is None
        held = engine._selectors
        held.anti_count = held.anti_count.at[0, 7].add(1)
        assert engine.verify(cluster) == "affinity-carriers"
        assert engine.antientropy_divergences == 1
        engine._rebase(cluster, [], 2000)
        assert engine.verify(cluster) is None
        assert_tables_equal_fresh(engine, cluster)
        # a presence that does not follow its count is one too
        held = engine._selectors
        held.exist_anti_base = held.exist_anti_base.at[0, 0].set(
            ~held.exist_anti_base[0, 0]
        )
        assert engine.verify(cluster) == "affinity-carriers"


def _misses(program: str) -> int:
    return sum(
        value for key, value in obs.metrics.snapshot().items()
        if key.startswith(obs.JIT_CACHE_MISS) and f'"{program}"' in key
    )


class TestTermAxes:
    # one term sits on an axis of one; three pad to four
    @pytest.mark.parametrize("n_terms", [1, 3])
    def test_padded_solve_equals_exact_solve(self, n_terms):
        cluster = host_cluster()
        engine = ServeEngine().attach(cluster)
        sched = scheduler()
        colors = [f"c{i}" for i in range(n_terms)]
        serial = 0
        for color in colors:
            for _ in range(2):
                serial += 1
                cluster.add_pod(anti_pod(serial, 0, color=color))
        run_cycle(sched, cluster, now=1000, serve=engine)
        for color in colors:
            for _ in range(2):
                serial += 1
                cluster.add_pod(anti_pod(serial, 2000, color=color))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        padded_snap, meta = engine.refresh(cluster, pending, now_ms=2000)
        exact_snap, _ = cluster.snapshot(
            pending, now_ms=2000, pad_nodes=engine.npad
        )
        mine, theirs = padded_snap.scheduling, exact_snap.scheduling
        assert theirs.exist_anti_base.shape[0] == n_terms
        assert mine.exist_anti_base.shape[0] == (1 if n_terms == 1 else 4)
        assert mine.exist_anti_base.dtype == theirs.exist_anti_base.dtype
        # a kind of term no pod of the store carries has no row at all
        assert mine.aff_track.shape[1] == mine.waff_track.shape[1] == 0
        assert mine.anti_track.shape[1] == theirs.anti_track.shape[1] == 1
        # a padded term is in the selector row no pod is in
        pad_rows = np.asarray(mine.exist_anti_sel)[n_terms:]
        assert not np.asarray(mine.pend_match)[pad_rows].any()
        assert not np.asarray(mine.exist_anti_base)[n_terms:].any()
        sched.prepare(meta, cluster)
        padded = sched.solve(padded_snap)
        exact = sched.solve(exact_snap)
        for name in ("assignment", "admitted", "wait", "failed_plugin"):
            np.testing.assert_array_equal(
                np.asarray(getattr(padded, name)),
                np.asarray(getattr(exact, name)), err_msg=name,
            )
        assert (np.asarray(padded.assignment)[:len(pending)] >= 0).all()

    @pytest.mark.parametrize("cells, rows", [
        (1, 1024), (1024, 1024), (1025, 2048), (2049, 4096), (4000, 4096),
    ])
    def test_the_delta_batch_sits_on_doublings_of_its_floor(self, cells,
                                                            rows):
        """A window of this cell folds 1,000-4,000 cells (a bind and a
        delete a pod, a track and a term each): three shapes, not one for
        every thousand."""
        from scheduler_plugins_tpu.serving.deltas import SelectorDeltas

        packed = SelectorDeltas.pack(
            {(j % 3, j): 1 for j in range(cells)}
        )
        assert packed.track.shape == packed.delta.shape == (rows,)
        assert int(packed.delta.sum()) == cells

    def test_a_term_beyond_its_bucket_rebases_once_and_is_counted(self):
        cluster = host_cluster()
        engine = ServeEngine().attach(cluster)
        sched = scheduler()
        cluster.add_pod(anti_pod(1, 0, color="c0"))
        cluster.add_pod(anti_pod(2, 0, color="c1"))
        run_cycle(sched, cluster, now=1000, serve=engine)
        held = engine._selectors
        assert held.anti_count.shape[0] == 2
        rebuilds = obs.metrics.get(obs.SERVE_SELECTOR_REBASES)
        solves = _misses("solve")
        # binds and deletes of carriers: +-1 rows, never a rebuild
        cluster.add_pod(anti_pod(3, 2000, color="c0"))
        cluster.remove_pod("default/p00001")
        run_cycle(sched, cluster, now=2000, serve=engine)
        assert obs.metrics.get(obs.SERVE_SELECTOR_REBASES) == rebuilds
        assert _misses("solve") == solves
        # a third term: past the bucket of two
        cluster.add_pod(anti_pod(4, 3000, color="c2"))
        report = run_cycle(sched, cluster, now=3000, serve=engine)
        assert report.bound and held.anti_count.shape[0] == 4
        assert obs.metrics.get(obs.SERVE_SELECTOR_REBASES) == rebuilds + 1
        assert _misses("solve") == solves + 1
        # a fourth sits in the bucket: the tables are built again (the set
        # of terms moved), the solve is the program it was
        cluster.add_pod(anti_pod(5, 4000, color="c3"))
        run_cycle(sched, cluster, now=4000, serve=engine)
        assert obs.metrics.get(obs.SERVE_SELECTOR_REBASES) == rebuilds + 2
        assert _misses("solve") == solves + 1
        assert engine.rebases == 1 and fallbacks() == fallbacks()
        assert engine.refresh(cluster, [], now_ms=4500) is not None
        assert engine.verify(cluster) is None


#: kind -> the spec of a BOUND pod whose term used to send every cycle back
#: to the fresh build under `pod-affinity`
NOW_RESIDENT = {
    "required-anti": dict(pod_anti_affinity_required=[term()]),
    "required-anti-zone": dict(
        pod_anti_affinity_required=[term(key=ZONE_LABEL)]),
    "required-affinity": dict(pod_affinity_required=[term("red")]),
    "preferred": dict(pod_affinity_preferred=[
        WeightedPodAffinityTerm(weight=10, term=term())]),
    "preferred-anti": dict(pod_anti_affinity_preferred=[
        WeightedPodAffinityTerm(weight=10, term=term())]),
    "explicit-namespaces": dict(pod_anti_affinity_required=[
        term(namespaces=("default", "other"))]),
    "every-namespace": dict(pod_anti_affinity_required=[
        term(namespace_selector=LabelSelector())]),
}


class TestWhatNoLongerFallsBack:
    @pytest.mark.parametrize("kind", sorted(NOW_RESIDENT))
    def test_a_bound_carrier_is_served_and_places_as_a_fresh_build(self,
                                                                   kind):
        clusters = host_cluster(6), host_cluster(6)
        engine = ServeEngine().attach(clusters[0])
        scheds = scheduler(), scheduler()
        total = fallbacks()
        for cluster in clusters:
            carrier = pod(1, 0, color="red", **NOW_RESIDENT[kind])
            carrier.node_name = "n002"
            cluster.add_pod(carrier)
            for serial in (2, 3, 4):
                cluster.add_pod(pod(serial, 1000))
        served = run_cycle(scheds[0], clusters[0], now=1000, serve=engine)
        fresh = run_cycle(scheds[1], clusters[1], now=1000)
        assert served.bound == fresh.bound and len(served.bound) == 3
        assert served.failed == fresh.failed
        assert fallbacks() == total and engine.rebases == 1
        assert engine.refresh(clusters[0], [], now_ms=1500) is not None
        assert engine.verify(clusters[0]) is None
        assert_tables_equal_fresh(engine, clusters[1])

    def test_a_namespace_selector_falls_back_until_its_pod_leaves(self):
        cluster = host_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = scheduler()
        cluster.add_pod(anti_pod(1, 0))
        run_cycle(sched, cluster, now=1000, serve=engine)
        scoped = pod(2, 2000, pod_anti_affinity_required=[term(
            namespace_selector=LabelSelector(match_labels={"team": "a"}),
        )])
        cluster.add_pod(scoped)
        reason = "affinity-namespace-selector"
        before = fallbacks(reason)
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        assert engine.fallback_reason(cluster, pending) == reason
        report = run_cycle(sched, cluster, now=2000, serve=engine)
        assert "default/p00002" in report.bound  # by the fresh build
        assert fallbacks(reason) == before + 1
        cluster.add_pod(anti_pod(3, 3000))
        run_cycle(sched, cluster, now=3000, serve=engine)
        assert fallbacks(reason) == before + 2
        cluster.remove_pod("default/p00002")
        cluster.add_pod(anti_pod(4, 4000))
        report = run_cycle(sched, cluster, now=4000, serve=engine)
        assert "default/p00004" in report.bound
        assert fallbacks(reason) == before + 2
        assert engine.refresh(cluster, [], now_ms=4500) is not None
        assert engine.verify(cluster) is None

    def test_a_pod_without_a_term_is_not_registered(self):
        cluster = host_cluster(3)
        cluster.add_pod(pod(1, 0))
        assert not cluster.selectors.tracks and not cluster.selectors._by_pod
        cluster.add_pod(anti_pod(2, 0))
        assert len(cluster.selectors.anti_terms) == 1
        cluster.remove_pod("default/p00002")
        assert not cluster.selectors.tracks
        assert not cluster.selectors.anti_terms
        assert not cluster._affinity_spec_pods


class TestReferenceAndCell:
    @pytest.mark.parametrize("resident", [False, True])
    @pytest.mark.parametrize("case", by_hand.CASES)
    def test_reference_equals_the_sequential_solve(self, case, resident):
        by_hand.assert_reference_equals_solve(case, 0, resident)

    def test_the_cell_rehearses_to_a_correct_result(self):
        result, info, stderr = by_hand.rehearse(3, trace=1)
        by_hand.assert_sound(result, info, stderr)
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert metrics["backlog.serve_fallback_share"] <= 0.0
        assert metrics["backlog.selector_rebases_in_window"] == 0
        assert metrics["backlog.compiles_in_window"] == 0
        assert metrics["backlog.affinity_tables_ms_per_cycle"] > 0
        assert metrics["backlog.affinity_carrier_rows_per_cycle"] > 1.0

    @pytest.mark.parametrize("rehearse", [False, True])
    def test_no_unit_that_is_to_bind_can_be_refused(self, rehearse):
        by_hand.test_no_unit_that_is_to_bind_can_be_refused(rehearse)

    def test_the_population_puts_one_prefilled_pod_on_a_node(self):
        from harness import spec

        config = spec.Cell(by_hand.CELL, rehearse=True).config
        for seed in (0, 3, 2147483777):
            counts = by_hand.population_counts(config, seed, 100)
            assert counts["nodes"] == counts["domains"] == 128
            assert counts["prefilled_nodes"] == 100
            assert counts["templated"] == counts["pods"]
            assert counts["namespaces"] == {"sched-0": 100, "sched-1": 264}

    def test_the_audit_finds_two_on_a_node(self):
        by_hand.test_the_audit_finds_two_on_a_node()
