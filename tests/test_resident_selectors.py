"""PodTopologySpread's selector and topology-domain counts as resident state
(ISSUE 32; docs/SERVING.md "Resident selector counts").

(i) randomized streams of labelled pods (adds, binds, deletes, a node whose
zone label changes, a second selector group and a `kubernetes.io/hostname`
key arriving mid-stream) through the resident engine and through a twin
that rebuilds `build_scheduling` every cycle: bit-equal placements, equal
matching-pod counts after every cycle, `engine.verify` clean; (ii) padded
track / key / domain axes solve as exact-size ones, and 60 cycles of
selector groups that come and go compile no more shapes than the buckets
crossed; (iii) `benchmark/references/spread.py` against the sequential
solve on seeded 48-node clusters (the tier-1 mirror of
`benchmark/tests/test_config_spread.py`), the cell rehearsed once through
the real command, and a planted off-by-one ending `correct: false`; (iv)
one case per clause `ServeEngine.fallback_reason` still refuses, asserting
the reason's counter.
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
    Pod,
    PodAffinityTerm,
    Taint,
    TopologySpreadConstraint,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.plugins.intree import PodTopologySpread
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.serving.engine import StreamingServeEngine
from scheduler_plugins_tpu.state import scheduling as S
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
for path in (os.path.join(BENCH_DIR, "tests"), BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_config_spread as by_hand  # noqa: E402

gib = 1 << 30
HOSTNAME = "kubernetes.io/hostname"


def zoned_node(i, zone=None, hostname=True):
    labels = {ZONE_LABEL: zone or f"z{i % 3}"}
    if hostname:
        labels[HOSTNAME] = f"n{i:03d}"
    return Node(
        name=f"n{i:03d}", labels=labels,
        allocatable={CPU: 4000 * (1 + i % 2), MEMORY: 16 * gib, PODS: 110},
    )


def zoned_cluster(n_nodes=12, hostname=True):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(zoned_node(i, hostname=hostname))
    return cluster


def spread_pod(serial, now, color="blue", keys=(ZONE_LABEL,), skew=1,
               hard=True, cpu=300):
    return Pod(
        name=f"p{serial:05d}", creation_ms=now + serial,
        labels={"color": color},
        containers=[Container(requests={CPU: cpu, MEMORY: gib // 2})],
        topology_spread=[
            TopologySpreadConstraint(
                max_skew=skew if key == ZONE_LABEL else skew + 2,
                topology_key=key,
                when_unsatisfiable=(
                    "DoNotSchedule" if hard else "ScheduleAnyway"
                ),
                label_selector=LabelSelector(match_labels={"color": color}),
            )
            for key in keys
        ],
    )


def spread_scheduler():
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), PodTopologySpread(),
    ]))


def resident_counts(engine) -> dict:
    """{(track key, domain value): count} as the engine's device table has
    it, decoded through its own axes."""
    held = engine._selectors
    if not held.live:
        return {}
    table = np.asarray(held.track_base)
    out = {}
    for (_s, k), t in held.axes.tracks.items():
        for value, code in held.domain_values[k].items():
            if table[t, code]:
                out[(held.track_keys[t], value)] = int(table[t, code])
    return out


def fresh_counts(cluster) -> dict:
    """The same, from the fresh build's own functions over the store."""
    axes = cluster.selectors.axes()
    if not axes.tracks:
        return {}
    nodes = list(cluster.nodes.values())
    N = len(nodes)
    topo_code, _has, values = S.topology_tables(axes.key_names, nodes, N)
    D = max(len(v) for v in values) or 1
    node_pos = {n.name: i for i, n in enumerate(nodes)}
    _, table = S.track_counts(
        axes, cluster._assigned_pods(), node_pos, topo_code,
        len(axes.tracks), N, D, per_node=False,
    )
    keys = list(cluster.selectors.tracks)
    out = {}
    for (_s, k), t in axes.tracks.items():
        for value, code in values[k].items():
            if table[t, code]:
                out[(keys[t], value)] = int(table[t, code])
    return out


def fallbacks(reason=None) -> int:
    if reason is not None:
        return obs.metrics.get(obs.SERVE_FALLBACKS, reason=reason)
    return sum(
        v for k, v in obs.metrics.snapshot().items()
        if k.startswith(obs.SERVE_FALLBACKS)
    )


class TestRandomizedStreams:
    @pytest.mark.parametrize("engine_class", [ServeEngine,
                                              StreamingServeEngine])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_resident_engine_equals_a_twin_that_rebuilds(self, seed,
                                                         engine_class):
        rng = np.random.default_rng(320 + seed)
        serve_cluster, base_cluster = zoned_cluster(), zoned_cluster()
        engine = engine_class().attach(serve_cluster)
        s_sched, b_sched = spread_scheduler(), spread_scheduler()
        fell_back = fallbacks()
        rebases = obs.metrics.get(obs.SERVE_SELECTOR_REBASES)
        serial = 0
        for cycle in range(12):
            now = 1000 * (cycle + 1)
            events = []
            for _ in range(int(rng.integers(1, 7))):
                serial += 1
                # a second selector group from cycle 4, a hostname key
                # beside the zone key from cycle 7
                color = "green" if cycle >= 4 and serial % 3 == 0 else "blue"
                keys = (ZONE_LABEL, HOSTNAME) if (
                    cycle >= 7 and serial % 2) else (ZONE_LABEL,)
                events.append(("pod", serial, color, keys,
                               int(rng.integers(100, 900))))
            bound = sorted(
                uid for uid, p in serve_cluster.pods.items() if p.node_name
            )
            for _ in range(int(rng.integers(0, 4))):
                if bound:
                    events.append((
                        "del", bound.pop(int(rng.integers(0, len(bound))))
                    ))
            if cycle == 3:
                events.append(("zone", 5, "z0"))  # n005 was in z2
            if cycle == 9:
                events.append(("node", 12))  # a node arrives
            for cl in (serve_cluster, base_cluster):
                for e in events:
                    if e[0] == "pod":
                        cl.add_pod(spread_pod(
                            e[1], now, color=e[2], keys=e[3], cpu=e[4]
                        ))
                    elif e[0] == "del":
                        cl.remove_pod(e[1])
                    elif e[0] == "zone":
                        cl.add_node(zoned_node(e[1], zone=e[2]))
                    elif e[0] == "node":
                        cl.add_node(zoned_node(e[1]))
            serve_report = run_cycle(
                s_sched, serve_cluster, now=now, serve=engine
            )
            base_report = run_cycle(b_sched, base_cluster, now=now)
            assert serve_report.bound == base_report.bound, cycle
            assert serve_report.failed == base_report.failed, cycle
            assert serve_report.bound or cycle > 8
            # the cycle's own binds are still in the delta sink
            assert engine.refresh(
                serve_cluster, [], now_ms=now + 500
            ) is not None
            assert resident_counts(engine) == fresh_counts(base_cluster)
            assert engine.verify(serve_cluster) is None, cycle
        assert fallbacks() == fell_back
        assert engine.antientropy_divergences == 0
        # the cold build, the zone label, green, the hostname key under
        # each colour: the tables were rebuilt for those (a track that
        # comes or goes), not for the ~40 binds and ~15 deletes
        assert obs.metrics.get(obs.SERVE_SELECTOR_REBASES) - rebases <= 8
        assert sum(fresh_counts(base_cluster).values()) > 20

    def test_a_count_off_by_one_is_a_divergence_and_heals(self):
        cluster = zoned_cluster()
        engine = ServeEngine().attach(cluster)
        sched = spread_scheduler()
        for serial in range(6):
            cluster.add_pod(spread_pod(serial, 1000))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.refresh(cluster, [], now_ms=1500) is not None
        assert engine.verify(cluster) is None
        held = engine._selectors
        held.track_base = held.track_base.at[0, 1].add(1)
        assert engine.verify(cluster) == "selector-counts"
        assert engine.antientropy_divergences == 1
        # what `refresh` does with a divergence: rebase, from the store
        engine._rebase(cluster, [], 2000)
        assert engine.verify(cluster) is None
        assert resident_counts(engine) == fresh_counts(cluster)

    def test_a_store_without_spread_pods_keeps_no_selector_state(self):
        cluster = zoned_cluster()
        engine = ServeEngine().attach(cluster)
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        rows = obs.metrics.get(obs.SERVE_SELECTOR_ROWS)
        for serial in range(4):
            cluster.add_pod(Pod(
                name=f"q{serial}", creation_ms=serial,
                containers=[Container(requests={CPU: 100, MEMORY: gib})],
            ))
        snap, _ = engine.refresh(
            cluster, sched.sort_pending(cluster.pending_pods(), cluster)
        )
        assert snap.scheduling is None
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.refresh(cluster, [], now_ms=1500) is not None
        assert not engine._selectors.live
        assert obs.metrics.get(obs.SERVE_SELECTOR_ROWS) == rows


class TestPaddedAxesAreInert:
    # 1 group pads the track axis to 8; 8 sit on it exactly (and push the
    # selector axis, which keeps one row no pod is in, to 16); 9 pad to 16
    @pytest.mark.parametrize("n_groups", [1, 8, 9])
    @pytest.mark.parametrize("hostname", [False, True])
    def test_padded_solve_equals_exact_solve(self, n_groups, hostname):
        cluster = zoned_cluster(hostname=hostname)
        engine = ServeEngine().attach(cluster)
        sched = spread_scheduler()
        keys = (ZONE_LABEL, HOSTNAME) if hostname else (ZONE_LABEL,)
        serial = 0
        for group in range(n_groups):
            for _ in range(3):
                serial += 1
                cluster.add_pod(spread_pod(
                    serial, 0, color=f"c{group}", keys=keys
                ))
        run_cycle(sched, cluster, now=1000, serve=engine)
        for group in range(n_groups):
            for _ in range(2):
                serial += 1
                cluster.add_pod(spread_pod(
                    serial, 2000, color=f"c{group}", keys=keys
                ))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        padded_snap, meta = engine.refresh(cluster, pending, now_ms=2000)
        exact_snap, _ = cluster.snapshot(
            pending, now_ms=2000, pad_nodes=engine.npad
        )
        mine, theirs = padded_snap.scheduling, exact_snap.scheduling
        n_tracks = n_groups * len(keys)
        assert theirs.track_base.shape[0] == n_tracks
        assert mine.track_base.shape[0] == bucket_size(n_tracks)
        assert mine.pend_match.shape[0] == bucket_size(n_groups + 1)
        assert mine.topo_code.shape[0] == 8 and theirs.topo_code.shape[0] == len(keys)
        assert mine.domain_exists.shape[1] == bucket_size(
            theirs.domain_exists.shape[1]
        )
        # a padded track is in the selector row no pod is in
        pad_rows = np.asarray(mine.track_sel)[n_tracks:]
        assert not np.asarray(mine.pend_match)[pad_rows].any()
        assert not np.asarray(mine.track_base)[n_tracks:].any()
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


class TestShapesFollowBuckets:
    def test_groups_that_come_and_go_compile_per_bucket_crossed(self):
        """60 served cycles over a store whose selector groups go from 1 to
        12 and back: the batch stays in one pod bucket and every pod has
        one constraint, so every `solve` shape is a (track, selector, key,
        domain) bucket tuple and every `serve_selector_apply` shape a
        (track, domain) pair of table sizes."""
        cluster = zoned_cluster()
        engine = ServeEngine().attach(cluster)
        sched = spread_scheduler()
        solve0 = _misses("solve")
        apply0 = _misses("serve_selector_apply")
        tuples, tables = set(), set()
        groups: list = []
        serial = 0
        for cycle in range(60):
            now = 1000 * (cycle + 1)
            rising = cycle < 30
            if cycle % 2 == 0:
                if rising and len(groups) < 12:
                    groups.append(f"g{cycle:02d}")
                elif not rising and len(groups) > 1:
                    gone = groups.pop()
                    for uid in [u for u, p in cluster.pods.items()
                                if p.labels.get("color") == gone]:
                        cluster.remove_pod(uid)
            # one or two pods a cycle: the pod bucket never moves
            for color in groups[-2:]:
                serial += 1
                cluster.add_pod(spread_pod(serial, now, color=color, cpu=50))
            report = run_cycle(sched, cluster, now=now, serve=engine)
            assert report.bound, cycle
            held = engine._selectors
            assert held.live and len(held.axes.tracks) == len(groups)
            tuples.add((
                held.track_base.shape, held.sel_rows, held.topo_code.shape,
            ))
            tables.add(held.track_base.shape)
        assert max(t[0][0] for t in tuples) >= 16 and len(tuples) >= 2
        assert _misses("solve") - solve0 <= len(tuples)
        assert _misses("serve_selector_apply") - apply0 <= len(tables)
        assert engine.refresh(cluster, [], now_ms=99_000) is not None
        assert engine.verify(cluster) is None


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
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert metrics["serve_fallback_share"] <= 0.0
        assert metrics["selector_rebases_in_window"] == 0
        assert metrics["selector_tables_ms_per_cycle"] > 0
        assert metrics["selector_rows_per_cycle"] > 1.0

    def test_a_planted_off_by_one_ends_not_correct(self, tmp_path):
        result, info, _ = by_hand.rehearse(
            3, index=by_hand.with_planted_fault(tmp_path)
        )
        assert result["correct"] is False
        assert "selector-counts" in by_hand.problems(info)

    def test_population_shapes_do_not_depend_on_the_seed(self):
        from harness import spec

        config = spec.Cell(by_hand.CELL, rehearse=True).config
        counts = [
            by_hand.population_counts(config, seed, 200)
            for seed in (0, 3, 2147483777)
        ]
        for found in counts:
            assert found["nodes"] == 48 and found["objects"] == 0
            assert found["zones"] == {"moon-1": 16, "moon-2": 16,
                                      "moon-3": 16}
            assert found["templated"] == found["pods"]
            assert max(found["prefilled"].values()) - min(
                found["prefilled"].values()) <= 1


def _plain_pod(name="q0", **spec):
    return Pod(
        name=name, creation_ms=1,
        containers=[Container(requests={CPU: 100, MEMORY: gib})], **spec
    )


def _web_term(**scope):
    return PodAffinityTerm(
        topology_key=ZONE_LABEL,
        label_selector=LabelSelector(match_labels={"app": "web"}),
        **scope,
    )


def _bound(pod, node="n000"):
    pod.node_name = node
    return pod


#: reason -> what makes the store, or the batch, fall under that clause
CLAUSES = {
    "nrt": lambda c: c.nrts.update({"n000": object()}),
    "app-group": lambda c: c.app_groups.update({"default/ag": object()}),
    "seccomp": lambda c: c.seccomp_profiles.update({"default/sp": object()}),
    "taints": lambda c: c.add_node(Node(
        name="n000", labels={ZONE_LABEL: "z0", HOSTNAME: "n000"},
        allocatable={CPU: 4000, MEMORY: 16 * gib, PODS: 110},
        taints=[Taint(key="dedicated", value="x")],
    )),
    # ISSUE 34 narrowed `pod-affinity` to the terms no row can keep: a
    # non-empty namespaceSelector (tests/test_resident_affinity.py)
    "affinity-namespace-selector": lambda c: c.add_pod(_bound(_plain_pod(
        "carrier", labels={"app": "web"},
        pod_anti_affinity_required=[_web_term(
            namespace_selector=LabelSelector(match_labels={"team": "a"}),
        )],
    ))),
    "nomination": lambda c: c.add_pod(_plain_pod(
        "nominee", nominated_node_name="n001",
    )),
    # ISSUE 38 narrowed `node-affinity` to the pod whose spread constraint
    # honours its own node term (tests/test_resident_node_terms.py)
    "spread-node-affinity": lambda c: c.add_pod(dataclasses.replace(
        spread_pod(901, 1), node_selector={ZONE_LABEL: "z1"},
    )),
    # a node with the zone key and no hostname key: a pod naming both in
    # one class has its domains counted by node
    "spread-node-counts": lambda c: (
        c.add_node(Node(
            name="n099", labels={ZONE_LABEL: "z0"},
            allocatable={CPU: 4000, MEMORY: 16 * gib, PODS: 110},
        )),
        c.add_pod(spread_pod(900, 1, keys=(ZONE_LABEL, HOSTNAME))),
    ),
}


class TestWhatStillFallsBack:
    @pytest.mark.parametrize("reason", sorted(CLAUSES))
    def test_each_remaining_clause_falls_back_under_its_reason(self, reason):
        cluster = zoned_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = spread_scheduler()
        cluster.add_pod(spread_pod(1, 0))
        run_cycle(sched, cluster, now=1000, serve=engine)
        assert engine.rebases == 1
        CLAUSES[reason](cluster)
        before = fallbacks(reason)
        total = fallbacks()
        cluster.add_pod(spread_pod(2, 2000))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        if reason != "taints":  # seen where the node's event is drained
            assert engine.fallback_reason(cluster, pending) == reason
        assert engine.refresh(cluster, pending, now_ms=2000) is None
        assert fallbacks(reason) == before + 1
        assert fallbacks() == total + 1
        if reason in ("nrt", "app-group", "seccomp"):
            return  # stand-in objects: nothing a fresh build can lower
        # and the cycle is solved all the same, by the fresh build
        report = run_cycle(sched, cluster, now=3000, serve=engine)
        assert "default/p00002" in report.bound or report.failed

    def test_a_spread_store_alone_does_not_fall_back(self):
        cluster = zoned_cluster(6)
        engine = ServeEngine().attach(cluster)
        sched = spread_scheduler()
        total = fallbacks()
        for serial in range(5):
            cluster.add_pod(spread_pod(
                serial, 0, keys=(ZONE_LABEL, HOSTNAME)
            ))
        report = run_cycle(sched, cluster, now=1000, serve=engine)
        assert len(report.bound) == 5
        cluster.add_pod(spread_pod(9, 2000, hard=False))
        report = run_cycle(sched, cluster, now=2000, serve=engine)
        assert len(report.bound) == 1
        assert fallbacks() == total and engine.rebases == 1
