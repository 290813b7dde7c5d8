"""Resilience layer: watchdog failover, host-solve parity, fault plans,
anti-entropy recovery, checkpoint/restore (docs/ROBUSTNESS.md).

The load-bearing invariant everywhere: faults cost LATENCY and REBASES,
never placements — the host failover solve is bit-identical to the
sequential parity path on the supported profile surface, and a poisoned
resident column survives at most one anti-entropy verification window.

Shapes are deliberately tiny and shared (6-node cluster, pod bucket 8)
so the whole module rides a handful of jit compiles — the tier-1 suite
sits near its time budget (ROADMAP).
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import Container, Node, Pod, Taint
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.resilience import (
    BackendUnavailable,
    Resilience,
    SolveWatchdog,
    faults,
    host_sequential_solve,
    solve_output_anomaly,
    supports_host_solve,
)
from scheduler_plugins_tpu.plugins import Coscheduling, NodeResourcesAllocatable
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

gib = 1 << 30

NODE_COLUMNS = (
    "alloc", "capacity", "requested", "nonzero_requested", "limits",
    "mask", "region", "zone", "pod_count", "terminating", "nominated",
)


def make_cluster(n_nodes=6, cpu=8000):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(Node(
            name=f"n{i:03d}",
            allocatable={CPU: cpu, MEMORY: 32 * gib, PODS: 32},
        ))
    return cluster


def make_pod(serial, now=0, cpu=500, mem=gib, **kw):
    return Pod(
        name=f"p{serial:05d}", creation_ms=now + serial,
        containers=[Container(requests={CPU: cpu, MEMORY: mem})], **kw,
    )


@pytest.fixture()
def no_faults():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def shared_scheduler():
    """One Scheduler for the whole module: every test solves the same
    (8-pod, 6-node) bucket, so the sequential solve compiles once."""
    return Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))


def fast_resilience(engine=None, timeout_s=30.0, attempts=2, probe_every=1):
    return Resilience(
        watchdog=SolveWatchdog(
            timeout_s=timeout_s, max_attempts=attempts,
            backoff_base_s=0.005, seed=0,
        ),
        probe_every=probe_every, engine=engine,
    )


class TestHostSolveParity:
    def test_bit_identical_including_failures(self, shared_scheduler):
        cluster = make_cluster(cpu=3000)
        # mix: placeable pods, an oversized pod (built-in fit failure),
        # and a scheduling-gated pod (PreFilter gate)
        for i in range(4):
            cluster.add_pod(make_pod(i, cpu=1000))
        cluster.add_pod(make_pod(4, cpu=50_000))
        gated = make_pod(5, cpu=100)
        gated.scheduling_gated = True
        cluster.add_pod(gated)
        s = shared_scheduler
        pending = s.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        s.prepare(meta, cluster)
        assert supports_host_solve(s, snap)
        dev = s.solve(snap)
        a, ad, w, f = host_sequential_solve(s, snap)
        np.testing.assert_array_equal(a, np.asarray(dev.assignment))
        np.testing.assert_array_equal(ad, np.asarray(dev.admitted))
        np.testing.assert_array_equal(w, np.asarray(dev.wait))
        np.testing.assert_array_equal(f, np.asarray(dev.failed_plugin))
        # the mix actually exercised both outcomes
        assert (a >= 0).any() and (a < 0).any()

    def test_supports_gates_on_profile_and_side_tables(self,
                                                       shared_scheduler):
        cluster = make_cluster()
        cluster.add_pod(make_pod(0))
        s = shared_scheduler
        pending = s.sort_pending(cluster.pending_pods(), cluster)
        snap, _ = cluster.snapshot(pending, now_ms=0)
        assert supports_host_solve(s, snap)
        mixed = Scheduler(Profile(
            plugins=[NodeResourcesAllocatable(), Coscheduling()]
        ))
        assert not supports_host_solve(mixed, snap)


class TestWatchdog:
    def test_timeout_then_retry_succeeds(self):
        import time as _time

        wd = SolveWatchdog(timeout_s=0.15, max_attempts=3,
                           backoff_base_s=0.005, seed=0)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) == 1:
                _time.sleep(1.0)  # first attempt hangs past the deadline
            return "ok"

        assert wd.run(flaky) == "ok"
        assert len(calls) == 2
        assert wd.abandoned == 1
        assert "timeout" in wd.last_reason
        # every watchdog worker — including the abandoned, still-stuck
        # one — must be a DAEMON thread: ThreadPoolExecutor workers are
        # non-daemon and joined at interpreter exit, which would turn a
        # hung backend into a process that can never exit 0 on SIGTERM
        import threading as _threading

        workers = [
            t for t in _threading.enumerate()
            if t.name.startswith("solve-watchdog")
        ]
        assert workers and all(t.daemon for t in workers)

    def test_exhausted_budget_raises_with_classification(self):
        wd = SolveWatchdog(timeout_s=1.0, max_attempts=2,
                           backoff_base_s=0.001, seed=0)

        def broken():
            raise RuntimeError("xla went away")

        with pytest.raises(BackendUnavailable) as exc:
            wd.run(broken)
        assert "device-error: RuntimeError" in exc.value.reason

    def test_backoff_schedule_deterministic_and_capped(self):
        a = SolveWatchdog(backoff_base_s=0.1, backoff_cap_s=0.4, seed=7)
        b = SolveWatchdog(backoff_base_s=0.1, backoff_cap_s=0.4, seed=7)
        seq_a = [a.backoff_s(k) for k in range(1, 7)]
        seq_b = [b.backoff_s(k) for k in range(1, 7)]
        assert seq_a == seq_b  # seeded: replays exactly
        for attempt, s in enumerate(seq_a, start=1):
            base = min(0.1 * 2 ** (attempt - 1), 0.4)
            assert 0.5 * base <= s <= base  # jitter in [0.5, 1.0] x base

    def test_output_anomaly_contract(self):
        a = np.array([0, -1, 2], np.int32)
        ok = np.ones(3, bool)
        assert solve_output_anomaly(a, ok, ok, 3) is None
        bad = a.copy()
        bad[0] = 3  # >= n_nodes
        assert "out of range" in solve_output_anomaly(bad, ok, ok, 3)
        assert "shape" in solve_output_anomaly(a, np.ones(2, bool), ok, 3)
        assert "NaN" in solve_output_anomaly(
            a, np.array([1.0, np.nan, 1.0]), ok, 3
        )


class TestResilienceCycle:
    def test_device_error_fails_over_bit_identical(self, shared_scheduler,
                                                   no_faults):
        def fresh():
            c = make_cluster()
            for i in range(5):
                c.add_pod(make_pod(i))
            return c

        baseline = run_cycle(shared_scheduler, fresh(), now=1000)
        plan = faults.install(faults.FaultPlan(seed=0))
        plan.specs.append(faults.FaultSpec(
            site=faults.SOLVE_DISPATCH, cycle=0, kind="device-error",
            repeat=8,
        ))
        plan.begin_cycle(0)
        rz = fast_resilience()
        chaos = fresh()
        report = run_cycle(shared_scheduler, chaos, now=1000, resilience=rz)
        assert report.solve_path == "host"
        assert report.degraded
        assert report.bound == baseline.bound
        assert report.failed == baseline.failed
        assert rz.failovers == 1
        assert obs.metrics.get(obs.DEGRADED) == 1.0
        # fault clears -> the next cycle's probation probe restores fast
        plan.begin_cycle(1)
        for i in range(5, 8):
            chaos.add_pod(make_pod(i))
        report2 = run_cycle(shared_scheduler, chaos, now=2000, resilience=rz)
        assert report2.solve_path == "device"
        assert not report2.degraded
        assert rz.recoveries and obs.metrics.get(obs.DEGRADED) == 0.0

    def test_garbage_output_is_a_backend_fault(self, shared_scheduler,
                                               no_faults):
        cluster = make_cluster()
        for i in range(5):
            cluster.add_pod(make_pod(i))
        plan = faults.install(faults.FaultPlan(seed=3))
        plan.specs.append(faults.FaultSpec(
            site=faults.SOLVE_DISPATCH, cycle=0, kind="garbage",
        ))
        plan.begin_cycle(0)
        rz = fast_resilience(attempts=2)
        report = run_cycle(shared_scheduler, cluster, now=1000,
                           resilience=rz)
        # one garbage answer -> retried clean on the second attempt
        assert report.solve_path == "device"
        assert not report.degraded
        assert "garbage-output" in rz.watchdog.last_reason

    def test_no_host_fallback_surfaces_backend_unavailable(self, no_faults):
        cluster = make_cluster()
        cluster.add_pod(make_pod(0))
        mixed = Scheduler(Profile(
            plugins=[NodeResourcesAllocatable(), Coscheduling()]
        ))
        plan = faults.install(faults.FaultPlan(seed=0))
        plan.specs.append(faults.FaultSpec(
            site=faults.SOLVE_DISPATCH, cycle=0, kind="device-error",
            repeat=8,
        ))
        plan.begin_cycle(0)
        rz = fast_resilience(attempts=1)
        with pytest.raises(BackendUnavailable):
            run_cycle(mixed, cluster, now=1000, resilience=rz)
        assert rz.degraded  # parked, not silently guessed


class TestFaultPlan:
    def test_standard_plan_deterministic(self):
        a = faults.FaultPlan.standard(42, 16)
        b = faults.FaultPlan.standard(42, 16)
        assert [(s.site, s.cycle, s.kind) for s in a.specs] == \
               [(s.site, s.cycle, s.kind) for s in b.specs]
        c = faults.FaultPlan.standard(43, 16)
        assert [(s.site, s.cycle, s.kind) for s in a.specs] != \
               [(s.site, s.cycle, s.kind) for s in c.specs]
        # full classification, one cycle each, all within (0, cycles-1)
        kinds = {s.kind for s in a.specs}
        assert kinds == {"hang", "device-error", "garbage", "drop", "dup",
                         "corrupt", "stall", "crash"}
        cycles = [s.cycle for s in a.specs]
        assert len(set(cycles)) == len(cycles)
        assert all(1 <= c <= 14 for c in cycles)

    def test_standard_plan_minimum_cycles(self):
        # 8 distinct slots need [1, cycles-2] to hold them: 10 is the
        # floor — 9 must raise the documented error, not a numpy one
        plan = faults.FaultPlan.standard(0, 10)
        assert len(plan.specs) == 8
        with pytest.raises(ValueError, match=">= 10 cycles"):
            faults.FaultPlan.standard(0, 9)

    def test_sticky_spec_rolls_forward_once(self):
        plan = faults.FaultPlan(seed=0)
        plan.specs.append(faults.FaultSpec(
            site=faults.DELTA_EVENT, cycle=3, kind="drop", sticky=True,
        ))
        plan.begin_cycle(2)
        assert plan.fire(faults.DELTA_EVENT) is None  # not due yet
        plan.begin_cycle(5)  # missed its slot: still pending
        assert plan.fire(faults.DELTA_EVENT).kind == "drop"
        assert plan.fire(faults.DELTA_EVENT) is None  # consumed
        assert plan.unfired() == []

    def test_zero_overhead_registry_off(self):
        assert faults.ACTIVE is None
        assert faults.fire(faults.SOLVE_DISPATCH) is None
        assert faults.mutate_delta(("pod_assign", None, "n", False)) == [
            ("pod_assign", None, "n", False)
        ]


def serve_cycle(scheduler, cluster, engine, now, n_new=3, serial=[0]):
    for _ in range(n_new):
        serial[0] += 1
        cluster.add_pod(make_pod(serial[0], now=now, cpu=100))
    return run_cycle(scheduler, cluster, now=now, serve=engine)


class TestAntiEntropy:
    def test_corrupted_resident_column_recovers_in_one_window(
        self, shared_scheduler
    ):
        """Satellite: seeded corruption of one resident column -> the
        next refresh's digest detects it, re-bases, and the cycle's
        placements are bit-exact vs a no-corruption control."""
        s = shared_scheduler
        cluster = make_cluster()
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 1
        control = make_cluster()
        ctrl_engine = ServeEngine().attach(control)
        ctrl_engine.verify_every = 1
        for now in (1000, 2000):
            serve_cycle(s, cluster, engine, now, serial=[now])
            serve_cycle(s, control, ctrl_engine, now, serial=[now])
        assert engine.resident_nodes is not None
        # seeded corruption: bump one cell of the requested column (the
        # shape of a lost/garbled delta that already landed)
        rng = np.random.default_rng(0)
        slot = int(rng.integers(0, len(cluster.nodes)))
        nodes = engine.resident_nodes
        engine._nodes = nodes.replace(
            requested=nodes.requested.at[slot, 0].add(1 << 20)
        )
        div0 = engine.antientropy_divergences
        r = serve_cycle(s, cluster, engine, 3000, serial=[3000])
        rc = serve_cycle(s, control, ctrl_engine, 3000, serial=[3000])
        assert engine.antientropy_divergences == div0 + 1  # detected
        assert r.bound == rc.bound  # re-based BEFORE the solve consumed it
        # and the resident base is exact again (one window, no lingering)
        div1 = engine.antientropy_divergences
        r = serve_cycle(s, cluster, engine, 4000, serial=[4000])
        rc = serve_cycle(s, control, ctrl_engine, 4000, serial=[4000])
        assert engine.antientropy_divergences == div1
        assert r.bound == rc.bound

    @pytest.mark.parametrize("column", ["cpu_avg", "missing_cpu_millis"])
    def test_corrupted_metrics_column_recovers_in_one_window(
        self, shared_scheduler, column
    ):
        """The resident metrics columns (the load watcher's report and the
        unreported CPU) are under the same digest: a corrupted cell is
        detected at the next refresh and re-based away."""
        s = shared_scheduler
        cluster = make_cluster()
        cluster.node_metrics = {
            name: {"cpu_avg": 10.0 + i, "mem_avg": 5.0}
            for i, name in enumerate(cluster.nodes)
        }
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 1
        for now in (1000, 2000):
            serve_cycle(s, cluster, engine, now, serial=[now])
        assert engine.refresh(cluster, [], now_ms=2500) is not None
        assert engine.verify(cluster) is None
        state = engine._metrics_state
        cell = np.asarray(getattr(state, column)).copy()
        cell[1] += 7
        engine._metrics_state = state.replace(**{column: cell})
        assert engine.verify(cluster) == "metrics-digest"
        div0, rebases0 = engine.antientropy_divergences, engine.rebases
        serve_cycle(s, cluster, engine, 3000, serial=[3000])
        assert engine.antientropy_divergences == div0 + 1  # detected
        assert engine.rebases == rebases0 + 1
        serve_cycle(s, cluster, engine, 4000, serial=[4000])
        assert engine.antientropy_divergences == div0 + 1  # and gone
        assert engine.refresh(cluster, [], now_ms=4500) is not None
        assert engine.verify(cluster) is None

    def test_dropped_sink_event_detected_within_window(
        self, shared_scheduler, no_faults
    ):
        s = shared_scheduler
        cluster = make_cluster()
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 1
        serve_cycle(s, cluster, engine, 1000, serial=[1])
        plan = faults.install(faults.FaultPlan(seed=0))
        plan.specs.append(faults.FaultSpec(
            site=faults.DELTA_EVENT, cycle=0, kind="drop", sticky=True,
        ))
        plan.begin_cycle(0)
        div0 = engine.antientropy_divergences
        serve_cycle(s, cluster, engine, 2000, serial=[2])  # bind dropped
        faults.clear()
        serve_cycle(s, cluster, engine, 3000, serial=[3])
        assert plan.unfired() == []
        assert engine.antientropy_divergences == div0 + 1

    def test_note_fault_forces_offcadence_verify(self, shared_scheduler):
        s = shared_scheduler
        cluster = make_cluster()
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 0  # periodic checks OFF
        serve_cycle(s, cluster, engine, 1000, serial=[100])
        checks0 = obs.metrics.get(obs.ANTIENTROPY_CHECKS)
        serve_cycle(s, cluster, engine, 2000, serial=[200])
        assert obs.metrics.get(obs.ANTIENTROPY_CHECKS) == checks0
        engine.note_fault("test-fault")
        serve_cycle(s, cluster, engine, 3000, serial=[300])
        assert obs.metrics.get(obs.ANTIENTROPY_CHECKS) == checks0 + 1

    def test_fallback_reentry_then_corruption_recovery(
        self, shared_scheduler
    ):
        """Satellite: repeated compatibility-fallback -> serve resume
        round trips (taint appears/clears, twice), then a corruption is
        still caught and recovered — the fallback windows must not
        desync the resident base."""
        s = shared_scheduler
        cluster = make_cluster()
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 1
        serial = [0]
        serve_cycle(s, cluster, engine, 1000, serial=serial)
        gen = engine.generation
        rebases0 = engine.rebases
        for round_ in range(2):
            node = cluster.nodes["n000"]
            node.taints = [Taint(key="k", value="v")]
            cluster.add_node(node)  # upsert: side state, serve falls back
            assert engine.refresh(cluster, [], now_ms=2000) is None
            node.taints = []
            cluster.add_node(node)  # cleared: serving resumes
            serve_cycle(s, cluster, engine, 3000 + round_, serial=serial)
            assert engine.generation > gen
            gen = engine.generation
        # fallback windows absorbed deltas — NO rebase was needed to
        # resume (verify_every=1 re-checked the base at every resumed
        # refresh, so staying at zero rebases PROVES the base stayed
        # bit-exact through both round trips)
        assert engine.rebases == rebases0
        assert engine.antientropy_divergences == 0


def _full_store():
    """A store with every kind of resident state, built without a solve:
    zoned nodes, a load watcher's report, a PodGroup under an ElasticQuota
    with bound members, bound pods under a zone-spread constraint, and a
    second window of binds that reached the columns as deltas."""
    from scheduler_plugins_tpu.api.objects import (
        POD_GROUP_LABEL,
        ZONE_LABEL,
        ElasticQuota,
        LabelSelector,
        PodGroup,
        TopologySpreadConstraint,
    )

    cluster = Cluster()
    for i in range(6):
        cluster.add_node(Node(
            name=f"n{i:03d}",
            allocatable={CPU: 8000, MEMORY: 32 * gib, PODS: 32},
            labels={ZONE_LABEL: f"z{i % 3}"},
        ))
    cluster.node_metrics = {
        name: {"cpu_avg": 10.0 + i, "mem_avg": 5.0}
        for i, name in enumerate(cluster.nodes)
    }
    cluster.add_quota(ElasticQuota(
        name="eq", namespace="team",
        min={CPU: 24_000, MEMORY: 96 * gib},
        max={CPU: 48_000, MEMORY: 160 * gib},
    ))
    cluster.add_pod_group(PodGroup(
        name="g0", namespace="team", min_member=2, creation_ms=100,
    ))
    spread = TopologySpreadConstraint(
        max_skew=1, topology_key=ZONE_LABEL,
        label_selector=LabelSelector(match_labels={"app": "web"}),
    )

    def bind(serial, node, now, **kw):
        pod = make_pod(serial, now=now, **kw)
        cluster.add_pod(pod)
        cluster.bind(pod.uid, node, now_ms=now)

    def window(first, now):
        bind(first, "n000", now)
        bind(first + 1, "n001", now, namespace="team",
             labels={POD_GROUP_LABEL: "g0"})
        bind(first + 2, "n002", now, labels={"app": "web"},
             topology_spread=[spread])

    window(1, 500)
    engine = ServeEngine().attach(cluster)
    engine.verify_every = 0
    assert engine.refresh(cluster, [], now_ms=1000) is not None  # cold build
    window(11, 1500)
    assert engine.refresh(cluster, [], now_ms=2000) is not None  # deltas
    assert engine.rebases == 1
    return cluster, engine


def _plant_nothing(cluster, engine):
    return None


def _plant_dropped_delta(cluster, engine):
    pod = make_pod(90, now=2100)
    cluster.add_pod(pod)
    cluster.bind(pod.uid, "n003", now_ms=2100)
    engine._sink.drain()  # the window's events never reach the engine
    return "column-digest"


def _plant_corrupted_column(cluster, engine):
    nodes = engine._nodes
    engine._nodes = nodes.replace(
        requested=nodes.requested.at[2, 0].add(1 << 20)
    )
    return "column-digest"


def _plant_row_order(cluster, engine):
    engine._names = list(reversed(engine._names))
    return "row-order"


def _plant_metrics_column(cluster, engine):
    state = engine._metrics_state
    cell = np.asarray(state.cpu_avg).copy()
    cell[1] += 7
    engine._metrics_state = state.replace(cpu_avg=cell)
    return "metrics-digest"


def _plant_gang_side_table(cluster, engine):
    side = engine._side
    engine._side = side.replace(
        gang_assigned=side.gang_assigned.at[0].add(1)
    )
    return "side-gang"


def _plant_quota_side_table(cluster, engine):
    side = engine._side
    engine._side = side.replace(quota_used=side.quota_used.at[0, 0].add(5))
    return "side-quota"


def _plant_selector_count(cluster, engine):
    held = engine._selectors
    held.track_base = held.track_base.at[0, 1].add(1)
    return "selector-counts"


PLANTED = {
    "clean": _plant_nothing,
    "dropped_delta": _plant_dropped_delta,
    "corrupted_column": _plant_corrupted_column,
    "row_order": _plant_row_order,
    "metrics_column": _plant_metrics_column,
    "gang_side_table": _plant_gang_side_table,
    "quota_side_table": _plant_quota_side_table,
    "selector_count": _plant_selector_count,
}


def _verify_spans(run):
    """`run()` under the tracer: its `ServeRefresh/verify` spans' args and
    the checks it counted."""
    checks0 = obs.metrics.get(obs.ANTIENTROPY_CHECKS)
    obs.tracer.start()
    try:
        out = run()
    finally:
        obs.tracer.stop()
    spans = [
        e["args"] for e in obs.tracer.export()["traceEvents"]
        if e.get("name") == "ServeRefresh/verify"
    ]
    return out, spans, obs.metrics.get(obs.ANTIENTROPY_CHECKS) - checks0


class TestAntiEntropyKinds:
    """ISSUE 37: the cadenced check reads the store's objects through the
    pod records (`verify_assigned`), the forced one a fresh snapshot
    (`verify`); they compare the same things and say the same."""

    @pytest.mark.parametrize("fault", sorted(PLANTED))
    def test_both_kinds_return_the_same_verdict(self, fault):
        cluster, engine = _full_store()
        assert engine.verify_assigned(cluster) is None
        assert engine.verify(cluster) is None
        expected = PLANTED[fault](cluster, engine)
        (fast, snapshot), spans, counted = _verify_spans(lambda: (
            engine.verify_assigned(cluster), engine.verify(cluster),
        ))
        assert fast == snapshot == expected
        # one check = one count, one span, and the span says which ran
        assert counted == 2
        assert [a["fast"] for a in spans] == [True, False]
        assert engine.antientropy_divergences == (2 if expected else 0)

    @pytest.mark.parametrize("fault", ["clean", "corrupted_column"])
    def test_cadence_runs_the_assigned_kind_and_a_fault_the_snapshot(
        self, fault
    ):
        cluster, engine = _full_store()
        engine.verify_every = 1
        expected = PLANTED[fault](cluster, engine)
        scoped = obs.metrics.scoped()

        def observed(kind):
            return scoped.hist_count(obs.SERVE_VERIFY_MS, kind=kind)

        out, spans, counted = _verify_spans(
            lambda: engine.refresh(cluster, [], now_ms=3000)
        )
        assert out is not None and counted == 1
        assert [a["fast"] for a in spans] == [True]
        assert (observed("assigned"), observed("snapshot")) == (1, 0)
        assert engine.rebases == (2 if expected else 1)
        engine.verify_every = 0
        engine.note_fault("test-fault")
        out, spans, counted = _verify_spans(
            lambda: engine.refresh(cluster, [], now_ms=4000)
        )
        assert out is not None and counted == 1
        assert [a["fast"] for a in spans] == [False]
        assert (observed("assigned"), observed("snapshot")) == (1, 1)
        # the fault's check ran once: the next refresh runs none
        out, spans, counted = _verify_spans(
            lambda: engine.refresh(cluster, [], now_ms=5000)
        )
        assert (spans, counted) == ([], 0)

    def test_a_resource_outside_the_axis_gets_the_snapshot_kind(self):
        """A store the O(assigned) path cannot describe falls through to
        the fresh snapshot inside the same span and count."""
        cluster, engine = _full_store()
        pod = Pod(
            name="gpu", creation_ms=2100,
            containers=[Container(requests={CPU: 100, "example.com/gpu": 1})],
        )
        pod.node_name = "n004"
        cluster.add_pod(pod)
        engine._sink.drain()  # the engine has not seen it yet
        reason, spans, counted = _verify_spans(
            lambda: engine.verify_assigned(cluster)
        )
        assert reason == "axis-width"
        assert counted == 1 and [a["fast"] for a in spans] == [False]

    @pytest.mark.parametrize("streaming", [False, True],
                             ids=["base", "streaming"])
    def test_the_metrics_expectation_is_staged_as_the_resident_columns(
        self, streaming
    ):
        """The digest compares what the device holds: a float64 column read
        back from a TPU is not bit for bit the host array put there, so the
        cadenced check's expectation goes where the resident columns (and a
        fresh snapshot's) went (`PERF.md` finding 40)."""
        from scheduler_plugins_tpu.serving import StreamingServeEngine

        cluster, _ = _full_store()
        engine = (StreamingServeEngine if streaming else ServeEngine)()
        engine.attach(cluster)
        assert engine.refresh(cluster, [], now_ms=2500) is not None
        expected = engine._expected_metrics(cluster, 2500)
        for field in ("cpu_avg", "missing_cpu_millis", "cpu_valid"):
            held = getattr(engine._metrics_state, field)
            assert type(getattr(expected, field)) is type(held), field
        assert engine.verify_assigned(cluster) is None

    def test_the_check_reads_records_and_lowers_nothing(self):
        cluster, engine = _full_store()
        scoped = obs.metrics.scoped()
        assert engine.verify_assigned(cluster) is None
        held = sum(1 for p in cluster.pods.values() if p.node_name)
        assert scoped.get(
            obs.SERVE_POD_LOWERINGS, reader="check", result="hit"
        ) == held
        assert scoped.get(
            obs.SERVE_POD_LOWERINGS, reader="check", result="miss"
        ) == 0


class TestCheckpointRestore:
    def _served_engine(self, scheduler, cluster):
        engine = ServeEngine().attach(cluster)
        engine.verify_every = 1
        serve_cycle(scheduler, cluster, engine, 1000, serial=[10])
        # drain the last cycle's bind deltas so the checkpoint is a
        # settled base (the daemon's shutdown path checkpoints after its
        # final refresh the same way)
        engine.refresh(cluster, [], now_ms=1500)
        return engine

    def test_restore_resumes_without_rebase(self, shared_scheduler,
                                            tmp_path):
        s = shared_scheduler
        cluster = make_cluster()
        engine = self._served_engine(s, cluster)
        path = str(tmp_path / "resident.ckpt")
        assert engine.save_checkpoint(path)
        gen = engine.generation
        engine.detach()

        restored = ServeEngine().attach(cluster)
        restored.verify_every = 1
        assert restored.restore_checkpoint(path)
        assert restored.generation == gen  # continuity, not a cold start
        r = serve_cycle(s, cluster, restored, 2000, serial=[20])
        # the forced anti-entropy verify PASSED: no divergence, no rebase
        assert restored.rebases == 0
        assert restored.antientropy_divergences == 0
        assert r.bound  # and it actually served decisions

    def test_stale_checkpoint_rebases_within_one_window(
        self, shared_scheduler, tmp_path
    ):
        s = shared_scheduler
        cluster = make_cluster()
        engine = self._served_engine(s, cluster)
        ckpt = engine.checkpoint_bytes()
        assert ckpt is not None
        engine.detach()
        # the store moves on while the process is "down": these deltas
        # never reach any sink, exactly like a crash's undrained events
        victim = next(
            uid for uid, p in cluster.pods.items()
            if p.node_name is not None
        )
        cluster.remove_pod(victim)

        restored = ServeEngine().attach(cluster)
        restored.verify_every = 1
        restored.restore_checkpoint(ckpt)  # bytes source: the crash path
        # the restored-but-stale base must be detected by the forced
        # verify and re-based BEFORE the first solve consumes it
        r = serve_cycle(s, cluster, restored, 2000, serial=[30])
        assert restored.antientropy_divergences == 1
        assert restored.rebases == 1
        # recovered: next refresh is clean
        serve_cycle(s, cluster, restored, 3000, serial=[40])
        assert restored.antientropy_divergences == 1
        assert r.bound

    def test_checkpoint_none_before_first_refresh(self, tmp_path):
        engine = ServeEngine()
        assert engine.checkpoint_bytes() is None
        assert not engine.save_checkpoint(str(tmp_path / "x.ckpt"))