"""Online self-tuning shadow lane (tuning.shadow + tuning.promotion,
ISSUE 15): the shared promotion-gate body's rank/disqualify decision
tables (the one copy tools/tune.py and the shadow lane both consume),
the guarded-rollout rollback decision tables (each objective regressing
in isolation rolls back within the probation window; sub-threshold noise
does not; a watchdog fault during probation rolls back immediately; the
controller cannot flap), the tune.sweep / tune.promote chaos sites, the
live-weights rollout seam (traced-argument weights, zero recompiles),
and the tuner state persistence round trip.

`TestTunedServingEndToEnd` runs the whole lane on a micro drifting-mix
workload: shadow sweeps over real ring records, a gated promotion, an
injected regression rolled back, and injected tuner faults that leave live
placements bit-identical to a no-tuner control. The other classes stay
host-side where possible — only the live-weights seam class compiles a
(tiny) solve."""

from types import SimpleNamespace

import numpy as np
import pytest

from scheduler_plugins_tpu.framework import Profile, Scheduler
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.resilience import faults
from scheduler_plugins_tpu.tuning import promotion
from scheduler_plugins_tpu.tuning.shadow import (
    PROBATION_OBJECTIVES,
    ShadowTuner,
)


def make_scheduler(weights=(1, 1)):
    plugins = [NodeResourcesAllocatable() for _ in weights]
    for plugin, w in zip(plugins, weights):
        plugin.weight = int(w)
    return Scheduler(Profile(plugins=plugins))


def make_tuner(scheduler=None, **kw):
    scheduler = scheduler or make_scheduler()
    kw.setdefault("probation_cycles", 6)
    kw.setdefault("baseline_min", 1)
    kw.setdefault("hysteresis", 0.01)
    kw.setdefault("regress_cycles", 2)
    kw.setdefault("cooldown_cycles", 4)
    kw.setdefault("sync", True)
    tuner = ShadowTuner(scheduler, **kw)
    return tuner


def report(quality=None, degraded=False, solve_path="device"):
    return SimpleNamespace(
        quality=quality, degraded=degraded, solve_path=solve_path,
    )


def flat_quality(**over):
    q = {name: 0.5 for name in PROBATION_OBJECTIVES}
    q.update(over)
    return q


class _ScriptedProbe:
    """Scripted paired-counterfactual probe: each entry is the
    {objective: (q_active, q_good)} pair the next probation cycle sees —
    the decision tables drive the regression detector without any jit."""

    def __init__(self, tuner, pairs):
        self.pairs = list(pairs)
        tuner._counterfactual_pair = self._next

    def _next(self):
        spec = self.pairs.pop(0) if self.pairs else {}
        q_active = flat_quality(**{k: v[0] for k, v in spec.items()})
        q_good = flat_quality(**{k: v[1] for k, v in spec.items()})
        return q_active, q_good


def start_probation(tuner, weights=(3, 3)):
    """Baseline one observed cycle, then promote `weights` via the
    harness injection hook (the decision tables adjudicate the window,
    not the gate)."""
    tuner.begin_cycle()
    tuner.observe_report(report(quality=flat_quality()))
    tuner.inject_promotion(weights)
    tuner.begin_cycle()
    assert tuner.state == "probation"
    assert [int(w) for w in tuner.active] == list(weights)


class TestPromotionGateBody:
    """Decision tables for the shared rank/disqualify rules — and the
    regression lock that tools/tune.py actually consumes them."""

    def _objectives(self, **cols):
        # lane 0 is the incumbent; columns are per-candidate values
        base = {name: np.zeros(3) for name in promotion.RANKED_OBJECTIVES}
        for name, vals in cols.items():
            base[name] = np.asarray(vals, float)
        return base

    def test_improvement_ranks_and_wins(self):
        objs = self._objectives(util_imbalance=[0.20, 0.15, 0.25])
        order, score, imps = promotion.rank_candidates(
            objs, np.zeros(3, np.int64), tolerance=0.01
        )
        assert int(order[0]) == 1 and score[1] == pytest.approx(0.05)
        assert promotion.strict_improvements(imps, 1) == ["util_imbalance"]

    def test_violations_disqualify(self):
        objs = self._objectives(util_imbalance=[0.20, 0.10, 0.25])
        order, score, _ = promotion.rank_candidates(
            objs, np.asarray([0, 3, 0]), tolerance=0.01
        )
        assert not np.isfinite(score[1])
        assert int(order[0]) == 0  # nothing beats the incumbent

    def test_tolerance_disqualifies_sold_objective(self):
        # candidate 1 buys util_imbalance by selling fragmentation
        objs = self._objectives(
            util_imbalance=[0.20, 0.10, 0.20],
            fragmentation=[0.50, 0.55, 0.50],
        )
        _, score, _ = promotion.rank_candidates(
            objs, np.zeros(3, np.int64), tolerance=0.01
        )
        assert not np.isfinite(score[1])
        # a looser tolerance readmits it
        _, score2, _ = promotion.rank_candidates(
            objs, np.zeros(3, np.int64), tolerance=0.10
        )
        assert score2[1] == pytest.approx(0.05)

    def test_rail_objective_guards_but_does_not_vote(self):
        # drift regresses 0.05: inside its own rail tolerance, excluded
        # from the rank sum — the shadow lane's configuration
        objs = self._objectives(
            util_imbalance=[0.20, 0.10, 0.20], drift=[0.0, -0.05, 0.0],
        )
        _, score, _ = promotion.rank_candidates(
            objs, np.zeros(3, np.int64), tolerance=0.01,
            rank_objectives=PROBATION_OBJECTIVES,
            tolerances={"drift": 0.10},
        )
        assert score[1] == pytest.approx(0.10)  # drift did not vote
        # beyond the rail it still disqualifies
        objs["drift"] = np.asarray([0.0, -0.15, 0.0])
        _, score3, _ = promotion.rank_candidates(
            objs, np.zeros(3, np.int64), tolerance=0.01,
            rank_objectives=PROBATION_OBJECTIVES,
            tolerances={"drift": 0.10},
        )
        assert not np.isfinite(score3[1])

    def test_offline_driver_consumes_shared_body(self):
        import inspect

        import tools.tune as tune

        # the refactor left exactly one copy of the gate: tools/tune.py
        # no longer defines its own rank/sweep/disqualify
        for legacy in ("_rank", "_sweep_corpus", "_strict_improvements"):
            assert not hasattr(tune, legacy)
        src = inspect.getsource(tune.cmd_tune)
        assert "promotion.evaluate_candidates" in src

    def test_weights_digest_stable_and_distinct(self):
        a = promotion.weights_digest([1, 20])
        assert a == promotion.weights_digest(np.asarray([1, 20]))
        assert a != promotion.weights_digest([20, 1])


class TestRollbackDecisionTables:
    """The probation window, driven by a scripted counterfactual probe."""

    @pytest.mark.parametrize("objective", PROBATION_OBJECTIVES)
    def test_each_objective_regressing_in_isolation_rolls_back(
        self, objective
    ):
        tuner = make_tuner()
        # sustained regression just past the band: detected by the
        # consecutive-cycles trigger within regress_cycles (= 2)
        _ScriptedProbe(tuner, [
            {objective: (0.515, 0.50)} for _ in range(4)
        ])
        start_probation(tuner)
        for k in range(4):
            tuner.begin_cycle()
            tuner.observe_report(report(quality=flat_quality()))
            if tuner.rollbacks:
                break
        assert tuner.rollbacks == 1
        assert tuner.last_rollback_reason == (
            f"quality-regression:{objective}"
        )
        assert tuner.last_rollback_detect_cycles <= 2
        assert [int(w) for w in tuner.active] == [1, 1]  # last-known-good
        assert (3, 3) in tuner.blocked

    def test_large_single_cycle_regression_rolls_back_immediately(self):
        tuner = make_tuner()
        # one cycle at >= hysteresis * regress_cycles: immediate
        _ScriptedProbe(tuner, [{"util_imbalance": (0.525, 0.50)}])
        start_probation(tuner)
        tuner.begin_cycle()
        tuner.observe_report(report(quality=flat_quality()))
        assert tuner.rollbacks == 1
        assert tuner.last_rollback_detect_cycles == 0

    def test_sub_threshold_noise_does_not_flap(self):
        tuner = make_tuner(probation_cycles=4)
        # alternating +/- inside the hysteresis band: never counted
        _ScriptedProbe(tuner, [
            {"util_imbalance": (0.505, 0.50)},
            {"util_imbalance": (0.495, 0.50)},
            {"util_imbalance": (0.508, 0.50)},
            {"util_imbalance": (0.494, 0.50)},
        ])
        start_probation(tuner)
        for _ in range(4):
            tuner.begin_cycle()
            tuner.observe_report(report(quality=flat_quality()))
        assert tuner.rollbacks == 0
        assert tuner.state == "idle"  # probation confirmed
        assert [int(w) for w in tuner.last_known_good] == [3, 3]

    def test_intermittent_regression_does_not_confirm_silently(self):
        # an above-band regression on non-consecutive cycles: each hit
        # resets nothing it should not, and a later big hit still fires
        tuner = make_tuner(probation_cycles=8)
        _ScriptedProbe(tuner, [
            {"util_imbalance": (0.515, 0.50)},
            {},
            {"util_imbalance": (0.525, 0.50)},  # large: immediate
        ])
        start_probation(tuner)
        for _ in range(3):
            tuner.begin_cycle()
            tuner.observe_report(report(quality=flat_quality()))
        assert tuner.rollbacks == 1

    def test_watchdog_fault_during_probation_rolls_back_immediately(self):
        tuner = make_tuner()
        _ScriptedProbe(tuner, [{}] * 4)
        start_probation(tuner)
        tuner.begin_cycle()
        tuner.observe_report(
            report(quality=flat_quality(), degraded=True)
        )
        assert tuner.rollbacks == 1
        assert tuner.last_rollback_reason.startswith("watchdog-fault")
        assert [int(w) for w in tuner.active] == [1, 1]

    def test_host_path_solve_counts_as_watchdog_fault(self):
        tuner = make_tuner()
        _ScriptedProbe(tuner, [{}] * 4)
        start_probation(tuner)
        tuner.begin_cycle()
        tuner.observe_report(
            report(quality=flat_quality(), solve_path="host")
        )
        assert tuner.rollbacks == 1

    def test_unadjudicable_probe_rolls_back(self):
        tuner = make_tuner()

        def boom():
            raise RuntimeError("probe died")

        tuner._counterfactual_pair = boom
        start_probation(tuner)
        tuner.begin_cycle()
        tuner.observe_report(report(quality=flat_quality()))
        assert tuner.rollbacks == 1
        assert "probe-unavailable" in tuner.last_rollback_reason

    def test_rolled_back_vector_is_blocked_and_cooldown_holds(self):
        tuner = make_tuner(cooldown_cycles=6)
        _ScriptedProbe(tuner, [{"util_imbalance": (0.53, 0.50)}])
        start_probation(tuner, weights=(5, 7))
        tuner.begin_cycle()
        tuner.observe_report(report(quality=flat_quality()))
        assert tuner.state == "cooldown"
        # a sweep winner equal to the rolled-back vector is never staged
        W = np.asarray([[1, 1], [5, 7]], np.int64)
        verdict = promotion.PromotionVerdict(
            objectives={}, violations=np.zeros(2, np.int64),
            anchor_mismatches=0, order=np.asarray([1, 0]),
            score=np.asarray([0.0, 0.5]),
            improvements={"util_imbalance": np.asarray([0.0, 0.1])},
            best=1, improved=["util_imbalance"], accepted=True,
        )
        for _ in range(tuner.confirm_sweeps + 1):
            tuner._consume_sweep_locked((verdict, W))
        assert tuner._pending is None
        assert tuner.promotions == 1  # only the injected one, ever

    def test_quality_none_cycles_do_not_advance_probation(self):
        tuner = make_tuner(probation_cycles=2)
        _ScriptedProbe(tuner, [{}] * 2)
        start_probation(tuner)
        for _ in range(3):
            tuner.begin_cycle()
            tuner.observe_report(report(quality=None))
        assert tuner.state == "probation"  # no evidence, no progress


class TestTunerFaultSites:
    def test_promote_crash_keeps_incumbent_and_counts(self):
        tuner = make_tuner()
        plan = faults.FaultPlan(seed=0)
        plan.specs = [faults.FaultSpec(
            site=faults.TUNE_PROMOTE, cycle=0, kind="crash", sticky=True,
        )]
        faults.install(plan)
        try:
            tuner.begin_cycle()
            tuner.observe_report(report(quality=flat_quality()))
            tuner.inject_promotion((9, 9))
            plan.begin_cycle(0)
            tuner.begin_cycle()
        finally:
            faults.clear()
        assert tuner.promotions == 0
        assert [int(w) for w in tuner.active] == [1, 1]
        assert tuner.sweep_failures == 1
        assert plan.log == [(0, faults.TUNE_PROMOTE, "crash")]

    def test_repeated_faults_disable_the_lane(self):
        tuner = make_tuner(max_failures=2)
        plan = faults.FaultPlan(seed=0)
        plan.specs = [
            faults.FaultSpec(site=faults.TUNE_PROMOTE, cycle=c,
                             kind="crash")
            for c in range(2)
        ]
        faults.install(plan)
        try:
            tuner.begin_cycle()
            tuner.observe_report(report(quality=flat_quality()))
            for c in range(2):
                tuner.inject_promotion((9, 9))
                plan.begin_cycle(c)
                tuner.begin_cycle()
        finally:
            faults.clear()
        assert tuner.state == "disabled"
        assert tuner.disabled_reason is not None
        # disabled lane is inert: further cycles change nothing
        tuner.inject_promotion((9, 9))
        tuner.begin_cycle()
        assert tuner.promotions == 0

    def test_sites_registered(self):
        assert faults.TUNE_SWEEP in faults.ALL_SITES
        assert faults.TUNE_PROMOTE in faults.ALL_SITES

    def test_sweep_failure_drops_shadow_scheduler_cache(self):
        # an abandoned (timed-out) job keeps running on its zombie
        # worker and still holds the cached shadow scheduler — the next
        # sweep/probe must rebuild fresh, never share it
        tuner = make_tuner()
        tuner._shadow_sched = object()
        tuner._shadow_key = ("k",)
        with tuner._lock:
            tuner._sweep_failed_locked("timeout (0.1s) in tune.sweep")
        assert tuner._shadow_sched is None and tuner._shadow_key is None


class TestTunerRequiresSequentialMode:
    def test_packing_profile_refused_at_construction(self):
        # a packing-mode profile would accept a gated promotion and then
        # raise on every solve (the live seam is the sequential path) —
        # the tuner must refuse at construction, not at first promotion
        sched = make_scheduler((1, 1))
        sched.profile.solve_mode = "packing"
        with pytest.raises(ValueError, match="sequential parity path"):
            ShadowTuner(sched, sync=True)


class TestStatePersistence:
    def test_state_dict_round_trip_resumes_weights_and_probation(self):
        tuner = make_tuner()
        _ScriptedProbe(tuner, [{}] * 8)
        start_probation(tuner, weights=(4, 6))
        tuner.begin_cycle()
        tuner.observe_report(report(quality=flat_quality()))
        state = tuner.state_dict()
        assert state["state"] == "probation"

        fresh_sched = make_scheduler()
        fresh = make_tuner(scheduler=fresh_sched)
        assert fresh.restore_state(state)
        assert [int(w) for w in fresh.active] == [4, 6]
        assert fresh.state == "probation"
        assert list(np.asarray(fresh_sched.live_weights)) == [4, 6]
        # the restored probation window still adjudicates: a watchdog
        # fault rolls back to the restored last-known-good
        fresh.begin_cycle()
        fresh.observe_report(
            report(quality=flat_quality(), degraded=True)
        )
        assert fresh.rollbacks == 1
        assert [int(w) for w in fresh.active] == [1, 1]

    def test_bad_state_file_starts_fresh(self):
        tuner = make_tuner()
        assert not tuner.restore_state({"format": 99})
        assert not tuner.restore_state({"format": 1, "active_weights": [1]})
        assert not tuner.restore_state("garbage")
        assert tuner.state == "idle"


class TestLiveWeightsSeam:
    """The rollout seam itself: a live-weight swap is bit-identical to a
    statically-weighted scheduler and never recompiles the solve."""

    def _solve(self, scheduler, seed=3):
        from scheduler_plugins_tpu.models import trimaran_scenario

        cluster = trimaran_scenario(n_nodes=16, n_pods=24, seed=seed)
        pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        scheduler.prepare(meta, cluster)
        return np.asarray(scheduler.solve(snap).assignment)

    def test_live_swap_parity_and_zero_recompiles(self):
        from scheduler_plugins_tpu import plugins as P
        from scheduler_plugins_tpu.utils import observability as obs

        def trimaran_sched(w):
            sched = Scheduler(Profile(plugins=[
                P.TargetLoadPacking(), P.LoadVariationRiskBalancing(),
            ]))
            for plugin, wi in zip(sched.profile.plugins, w):
                plugin.weight = wi
            return sched

        static = trimaran_sched([3, 7])
        want = self._solve(static)

        live = trimaran_sched([1, 1])
        base = self._solve(live)
        live.set_live_weights([3, 7])
        m0 = obs.metrics.get(obs.JIT_CACHE_MISS, program="solve_live")
        got = self._solve(live)
        m1 = obs.metrics.get(obs.JIT_CACHE_MISS, program="solve_live")
        np.testing.assert_array_equal(got, want)
        assert (got != base).any()  # the swap really changed placements
        # rollback = argument change on the SAME compiled program
        live.set_live_weights([1, 1])
        back = self._solve(live)
        m2 = obs.metrics.get(obs.JIT_CACHE_MISS, program="solve_live")
        np.testing.assert_array_equal(back, base)
        assert m1 - m0 == 1 and m2 - m1 == 0
        # host-side consumers follow the swap (hostsolve/recorder read
        # plugin.weight)
        assert [p.weight for p in live.profile.plugins] == [1, 1]

    def test_live_weights_validated(self):
        sched = make_scheduler((1, 1))
        with pytest.raises(ValueError, match="shape"):
            sched.set_live_weights([1, 2, 3])
        with pytest.raises(ValueError, match="positive"):
            sched.set_live_weights([0, 1])
        sched.set_live_weights(None)
        assert sched.live_weights is None

    def test_packing_mode_refuses_live_weights(self):
        from scheduler_plugins_tpu.models import trimaran_scenario

        sched = make_scheduler((1,))
        sched.profile.solve_mode = "packing"
        sched.set_live_weights([2])
        cluster = trimaran_scenario(n_nodes=8, n_pods=4, seed=0)
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        with pytest.raises(ValueError, match="sequential parity path"):
            sched.solve(snap)


# ---------------------------------------------------------------------------
# end to end: a micro drifting-mix workload through the real cycle
# ---------------------------------------------------------------------------

#: a hot/cold fleet serving churn whose mix drifts at `drift_at`: the cold
#: class turns metric-noisy while the pod sizes go bimodal, so the static
#: weights (LVRB 20 : TLP 1, which trust the variance signal) start
#: steering arrivals onto the hot nodes — the opening the tuner must find
DRIFT = dict(n_nodes=24, hot=6, hot_util=0.62, cold_util=0.15,
             arrivals=8, departs=3, drift_at=4)


def drift_cluster():
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    cluster = Cluster()
    serial = 0
    for i in range(DRIFT["n_nodes"]):
        cluster.add_node(Node(
            name=f"node-{i:05d}",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 512},
        ))
        util = DRIFT["hot_util" if i < DRIFT["hot"] else "cold_util"]
        for _ in range(-(-int(64_000 * util) // 2000)):
            serial += 1
            pod = Pod(
                name=f"base-{serial:06d}", creation_ms=serial,
                containers=[Container(requests={CPU: 2000,
                                                MEMORY: 4 * gib})],
            )
            pod.node_name = f"node-{i:05d}"
            cluster.add_pod(pod)
    return cluster


def drift_script(cycles, seed=1):
    """[(phase, arrivals [(name, cpu, mem)], departures [names])] from the
    rng stream alone, independent of placements: every arm replays the
    identical workload."""
    rng = np.random.default_rng(seed)
    gib = 1 << 30
    serial, live, script = 0, [], []
    for c in range(cycles):
        phase = "a" if c < DRIFT["drift_at"] else "b"
        k = min(DRIFT["departs"], len(live))
        picks = set(
            int(x) for x in rng.choice(len(live), size=k, replace=False)
        ) if k else set()
        departs = [live[i] for i in sorted(picks)]
        live = [nm for i, nm in enumerate(live) if i not in picks]
        arrivals = []
        for _ in range(DRIFT["arrivals"]):
            serial += 1
            if phase == "a":
                cpu = int(rng.integers(800, 1600))
                mem = int(rng.integers(gib, 2 * gib))
            elif rng.random() < 0.5:
                cpu, mem = 600, gib // 2  # sidecar dust
            else:
                cpu, mem = 4200, 3 * gib  # fat batch pods
            arrivals.append((f"arr-{serial:06d}", cpu, mem))
            live.append(arrivals[-1][0])
        script.append((phase, arrivals, departs))
    return script


def drift_step(cluster, phase, arrivals, departs, now):
    """Apply one cycle's events, then refresh the load watcher's report:
    averages mirror the requested utilization, the variance term drifts
    with the phase."""
    from scheduler_plugins_tpu.api.objects import Container, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    for name in departs:
        if f"default/{name}" in cluster.pods:
            cluster.remove_pod(f"default/{name}")
    for name, cpu, mem in arrivals:
        cluster.add_pod(Pod(
            name=name, creation_ms=now,
            containers=[Container(requests={CPU: cpu, MEMORY: mem})],
        ))
    used = {name: [0, 0] for name in cluster.nodes}
    for pod in cluster.pods.values():
        if pod.node_name is not None:
            req = pod.effective_request()
            used[pod.node_name][0] += req.get(CPU, 0)
            used[pod.node_name][1] += req.get(MEMORY, 0)
    metrics = {}
    for i, (name, node) in enumerate(cluster.nodes.items()):
        noisy = phase == "b" and i >= DRIFT["hot"]
        metrics[name] = {
            "cpu_avg": min(100.0 * used[name][0] / node.allocatable[CPU],
                           100.0),
            "cpu_std": 60.0 if noisy else 3.0,
            "mem_avg": min(100.0 * used[name][1] / node.allocatable[MEMORY],
                           100.0),
            "mem_std": 8.0 if noisy else 2.0,
        }
    cluster.node_metrics = metrics


def drift_scheduler():
    from scheduler_plugins_tpu import plugins as P

    lvrb = P.LoadVariationRiskBalancing()
    lvrb.weight = 20
    return Scheduler(Profile(plugins=[P.TargetLoadPacking(), lvrb]))


def run_drift(cycles, tuner_kw=None, plan=None, inject=None):
    """One arm over the drift script; `tuner_kw` arms the flight recorder
    and a synchronous ShadowTuner. `inject` stages a known-bad vector past
    the gates once the real promotion has been confirmed. Returns the
    per-cycle bound maps, the tuner, the weights each cycle solved under
    and the cycle the injection happened at."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.utils import flightrec

    cluster, scheduler = drift_cluster(), drift_scheduler()
    tuner = None
    if tuner_kw is not None:
        flightrec.recorder.start(capacity=4)
        tuner = ShadowTuner(
            scheduler, corpus_cycles=2, sweep_every=2, tolerance=0.01,
            probation_cycles=8, baseline_window=8, baseline_min=2,
            baseline_recent=3, hysteresis=0.002, regress_cycles=2,
            cooldown_cycles=16, sync=True, seed=0, **tuner_kw,
        )
    if plan is not None:
        faults.install(plan)
    bound, trail, injected_at = [], [], None
    try:
        for c, (phase, arrivals, departs) in enumerate(drift_script(cycles)):
            now = 1000 * (c + 1)
            drift_step(cluster, phase, arrivals, departs, now)
            if plan is not None:
                plan.begin_cycle(c)
            if tuner is not None:
                st = tuner.status()
                if (
                    inject is not None and injected_at is None
                    and st["state"] == "idle"
                    and st["promotions"] > st["rollbacks"]
                    and st["active_weights"] == st["last_known_good"]
                ):
                    tuner.inject_promotion(inject)
                    injected_at = c
                tuner.begin_cycle(now_ms=now)
                trail.append(tuner.status()["active_weights"])
            report = run_cycle(scheduler, cluster, now=now)
            if tuner is not None:
                tuner.observe_report(report)
            bound.append(dict(report.bound))
            snap, _ = cluster.snapshot([], now_ms=now)
            assert (np.asarray(snap.nodes.requested)
                    <= np.asarray(snap.nodes.alloc)).all(), c
    finally:
        if plan is not None:
            faults.clear()
        if tuner is not None:
            flightrec.recorder.stop()
    return bound, tuner, trail, injected_at


class TestTunedServingEndToEnd:
    def test_ring_sweep_promotes_and_injected_regression_rolls_back(self):
        bound, tuner, trail, injected_at = run_drift(
            27, tuner_kw=dict(candidates=8, confirm_sweeps=2),
            inject=(1, 64),
        )
        st = tuner.status()
        # a gated promotion out of sweeps over real ring records, after
        # the drift, confirmed through probation before the injection
        promoted = next(w for w in trail if w != [1, 20])
        assert promoted != [1, 64]
        assert DRIFT["drift_at"] <= trail.index(promoted) < injected_at
        assert st["sweeps"] >= 2 and st["sweep_failures"] == 0
        # the injected vector went live, was caught on probation within
        # two cycles of being detectable, and the confirmed weights rule
        applied = trail.index([1, 64])
        assert applied >= injected_at
        assert st["promotions"] == 2 and st["rollbacks"] == 1
        assert st["last_rollback_reason"].startswith("quality-regression")
        assert st["last_rollback_detect_cycles"] <= 2
        assert trail.count([1, 64]) <= 1 + st["last_rollback_detect_cycles"]
        # no flapping: the confirmed weights rule to the end of the run
        assert all(w == promoted for w in trail[applied + 3:])
        assert st["state"] == "cooldown"
        assert st["active_weights"] == st["last_known_good"] == promoted
        assert all(bound)  # every cycle placed pods

    def test_tuner_faults_never_reach_live_placements(self):
        cycles = 14
        control, _t, _trail, _i = run_drift(cycles)
        plan = faults.FaultPlan(seed=5)
        plan.specs = [
            # garbage sweep output: the numpy oracles must disqualify
            # every corrupted lane
            faults.FaultSpec(site=faults.TUNE_SWEEP, cycle=5,
                             kind="garbage", sticky=True),
        ] + [
            # every promotion application crashes: nothing the sweeps
            # stage may ever reach the live weights
            faults.FaultSpec(site=faults.TUNE_PROMOTE, cycle=c,
                             kind="crash")
            for c in range(cycles)
        ]
        chaos, tuner, trail, _i = run_drift(
            cycles, tuner_kw=dict(candidates=8, confirm_sweeps=1),
            plan=plan,
        )
        assert chaos == control  # bit-identical, cycle for cycle
        st = tuner.status()
        fired = {(site, kind) for _c, site, kind in plan.log}
        assert (faults.TUNE_SWEEP, "garbage") in fired
        assert (faults.TUNE_PROMOTE, "crash") in fired
        assert st["promotions"] == 0 and st["sweep_failures"] >= 1
        assert all(w == [1, 20] for w in trail)
        assert st["last_known_good"] == [1, 20]
        assert st["state"] in ("idle", "cooldown", "disabled")
