"""Packing solve mode tests (ISSUE 14; docs/PACKING.md).

- differential: the jitted `ops.packing.packing_refine` vs its numpy
  twin, bit-exact on assignment AND free across iteration budgets and
  temperature schedules (knobs ride the traced pack_aux vector, so the
  whole matrix shares ONE compile);
- wave-parity anchor: budget 0 == `batch_solve` placements bit-exactly;
- hard constraints: the `tuning.gates` replay oracles stay clean at
  every budget (fit/mask/quota/gang-quorum);
- config surface: solveMode/packingConfig round-trip through
  `api.config`, invalid modes/args/profiles rejected;
- cycle wiring: a packing-mode profile solves through `run_cycle`
  (binds land, quality stamped, the flight recorder labels the outputs
  "packing");
- recorder: GangPhase elastic desired-width transitions land on the
  manifest (ROADMAP item 3's recorder slice).

Compile budget: every jit entry here runs at ONE shared problem shape
(the module-scope fixture), and the budget/temperature matrix varies
only traced arguments.
"""

import numpy as np
import pytest

from scheduler_plugins_tpu.api.config import load_profile, profile_spec
from scheduler_plugins_tpu.framework import (
    PackingConfig,
    Profile,
    Scheduler,
    run_cycle,
)
from scheduler_plugins_tpu.models.problems import packing_problem
from scheduler_plugins_tpu.ops.packing import (
    pack_aux_vector,
    packing_refine,
    packing_refine_np,
)
from scheduler_plugins_tpu.parallel.solver import (
    PackingSolveView,
    batch_admission,
    batch_solve,
    packing_solve,
)
from scheduler_plugins_tpu.tuning.gates import hard_violations

#: the one problem shape every jit entry in this module runs at
_SHAPE = dict(n_nodes=24, demand_frac=0.85, empty_frac=0.15, seed=0)


@pytest.fixture(scope="module")
def problem():
    cluster, snap, meta, weights = packing_problem(**_SHAPE)
    return cluster, snap, meta, weights


@pytest.fixture(scope="module")
def wave_inputs(problem):
    """The refinement's inputs: the wave placement + its free carry, plus
    the static ranking — staged exactly as `packing_solve` stages them."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.ops.allocatable import (
        MODE_LEAST,
        allocatable_scores,
        demote_scores_int32,
    )
    from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted
    from scheduler_plugins_tpu.ops.fit import free_capacity

    _, snap, _, weights = problem
    free0 = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    admitted = batch_admission(snap, free0)
    raw = demote_scores_int32(
        allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
    ).astype(jnp.int64)
    solve_free0 = jnp.where(snap.nodes.mask[:, None], free0, 0)
    a_w, f_w = waterfill_assign_targeted(
        raw, snap.pods.req, admitted, solve_free0
    )
    return snap, raw, admitted, a_w, f_w


def _jit_refine():
    """ONE jitted refine wrapper for the whole knob matrix — jax.jit
    caches per wrapper object, so a per-case lambda would recompile 6×
    and defeat the traced-knob compile sharing this module documents."""
    import jax

    global _JIT_REFINE
    if _JIT_REFINE is None:
        _JIT_REFINE = jax.jit(lambda *xs: packing_refine(*xs, mover_cap=32))
    return _JIT_REFINE


_JIT_REFINE = None


class TestPackingDifferential:
    """jit == numpy twin, bit-exact, across the knob matrix (one
    compile: knobs are traced and every case shares `_jit_refine`)."""

    @pytest.mark.parametrize("budget,price,temp,decay", [
        (0, 4.0, 0.0, 0.5),
        (1, 4.0, 0.0, 0.5),
        (6, 4.0, 0.0, 0.5),
        (17, 4.0, 0.25, 0.5),
        (40, 0.0, 0.0, 1.0),
        (40, 8.0, 0.1, 0.9),
        # fractional budget: both builds must FLOOR (a continuous tuner
        # proposal runs the same round count on the jax and numpy sides)
        (2.5, 4.0, 0.0, 0.5),
    ])
    def test_refine_twin_bit_parity(self, wave_inputs, budget, price,
                                    temp, decay):
        snap, raw, admitted, a_w, f_w = wave_inputs
        aux = pack_aux_vector(budget, price, temp, decay)
        aj, fj, sj = _jit_refine()(
            raw, snap.pods.req, admitted, snap.nodes.alloc,
            snap.nodes.mask, f_w, a_w, aux,
        )
        an, fn, sn = packing_refine_np(
            raw, snap.pods.req, admitted, snap.nodes.alloc,
            snap.nodes.mask, f_w, a_w, aux, mover_cap=32,
        )
        assert (np.asarray(aj) == an).all()
        assert (np.asarray(fj) == fn).all()
        for k in ("rounds", "moves", "emptied"):
            assert int(sj[k]) == int(sn[k]), k

    def test_budget_zero_is_identity(self, wave_inputs):
        snap, raw, admitted, a_w, f_w = wave_inputs
        an, fn, sn = packing_refine_np(
            raw, snap.pods.req, admitted, snap.nodes.alloc,
            snap.nodes.mask, f_w, a_w, pack_aux_vector(0, 4.0, 0.0, 0.5),
        )
        assert (an == np.asarray(a_w)).all()
        assert (fn == np.asarray(f_w)).all()
        assert sn["moves"] == 0


class TestPackingSolve:
    def test_budget_zero_bit_matches_wave_path(self, problem):
        _, snap, _, weights = problem
        a_ref, adm_ref, w_ref = batch_solve(snap, weights)
        a0, adm0, w0 = packing_solve(
            snap, weights, pack_aux_vector(0, 4.0, 0.0, 0.5)
        )
        assert (np.asarray(a0) == np.asarray(a_ref)).all()
        assert (np.asarray(adm0) == np.asarray(adm_ref)).all()
        assert (np.asarray(w0) == np.asarray(w_ref)).all()

    def test_oracles_clean_and_placed_set_preserved(self, problem):
        _, snap, _, weights = problem
        a_w, _, wait_w = batch_solve(snap, weights)
        for budget in (4, 24):
            a, _, wait = packing_solve(
                snap, weights, pack_aux_vector(budget, 4.0, 0.0, 0.5)
            )
            a, wait = np.asarray(a), np.asarray(wait)
            verdict = hard_violations(snap, a, wait)
            assert verdict["total"] == 0, verdict
            # refinement moves placements, never unplaces them
            assert ((a >= 0) == (np.asarray(a_w) >= 0)).all()

    def test_refinement_improves_packing_objectives(self, problem):
        from scheduler_plugins_tpu.tuning import quality as Q

        _, snap, _, weights = problem
        a_w, _, wait_w = batch_solve(snap, weights)
        a_p, _, wait_p = packing_solve(
            snap, weights, pack_aux_vector(24, 4.0, 0.0, 0.5)
        )
        qw = Q.cycle_quality(snap, np.asarray(a_w), None, np.asarray(wait_w))
        qp = Q.cycle_quality(snap, np.asarray(a_p), None, np.asarray(wait_p))
        assert qp["packed_utilization"] > qw["packed_utilization"]
        assert qp["fragmentation"] <= qw["fragmentation"]
        # what consolidation costs on the wave path's own objective (the
        # static allocatable scores) stays inside the documented bound
        from scheduler_plugins_tpu.ops.allocatable import (
            MODE_LEAST,
            allocatable_scores,
        )

        scores = np.asarray(
            allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
        )
        drift = Q.score_drift(
            np.broadcast_to(scores, (snap.num_pods, scores.shape[0])),
            np.asarray(a_p), np.asarray(a_w),
        )
        assert abs(drift) <= 0.15, drift


class TestPackingConfigSurface:
    def _packing_spec(self):
        return {
            "profileName": "pack",
            "plugins": ["NodeResourcesAllocatable"],
            "solveMode": "packing",
            "packingConfig": {"iterations": 12, "priceWeight": 2.5,
                              "temperature": 0.1, "decay": 0.75,
                              "moverCap": 64},
        }

    def test_round_trip(self):
        profile = load_profile(self._packing_spec())
        assert profile.solve_mode == "packing"
        assert profile.packing.iterations == 12
        assert profile.packing.price_weight == 2.5
        assert profile.packing.mover_cap == 64
        spec = profile_spec(profile)
        assert spec["solveMode"] == "packing"
        assert spec["packingConfig"] == self._packing_spec()["packingConfig"]
        again = load_profile(spec)
        assert again.solve_mode == "packing"
        assert again.packing == profile.packing

    def test_sequential_default_not_exported(self):
        profile = load_profile({"plugins": ["NodeResourcesAllocatable"]})
        assert profile.solve_mode == "sequential"
        spec = profile_spec(profile)
        assert "solveMode" not in spec
        assert "packingConfig" not in spec

    def test_unknown_mode_and_args_rejected(self):
        with pytest.raises(ValueError, match="solveMode"):
            load_profile({"plugins": ["NodeResourcesAllocatable"],
                          "solveMode": "annealing"})
        with pytest.raises(ValueError, match="packingConfig"):
            load_profile({"plugins": ["NodeResourcesAllocatable"],
                          "solveMode": "packing",
                          "packingConfig": {"budget": 3}})
        with pytest.raises(ValueError):
            PackingConfig(decay=0.0)
        with pytest.raises(ValueError):
            PackingConfig(iterations=-1)
        with pytest.raises(ValueError, match="integral"):
            PackingConfig(iterations=1.5)

    def test_non_fast_path_profile_rejected(self):
        # TaintToleration adds a Filter: the packing gate must refuse
        with pytest.raises(ValueError, match="packing"):
            load_profile({
                "plugins": ["NodeResourcesAllocatable", "TaintToleration"],
                "solveMode": "packing",
            })

    def test_scheduler_solve_rejects_auxes_under_packing(self, problem):
        _, snap, _, _ = problem
        profile = load_profile(self._packing_spec())
        sched = Scheduler(profile)
        with pytest.raises(ValueError, match="sequential"):
            sched.solve(snap, auxes=(None,))
        # a caller-prepared carry gets the same rejection, never a
        # silent drop (the packing solve builds its own initial state)
        with pytest.raises(ValueError, match="sequential"):
            sched.solve(snap, state0=sched.initial_state(snap))


class TestPackingCycle:
    def _cluster(self):
        cluster, _, _, _ = packing_problem(**_SHAPE)
        return cluster

    def test_run_cycle_with_packing_profile(self):
        from scheduler_plugins_tpu.utils import flightrec

        cluster = self._cluster()
        profile = load_profile({
            "profileName": "pack",
            "plugins": ["NodeResourcesAllocatable"],
            "solveMode": "packing",
            "packingConfig": {"iterations": 8},
        })
        flightrec.recorder.start(capacity=4)
        try:
            report = run_cycle(Scheduler(profile), cluster, now=1000)
        finally:
            rec = flightrec.recorder.records()[-1]
            flightrec.recorder.stop()
        assert report.bound, "packing cycle bound nothing"
        assert report.quality is not None
        assert "packed_utilization" in report.quality
        # the recorder labels packing outputs as such — replay treats
        # them as evidence, never as sequential-parity anchors
        assert rec.manifest["outputs"]["mode"] == "packing"
        assert rec.manifest["profile_config"]["solveMode"] == "packing"

    def test_packing_cycle_places_like_direct_solve(self):
        """The cycle's bind stage commits exactly the packing solve's
        placements (the dispatch seam does not reroute silently)."""
        cluster = self._cluster()
        profile = load_profile({
            "profileName": "pack",
            "plugins": ["NodeResourcesAllocatable"],
            "solveMode": "packing",
            "packingConfig": {"iterations": 8},
        })
        sched = Scheduler(profile)
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        view = sched.solve(snap)
        assert isinstance(view, PackingSolveView)
        assert view.stats["rounds"] >= 1
        report = run_cycle(sched, self._cluster(), now=1000)
        a = np.asarray(view.assignment)
        expected = {
            pending[i].uid: meta.node_names[int(a[i])]
            for i in range(len(pending)) if a[i] >= 0
        }
        assert report.bound == expected


class TestElasticTransitionRecording:
    """GangPhase records PodGroup desired-width transitions on the
    flight-recorder manifest (pure recorder schema — ROADMAP item 3's
    corpus slice for counterfactual block-policy sweeps)."""

    def test_desired_width_transitions_recorded(self):
        from scheduler_plugins_tpu.gangs.phase import GangPhase
        from scheduler_plugins_tpu.models import rank_gang_scenario
        from scheduler_plugins_tpu.utils import flightrec

        cluster = rank_gang_scenario(
            n_nodes=16, n_regions=2, zones_per_region=2, n_mpi=1,
            mpi_ranks=4, n_dl=1, dl_min=2, dl_desired=3, dl_max=4,
        )
        phase = GangPhase(host_twin=True)
        profile = Profile(plugins=[])
        sched = Scheduler(profile)
        flightrec.recorder.start(capacity=8)
        try:
            run_cycle(sched, cluster, now=1000, gangs=phase)
            rec0 = flightrec.recorder.records()[-1]
            # first sighting: every rank gang records its initial width
            t0 = rec0.manifest.get("elastic_transitions")
            assert t0, "initial widths not recorded"
            by_gang = {t["gang"]: t for t in t0}
            dl = next(
                pg for pg in cluster.pod_groups.values()
                if getattr(pg, "max_replicas", None)
            )
            assert by_gang[dl.full_name]["from"] is None
            assert by_gang[dl.full_name]["to"] == dl.desired_replicas

            # width change: recorded as a from -> to transition
            prev = dl.desired_replicas
            dl.desired_replicas = prev + 1
            run_cycle(sched, cluster, now=2000, gangs=phase)
            rec1 = flightrec.recorder.records()[-1]
            t1 = rec1.manifest.get("elastic_transitions")
            assert t1 == [{
                "gang": dl.full_name, "from": prev, "to": prev + 1,
                "min": dl.min_member, "max": dl.max_replicas,
            }]

            # steady state: no transitions key at all
            run_cycle(sched, cluster, now=3000, gangs=phase)
            rec2 = flightrec.recorder.records()[-1]
            assert "elastic_transitions" not in rec2.manifest
        finally:
            flightrec.recorder.stop()
