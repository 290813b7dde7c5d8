"""Online self-tuning shadow lane with guarded rollout (ROADMAP item 2).

PR 8's counterfactual tuner closes the scoring loop OFFLINE: record a
corpus, sweep candidate weight vectors, emit a gated profile. "Learning
to Score" (arxiv 2603.10545) and the RL scheduler paper (arxiv
2601.13579) both argue the loop must close *online* — and closing it
safely is a robustness problem, not a perf one. `ShadowTuner` is that
closure, built so the live serving path can never be stalled, corrupted,
or silently regressed by its own tuner:

- **Shadow lane, off the cycle thread.** Every `sweep_every` cycles the
  tuner snapshots the last N COMPLETE flight-recorder ring records (the
  PR 5 capture at the Snapshot boundary) and replays them under K
  candidate weight vectors through the existing vmapped
  `parallel.solver.sweep_solve_fn` — on a dedicated daemon worker
  thread, against a SHADOW scheduler rebuilt from the records' own
  profile capture (`flightrec.rebuild_scheduler`), never the live one
  (tracing against the live plugins from a second thread would race the
  cycle's bind state). The in-flight job is deadlined (the PR 9
  watchdog-abandonment pattern): a hung sweep is orphaned and counted,
  and the lane degrades to "no tuning" — a tick is never stalled.
- **Promotion only through the gates.** A candidate is staged for
  promotion only when the shared promotion-gate body
  (`tuning.promotion` — the SAME code `tools/tune.py` emits offline
  profiles through) accepts it: zero hard-constraint violations across
  the whole corpus replay (numpy fit/mask/quota/gang-quorum oracles),
  no objective sold beyond tolerance, a strictly positive rank score —
  AND the same winner must repeat for `confirm_sweeps` consecutive
  sweeps (a sustained win, not one lucky corpus).
- **Rollout through the aux channel.** The swap applies at the cycle
  boundary (`framework.cycle.run_cycle(tuner=...)` calls `begin_cycle`
  before anything reads the profile) via
  `Scheduler.set_live_weights` — the weight vector is a traced argument
  of the "solve_live" program (`Plugin.bind_weight`), so promotion and
  rollback are argument changes with ZERO recompiles: the whole point
  of the aux-channel discipline.
- **Probation + auto-rollback.** Every promotion opens a probation
  window adjudicated by a PAIRED COUNTERFACTUAL PROBE: each probation
  cycle's ring record is replayed under [active, last-known-good] in
  one deadlined 2-lane sweep and the `scheduler_placement_quality`
  objectives are compared ON THE SAME SNAPSHOT — the cumulative gauges
  ride the workload's own common-mode trend, and only a paired
  same-cycle comparison isolates what the promotion changed (the PR 9
  probation-probe pattern, pointed at weights instead of backends; a
  level-vs-recent-baseline comparison is the fallback when no record
  exists). Any objective regressing beyond the `hysteresis` band —
  a large single-cycle regression immediately, a sustained one after
  `regress_cycles` consecutive cycles — or ANY watchdog fault
  (degraded flag / host-path solve / unadjudicable probe) rolls back
  to the last-known-good weights within <= `regress_cycles` (default
  2) cycles of the regression appearing. Rolled-back vectors are
  blocked from re-promotion and a cooldown window follows, so the
  controller cannot flap.
- **Self-disable.** `max_failures` consecutive sweep/promotion faults
  disable the lane entirely (state "disabled",
  `scheduler_tuner_state` = 3): a sick tuner turns itself off and live
  serving continues exactly as if `--tune` had never been passed.

Chaos sites `tune.sweep` (hang / garbage) and `tune.promote` (crash)
instrument the seams (`resilience.faults`);
tests/test_shadow_tuner.py::TestTunedServingEndToEnd proves every injected
tuner fault leaves live placements bit-identical to a no-tuner control,
and drives the whole lane (ring records, sweeps, gated promotion,
injected-regression rollback) on a micro drifting mix.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

from scheduler_plugins_tpu.resilience import faults
from scheduler_plugins_tpu.tuning import promotion
from scheduler_plugins_tpu.utils import flightrec, observability as obs

#: the per-cycle quality objectives the probation window compares (the
#: subset of `promotion.RANKED_OBJECTIVES` that `run_cycle` stamps every
#: cycle — drift needs a replay anchor and is a sweep-time objective)
PROBATION_OBJECTIVES = (
    "fragmentation", "util_imbalance", "gang_wait_frac", "unplaced_frac",
)

#: tuner state -> `scheduler_tuner_state` gauge value
STATE_GAUGE = {"idle": 0, "probation": 1, "cooldown": 2, "disabled": 3}

#: tuner state-file format version (bump on incompatible layout change)
STATE_FORMAT = 1


def _prepare_ring_cycle(scheduler, rec, meta) -> None:
    """Re-prepare the shadow scheduler for ONE ring record and re-bake
    that record's captured host_state (the ring twin of
    `tools/tune.py._prepare_for_cycle` — must run immediately before
    every solve/score of that cycle)."""
    scheduler.prepare(meta, None)
    for plugin, prec in zip(scheduler.profile.plugins,
                            rec.manifest["plugins"]):
        hs = prec.get("host_state")
        if hs is not None:
            plugin.restore_host_state(flightrec.unpack_pytree(hs, rec.blobs))


def ring_corpus(records, scheduler, base_weights=None):
    """`promotion.CorpusCycle` list over COMPLETE in-memory ring records
    (newest last), all sharing `scheduler` (the rebuilt shadow scheduler
    — its jit caches amortize across sweeps). A record captured under
    weights other than `base_weights` (the sweep's lane-0 incumbent —
    e.g. pre-promotion cycles still in the ring) keeps its snapshot but
    drops its anchor: the incumbent lane legitimately places differently
    from what was recorded, so the anchor-mismatch disqualifier and the
    drift yardstick fall back to lane 0's own replayed placements."""
    base = (None if base_weights is None
            else tuple(int(w) for w in base_weights))
    corpus = []
    for rec in records:
        if not rec.complete or "outputs" not in rec.manifest:
            continue
        manifest = rec.manifest
        meta = flightrec.unpack_meta(manifest["meta"])
        snap = flightrec.unpack_pytree(manifest["snapshot"], rec.blobs)
        auxes = tuple(
            flightrec.unpack_pytree(p["aux"], rec.blobs)
            for p in manifest["plugins"]
        )
        out = manifest["outputs"]
        assignment = flightrec.unpack_pytree(out["assignment"], rec.blobs)
        wait_spec = out.get("wait")
        wait = (
            None if wait_spec is None
            else flightrec.unpack_pytree(wait_spec, rec.blobs)
        )
        rec_weights = tuple(
            int(p.get("weight", 1)) for p in manifest["plugins"]
        )
        anchor = (
            np.asarray(assignment)
            if base is None or rec_weights == base else None
        )
        corpus.append(promotion.CorpusCycle(
            scheduler=scheduler, snap=snap, meta=meta, auxes=auxes,
            anchor=anchor,
            wait=None if wait is None else np.asarray(wait),
            mode=out.get("mode"),
            prepare=(lambda sched, rec=rec, meta=meta:
                     _prepare_ring_cycle(sched, rec, meta)),
        ))
    return corpus


class _SweepWorker:
    """Persistent single daemon worker (the `resilience.watchdog._Worker`
    shape, non-blocking consumer side): jobs are polled, not awaited, so
    the cycle thread never blocks on the shadow lane; a job that outlives
    its deadline is ABANDONED with its worker (daemon thread — it can
    idle in a hung backend call forever without blocking process exit)."""

    def __init__(self):
        import queue

        self._jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="shadow-tuner"
        )
        self._thread.start()

    def _loop(self):
        while True:
            fn, box, done = self._jobs.get()
            try:
                box["value"] = fn()
            except BaseException as exc:  # noqa: BLE001 - polled by owner
                box["error"] = exc
            finally:
                done.set()

    def submit(self, fn):
        box: dict = {}
        done = threading.Event()
        self._jobs.put((fn, box, done))
        return box, done


class ShadowTuner:
    """The guarded-rollout controller (module docstring has the design).

    Cycle-thread API (wired by `run_cycle(tuner=...)` / the daemon):

    - `begin_cycle(now_ms)` — the ONLY point weights may change: polls
      the shadow worker, applies a staged promotion or a decided
      rollback, dispatches the next sweep.
    - `observe_report(report)` — feeds the probation window from the
      cycle's quality stamp; decides rollbacks.
    - `note_fault(reason)` — immediate rollback while on probation (the
      daemon's watchdog seam; `observe_report` also reads the report's
      degraded/host-path flags).

    `sync=True` runs each sweep inline through
    `resilience.call_with_deadline` instead of the polled worker —
    deterministic for benches/tests; the deadline (and the degrade-to-
    no-tuning contract) is identical. `observe_only=True` keeps the full
    shadow lane running but never stages a promotion — the overhead
    measurement mode, and a standing proof the lane alone cannot change
    live placements."""

    def __init__(self, scheduler, recorder=None, *, candidates: int = 24,
                 corpus_cycles: int = 3, sweep_every: int = 4,
                 confirm_sweeps: int = 2, tolerance: float = 0.01,
                 drift_tolerance: float = 0.10,
                 probation_cycles: int = 6, baseline_window: int = 8,
                 baseline_min: int = 2, baseline_recent: int = 4,
                 hysteresis: float = 0.01,
                 regress_cycles: int = 2, max_failures: int = 3,
                 cooldown_cycles: int = 8, deadline_s: Optional[float] = None,
                 observe_only: bool = False, sync: bool = False,
                 seed: int = 0):
        from collections import deque

        if getattr(scheduler.profile, "solve_mode", "sequential") != (
            "sequential"
        ):
            # fail at construction, not at the first promotion: the live
            # rollout seam is the sequential parity path's traced-weight
            # argument — a packing-mode profile would accept a gated
            # promotion and then raise on every subsequent solve
            raise ValueError(
                f"online tuning requires the sequential parity path; "
                f"profile {scheduler.profile.name!r} selects solve mode "
                f"{scheduler.profile.solve_mode!r}"
            )
        self.scheduler = scheduler
        self.recorder = recorder if recorder is not None else flightrec.recorder
        self.candidates = max(2, int(candidates))
        self.corpus_cycles = max(1, int(corpus_cycles))
        self.sweep_every = max(1, int(sweep_every))
        self.confirm_sweeps = max(1, int(confirm_sweeps))
        self.tolerance = float(tolerance)
        #: drift (score-sum vs the incumbent surface) stays a
        #: disqualification RAIL but gets its own, looser tolerance and
        #: no rank-sum vote: over a drifting workload the incumbent's
        #: score surface is exactly what goes stale, and ranking on
        #: drift-vs-incumbent would veto every adaptation (see
        #: `promotion.rank_candidates`)
        self.drift_tolerance = float(drift_tolerance)
        self.probation_cycles = max(1, int(probation_cycles))
        self.baseline_min = max(1, int(baseline_min))
        self.baseline_recent = max(1, int(baseline_recent))
        self.hysteresis = float(hysteresis)
        self.regress_cycles = max(1, int(regress_cycles))
        self.max_failures = max(1, int(max_failures))
        self.cooldown_cycles = max(0, int(cooldown_cycles))
        if deadline_s is None:
            deadline_s = float(os.environ.get("SPT_TUNE_TIMEOUT_S", 30.0))
        self.deadline_s = deadline_s
        self.observe_only = bool(observe_only)
        self.sync = bool(sync)
        self.seed = int(seed)

        #: the weights currently live (== scheduler's view); promotions
        #: move it, rollbacks restore `last_known_good`
        self.active = np.asarray(
            [int(p.weight) for p in scheduler.profile.plugins], np.int64
        )
        self.last_known_good = self.active.copy()
        self.state = "idle"
        self.disabled_reason: Optional[str] = None
        self.cycle = 0
        self.promotions = 0
        self.rollbacks = 0
        self.sweeps = 0
        self.sweep_failures = 0
        self.last_rollback_reason: Optional[str] = None
        self.last_promotion_cycle: Optional[int] = None
        self.last_rollback_cycle: Optional[int] = None
        #: weight tuples rolled back on probation — never re-promoted
        self.blocked: set = set()
        self._lock = threading.Lock()
        self._window: "deque" = deque(maxlen=max(2, int(baseline_window)))
        self._baseline: Optional[dict] = None
        self._probation_elapsed = 0
        self._regress_counts: dict = {}
        self._cooldown_until = -1
        self._consecutive_failures = 0
        self._first_regress_cycle = None
        self.last_rollback_detect_cycles = None
        self._pending: Optional[dict] = None
        self._last_winner: Optional[tuple] = None
        self._winner_streak = 0
        self._sweep_seq = 0
        self._worker: Optional[_SweepWorker] = None
        self._inflight: Optional[dict] = None
        #: shadow scheduler cache (one rebuild per profile identity — its
        #: jit caches amortize the sweep program across jobs). Guarded by
        #: its own lock, NOT self._lock: the sweep worker, the deadlined
        #: counterfactual probe, and main-thread invalidation all touch
        #: it, and the rebuild trace it serializes is too slow to hold
        #: the controller lock across. Order: _lock may nest _shadow_lock,
        #: never the reverse.
        self._shadow_lock = threading.Lock()
        self._shadow_key = None
        self._shadow_sched = None
        self._export_gauges()

    # -- gauges ----------------------------------------------------------
    def _export_gauges(self) -> None:
        obs.metrics.set_gauge(obs.TUNER_STATE, STATE_GAUGE[self.state])
        digest = promotion.weights_digest(self.active)
        obs.metrics.set_gauge(
            obs.TUNER_ACTIVE_WEIGHTS, int(digest, 16)
        )

    # -- the cycle-boundary hook (weight-swap seam) ----------------------
    def begin_cycle(self, now_ms: int = 0) -> None:
        """Runs on the cycle thread BEFORE the cycle reads the profile:
        the one safe point to swap weights. Never raises — a tuner fault
        must cost tuning, not the tick."""
        with self._lock:
            self.cycle += 1
            if self.state == "disabled":
                return
            self._poll_inflight_locked()
            if self.state == "cooldown" and self.cycle >= self._cooldown_until:
                self.state = "idle"
            if (
                self._pending is not None
                and self.state in ("idle", "cooldown")
                # never start probation while a sweep job is still in
                # flight: the probation probe and the job would share
                # the shadow scheduler from two threads
                and self._inflight is None
            ):
                self._apply_pending_locked()
            self._maybe_dispatch_locked()
            self._export_gauges()

    def observe_report(self, report) -> None:
        """Runs on the cycle thread after finalize: probation evidence.
        A cycle with no solve (no quality stamp) contributes nothing —
        probation advances only on observed cycles."""
        with self._lock:
            if self.state == "disabled":
                return
            degraded = bool(getattr(report, "degraded", False)) or (
                getattr(report, "solve_path", None) == "host"
            )
            if self.state == "probation" and degraded:
                # ANY watchdog fault during probation rolls back
                # immediately: a degraded cycle's quality is evidence of
                # nothing, and new weights must never ride out an
                # incident window unobserved
                self._rollback_locked(
                    "watchdog-fault:"
                    + (getattr(report, "solve_path", None) or "degraded")
                )
                return
            quality = getattr(report, "quality", None)
            if quality is None:
                return
            q = {
                name: float(quality[name])
                for name in PROBATION_OBJECTIVES if name in quality
            }
            if not q:
                return
            if self.state != "probation":
                self._window.append(q)
                return
            self._probation_elapsed += 1
        # the counterfactual probe runs OUTSIDE the lock: it is deadlined
        # at `deadline_s` and pays the 2-lane sweep compile once per pod
        # bucket — /healthz `status()` and the SIGTERM `state_dict()`
        # must stay responsive meanwhile. All state MUTATION happens on
        # this (cycle) thread, so only readers and `note_fault` can
        # interleave; the verdict is re-checked under the lock.
        deltas = self._probation_deltas(q)
        with self._lock:
            if self.state != "probation":
                return  # note_fault rolled back while the probe ran
            if deltas is None:
                # the counterfactual probe could not run (hung, errored):
                # an UNVERIFIABLE probation cycle is a watchdog fault —
                # new weights must not ride out a window the controller
                # cannot adjudicate. A timed-out probe also leaves a
                # zombie worker holding the cached shadow scheduler —
                # drop the cache so later sweeps rebuild fresh
                with self._shadow_lock:
                    self._shadow_sched = None
                    self._shadow_key = None
                self._rollback_locked("watchdog-fault:probe-unavailable")
                return
            for name, delta in deltas.items():
                # sense-adjusted delta: negative = worse than the
                # last-known-good counterfactual (or, on the fallback
                # path, the recent pre-promotion baseline). Two-trigger
                # detector, both gated by the `hysteresis` amplitude
                # band so sub-threshold noise can never fire (the
                # no-flap contract): a LARGE single-cycle regression
                # (>= regress_cycles * hysteresis) rolls back
                # immediately; a SUSTAINED one (beyond hysteresis for
                # regress_cycles consecutive cycles) rolls back within
                # the window — so any real regression is out within
                # regress_cycles (default 2) cycles of appearing
                if delta < -self.hysteresis:
                    if self._first_regress_cycle is None:
                        self._first_regress_cycle = self.cycle
                    self._regress_counts[name] = (
                        self._regress_counts.get(name, 0) + 1
                    )
                else:
                    self._regress_counts[name] = 0
                if (
                    delta < -(self.hysteresis * self.regress_cycles)
                    or self._regress_counts[name] >= self.regress_cycles
                ):
                    self._rollback_locked(f"quality-regression:{name}")
                    return
            if self._probation_elapsed >= self.probation_cycles:
                self._confirm_locked()

    def _probation_deltas(self, q: dict) -> Optional[dict]:
        """Per-objective sense-adjusted deltas for one probation cycle,
        positive = the promoted weights are doing fine.

        Primary instrument: the PAIRED COUNTERFACTUAL PROBE — replay the
        cycle that JUST finalized (its ring record) under [active,
        last-known-good] in one 2-lane sweep and compare placement
        quality ON THE SAME SNAPSHOT. The per-cycle quality gauges are
        cumulative cluster-state reductions that ride the workload's own
        common-mode trend (a drifting mix makes them rise and fall for
        reasons no weight vector controls); a paired same-cycle
        comparison cancels the trend exactly, so the regression decision
        measures only what the promotion changed — the PR 9 probation-
        probe pattern, pointed at weights instead of backends. The probe
        is deadlined; a hung/errored probe returns None and the caller
        treats the cycle as a watchdog fault.

        Fallback (recorder has no usable record of this cycle): the
        sense-adjusted level vs the recent pre-promotion baseline."""
        from scheduler_plugins_tpu.tuning.quality import SENSE

        probe = None
        try:
            from scheduler_plugins_tpu.resilience.watchdog import (
                call_with_deadline,
            )

            probe = call_with_deadline(
                self._counterfactual_pair, self.deadline_s,
                label="tune.probe",
            )
        except Exception:  # noqa: BLE001 - adjudicated by the caller
            return None
        if probe is not None:
            q_active, q_good = probe
            return {
                name: SENSE[name] * (q_active[name] - q_good[name])
                for name in PROBATION_OBJECTIVES
                if name in q_active and name in q_good
            }
        if self._baseline is None:
            return None
        return {
            name: SENSE[name] * (value - self._baseline[name])
            for name, value in q.items()
            if name in self._baseline
        }

    def _counterfactual_pair(self):
        """({objective: float} under active, same under last-known-good)
        for the newest complete ring record — one 2-lane vmapped sweep,
        or None when no record exists (fallback path adjudicates)."""
        records = [
            rec for rec in self.recorder.records()
            if rec.complete and "outputs" in rec.manifest
        ]
        if not records:
            return None
        from scheduler_plugins_tpu.tuning import quality as Q
        from scheduler_plugins_tpu.tuning import sweep as sweep_mod

        rec = records[-1]
        # paired snapshot under the controller lock: `active` and
        # `last_known_good` must come from the SAME promotion epoch —
        # this probe runs on a deadline worker while the main thread can
        # promote/rollback between two bare attribute reads, and a torn
        # pair makes the 2-lane counterfactual compare weight vectors
        # that never coexisted (race_audit CA001)
        with self._lock:
            active = np.asarray(self.active, np.int64).copy()
            good = np.asarray(self.last_known_good, np.int64).copy()
        shadow = self._shadow_scheduler(rec)
        corpus = ring_corpus([rec], shadow, base_weights=active)
        cc = corpus[0]
        cc.prepare(cc.scheduler)
        W = np.stack([active, good])
        A, _adm, wt = sweep_mod.sweep_cycle(shadow, cc.snap, W,
                                            auxes=cc.auxes)
        q = Q.batch_quality(cc.snap, A, wt)
        q_active = {name: float(v[0]) for name, v in q.items()}
        q_good = {name: float(v[1]) for name, v in q.items()}
        return q_active, q_good

    def note_fault(self, reason: Optional[str] = None) -> None:
        """External watchdog seam: a backend fault observed outside the
        report path (the daemon's resilience layer) rolls an active
        probation back immediately."""
        with self._lock:
            if self.state == "probation":
                self._rollback_locked(f"watchdog-fault:{reason or 'fault'}")

    def inject_promotion(self, weights) -> None:
        """Harness hook (bench config 14's injected-regression phase, the
        rollback decision tables): stage `weights` for promotion at the
        next cycle boundary, BYPASSING the gates. Never used by
        production wiring — the daemon has no path to it; it exists so
        the auto-rollback machinery can be demonstrated on demand."""
        with self._lock:
            self._pending = {
                "weights": tuple(int(w) for w in weights), "forced": True,
            }

    # -- promotion / rollback (all under self._lock) ---------------------
    def _apply_pending_locked(self) -> None:
        pending, self._pending = self._pending, None
        if self.observe_only and not pending.get("forced"):
            return
        if self._baseline_snapshot() is None:
            # no pre-promotion baseline yet: without one the probation
            # window could not detect a regression — re-stage and wait
            self._pending = pending
            return
        weights = np.asarray(pending["weights"], np.int64)
        prev = self.active.copy()
        spec = None
        if faults.ACTIVE is not None:
            spec = faults.ACTIVE.fire(faults.TUNE_PROMOTE)
        try:
            if spec is not None and spec.kind == "crash":
                raise RuntimeError("injected promotion crash (tune.promote)")
            self.scheduler.set_live_weights(weights)
        except Exception as exc:
            # the promotion died mid-apply: restore the incumbent
            # defensively (set_live_weights may or may not have landed),
            # count the fault, and keep serving — live placements are
            # untouched either way
            try:
                self.scheduler.set_live_weights(prev)
            except Exception as restore_exc:  # graft-lint: ignore[GL010] — best-effort incumbent restore inside the fault handler below, which already counts/logs/disables; `prev` was valid moments ago so this cannot realistically fail
                obs.logger.warning(
                    "tuner incumbent restore failed too: %s", restore_exc
                )
            self.sweep_failures += 1
            obs.metrics.inc(obs.TUNER_SWEEP_FAILURES)
            self._consecutive_failures += 1
            obs.logger.warning("tuner promotion failed (%s): incumbent "
                               "weights kept", exc)
            self._maybe_disable_locked(f"promote-crash: {exc}")
            return
        self.active = weights
        self.promotions += 1
        obs.metrics.inc(obs.TUNER_PROMOTIONS)
        self.last_promotion_cycle = self.cycle
        self._baseline = self._baseline_snapshot()
        self._probation_elapsed = 0
        self._regress_counts = {}
        self._first_regress_cycle = None
        self.state = "probation"
        self._winner_streak = 0
        self._last_winner = None
        obs.logger.info(
            "tuner promoted weights %s (digest %s): probation for %d "
            "cycles vs baseline %s",
            [int(w) for w in weights], promotion.weights_digest(weights),
            self.probation_cycles,
            {k: round(v, 4) for k, v in (self._baseline or {}).items()},
        )

    def _baseline_snapshot(self) -> Optional[dict]:
        if len(self._window) < self.baseline_min:
            return None
        # the MOST RECENT pre-promotion cycles only: the quality gauges
        # are cumulative cluster-state reductions that TREND under a
        # drifting workload, and a baseline averaged over the whole
        # window would sit below/above the trend — falsely rolling back
        # a good promotion (or masking a bad one) on level, not effect
        recent = list(self._window)[-self.baseline_recent:]
        names = set().union(*(q.keys() for q in recent))
        return {
            name: float(np.mean([q[name] for q in recent if name in q]))
            for name in names
        }

    def _rollback_locked(self, reason: str) -> None:
        self.blocked.add(tuple(int(w) for w in self.active))
        try:
            self.scheduler.set_live_weights(self.last_known_good)
        except Exception as exc:  # pragma: no cover - defensive
            obs.logger.warning("tuner rollback set_live_weights failed: %s",
                               exc)
        self.active = np.asarray(self.last_known_good, np.int64).copy()
        self.rollbacks += 1
        obs.metrics.inc(obs.TUNER_ROLLBACKS)
        self.last_rollback_reason = reason
        self.last_rollback_cycle = self.cycle
        #: cycles from the first above-hysteresis regression observation
        #: to this rollback — the "rollback <= regress_cycles" evidence
        #: (0 for watchdog-fault rollbacks with no quality prelude)
        self.last_rollback_detect_cycles = (
            self.cycle - self._first_regress_cycle
            if self._first_regress_cycle is not None else 0
        )
        self.state = "cooldown"
        self._cooldown_until = self.cycle + self.cooldown_cycles
        self._baseline = None
        self._probation_elapsed = 0
        self._regress_counts = {}
        self._window.clear()
        self._pending = None
        self._winner_streak = 0
        self._last_winner = None
        self._export_gauges()
        obs.logger.warning(
            "tuner ROLLBACK (%s): last-known-good weights %s restored, "
            "cooldown %d cycles",
            reason, [int(w) for w in self.active], self.cooldown_cycles,
        )

    def _confirm_locked(self) -> None:
        self.last_known_good = self.active.copy()
        self.state = "idle"
        self._baseline = None
        self._probation_elapsed = 0
        self._regress_counts = {}
        # the pre-promotion window described the OLD weights' regime:
        # restart baseline accumulation under the confirmed vector
        self._window.clear()
        obs.logger.info(
            "tuner promotion CONFIRMED: weights %s are the new "
            "last-known-good", [int(w) for w in self.active],
        )

    def _maybe_disable_locked(self, reason: str) -> None:
        if self._consecutive_failures >= self.max_failures:
            self.state = "disabled"
            self.disabled_reason = reason
            self._pending = None
            self._inflight = None
            obs.logger.warning(
                "shadow tuner DISABLED after %d consecutive faults (%s): "
                "live serving continues on the incumbent weights",
                self._consecutive_failures, reason,
            )
            self._export_gauges()

    # -- the shadow sweep lane -------------------------------------------
    def _maybe_dispatch_locked(self) -> None:
        if (
            self.state not in ("idle", "cooldown")
            or self._pending is not None
            or self.cycle % self.sweep_every != 0
        ):
            return
        if self._inflight is not None:
            return
        if not self.recorder.enabled:
            return
        records = [
            rec for rec in self.recorder.records()
            if rec.complete and "outputs" in rec.manifest
        ]
        if len(records) < self.corpus_cycles:
            return
        records = records[-self.corpus_cycles:]
        base = self.active.copy()
        self._sweep_seq += 1
        # candidate generation is seeded per INCUMBENT EPOCH, not per
        # sweep: consecutive sweeps propose the same candidate set over
        # FRESH corpora, so a `confirm_sweeps` streak measures corpus
        # stability (a sustained win), never candidate-set luck
        seq = 97 * (self.promotions + self.rollbacks)
        if self.sync:
            from scheduler_plugins_tpu.resilience.watchdog import (
                BackendUnavailable,
                call_with_deadline,
            )

            try:
                verdict_w = call_with_deadline(
                    lambda: self._sweep_job(records, base, seq),
                    self.deadline_s, label="tune.sweep",
                )
                self._consume_sweep_locked(verdict_w)
            except BackendUnavailable as exc:
                self._sweep_failed_locked(str(exc))
            except Exception as exc:  # noqa: BLE001 - lane must not raise
                self._sweep_failed_locked(f"{type(exc).__name__}: {exc}")
            return
        if self._worker is None:
            self._worker = _SweepWorker()
        box, done = self._worker.submit(
            lambda: self._sweep_job(records, base, seq)
        )
        self._inflight = {
            "box": box, "done": done, "started": time.monotonic(),
        }

    def _poll_inflight_locked(self) -> None:
        job = self._inflight
        if job is None:
            return
        if job["done"].is_set():
            self._inflight = None
            if "error" in job["box"]:
                exc = job["box"]["error"]
                self._sweep_failed_locked(f"{type(exc).__name__}: {exc}")
            else:
                self._consume_sweep_locked(job["box"]["value"])
            return
        if time.monotonic() - job["started"] > self.deadline_s:
            # hung sweep: abandon the worker (it cannot be interrupted
            # inside a backend call; daemon thread, result discarded) —
            # the lane degrades to "no tuning", the tick is unaffected
            self._inflight = None
            self._worker = None
            self._sweep_failed_locked(
                f"timeout ({self.deadline_s}s) in tune.sweep"
            )

    def _sweep_failed_locked(self, reason: str) -> None:
        self.sweep_failures += 1
        obs.metrics.inc(obs.TUNER_SWEEP_FAILURES)
        self._consecutive_failures += 1
        # drop the cached shadow scheduler: an ABANDONED (timed-out) job
        # keeps running on its worker and still holds this object — the
        # next sweep/probe must rebuild a fresh one rather than race the
        # zombie's plugin host-state mutations (a shared scheduler under
        # two threads could produce feasible-but-wrong candidates that
        # PASS the gates). Costs one rebuild + retrace after a failure.
        with self._shadow_lock:
            self._shadow_sched = None
            self._shadow_key = None
        obs.logger.warning("shadow sweep failed (%s): no tuning this round",
                           reason)
        self._maybe_disable_locked(reason)

    def _consume_sweep_locked(self, result) -> None:
        self.sweeps += 1
        obs.metrics.inc(obs.TUNER_SWEEPS)
        self._consecutive_failures = 0
        verdict, W = result
        if not verdict.accepted or self.observe_only:
            self._winner_streak = 0
            self._last_winner = None
            return
        winner = None
        W = np.asarray(W)
        for k in verdict.order:
            k = int(k)
            if (
                k == 0 or not np.isfinite(verdict.score[k])
                or verdict.score[k] <= 0 or verdict.violations[k] > 0
            ):
                break  # order is best-first: nothing promotable remains
            cand = tuple(int(w) for w in W[k])
            if cand not in self.blocked:
                winner = cand
                break
        if winner is None:
            self._winner_streak = 0
            self._last_winner = None
            return
        if winner == self._last_winner:
            self._winner_streak += 1
        else:
            self._last_winner = winner
            self._winner_streak = 1
        # sustained win: the same vector must survive `confirm_sweeps`
        # independent corpus evaluations before it may touch live serving
        if self._winner_streak >= self.confirm_sweeps:
            self._pending = {"weights": winner, "forced": False}

    def _sweep_job(self, records, base, seq):
        """Runs OFF the cycle thread (or deadlined inline under `sync`):
        rebuild/reuse the shadow scheduler, sweep the ring corpus under
        the candidate matrix, gate through `tuning.promotion`. The
        TUNE_SWEEP chaos site instruments exactly this seam."""
        spec = None
        if faults.ACTIVE is not None:
            spec = faults.ACTIVE.fire(faults.TUNE_SWEEP)
        if spec is not None and spec.kind == "hang":
            time.sleep(spec.seconds)
        shadow = self._shadow_scheduler(records[0])
        # the drift yardstick is the INCUMBENT's objective: score the
        # corpus with the live weight vector, not the recorded one
        for plugin, w in zip(shadow.profile.plugins, base):
            plugin.weight = int(w)
        corpus = ring_corpus(records, shadow, base_weights=base)
        from scheduler_plugins_tpu.tuning import sweep as sweep_mod

        W = sweep_mod.candidate_weights(
            base, self.candidates, seed=self.seed + seq
        )
        mutate = None
        if spec is not None and spec.kind == "garbage":
            rng = faults.ACTIVE.rng

            def mutate(A, adm, wt):
                # a desynced sweep answers with plausible-length junk on
                # every candidate lane; the incumbent lane is kept so the
                # gate's frame of reference survives — the oracles must
                # disqualify every corrupted lane
                A = np.asarray(A).copy()
                n_nodes = 1 << 20
                A[1:] = rng.integers(
                    n_nodes, n_nodes + 1000, size=A[1:].shape
                )
                return A, adm, wt

        verdict = promotion.evaluate_candidates(
            corpus, W, self.tolerance, mutate=mutate,
            rank_objectives=PROBATION_OBJECTIVES,
            tolerances={"drift": self.drift_tolerance},
        )
        return verdict, W

    def _shadow_scheduler(self, rec):
        """Rebuild (or reuse) the shadow replay scheduler from a ring
        record's own profile capture — the live scheduler is never
        touched from the sweep thread. `_shadow_lock` serializes the
        memo AND the rebuild itself: the sweep worker and the deadlined
        counterfactual probe both land here, and two threads tracing
        through `rebuild_scheduler` at once corrupt the jit cache (the
        _EXPLAIN_LOCK lesson; race_audit CA001/CA003)."""
        manifest = rec.manifest
        key = (
            flightrec._canonical_json(manifest.get("profile_config")),
            tuple(p["class"] for p in manifest["plugins"]),
        )
        with self._shadow_lock:
            if self._shadow_key == key and self._shadow_sched is not None:
                return self._shadow_sched
            scheduler, _meta, _faithful = flightrec.rebuild_scheduler(
                manifest,
                lambda s, rec=rec: flightrec.unpack_pytree(s, rec.blobs),
            )
            # an ABANDONED probe/sweep may still reach this publish after
            # its deadline: lock-serialized and key-idempotent, so a late
            # stale publish costs at most one rebuild on the next key
            # check — it can never hand two threads one scheduler
            self._shadow_key = key  # race-audit: safe[CA005] — lock-serialized key-idempotent memo publish
            self._shadow_sched = scheduler  # race-audit: safe[CA005] — lock-serialized key-idempotent memo publish
            return scheduler

    def quiesce(self, timeout_s: float = 60.0) -> bool:
        """Wait for the in-flight shadow sweep (if any) to finish running
        — a bench/test determinism helper (the result is still consumed
        by the next `begin_cycle`); True when nothing is left running."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                job = self._inflight
            if job is None or job["done"].is_set():
                return True
            job["done"].wait(0.05)
        return False

    # -- introspection / persistence -------------------------------------
    def status(self) -> dict:
        """The /healthz tuner block."""
        with self._lock:
            return {
                "state": self.state,
                "active_weights": [int(w) for w in self.active],
                "active_digest": promotion.weights_digest(self.active),
                "last_known_good": [int(w) for w in self.last_known_good],
                "promotions": self.promotions,
                "rollbacks": self.rollbacks,
                "sweeps": self.sweeps,
                "sweep_failures": self.sweep_failures,
                "probation_elapsed": (
                    self._probation_elapsed
                    if self.state == "probation" else None
                ),
                "baseline": (
                    None if self._baseline is None
                    else {k: round(v, 6)
                          for k, v in self._baseline.items()}
                ),
                "staged": self._pending is not None,
                "last_rollback_reason": self.last_rollback_reason,
                "last_rollback_detect_cycles":
                    self.last_rollback_detect_cycles,
                "disabled_reason": self.disabled_reason,
                "observe_only": self.observe_only,
            }

    def state_dict(self) -> dict:
        """Persistable controller state (the daemon writes it next to the
        resilience checkpoint on SIGTERM; restart resumes with the
        promoted weights and the open probation window)."""
        with self._lock:
            return {
                "format": STATE_FORMAT,
                "active_weights": [int(w) for w in self.active],
                "last_known_good": [int(w) for w in self.last_known_good],
                "state": self.state,
                "probation_elapsed": self._probation_elapsed,
                "baseline": self._baseline,
                "promotions": self.promotions,
                "rollbacks": self.rollbacks,
                "blocked": [list(w) for w in sorted(self.blocked)],
                "disabled_reason": self.disabled_reason,
            }

    def restore_state(self, state: dict) -> bool:
        """Resume from a persisted `state_dict`. Returns False (and
        starts fresh) on a format/shape mismatch — a stale state file
        must never block startup."""
        if not isinstance(state, dict) or state.get("format") != STATE_FORMAT:
            return False
        L = len(self.scheduler.profile.plugins)
        active = state.get("active_weights")
        good = state.get("last_known_good")
        if (
            not isinstance(active, list) or len(active) != L
            or not isinstance(good, list) or len(good) != L
        ):
            return False
        with self._lock:
            self.scheduler.set_live_weights(active)
            self.active = np.asarray(active, np.int64)
            self.last_known_good = np.asarray(good, np.int64)
            restored = state.get("state", "idle")
            self.state = (
                restored if restored in STATE_GAUGE else "idle"
            )
            if self.state == "cooldown":
                self._cooldown_until = self.cycle + self.cooldown_cycles
            self._probation_elapsed = int(state.get("probation_elapsed", 0))
            baseline = state.get("baseline")
            self._baseline = baseline if isinstance(baseline, dict) else None
            if self.state == "probation" and self._baseline is None:
                # probation without a baseline cannot adjudicate: treat
                # the restart as a fresh confirmation window instead
                self.state = "idle"
            self.promotions = int(state.get("promotions", 0))
            self.rollbacks = int(state.get("rollbacks", 0))
            self.blocked = {
                tuple(int(x) for x in w)
                for w in state.get("blocked", []) or []
            }
            self.disabled_reason = state.get("disabled_reason")
            self._export_gauges()
        return True
