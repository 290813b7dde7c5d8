#!/usr/bin/env python
"""Counterfactual weight tuner over flight-recorder corpora, and the
`make tune-smoke` gate.

`tune BUNDLE` replays every recorded cycle of a bundle under K candidate
plugin-weight vectors in ONE vmapped batched solve per cycle
(`tuning.sweep`: candidate weights are traced per-lane arguments, so the
whole sweep compiles exactly once — asserted via the PR 5 compile-watch
counters), scores each candidate on the placement-quality objective
vector (`tuning.quality`: fragmentation, utilization imbalance, gang
wait, unplaced fraction, plus score drift vs the recorded sequential
anchor on the baseline profile's own cycle-initial objective), replays
every candidate's placements through the independent numpy
hard-constraint oracles (`tuning.gates`: fit, queue-order quota, gang
quorum — the PR 2/7 differential oracles), and emits a tuned profile
JSON through the `api.config.profile_spec` inverse — ONLY when the
winning candidate strictly improves at least one objective with ZERO
hard-constraint violations across every tuned replay. The tuner is never
a black box: `--explain UID` renders the before/after per-plugin score
table (`Scheduler.explain_rows` via `flightrec.explain_solver`) for any
recorded pod, so every weight change is inspectable decision by
decision.

Ranking: per candidate, each objective's delta vs the in-band baseline
(lane 0 = the recorded profile's own weights) is sense-adjusted
(`tuning.quality.SENSE`) and taken in the objective's own dimensionless
units (every ranked objective is a fraction/relative quantity); the rank
score is the sum. A candidate that regresses any objective by more than
`--tolerance` points (default 0.01) is disqualified — a tune must not
buy one objective by silently selling another.

`smoke` is the CI gate (`make tune-smoke`): record a reduced trimaran
corpus through the REAL `run_cycle` hooks, sweep >= 64 candidates, and
require one compile for the sweep program, an emitted profile, and a
clean constraint audit.

One JSON line per action on stdout; rc 1 on any failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # `python tools/tune.py` from anywhere
    sys.path.insert(0, str(REPO))

#: reduced trimaran corpus for the smoke gate: two scoring plugins with a
#: real packing-vs-balance trade-off (synthetic per-node metrics), small
#: enough for a 2-core runner, 3 cycles with distinct seeds
SMOKE_SHAPE = dict(n_nodes=96, n_pods=128, cycles=3)
SMOKE_CANDIDATES = 64


def _prepare_for_cycle(scheduler, lc, meta) -> None:
    """Re-prepare the shared scheduler for ONE recorded cycle and re-bake
    that cycle's recorded host_state — must run immediately before every
    solve/score of that cycle (cycles of one corpus can carry different
    layouts or cluster-derived specializations; solving cycle i under
    cycle j's prepared state would replay a program the recorded cycle
    never ran). Equal static_keys across cycles keep one compiled sweep
    program; a cycle whose specialization genuinely differs retraces,
    which is correct."""
    from scheduler_plugins_tpu.utils import flightrec

    scheduler.prepare(meta, None)
    for plugin, rec in zip(scheduler.profile.plugins, lc.manifest["plugins"]):
        hs = rec.get("host_state")
        if hs is not None:
            plugin.restore_host_state(
                flightrec.unpack_pytree(hs, lc._blobs_for(hs))
            )


def _load_corpus(bundle_dir: str):
    """[(LoadedCycle, scheduler, snap, meta, auxes, anchor, wait, mode)]
    for every complete recorded cycle, with ONE rebuilt scheduler shared
    across the corpus (its jit caches amortize across cycles; callers
    `_prepare_for_cycle` before touching any one cycle)."""
    import numpy as np

    from scheduler_plugins_tpu.utils import flightrec

    cycles = flightrec.load_bundle(bundle_dir)
    if not cycles:
        raise SystemExit(f"no cycles in bundle {bundle_dir!r}")
    scheduler = None
    corpus = []
    for lc in cycles:
        if not lc.manifest.get("complete"):
            continue
        if scheduler is None:
            scheduler, _faithful = lc.scheduler()
        snap = lc.snapshot()
        meta = lc.meta()
        auxes = lc.auxes()
        anchor = lc.output("assignment")
        wait = lc.output("wait")
        if anchor is None:
            continue
        mode = (lc.manifest.get("outputs") or {}).get("mode")
        corpus.append((
            lc, scheduler, snap, meta, auxes,
            np.asarray(anchor), np.asarray(wait), mode,
        ))
    if not corpus:
        raise SystemExit("bundle has no complete cycles with outputs")
    return corpus


def _promotion_corpus(corpus):
    """Wrap `_load_corpus` tuples as `tuning.promotion.CorpusCycle`s —
    the gate/rank/disqualify body itself lives in `tuning.promotion`,
    shared verbatim with the online shadow lane (`tuning.shadow`)."""
    from scheduler_plugins_tpu.tuning.promotion import CorpusCycle

    return [
        CorpusCycle(
            scheduler=scheduler, snap=snap, meta=meta, auxes=auxes,
            anchor=anchor, wait=wait, mode=mode,
            prepare=(lambda sched, lc=lc, meta=meta:
                     _prepare_for_cycle(sched, lc, meta)),
        )
        for lc, scheduler, snap, meta, auxes, anchor, wait, mode in corpus
    ]


def _tuned_spec(corpus, W, k):
    """Tuned profile JSON via the `profile_spec` inverse: the recorded
    profile config with candidate k's weights applied."""
    from scheduler_plugins_tpu.api.config import load_profile, profile_spec

    manifest = corpus[0][0].manifest
    profile = load_profile(manifest["profile_config"])
    profile.name = manifest.get("profile", profile.name)
    for plugin, w in zip(profile.plugins, W[k]):
        plugin.weight = int(w)
    return profile_spec(profile)


def _explain_pair(corpus, W, k, uid, top=5):
    """(baseline table, tuned table) for one recorded pod — the
    before/after score breakdown that makes the tuner's choice
    inspectable (`flightrec.explain_solver` on a scheduler rebuilt with
    each weight vector)."""
    from scheduler_plugins_tpu.utils import flightrec

    for lc, _s, snap, meta, auxes, anchor, _w, _mode in corpus:
        if uid not in meta.pod_names:
            continue

        def table(weights, assignment):
            scheduler, _m, _f = flightrec.rebuild_scheduler(
                lc.manifest,
                lambda spec: flightrec.unpack_pytree(
                    spec, lc._blobs_for(spec)
                ),
            )
            for plugin, w in zip(scheduler.profile.plugins, weights):
                plugin.weight = int(w)
            return flightrec.explain_solver(
                scheduler, snap, meta, uid, top_k=top,
                assignment=assignment, auxes=auxes,
                cycle=lc.manifest["cycle"],
            )

        return table(W[0], anchor), table(W[k], None)
    raise SystemExit(f"uid {uid!r} not found in any recorded cycle")


def cmd_tune(args) -> int:
    from scheduler_plugins_tpu.tuning import promotion, sweep
    from scheduler_plugins_tpu.utils import observability as obs

    corpus = _load_corpus(args.bundle)
    scheduler = corpus[0][1]
    base = [int(p.weight) for p in scheduler.profile.plugins]
    W = sweep.candidate_weights(base, args.candidates, seed=args.seed)
    # scoped registry view: count only the compiles THIS sweep causes,
    # not whatever the corpus replay above already accumulated
    scope = obs.metrics.scoped()
    # the gate/rank/disqualify body shared with the online shadow lane
    # (tuning.promotion — ONE copy of the acceptance rules)
    verdict = promotion.evaluate_candidates(
        _promotion_corpus(corpus), W, args.tolerance
    )
    sweep_compiles = scope.get(obs.JIT_CACHE_MISS, program="sweep_solve")
    best = verdict.best

    out = {
        "metric": "tune",
        "bundle": args.bundle,
        "cycles": len(corpus),
        "candidates": int(W.shape[0]),
        "sweep_compiles": int(sweep_compiles),
        "plugins": [p.name for p in scheduler.profile.plugins],
        "baseline_weights": base,
        "baseline_objectives": {
            name: round(float(v[0]), 6)
            for name, v in verdict.objectives.items()
        },
        "tuned_weights": [int(w) for w in W[best]],
        "tuned_objectives": {
            name: round(float(v[best]), 6)
            for name, v in verdict.objectives.items()
        },
        "improvement_pct": {
            name: round(100.0 * float(imp[best]), 3)
            for name, imp in verdict.improvements.items()
        },
        "improved_objectives": verdict.improved,
        "hard_violations": int(verdict.violations[best]),
        "anchor_mismatches": int(verdict.anchor_mismatches),
        "candidates_disqualified": verdict.disqualified,
        "accepted": verdict.accepted,
    }
    if verdict.accepted and args.out:
        spec = _tuned_spec(corpus, W, best)
        obs.atomic_write(
            args.out, json.dumps(spec, indent=2, sort_keys=True) + "\n"
        )
        out["profile"] = args.out
    if args.explain:
        before, after = _explain_pair(corpus, W, best, args.explain,
                                      top=args.top)
        out["explain"] = {"uid": args.explain, "before": before,
                          "after": after}
    print(json.dumps(out))
    return 0 if verdict.accepted else 1


# ---------------------------------------------------------------------------
# quality over a bundle (shared with tools/replay.py quality)
# ---------------------------------------------------------------------------


def bundle_quality(bundle_dir: str) -> dict:
    """Per-cycle quality of a bundle's RECORDED placements (the jitted
    tensor core), diffed against the recorded per-cycle stamp when one
    exists, plus the corpus-level gang admission latency."""
    import numpy as np

    from scheduler_plugins_tpu.tuning import quality
    from scheduler_plugins_tpu.utils import flightrec

    cycles = flightrec.load_bundle(bundle_dir)
    rows = []
    latency_feed = []
    for lc in cycles:
        assignment = lc.output("assignment")
        if assignment is None:
            continue
        snap = lc.snapshot()
        wait = lc.output("wait")
        admitted = lc.output("admitted")
        wait = (
            np.zeros(len(np.asarray(assignment)), bool)
            if wait is None else np.asarray(wait)
        )
        q = quality.cycle_quality(snap, np.asarray(assignment), admitted,
                                  wait)
        recorded = (lc.manifest.get("report") or {}).get("quality")
        row = {
            "cycle": lc.manifest["cycle"],
            "quality": {k: round(v, 6) for k, v in q.items()},
        }
        if recorded is not None:
            row["recorded_quality"] = recorded
            row["matches_recorded"] = all(
                abs(q[k] - recorded[k]) < 1e-9 for k in q if k in recorded
            )
        rows.append(row)
        gang = np.asarray(snap.pods.gang) if snap.gangs is not None else None
        if gang is not None:
            latency_feed.append(
                (lc.manifest["meta"]["gang_names"], gang,
                 np.asarray(assignment), wait)
            )
    out = {"bundle": bundle_dir, "cycles": rows}
    if latency_feed:
        lat = quality.gang_admission_latency(latency_feed)
        out["gang_latency_cycles"] = (
            round(float(np.mean(list(lat.values()))), 3) if lat else None
        )
    return out


# ---------------------------------------------------------------------------
# the CI gate
# ---------------------------------------------------------------------------


def _record_smoke_corpus(out_dir: str) -> None:
    """Record the reduced trimaran corpus through the REAL `run_cycle`
    hooks: one shared Scheduler (warm jit cache), a fresh seeded cluster
    per cycle (clusters are single-use — run_cycle binds their pods),
    distinct seeds so the corpus is not one cycle three times."""
    from scheduler_plugins_tpu import plugins as P
    from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
    from scheduler_plugins_tpu.models import trimaran_scenario
    from scheduler_plugins_tpu.utils import flightrec

    scheduler = Scheduler(Profile(plugins=[
        P.TargetLoadPacking(), P.LoadVariationRiskBalancing(),
    ]))

    def one_cycle(seed):
        cluster = trimaran_scenario(
            n_nodes=SMOKE_SHAPE["n_nodes"], n_pods=SMOKE_SHAPE["n_pods"],
            seed=seed,
        )
        return run_cycle(scheduler, cluster, now=1000 + seed)

    one_cycle(0)  # compile warmup, recorder off
    flightrec.recorder.start(capacity=SMOKE_SHAPE["cycles"] + 1)
    for seed in range(SMOKE_SHAPE["cycles"]):
        flightrec.recorder.seed = seed
        one_cycle(seed)
    flightrec.recorder.save(out_dir)
    flightrec.recorder.stop()


def cmd_smoke(args) -> int:
    out_dir = args.out or os.path.join(
        tempfile.mkdtemp(prefix="tune_smoke_"), "bundle"
    )
    _record_smoke_corpus(out_dir)
    profile_path = os.path.join(out_dir, "tuned_profile.json")
    ns = argparse.Namespace(
        bundle=out_dir, candidates=SMOKE_CANDIDATES, seed=0,
        tolerance=0.05, out=profile_path, explain=None, top=5,
    )
    # capture cmd_tune's JSON line so the smoke emits ONE line
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cmd_tune(ns)
    tune = json.loads(buf.getvalue())

    # re-verify the EMITTED profile independently: load it back through
    # api.config, re-solve every recorded cycle with the tuned weights
    # via the replay path, and re-run the hard-constraint oracles
    emitted_ok = False
    emitted_violations = None
    if tune.get("profile"):
        import numpy as np

        from scheduler_plugins_tpu.api.config import load_profile
        from scheduler_plugins_tpu.framework import Scheduler
        from scheduler_plugins_tpu.tuning import gates

        with open(tune["profile"]) as f:
            spec = json.load(f)
        tuned_sched = Scheduler(load_profile(spec))
        corpus = _load_corpus(out_dir)
        emitted_violations = 0
        for lc, _s, snap, meta, auxes, _anchor, _w, _mode in corpus:
            _prepare_for_cycle(tuned_sched, lc, meta)
            result = tuned_sched.solve(snap, auxes=auxes, mode="sequential")
            emitted_violations += gates.hard_violations(
                snap, np.asarray(result.assignment), np.asarray(result.wait)
            )["total"]
        emitted_ok = emitted_violations == 0

    ok = (
        tune.get("accepted") is True
        and tune.get("sweep_compiles", 99) <= 1
        and tune.get("candidates", 0) >= SMOKE_CANDIDATES
        and tune.get("hard_violations", 1) == 0
        and emitted_ok
    )
    print(json.dumps({
        "metric": "tune_smoke",
        "bundle": out_dir,
        "sweep_compiles": tune.get("sweep_compiles"),
        "candidates": tune.get("candidates"),
        "improved_objectives": tune.get("improved_objectives"),
        "improvement_pct": tune.get("improvement_pct"),
        "tuned_weights": tune.get("tuned_weights"),
        "baseline_weights": tune.get("baseline_weights"),
        "emitted_profile": tune.get("profile"),
        "emitted_profile_violations": emitted_violations,
        "accepted": tune.get("accepted"),
        "ok": bool(ok),
    }))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools/tune.py", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_tune = sub.add_parser(
        "tune", help="sweep a bundle corpus, rank candidates, emit a "
        "gated tuned profile"
    )
    p_tune.add_argument("bundle")
    p_tune.add_argument("--candidates", type=int, default=64)
    p_tune.add_argument("--seed", type=int, default=0)
    p_tune.add_argument("--tolerance", type=float, default=0.01,
                        help="max fractional regression allowed on any "
                             "objective (default 1%%)")
    p_tune.add_argument("--out", default=None,
                        help="tuned profile JSON path (emitted only when "
                             "the gates accept)")
    p_tune.add_argument("--explain", default=None, metavar="UID",
                        help="render the before/after per-plugin score "
                             "table for this recorded pod")
    p_tune.add_argument("--top", type=int, default=5)
    p_smoke = sub.add_parser("smoke", help="the make tune-smoke CI gate")
    p_smoke.add_argument("--out", default=None,
                         help="corpus dir (default: temp dir)")
    args = ap.parse_args(argv)
    return {"tune": cmd_tune, "smoke": cmd_smoke}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
