#!/usr/bin/env python
"""Flight-recorder replay + explain CLI, and the `make replay-smoke` gate.

Subcommands over bundles written by `utils.flightrec` (the daemon's
`--record/--record-dir`, or `FlightRecorder.save`):

- `info BUNDLE` — list recorded cycles (digest, mode, batch size, placed).
- `replay BUNDLE [--cycle K]` — re-run recorded cycles offline through the
  bit-identical sequential parity path (`Scheduler.solve`) with the
  RECORDED aux arrays bound, and diff placements. A sequential-mode record
  that fails to replay bit-identically is an error (rc 1); wave-mode
  records (batch/streamed) report their diff as evidence (soft
  tie-breaking may differ) without failing.
- `explain BUNDLE --uid UID [--cycle K] [--top N] [--batched]` — the
  per-plugin score table for one recorded pod (the upstream `--v=10`
  score dump): per-plugin weighted normalized columns, built-in fit
  margin, winner gap.
- `quality BUNDLE` — placement-quality objectives (`tuning.quality`:
  fragmentation, utilization imbalance, gang wait, unplaced fraction;
  corpus-level gang admission latency when gangs are recorded) for every
  recorded cycle's placements, diffed against the per-cycle stamp
  `run_cycle` recorded when one exists.
- `smoke` — the CI gate (`make replay-smoke`): record a reduced
  gang+quota cycle through the REAL `run_cycle` hooks, save/load the
  bundle, replay it (diff must be empty), validate the explain JSON
  against `EXPLAIN_SCHEMA`, and check the explain columns sum to the
  solver's total.

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
if str(REPO) not in sys.path:  # `python tools/replay.py` from anywhere
    sys.path.insert(0, str(REPO))

#: reduced gang+quota roster shape (BASELINE config 4) for the smoke gate
SMOKE_SHAPE = dict(n_gangs=4, gang_size=8, n_nodes=64)


# ---------------------------------------------------------------------------
# explain JSON schema (stdlib check — no jsonschema dependency)
# ---------------------------------------------------------------------------

#: field -> allowed types (None in the tuple = nullable)
EXPLAIN_SCHEMA = {
    "uid": (str,),
    "cycle": (int, None),
    "pod_index": (int,),
    "profile": (str,),
    "path": (str,),
    "admitted": (bool,),
    "placed": (bool, None),
    "assigned": (str, None),
    "failed_plugin": (str, None),
    "winner": (str, None),
    "winner_total": (int, None),
    "runner_up_gap": (int, None),
    "weights": (dict,),
    "candidates": (list,),
}

CANDIDATE_SCHEMA = {
    "node": (str,),
    "total": (int,),
    "gap_to_winner": (int, None),
    "feasible": (bool,),
    "fit_margin": (int, None),
    "scores": (dict,),
}


def _check_fields(obj: dict, schema: dict, where: str) -> list[str]:
    errors = []
    for field, types in schema.items():
        if field not in obj:
            errors.append(f"{where}: missing field {field!r}")
            continue
        value = obj[field]
        if value is None:
            if None not in types:
                errors.append(f"{where}.{field}: unexpected null")
            continue
        concrete = tuple(t for t in types if t is not None)
        # bool is an int subclass: reject bools where ints are expected
        if isinstance(value, bool) and bool not in concrete:
            errors.append(f"{where}.{field}: bool where {concrete} expected")
        elif not isinstance(value, concrete):
            errors.append(
                f"{where}.{field}: {type(value).__name__} not in "
                f"{[t.__name__ for t in concrete]}"
            )
    return errors


def validate_explain(obj) -> list[str]:
    """Structural errors in one explain JSON object (empty list = valid).
    Shared by the smoke gate and tests/test_explain.py."""
    if not isinstance(obj, dict):
        return ["explain payload is not an object"]
    errors = _check_fields(obj, EXPLAIN_SCHEMA, "explain")
    for name, weight in (obj.get("weights") or {}).items():
        if not isinstance(name, str) or isinstance(weight, bool) or not (
            isinstance(weight, int)
        ):
            errors.append(f"explain.weights[{name!r}]: not str -> int")
    candidates = obj.get("candidates")
    if isinstance(candidates, list):
        if not candidates:
            errors.append("explain.candidates: empty")
        for i, cand in enumerate(candidates):
            if not isinstance(cand, dict):
                errors.append(f"candidates[{i}]: not an object")
                continue
            errors += _check_fields(cand, CANDIDATE_SCHEMA, f"candidates[{i}]")
            scores = cand.get("scores")
            if isinstance(scores, dict):
                if set(scores) != set(obj.get("weights") or {}):
                    errors.append(
                        f"candidates[{i}].scores: plugin set != weights set"
                    )
                # the tentpole invariant: columns sum to the total
                if all(
                    isinstance(v, int) and not isinstance(v, bool)
                    for v in scores.values()
                ) and isinstance(cand.get("total"), int):
                    if sum(scores.values()) != cand["total"]:
                        errors.append(
                            f"candidates[{i}]: score columns sum "
                            f"{sum(scores.values())} != total {cand['total']}"
                        )
    return errors


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------


def _cost_stamp_drift(bundle: str) -> dict | None:
    """Compare the bundle's recorded static-cost provenance (`cost.json`,
    written by flightrec.save) against the CURRENT docs/cost_model.json:
    a digest mismatch means the bundle was recorded under a program with
    a different cost shape — replay numbers then compare an old
    algorithm against new expectations. None when the bundle predates
    the stamp (old bundles stay loadable)."""
    import os

    from scheduler_plugins_tpu.obs import costmodel

    path = os.path.join(bundle, "cost.json")
    try:
        with open(path) as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        return None
    current = costmodel.load_manifest()
    if not current:
        return {"recorded_digest": recorded.get("manifest_digest"),
                "current_digest": None, "drifted": None,
                "warning": "no committed cost manifest to compare against"}
    cur_digest = costmodel.manifest_digest(current)
    drifted = cur_digest != recorded.get("manifest_digest")
    out = {
        "recorded_digest": recorded.get("manifest_digest"),
        "current_digest": cur_digest,
        "drifted": drifted,
    }
    if drifted:
        cur_p = {n: r.get("cost_digest")
                 for n, r in current.get("programs", {}).items()}
        rec_p = recorded.get("programs", {})
        out["changed_programs"] = sorted(
            n for n in set(cur_p) | set(rec_p) if cur_p.get(n) != rec_p.get(n)
        )
        out["warning"] = (
            "bundle was recorded under a program with a different cost "
            "shape — replay compares an old algorithm against the "
            "current tree"
        )
    return out


def cmd_info(args) -> int:
    from scheduler_plugins_tpu.utils import flightrec

    cycles = flightrec.load_bundle(args.bundle)
    out = []
    for lc in cycles:
        m = lc.manifest
        outputs = m.get("outputs") or {}
        out.append({
            "cycle": m["cycle"],
            "digest": m.get("digest"),
            "digest_ok": lc.digest_ok(),
            "profile": m.get("profile"),
            "mode": outputs.get("mode"),
            "pods": len(m.get("meta", {}).get("pod_names", [])),
            "nodes": len(m.get("meta", {}).get("node_names", [])),
            "seed": m.get("seed"),
            "complete": m.get("complete"),
        })
    print(json.dumps({"bundle": args.bundle, "cycles": out,
                      "cost_shape": _cost_stamp_drift(args.bundle)}))
    return 0


def cmd_replay(args) -> int:
    from scheduler_plugins_tpu.utils import flightrec

    cycles = flightrec.load_bundle(args.bundle)
    if args.cycle is not None:
        cycles = [c for c in cycles if c.manifest["cycle"] == args.cycle]
        if not cycles:
            print(json.dumps({"error": f"cycle {args.cycle} not in bundle"}))
            return 1
    failed = False
    results = []
    for lc in cycles:
        out = flightrec.replay_cycle(lc)
        public = {k: v for k, v in out.items() if not k.startswith("_")}
        # bit-identical replay is the CONTRACT for sequential records; a
        # wave-mode record's diff is evidence of soft tie-break drift
        must_match = out["mode"] == "sequential"
        ok = (
            out["digest_ok"]
            and (out["placements_match"] or not must_match)
        )
        public["ok"] = ok
        failed |= not ok
        results.append(public)
    print(json.dumps({"bundle": args.bundle, "replays": results,
                      "ok": not failed}))
    return 1 if failed else 0


def cmd_explain(args) -> int:
    from scheduler_plugins_tpu.utils import flightrec

    cycles = flightrec.load_bundle(args.bundle)
    chosen = None
    for lc in reversed(cycles):
        if args.cycle is not None and lc.manifest["cycle"] != args.cycle:
            continue
        if args.uid in lc.manifest.get("meta", {}).get("pod_names", []):
            chosen = lc
            break
    if chosen is None:
        print(json.dumps({
            "error": f"uid {args.uid!r} not found in bundle"
            + (f" cycle {args.cycle}" if args.cycle is not None else "")
        }))
        return 1
    table = flightrec.explain_record(
        chosen, args.uid, top_k=args.top, batched=args.batched
    )
    errors = validate_explain(table)
    table["schema_errors"] = errors
    print(json.dumps(table))
    return 1 if errors else 0


def cmd_timeline(args) -> int:
    """Reconstruct one pod's cross-cycle lifecycle story from a bundle's
    pod-ledger segment (`ledger.json`, written by FlightRecorder.save
    when the obs.ledger was live): events with (cycle, lane, seq)
    coordinates, the per-stage latency decomposition and the observing
    cycles' meta. Without --uid, prints the bundle's SLI summary and the
    recorded uids instead."""
    import os

    path = os.path.join(args.bundle, "ledger.json")
    if not os.path.exists(path):
        print(json.dumps({
            "error": "bundle has no ledger.json (the pod-lifecycle "
                     "ledger was disabled when the bundle was saved)"
        }))
        return 1
    with open(path) as f:
        export = json.load(f)
    records = export.get("retired", []) + export.get("live", [])
    if not args.uid:
        print(json.dumps({
            "bundle": args.bundle,
            "sli": export.get("sli"),
            "pods": [
                {"uid": r["uid"], "outcome": r["outcome"],
                 "e2e_ms": r["e2e_ms"], "attempts": r["attempts"]}
                for r in records
            ],
        }))
        return 0
    rec = next((r for r in records if r["uid"] == args.uid), None)
    if rec is None:
        print(json.dumps(
            {"error": f"uid {args.uid!r} not in the bundle's ledger"}
        ))
        return 1
    cycles = {m["cycle"]: m for m in export.get("cycles", [])}
    rec = dict(rec)
    rec["cycles"] = [
        cycles[c] for c in sorted({e["cycle"] for e in rec["events"]})
        if c in cycles
    ]
    # the decomposition invariant, re-checked on the persisted copy (ms
    # floats survive the ns->ms conversion exactly for any realistic
    # lifetime: both sides are the same sums scaled by 1e-6)
    if rec["e2e_ms"] is not None:
        rec["stages_sum_ms"] = sum(rec["stages_ms"].values())
        rec["decomposition_exact"] = (
            abs(rec["stages_sum_ms"] - rec["e2e_ms"]) < 1e-6
        )
    print(json.dumps(rec))
    return 0


def cmd_quality(args) -> int:
    """Quality objectives over a bundle's recorded placements (the jitted
    `tuning.quality` tensor core; `tools/tune.py` owns the shared
    implementation so the tuner and this view cannot diverge)."""
    from tools.tune import bundle_quality

    out = bundle_quality(args.bundle)
    mismatched = [
        row["cycle"] for row in out["cycles"]
        if row.get("matches_recorded") is False
    ]
    out["ok"] = not mismatched
    print(json.dumps(out))
    return 1 if mismatched else 0


# ---------------------------------------------------------------------------
# the CI gate
# ---------------------------------------------------------------------------


def cmd_smoke(args) -> int:
    from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.utils import flightrec

    out_dir = args.out or os.path.join(
        tempfile.mkdtemp(prefix="replay_smoke_"), "bundle"
    )
    cluster, plugins, _ = problems.config_problem(4, shape=SMOKE_SHAPE)
    scheduler = Scheduler(Profile(plugins=plugins))
    flightrec.recorder.start(capacity=2)
    flightrec.recorder.seed = 0  # config_problem scenarios are seed-0
    try:
        report = run_cycle(scheduler, cluster, now=1000)
        save = flightrec.recorder.save(out_dir)
    finally:
        flightrec.recorder.stop()
    cycles = flightrec.load_bundle(out_dir)
    replay = flightrec.replay_cycle(cycles[-1])
    replay_ok = (
        replay["digest_ok"]
        and replay["placements_match"]
        and replay["aux_match"]
        and replay["mode"] == "sequential"
    )

    # explain a failed pod when the cycle had one, else the first pod;
    # schema validation includes the columns-sum-to-total invariant
    pod_names = cycles[-1].manifest["meta"]["pod_names"]
    uid = report.failed[0] if report.failed else pod_names[0]
    table = flightrec.explain_record(cycles[-1], uid)
    schema_errors = validate_explain(table)

    ok = replay_ok and not schema_errors and bool(report.bound)
    print(json.dumps({
        "metric": "replay_smoke",
        "bundle": save,
        "replay": {k: v for k, v in replay.items()
                   if not k.startswith("_")},
        "replay_ok": replay_ok,
        "explain_uid": uid,
        "explain_schema_errors": schema_errors[:5],
        "ok": ok,
    }))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools/replay.py", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_info = sub.add_parser("info", help="list a bundle's recorded cycles")
    p_info.add_argument("bundle")
    p_replay = sub.add_parser(
        "replay", help="re-run recorded cycles through Scheduler.solve "
        "and diff placements"
    )
    p_replay.add_argument("bundle")
    p_replay.add_argument("--cycle", type=int, default=None)
    p_explain = sub.add_parser(
        "explain", help="per-plugin score table for one recorded pod"
    )
    p_explain.add_argument("bundle")
    p_explain.add_argument("--uid", required=True)
    p_explain.add_argument("--cycle", type=int, default=None)
    p_explain.add_argument("--top", type=int, default=5)
    p_explain.add_argument("--batched", action="store_true",
                           help="derive columns through the batched "
                                "solver's class-collapsed row hooks")
    p_quality = sub.add_parser(
        "quality", help="placement-quality objectives for every recorded "
        "cycle (tuning.quality)"
    )
    p_quality.add_argument("bundle")
    p_timeline = sub.add_parser(
        "timeline", help="one pod's cross-cycle lifecycle story from the "
        "bundle's pod-ledger segment (ledger.json)"
    )
    p_timeline.add_argument("bundle")
    p_timeline.add_argument("--uid", default=None,
                            help="pod uid (omit to list recorded pods + "
                                 "the bundle's SLI summary)")
    p_smoke = sub.add_parser("smoke", help="the make replay-smoke CI gate")
    p_smoke.add_argument("--out", default=None,
                         help="bundle output dir (default: temp dir)")
    args = ap.parse_args(argv)
    return {
        "info": cmd_info,
        "replay": cmd_replay,
        "explain": cmd_explain,
        "quality": cmd_quality,
        "timeline": cmd_timeline,
        "smoke": cmd_smoke,
    }[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
