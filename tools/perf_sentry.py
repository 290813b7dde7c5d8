"""Bench-regression sentry: change-point verdicts that survive noisy hosts.

A bench history (files of bench JSON lines) plus fresh runs form a
series per metric.  A naive "new mean < old mean" check on such a series
is worthless: a history can hold runs that failed (an ``error`` field,
value 0) and fresh runs may land on a small shared host whose noise
floor dwarfs small real regressions.  The sentry therefore applies three
disciplines:

1. **Degenerate-sample quarantine** — history entries with a nonzero
   rc, a parse error, an ``error`` field, or a non-positive value are
   classified unusable.  Too few usable baselines produces the verdict
   ``no-baseline``, never ``regression``.

2. **Paired-sorted deltas** — baseline and candidate series are sorted
   and paired elementwise; the per-pair relative slowdown is computed
   and the *median* taken.  A reshuffle of the same measurements gives
   identical sorted series, hence exactly zero deltas and a quiet
   verdict (this is the zero-false-positive property ``selftest``
   checks); a uniform injected slowdown survives the pairing intact.

3. **Robust noise floor + host-health stamping** — the flag threshold
   is ``max(--rel-threshold, baseline p10–p90 spread / median)``, and
   every verdict is stamped with tools/host_health.py's probe.  A
   slowdown measured on an unhealthy host is reported as
   ``degraded-host`` (rc 0), not ``regression`` (rc 1): re-run when
   the machine recovers instead of blaming the commit.

4. **The cost arm (ISSUE 20)** — wall-clock is only one witness.  XLA's
   static cost census (docs/cost_model.json, tools/cost_observatory.py)
   is a pure function of the compiled program: a cost delta between two
   manifests has a ZERO noise floor, so the cost arm's ``regression`` is
   never downgraded by a sick host — an injected algorithmic regression
   is flagged even where the timing arm must say ``degraded-host``, and
   a pure timing wobble with zero cost delta stays quiet.  ``selftest``
   proves that exact split.

Usage:
  python tools/perf_sentry.py check --history 'runs/*.json' --new run.json
  python tools/perf_sentry.py cost --baseline old_cost_model.json
  python tools/perf_sentry.py selftest
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import host_health  # noqa: E402

from scheduler_plugins_tpu.obs import costmodel  # noqa: E402

MIN_BASELINE = 3
DEFAULT_REL_THRESHOLD = 0.10

# Which direction is "worse" per metric family.  Throughput-style
# metrics regress downward, latency-style metrics regress upward.
_LOWER_IS_BETTER_SUFFIXES = ("_ms", "_ns", "_s", "_seconds", "_latency")


def lower_is_better(metric: str) -> bool:
    return metric.endswith(_LOWER_IS_BETTER_SUFFIXES)


# ---------------------------------------------------------------------------
# History ingestion
# ---------------------------------------------------------------------------

def _sample_from_line(line: dict, source: str) -> dict:
    """Normalise one bench JSON line into a sample dict."""
    metric = line.get("metric", "unknown")
    value = line.get("value")
    err = line.get("error")
    usable = (
        err in (None, "")
        and isinstance(value, (int, float))
        and math.isfinite(float(value))
        and float(value) > 0
    )
    return {
        "source": source,
        "metric": metric,
        "value": float(value) if isinstance(value, (int, float)) else None,
        "error": err,
        "usable": usable,
    }


def extract_samples(obj, source: str) -> list[dict]:
    """Accept either a committed wrapper {n, cmd, rc, tail, parsed},
    a raw bench line {metric, value, ...}, or a list of either."""
    if isinstance(obj, list):
        out: list[dict] = []
        for item in obj:
            out.extend(extract_samples(item, source))
        return out
    if not isinstance(obj, dict):
        return []
    if "parsed" in obj or "rc" in obj:  # committed wrapper
        parsed = obj.get("parsed")
        if obj.get("rc", 1) != 0 or parsed is None:
            return [{
                "source": source, "metric": "unknown", "value": None,
                "error": "run-failed", "usable": False,
            }]
        return extract_samples(parsed, source)
    if "metric" in obj:
        return [_sample_from_line(obj, source)]
    # multi-line runs: {"lines": [...]} or dict of named lines
    if "lines" in obj and isinstance(obj["lines"], list):
        return extract_samples(obj["lines"], source)
    return []


def load_files(paths: list[str]) -> list[dict]:
    samples: list[dict] = []
    for path in paths:
        try:
            with open(path) as fh:
                text = fh.read()
        except OSError as exc:
            samples.append({"source": path, "metric": "unknown", "value": None,
                            "error": f"unreadable: {exc}", "usable": False})
            continue
        # A file may hold one pretty-printed object or one JSON line per row.
        try:
            samples.extend(extract_samples(json.loads(text), path))
            continue
        except ValueError:
            pass
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln:
                continue
            try:
                samples.extend(extract_samples(json.loads(ln), path))
            except ValueError:
                continue
    return samples


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------

def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q * (len(sorted_vals) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if n == 0:
        return float("nan")
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def verdict(baseline: list[float], candidate: list[float], *,
            metric: str = "unknown",
            rel_threshold: float = DEFAULT_REL_THRESHOLD,
            health: dict | None = None) -> dict:
    """Paired-sorted change-point verdict for one metric series."""
    out: dict = {
        "metric": metric,
        "baseline_n": len(baseline),
        "candidate_n": len(candidate),
        "rel_threshold": rel_threshold,
    }
    if len(baseline) < MIN_BASELINE or not candidate:
        out["verdict"] = "no-baseline"
        out["reason"] = (
            f"need >= {MIN_BASELINE} usable baseline samples and >= 1 "
            f"candidate sample (have {len(baseline)}/{len(candidate)})")
        return out

    base = sorted(baseline)
    cand = sorted(candidate)
    med = _median(base)
    p10, p90 = _percentile(base, 0.10), _percentile(base, 0.90)
    spread_rel = (p90 - p10) / med if med > 0 else float("inf")
    floor = max(rel_threshold, spread_rel)
    out["baseline_median"] = med
    out["baseline_spread_rel"] = round(spread_rel, 6)
    out["noise_floor"] = round(floor, 6)

    # Pair k-th smallest with k-th smallest; with unequal lengths pair the
    # shorter series against evenly spaced quantiles of the longer one so
    # neither tail dominates.
    n = min(len(base), len(cand))
    if len(base) == len(cand):
        pairs = list(zip(base, cand))
    elif len(cand) < len(base):
        pairs = [(_percentile(base, (i + 0.5) / n), cand[i]) for i in range(n)]
    else:
        pairs = [(base[i], _percentile(cand, (i + 0.5) / n)) for i in range(n)]

    worse = lower_is_better(metric)
    deltas = []
    for b, c in pairs:
        if b <= 0:
            continue
        slow = (c - b) / b if worse else (b - c) / b
        deltas.append(slow)
    if not deltas:
        out["verdict"] = "no-baseline"
        out["reason"] = "no positive baseline pairs"
        return out

    med_slow = _median(deltas)
    out["median_slowdown"] = round(med_slow, 6)
    out["pair_deltas"] = [round(d, 6) for d in deltas]

    if med_slow > floor:
        if health is not None and not health.get("healthy", True):
            out["verdict"] = "degraded-host"
            out["reason"] = ("slowdown exceeds noise floor but host probe is "
                            f"unhealthy ({health.get('reasons')}); re-run on a "
                            "healthy host before blaming the change")
        else:
            out["verdict"] = "regression"
            out["reason"] = (f"median paired slowdown {med_slow:.1%} exceeds "
                            f"noise floor {floor:.1%}")
    elif med_slow < -floor:
        out["verdict"] = "improved"
        out["reason"] = f"median paired speedup {-med_slow:.1%}"
    else:
        out["verdict"] = "ok"
        out["reason"] = (f"median paired slowdown {med_slow:.1%} within "
                        f"noise floor {floor:.1%}")
    if health is not None:
        out["host"] = health
    return out


def check_series(history_samples: list[dict], new_samples: list[dict], *,
                 rel_threshold: float, health: dict | None) -> dict:
    """Group samples by metric and produce one verdict per metric."""
    metrics: dict[str, tuple[list[float], list[float]]] = {}
    for s in history_samples:
        if s["usable"]:
            metrics.setdefault(s["metric"], ([], []))[0].append(s["value"])
    for s in new_samples:
        if s["usable"]:
            metrics.setdefault(s["metric"], ([], []))[1].append(s["value"])
    verdicts = {
        m: verdict(base, cand, metric=m, rel_threshold=rel_threshold,
                   health=health)
        for m, (base, cand) in sorted(metrics.items())
    }
    if not verdicts:
        verdicts["unknown"] = {
            "metric": "unknown", "verdict": "no-baseline",
            "reason": "no usable samples in history or candidate runs",
            "baseline_n": 0, "candidate_n": 0,
        }
    order = ("no-baseline", "improved", "ok", "degraded-host", "regression")
    worst = max((v["verdict"] for v in verdicts.values()), key=order.index)
    unusable = [s for s in history_samples + new_samples if not s["usable"]]
    return {
        "sentry": "perf_sentry",
        "overall": worst,
        "verdicts": verdicts,
        "unusable_samples": len(unusable),
        "unusable_detail": [
            {"source": s["source"], "error": s["error"]} for s in unusable[:10]
        ],
    }


# ---------------------------------------------------------------------------
# The cost arm: deterministic verdicts from static cost manifests
# ---------------------------------------------------------------------------

#: combined-verdict severity order — cost "regression" outranks the
#: timing arm's "degraded-host": a sick host can invalidate a timing
#: but it cannot change a compiled program's static cost.
VERDICT_ORDER = ("no-baseline", "improved", "ok", "degraded-host",
                 "regression")


def cost_verdict(base_row: dict | None, cand_row: dict | None, *,
                 program: str = "unknown",
                 health: dict | None = None) -> dict:
    """Deterministic verdict for one program's static cost shape.

    Compares the budgeted cost axes (flops, bytes accessed, peak bytes)
    of two docs/cost_model.json rows.  The noise floor is EXACTLY zero:
    any increase on any budgeted axis is a regression, any decrease an
    improvement, digest-identical rows are quiet.  ``health`` is
    accepted for interface symmetry with `verdict()` but deliberately
    NEVER downgrades — that asymmetry is the whole point of the arm."""
    out: dict = {"program": program, "arm": "cost", "noise_floor": 0.0}
    if not base_row or not cand_row:
        out["verdict"] = "no-baseline"
        out["reason"] = "missing cost row (run tools/cost_observatory.py)"
        return out
    if base_row.get("cost_digest") == cand_row.get("cost_digest"):
        out["verdict"] = "ok"
        out["reason"] = "identical cost digest (zero cost delta)"
        out["max_rel_delta"] = 0.0
        return out
    deltas = {}
    for f in costmodel.BUDGET_FIELDS:
        b, c = base_row.get(f), cand_row.get(f)
        if b is None or c is None:
            continue
        deltas[f] = round((c - b) / b, 6) if b else (1.0 if c else 0.0)
    if not deltas:
        # static-only rows: the digest covers TPU StableHLO + collective
        # census — a digest move with no CPU cost axes is still a shape
        # change that must be reviewed, but has no magnitude to rank.
        out["verdict"] = "regression"
        out["reason"] = ("static-only cost shape changed (TPU digest or "
                         "collective census drift)")
        return out
    worst_field = max(deltas, key=lambda f: deltas[f])
    worst = deltas[worst_field]
    out["deltas"] = deltas
    out["max_rel_delta"] = worst
    if worst > 0:
        out["verdict"] = "regression"
        out["reason"] = (f"{worst_field} grew {worst:+.1%}; static cost "
                         "deltas have no noise floor — a sick host cannot "
                         "explain this away")
    elif any(d < 0 for d in deltas.values()):
        out["verdict"] = "improved"
        out["reason"] = f"cost shrank (worst axis {worst_field} {worst:+.1%})"
    else:
        out["verdict"] = "ok"
        out["reason"] = "cost digest moved but budgeted axes are unchanged"
    return out


def cost_check(base_manifest: dict | None,
               cand_manifest: dict | None) -> dict:
    """Per-program cost verdicts between two cost manifests."""
    base_p = (base_manifest or {}).get("programs", {})
    cand_p = (cand_manifest or {}).get("programs", {})
    verdicts = {
        name: cost_verdict(base_p.get(name), cand_p.get(name), program=name)
        for name in sorted(set(base_p) | set(cand_p))
    }
    if not verdicts:
        verdicts["unknown"] = {
            "program": "unknown", "arm": "cost", "verdict": "no-baseline",
            "reason": "no cost manifests to compare",
        }
    worst = max((v["verdict"] for v in verdicts.values()),
                key=VERDICT_ORDER.index)
    return {
        "sentry": "perf_sentry_cost_arm",
        "overall": worst,
        "jax_baseline": (base_manifest or {}).get("jax"),
        "jax_candidate": (cand_manifest or {}).get("jax"),
        "comparable_jax": (base_manifest or {}).get("jax")
        == (cand_manifest or {}).get("jax"),
        "verdicts": verdicts,
    }


def combine_arms(timing: str, cost: str) -> str:
    """Two-arm combined verdict: worst of both by VERDICT_ORDER.  A cost
    ``regression`` therefore overrides a timing ``degraded-host`` —
    exactly the split the selftest proves — while a cost ``ok`` never
    upgrades a timing regression (a runtime-only regression, e.g. a bad
    donation pattern, is invisible to static cost)."""
    return max((timing, cost), key=VERDICT_ORDER.index)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def cmd_check(args) -> int:
    hist_paths: list[str] = []
    for pat in args.history:
        hist_paths.extend(sorted(glob.glob(pat)))
    new_paths: list[str] = []
    for pat in args.new:
        new_paths.extend(sorted(glob.glob(pat)))
    health = None if args.no_probe else host_health.probe(args.probe_timeout)
    report = check_series(
        load_files(hist_paths), load_files(new_paths),
        rel_threshold=args.rel_threshold, health=health)
    report["history_files"] = hist_paths
    report["new_files"] = new_paths
    if args.cost_baseline:
        cost = cost_check(
            costmodel.load_manifest(args.cost_baseline),
            costmodel.load_manifest(args.cost_candidate))
        report["cost_arm"] = cost
        report["timing_overall"] = report["overall"]
        report["overall"] = combine_arms(report["overall"], cost["overall"])
    print(json.dumps(report, sort_keys=True))
    return 1 if report["overall"] == "regression" else 0


def cmd_cost(args) -> int:
    """Standalone cost-arm verdict between two cost manifests."""
    report = cost_check(
        costmodel.load_manifest(args.baseline),
        costmodel.load_manifest(args.candidate))
    print(json.dumps(report, sort_keys=True))
    return 1 if report["overall"] == "regression" else 0


def _timed_series(n: int, work: int, reps: int = 5) -> list[float]:
    """Really-measured wall times of a fixed deterministic workload.

    Each sample is the min over ``reps`` back-to-back runs: the minimum
    is the classic robust timer — scheduler preemptions and co-tenant
    noise only ever add time, so min-of-k recovers the workload's true
    cost and keeps the series' p10-p90 spread below the injected shifts
    the selftest must detect even on a loaded single-core container.
    """
    out = []
    for _ in range(n):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            acc = 0
            for i in range(work):
                acc += i * i
            best = min(best, time.perf_counter() - t0)
        out.append(best * 1e3)
    assert acc >= 0
    return out


def cmd_selftest(args) -> int:
    """Prove the three sentry properties on real timings:
    reshuffle => quiet, injected 20% slowdown => flagged,
    degenerate committed history => no-baseline."""
    health_ok = {"healthy": True, "reasons": []}

    # Measure a real series, re-measuring with more reps if this host is
    # too noisy for the nominal 25% injection to clear its own floor.
    inject_factor = 1.25
    for reps in (5, 11, 21):
        base = _timed_series(n=15, work=20_000, reps=reps)
        probe_v = verdict(base, base, metric="selftest_ms", health=health_ok)
        if probe_v["noise_floor"] < (inject_factor - 1.0) * 0.8:
            break
    scaled = False
    if probe_v["noise_floor"] >= (inject_factor - 1.0) * 0.8:
        # Host never settled: a 25% shift genuinely drowns in this
        # machine's noise and a correct sentry must stay quiet on it.
        # Test the same property at a detectable magnitude instead.
        inject_factor = 1.0 + 2.0 * probe_v["noise_floor"]
        scaled = True

    # 1. Reshuffle: same measurements, different order -> exactly quiet.
    shuffled = list(base)
    random.Random(1234).shuffle(shuffled)
    v_shuffle = verdict(base, shuffled, metric="selftest_ms",
                        health=health_ok)
    quiet = v_shuffle["verdict"] == "ok" and v_shuffle["median_slowdown"] == 0.0

    # 2. Inject a uniform slowdown (nominally 20% throughput loss, i.e.
    #    x1.25 latency) -> flagged even against this host's measured
    #    noise, because pairing keeps the shift intact on every pair.
    injected = [t * inject_factor for t in base]
    v_inject = verdict(base, injected, metric="selftest_ms",
                       health=health_ok)
    flagged = v_inject["verdict"] == "regression"

    # 2b. Same injection on an unhealthy host downgrades, never blames.
    v_degraded = verdict(base, injected, metric="selftest_ms",
                         health={"healthy": False, "reasons": ["load_high"]})
    downgraded = v_degraded["verdict"] == "degraded-host"

    # 3. A degenerate history (failed runs, value 0) must yield
    #    no-baseline, not a regression.
    hist = [
        _sample_from_line(
            {"metric": "pods_scheduled_per_sec", "value": 0,
             "error": "backend-unavailable"}, f"failed-run-{i}")
        for i in range(5)
    ]
    usable = [s for s in hist if s["usable"]]
    v_hist = check_series(hist, [_sample_from_line(
        {"metric": "pods_scheduled_per_sec", "value": 100.0}, "selftest")],
        rel_threshold=DEFAULT_REL_THRESHOLD, health=health_ok)
    no_baseline = (not usable) == (v_hist["overall"] == "no-baseline")

    # 4. The two-arm split (ISSUE 20).  Same simulated sick host as 2b,
    #    but the candidate carries an injected ALGORITHMIC regression: a
    #    doubled flops/bytes cost shape (the accidental O(N*P) gather).
    #    The timing arm must downgrade (it cannot trust this host); the
    #    cost arm must still say regression (static cost has a zero
    #    noise floor); the combined verdict must side with the cost arm.
    base_cost = {"flops": 1_000_000, "bytes_accessed": 2_000_000,
                 "peak_bytes": 500_000}
    base_cost["cost_digest"] = costmodel.cost_digest(base_cost)
    bad_cost = {"flops": base_cost["flops"] * 2,
                "bytes_accessed": base_cost["bytes_accessed"] * 2,
                "peak_bytes": base_cost["peak_bytes"]}
    bad_cost["cost_digest"] = costmodel.cost_digest(bad_cost)
    sick = {"healthy": False, "reasons": ["load_high"]}
    v_cost_sick = cost_verdict(base_cost, bad_cost, program="selftest",
                               health=sick)
    split = (
        v_degraded["verdict"] == "degraded-host"        # timing arm yields
        and v_cost_sick["verdict"] == "regression"       # cost arm does not
        and combine_arms(v_degraded["verdict"],
                         v_cost_sick["verdict"]) == "regression"
    )

    # 4b. Pure timing wobble with ZERO cost delta stays quiet on the
    #     cost arm: identical digests short-circuit to ok.
    v_cost_same = cost_verdict(base_cost, dict(base_cost),
                               program="selftest", health=sick)
    cost_quiet = (v_cost_same["verdict"] == "ok"
                  and v_cost_same["max_rel_delta"] == 0.0
                  and combine_arms("ok", v_cost_same["verdict"]) == "ok")

    ok = quiet and flagged and downgraded and no_baseline and split \
        and cost_quiet
    print(json.dumps({
        "sentry": "perf_sentry_selftest",
        "ok": ok,
        "reshuffle_quiet": quiet,
        "injection_flagged": flagged,
        "unhealthy_host_downgraded": downgraded,
        "degenerate_history_no_baseline": no_baseline,
        "cost_arm_overrides_degraded_host": split,
        "cost_arm_zero_delta_quiet": cost_quiet,
        "usable_history_samples": len(usable),
        "injected_factor": round(inject_factor, 6),
        "injection_scaled_to_host_noise": scaled,
        "injected_median_slowdown": v_inject.get("median_slowdown"),
        "noise_floor": v_inject.get("noise_floor"),
        "cost_arm_max_rel_delta": v_cost_sick.get("max_rel_delta"),
    }, sort_keys=True))
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    chk = sub.add_parser("check", help="verdict new runs against history")
    chk.add_argument("--history", action="append", required=True,
                     help="glob of history files; repeatable")
    chk.add_argument("--new", action="append", required=True,
                     help="glob of fresh bench JSON files; repeatable")
    chk.add_argument("--rel-threshold", type=float,
                     default=DEFAULT_REL_THRESHOLD)
    chk.add_argument("--no-probe", action="store_true",
                     help="skip the host-health probe stamp")
    chk.add_argument("--probe-timeout", type=float,
                     default=host_health.DEFAULT_TIMEOUT_S)
    chk.add_argument("--cost-baseline",
                     help="baseline docs/cost_model.json to run the "
                          "deterministic cost arm against (combined "
                          "verdict: cost regression overrides "
                          "degraded-host)")
    chk.add_argument("--cost-candidate", default=None,
                     help="candidate cost manifest (default: the "
                          "committed docs/cost_model.json)")
    chk.set_defaults(fn=cmd_check)

    cst = sub.add_parser("cost", help="deterministic cost-arm verdict "
                                      "between two cost manifests")
    cst.add_argument("--baseline", required=True,
                     help="baseline cost_model.json (e.g. from the "
                          "merge-base commit)")
    cst.add_argument("--candidate", default=None,
                     help="candidate manifest (default: committed "
                          "docs/cost_model.json)")
    cst.set_defaults(fn=cmd_cost)

    st = sub.add_parser("selftest", help="prove sentry properties on "
                                         "real timings; rc 1 on failure")
    st.set_defaults(fn=cmd_selftest)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
