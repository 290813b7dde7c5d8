"""Compiled-cost observatory: the static FLOP/byte/memory census (ISSUE 20).

Walks the SAME 23-program registry that tools/tpu_lower.py, jaxpr_audit
and kernel_audit share (`tpu_lower.PROGRAMS` — one registry, four
auditors), compiles each program on the deterministic CPU backend, and
records XLA's own `cost_analysis()` / `memory_analysis()` numbers joined
with the three static censuses the repo already commits:

- the TPU StableHLO op histogram + digest (docs/tpu_lowering.json),
- the collective census from `parallel/solver.collective_census` for the
  mesh programs (per-wave psum/ppermute/dma counts),
- the Pallas VMEM envelopes from docs/kernel_audit.json,

then projects a TPU roofline bound per program (peaks owned by
`parallel/vmem.py`, next to the VMEM budget): compute-vs-memory-bound
verdict and step-time floor (a projection from counts, not a timing).

The three Mosaic-kernel programs cannot CPU-compile (`Only interpret
mode is supported on CPU backend`) and get STATIC-ONLY rows: null CPU
cost, digest based on the TPU StableHLO sha + collective census — still
counted toward 23/23 coverage, still drift-gated.

Manifest discipline (the tpu_lower pattern):

- `python tools/cost_observatory.py` re-measures everything and refreshes
  docs/cost_model.json — ONLY on a fully-clean full-registry run.
  Budgets are review-gated: carried forward from the committed manifest
  (a refresh can't silently launder a breach); `--rebudget` re-derives
  them at BUDGET_HEADROOM over fresh measurements.
- `--check` (make cost-audit-check) is read-only and fail-closed:
  missing manifest, coverage gap, budget breach, or cost-digest drift
  (enforced only under the manifest's pinned jax version — codegen
  differs across versions; CI pins jax to the manifest's pin) all exit
  non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

import tpu_lower  # noqa: E402  (shared registry + CPU bootstrap)

from scheduler_plugins_tpu.obs import costmodel  # noqa: E402

MANIFEST = costmodel.MANIFEST_PATH
TPU_LOWERING = REPO / "docs" / "tpu_lowering.json"
KERNEL_AUDIT = REPO / "docs" / "kernel_audit.json"

#: Mosaic-kernel programs: pallas_call lowers only in interpret mode on
#: the CPU backend, so there is no CPU compile to cost — their rows are
#: static-only (TPU digest + census + VMEM envelope), by design.
STATIC_ONLY = {
    "sharded_wave_chunk_pallas": "mosaic-kernel-not-cpu-compilable",
    "pallas_ring_offsets": "mosaic-kernel-not-cpu-compilable",
    "pallas_fused_election": "mosaic-kernel-not-cpu-compilable",
}


def _load(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def measure(name: str, tpu_manifest: dict, kernel_manifest: dict) -> dict:
    """One program's full cost row (compile + joins + roofline)."""
    import jax

    from scheduler_plugins_tpu.parallel.solver import collective_census

    fn, args, mesh = tpu_lower.PROGRAMS[name]()
    row: dict = {f: None for f in costmodel.COST_FIELDS}

    if name in STATIC_ONLY:
        row["static_only"] = STATIC_ONLY[name]
    else:
        row["static_only"] = None
        row.update(costmodel.compiled_cost(fn, args, mesh))

    # collective census: the mesh programs' per-wave collective counts,
    # plus the Mosaic programs (their pallas_call/dma_start equations are
    # the ring transfers the roofline can't see)
    if mesh is not None or name in STATIC_ONLY:
        if mesh is not None:
            with jax.set_mesh(mesh):
                census = collective_census(fn, *args)
        else:
            census = collective_census(fn, *args)
        row["collectives"] = {k: int(v) for k, v in sorted(census.items())}
    else:
        row["collectives"] = {}

    # TPU StableHLO join (committed, separately gated by tpu-lower-check)
    tpu_row = tpu_manifest.get("programs", {}).get(name)
    if tpu_row:
        row["tpu"] = {
            "sha256": tpu_row["sha256"],
            "stablehlo_bytes": int(tpu_row["stablehlo_bytes"]),
            "ops_total": int(sum(tpu_row.get("ops", {}).values())),
        }
    else:
        row["tpu"] = None

    # Pallas VMEM envelope join (committed, gated by kernel-audit-check)
    kernels = (
        kernel_manifest.get("programs", {}).get(name, {}).get("kernels", [])
    )
    row["kernels"] = [
        {
            "name": k["name"],
            "vmem_bytes": int(k["vmem_bytes"]),
            "budget_bytes": int(k["budget_bytes"]),
            "payload_copies": int(k["payload_copies"]),
        }
        for k in kernels
    ]

    if row["flops"] is not None:
        row["roofline"] = costmodel.roofline(
            row["flops"], row["bytes_accessed"]
        )
    else:
        row["roofline"] = None

    row["cost_digest"] = costmodel.cost_digest(row)
    return row


def _hardware_block() -> dict:
    from scheduler_plugins_tpu.parallel import vmem

    t = vmem.VMEM_TARGET
    return {
        "target": t,
        "peak_flops_per_s": vmem.PEAK_FLOPS_PER_S[t],
        "hbm_bytes_per_s": vmem.HBM_BYTES_PER_S[t],
        "vmem_budget_bytes": vmem.VMEM_BUDGET_BYTES[t],
    }


def run(names: list[str], check: bool, rebudget: bool = False) -> int:
    import jax

    prior = _load(MANIFEST)
    tpu_manifest = _load(TPU_LOWERING)
    kernel_manifest = _load(KERNEL_AUDIT)
    full_set = list(names) == list(tpu_lower.PROGRAMS)

    if check:
        if not prior:
            print(f"[cost-audit] FAIL: missing manifest {MANIFEST}")
            return 1
        missing = sorted(set(tpu_lower.PROGRAMS) - set(prior.get("programs", {})))
        if missing:
            print(f"[cost-audit] FAIL: manifest missing programs: {missing}")
            return 1

    same_jax = prior.get("jax") == jax.__version__
    if check and not same_jax:
        print(
            f"[cost-audit] jax {jax.__version__} != manifest pin "
            f"{prior.get('jax')}: digest drift not comparable, budgets "
            "still enforced"
        )

    results, failures = {}, []
    for name in names:
        print(f"[cost-audit] {name} ...", flush=True)
        try:
            row = measure(name, tpu_manifest, kernel_manifest)
        except Exception as exc:  # a cost-compile failure IS the gate
            failures.append(f"{name}: cost measurement failed: {exc!r}")
            continue

        prior_row = prior.get("programs", {}).get(name, {})
        if rebudget or not prior_row.get("budgets"):
            budgets = costmodel.default_budgets(row)
        else:
            budgets = prior_row["budgets"]
        row["budgets"] = budgets

        for v in costmodel.budget_violations(row, budgets):
            failures.append(f"{name}: budget violation: {v}")

        if check and same_jax:
            committed = prior_row.get("cost_digest")
            if committed != row["cost_digest"]:
                failures.append(
                    f"{name}: cost drift: measured digest "
                    f"{row['cost_digest'][:12]} != committed "
                    f"{str(committed)[:12]} (refresh via `make cost-audit` "
                    "and review the delta)"
                )

        results[name] = row
        rl = row["roofline"]
        desc = (
            f"{rl['bound']}-bound, floor {rl['step_floor_us']:.1f}us"
            if rl
            else f"static-only ({row['static_only']})"
        )
        print(
            f"[cost-audit] {name}: flops={row['flops']} "
            f"bytes={row['bytes_accessed']} peak={row['peak_bytes']} "
            f"[{desc}]"
        )

    for f in failures:
        print(f"[cost-audit] FAIL: {f}")

    if check:
        print(
            f"[cost-audit] check: {len(results)}/{len(names)} measured, "
            f"{len(failures)} failures"
        )
        return 1 if failures else 0

    if failures:
        print("[cost-audit] NOT writing manifest (failures above)")
        return 1
    if not full_set:
        print(
            "[cost-audit] partial run (--programs): NOT writing manifest; "
            "refresh requires the full registry"
        )
        return 0
    manifest = {
        "jax": jax.__version__,
        "platform": "cpu",
        "hardware": _hardware_block(),
        "programs": {k: results[k] for k in sorted(results)},
    }
    MANIFEST.write_text(json.dumps(manifest, indent=1, sort_keys=True) + "\n")
    n_static = sum(1 for r in results.values() if r["static_only"])
    print(
        f"[cost-audit] wrote {MANIFEST.relative_to(REPO)}: "
        f"{len(results)} programs ({n_static} static-only), "
        f"manifest digest {costmodel.manifest_digest(manifest)[:12]}"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--check", action="store_true",
        help="read-only fail-closed gate: re-measure and compare against "
             "the committed manifest (budgets always; digests under the "
             "pinned jax version)")
    ap.add_argument(
        "--programs",
        help="comma-separated subset (refresh still requires a full run "
             "to write the manifest)")
    ap.add_argument(
        "--rebudget", action="store_true",
        help="re-derive review-gated budgets at the standard headroom "
             "over fresh measurements (default: carry committed budgets "
             "forward)")
    args = ap.parse_args(argv)

    tpu_lower.bootstrap()
    if args.programs:
        names = [n.strip() for n in args.programs.split(",") if n.strip()]
        unknown = [n for n in names if n not in tpu_lower.PROGRAMS]
        if unknown:
            ap.error(f"unknown programs: {unknown}")
    else:
        names = list(tpu_lower.PROGRAMS)
    return run(names, check=args.check, rebudget=args.rebudget)


if __name__ == "__main__":
    sys.exit(main())
