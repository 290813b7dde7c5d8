"""Compiled-cost observatory core (ISSUE 20): deterministic cost telemetry.

A wall-clock number is hostage to the host it was taken on. XLA's own
``cost_analysis()`` and ``memory_analysis()`` are pure functions of the
COMPILED program — the same ints on any machine under any load — so a
cost delta between two commits has a ZERO noise floor. These are counts,
not speed: what a program costs on a chip is measured on the chip
(``chip_smoke.py``, PERF.md). This module is the one copy of that
arithmetic, read by three consumers:

- ``tools/cost_observatory.py`` measures the full 23-program registry
  (the same one ``tools/tpu_lower.py`` / jaxpr_audit / kernel_audit
  share) and commits ``docs/cost_model.json``;
- ``tools/perf_sentry.py`` runs the cost arm: the deterministic second
  verdict that flags an algorithmic regression even on a host where the
  timing arm downgrades to ``degraded-host``;
- the daemon (``__main__.py``) and ``utils/flightrec.py`` stamp runtime
  device-memory watermarks and bundle cost provenance.

Hardware peaks live in ``parallel/vmem.py`` next to the VMEM budget (one
module owns all hardware numbers). The roofline is a step-time FLOOR:
``max(flops / peak_flops, bytes / hbm_bw)`` with the spec-sheet peaks.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import jax

from scheduler_plugins_tpu.parallel.vmem import (
    HBM_BYTES_PER_S,
    PEAK_FLOPS_PER_S,
    VMEM_TARGET,
)

__all__ = [
    "COST_FIELDS",
    "MANIFEST_PATH",
    "compiled_cost",
    "roofline",
    "cost_digest",
    "manifest_digest",
    "load_manifest",
    "budget_violations",
    "default_budgets",
    "device_identity",
    "device_memory_block",
    "stamp_device_memory",
]

#: repo-relative committed manifest (docs/cost_model.json)
MANIFEST_PATH = (
    Path(__file__).resolve().parent.parent.parent / "docs" / "cost_model.json"
)

#: the measured cost fields, in digest order — the cost SHAPE of a program.
#: `generated_code_size` is deliberately excluded: it tracks codegen
#: details (inlining luck, scheduling), not the algorithm.
COST_FIELDS = (
    "flops",
    "transcendentals",
    "bytes_accessed",
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "peak_bytes",
)

#: budgeted subset of COST_FIELDS: the axes an algorithmic regression
#: moves (an accidental O(N*P) gather lands in flops+bytes, a
#: VMEM-spilling reshape in temp/peak bytes)
BUDGET_FIELDS = ("flops", "bytes_accessed", "peak_bytes")

#: review-gated budget headroom over a fresh measurement: wide enough to
#: absorb jax-version codegen drift, tight enough that a doubled
#: collective payload or a quadratic blow-up always breaches
BUDGET_HEADROOM = 1.5


def compiled_cost(fn, args, mesh=None) -> dict:
    """Static cost census of ``fn(*args)`` compiled on the CURRENT backend
    (the observatory runs it on CPU — deterministic per jax version).
    Returns ``{field: int}`` over ``COST_FIELDS``. ``peak_bytes`` is the
    conservative live-set bound argument+output+temp (XLA's CPU memory
    stats expose no tighter peak). Raises whatever lower/compile raises —
    the Mosaic-kernel programs are not CPU-compilable and the caller
    records them static-only."""
    if mesh is not None:
        with jax.set_mesh(mesh):
            compiled = fn.lower(*args).compile()
    else:
        compiled = fn.lower(*args).compile()
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    row = {
        "flops": int(max(ca.get("flops", 0.0), 0.0)),
        "transcendentals": int(max(ca.get("transcendentals", 0.0), 0.0)),
        "bytes_accessed": int(max(ca.get("bytes accessed", 0.0), 0.0)),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
    }
    row["peak_bytes"] = (
        row["argument_bytes"] + row["output_bytes"] + row["temp_bytes"]
    )
    return row


def roofline(
    flops: int, bytes_accessed: int, target: str | None = None
) -> dict:
    """TPU roofline projection for one program's static cost: predicted
    compute-vs-memory-bound verdict and the step-time floor in seconds.
    ``intensity`` is arithmetic intensity (flops/byte); the ``ridge``
    point is where the two roofs meet — below it the program is
    memory-bound on this generation. Pure arithmetic: the decision table
    in tests/test_cost_observatory.py pins it against hand-computed
    oracles."""
    target = target or VMEM_TARGET
    peak = PEAK_FLOPS_PER_S[target]
    bw = HBM_BYTES_PER_S[target]
    flops = max(int(flops), 0)
    bytes_accessed = max(int(bytes_accessed), 0)
    compute_s = flops / peak
    memory_s = bytes_accessed / bw
    ridge = peak / bw
    intensity = flops / bytes_accessed if bytes_accessed else float("inf")
    bound = "compute" if intensity >= ridge else "memory"
    return {
        "target": target,
        "intensity_flops_per_byte": round(intensity, 6)
        if intensity != float("inf") else None,
        "ridge_flops_per_byte": round(ridge, 6),
        "bound": bound,
        "compute_floor_us": round(compute_s * 1e6, 6),
        "memory_floor_us": round(memory_s * 1e6, 6),
        "step_floor_us": round(max(compute_s, memory_s) * 1e6, 6),
    }


def cost_digest(row: dict) -> str:
    """SHA-256 over the canonical cost shape of one program row.

    For CPU-compilable programs this is the COST_FIELDS vector; for the
    Mosaic-kernel programs (static-only rows) it falls back to the TPU
    StableHLO digest joined with the collective census — either way, two
    trees with the same digest have the same compiled cost shape, and an
    algorithmic change moves it. Digests are comparable only under one
    jax version (the manifest pins it, the tpu_lower discipline)."""
    basis: dict = {}
    if row.get(COST_FIELDS[0]) is not None:
        basis["cost"] = [int(row.get(f) or 0) for f in COST_FIELDS]
    if row.get("tpu"):
        basis["tpu_sha256"] = row["tpu"].get("sha256")
    if row.get("collectives"):
        basis["collectives"] = dict(sorted(row["collectives"].items()))
    text = json.dumps(basis, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()


def default_budgets(row: dict) -> dict:
    """Fresh review-gated budgets: BUDGET_HEADROOM over the measured
    value per budget field (ceil to int). Static-only rows (no CPU cost)
    get no budgets — their drift gate is the cost digest."""
    if row.get(BUDGET_FIELDS[0]) is None:
        return {}
    return {
        f: int(-(-int(row[f]) * BUDGET_HEADROOM // 1))
        for f in BUDGET_FIELDS
    }


def budget_violations(row: dict, budgets: dict | None) -> list[str]:
    """Budget-field values of ``row`` exceeding their committed budget.
    Empty budgets (static-only rows) never violate; a MISSING budget for
    a measured field is itself a violation — the gate must fail closed
    when a new cost axis ships unbudgeted."""
    if not budgets:
        return []
    out = []
    for f in BUDGET_FIELDS:
        measured = row.get(f)
        if measured is None:
            continue
        cap = budgets.get(f)
        if cap is None:
            out.append(f"{f}: measured {measured} has no committed budget")
        elif int(measured) > int(cap):
            out.append(f"{f}: measured {measured} exceeds budget {cap}")
    return out


def manifest_digest(manifest: dict) -> str:
    """Content digest of a cost manifest's program section (jax version
    included: cost shapes are only comparable under one pin). Stamped
    into flight-recorder bundles so `tools/replay.py info` can flag a
    bundle recorded under a different cost shape."""
    basis = {
        "jax": manifest.get("jax"),
        "programs": {
            name: row.get("cost_digest")
            for name, row in sorted(manifest.get("programs", {}).items())
        },
    }
    text = json.dumps(basis, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()


def load_manifest(path: str | os.PathLike | None = None) -> dict | None:
    """The committed docs/cost_model.json, or None when absent/unreadable
    (callers are null-safe: a missing manifest fails ONLY the explicit
    `make cost-audit-check` gate)."""
    p = Path(path) if path is not None else MANIFEST_PATH
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Runtime device-memory watermarks
# ---------------------------------------------------------------------------


def device_identity() -> dict:
    """What JAX says it runs on — ``{"platform", "device_kind",
    "count"}`` of ``jax.devices()`` — stamped on the daemon's ready line
    and /healthz and every chip_smoke line, so a number
    can never be read without the device it came from. Initializes the
    backend, and raises its error when the device asked for is absent."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory_block() -> dict:
    """JSON-ready device-memory snapshot for /healthz and the per-cycle
    gauges: per-device ``bytes_in_use`` / ``peak_bytes_in_use`` from the
    backend's allocator stats. The CPU backend reports no stats:
    ``available`` False with null totals."""
    per_device = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        per_device.append({
            "id": d.id,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(
                stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
            ),
        })
    return {
        "backend": jax.default_backend(),
        "available": bool(per_device),
        "bytes_in_use": sum(d["bytes_in_use"] for d in per_device)
        if per_device else None,
        "peak_bytes_in_use": sum(d["peak_bytes_in_use"] for d in per_device)
        if per_device else None,
        "devices": per_device,
    }


def stamp_device_memory(metrics=None) -> dict:
    """Per-cycle watermark stamp: read the allocator stats once and set
    the ``scheduler_device_bytes_in_use`` / ``..._peak_bytes_in_use``
    gauges (last write wins). Returns the /healthz memory block. One
    allocator read per local device and cycle, no transfer, no sync
    (tests/test_cost_observatory.py holds it to that); its cost a tick is
    the daemon's `TickTail/memory` span."""
    block = device_memory_block()
    if metrics is None:
        from scheduler_plugins_tpu.utils import observability as obs

        metrics = obs.metrics
    if block["available"]:
        from scheduler_plugins_tpu.utils import observability as obs

        metrics.set_gauge(obs.DEVICE_BYTES_IN_USE, block["bytes_in_use"])
        metrics.set_gauge(
            obs.DEVICE_PEAK_BYTES, block["peak_bytes_in_use"]
        )
    return block
