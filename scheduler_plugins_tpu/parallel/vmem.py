"""Static VMEM envelope model for the Pallas ring kernels (ISSUE 18).

ONE copy of the on-chip budget arithmetic, read by BOTH consumers:

- `parallel.kernels` derives its `PALLAS_MAX_ELECTION_ELEMS` solver gate
  (the static fall-back-to-lax threshold for oversize election payloads)
  from `derive_max_election_elems()` — the constant is no longer
  hand-picked;
- `tools/kernel_audit.py` (KA001) re-computes every traced kernel body's
  worst-case VMEM footprint against the same budget table and re-derives
  the threshold, failing closed if either side drifts.

The model is deliberately simple and conservative — it must UPPER-bound
what Mosaic resident-allocates, not estimate it:

- every kernel-body VMEM ref (block-mapped inputs/outputs + VMEM scratch)
  is resident for the whole body: bytes = prod(block_shape) * itemsize;
- with a nontrivial grid, Mosaic double-buffers each block-mapped operand
  to overlap the HBM copy of step k+1 with step k's compute — 2 copies
  per grid-streamed ref (scratch is never pipelined: 1 copy). The ring
  kernels are gridless today; the factor exists so ROADMAP item 3's
  grid-tiled mega election is checked against the budget it will actually
  occupy;
- semaphores live in semaphore memory, not VMEM: counted separately,
  never charged against the VMEM budget.

Derivation of the election threshold: every `parallel.kernels` ring
program holds `1 (input) + n_out (outputs) + COMM_SLOTS (comm scratch)`
same-shape int32 buffers in VMEM at once (`kernels._ring_call` — the one
shared pallas_call plumbing). The worst family is `ring_offsets` with
n_out = 2 → 6 buffer copies. The threshold is the largest power of two
E with E * worst_copies * 4 bytes <= the target budget; powers of two
keep the padded-buffer compile bucketing stable. At the 16 MiB/core
target this derives 2^19 — equal to the constant PR 13 hand-picked, so
the derivation changed the PROVENANCE of the number, not its value
(docs/kernel_audit.json records both).
"""

from __future__ import annotations

import os

__all__ = [
    "VMEM_BUDGET_BYTES",
    "VMEM_TARGET",
    "COMM_SLOTS",
    "RING_FAMILIES",
    "WORST_RING_COPIES",
    "PEAK_FLOPS_PER_S",
    "HBM_BYTES_PER_S",
    "ROOFLINE_TARGETS",
    "DEVICE_KIND_TARGETS",
    "target_for_device_kind",
    "ring_buffer_copies",
    "derive_max_election_elems",
    "max_election_elems",
]

#: per-core VMEM budget, bytes, by lowering target. ~16 MiB/core on every
#: shipping TPU generation the repo targets (pallas guide §memory-spaces);
#: a per-generation row exists so a smaller-VMEM target can be audited
#: without touching the model.
VMEM_BUDGET_BYTES = {
    "tpu_v4": 16 * 1024 * 1024,
    "tpu_v5e": 16 * 1024 * 1024,
    "tpu_v5p": 16 * 1024 * 1024,
}

#: the STATIC audit tools' lowering target (tools/kernel_audit.py,
#: tools/cost_observatory.py: SPT_VMEM_TARGET re-derives their manifests
#: for another generation — a manifest pins the target it was written
#: for). The solver's election gate is derived for the same target and
#: cross-checked by the kernel auditor; every budget row is 16 MiB today,
#: so the gate holds on each. Peaks for a RUNNING device come from
#: `target_for_device_kind`, never from this default.
VMEM_TARGET = os.environ.get("SPT_VMEM_TARGET", "tpu_v4")

#: `jax.devices()[0].device_kind` -> hardware row. A v5e reports
#: "TPU v5 lite"; a v5p reports "TPU v5" or "TPU v5p" by libtpu version.
DEVICE_KIND_TARGETS = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v5p": "tpu_v5p",
}


def target_for_device_kind(device_kind: str) -> str:
    """The hardware row of the RUNNING device. A device that is not in
    the table is an error, never a default: a roofline share against the
    wrong chip's peaks is worse than none."""
    try:
        return DEVICE_KIND_TARGETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware row for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_KIND_TARGETS)}); add its VMEM budget "
            "and peaks to parallel/vmem.py"
        ) from None

# ---------------------------------------------------------------------------
# Roofline peaks (ISSUE 20): ONE module owns all hardware numbers — the VMEM
# budget above and the chip peaks below — so the kernel auditor and the
# compiled-cost observatory (obs/costmodel.py) can never disagree about what
# "the hardware" is. Public per-chip spec-sheet numbers; deliberately the
# OPTIMISTIC peaks (dense-MXU bf16 FLOP/s, full HBM streams): the roofline
# they induce is a step-time FLOOR, never an estimate. The solver programs
# are int32/f64 vector work, so real chips land well above the floor — the
# committed `roofline_calibration` column on bench lines measures by how
# much, per backend.
# ---------------------------------------------------------------------------

#: peak dense FLOP/s per chip (bf16 MXU — the spec-sheet headline)
PEAK_FLOPS_PER_S = {
    "tpu_v4": 275e12,
    "tpu_v5e": 197e12,
    "tpu_v5p": 459e12,
}

#: HBM bandwidth, bytes/s per chip
HBM_BYTES_PER_S = {
    "tpu_v4": 1.2e12,
    "tpu_v5e": 0.82e12,
    "tpu_v5p": 2.765e12,
}

#: generations with a complete hardware row (VMEM budget + both peaks) —
#: the set a roofline can be projected for
ROOFLINE_TARGETS = tuple(
    sorted(set(VMEM_BUDGET_BYTES) & set(PEAK_FLOPS_PER_S) & set(HBM_BYTES_PER_S))
)

#: 3-slot ring communication buffer (kernels._ring_call scratch): slot k%3
#: receives while slot (k-1)%3 sends and the step k-1 buffer is folded
COMM_SLOTS = 3

#: ring kernel families -> output-buffer count (kernels._ring_call n_out).
#: Every family holds 1 input + n_out outputs + COMM_SLOTS comm slots of
#: ONE padded (H, L) int32 buffer in VMEM; DMA semaphores ride semaphore
#: memory. New ring kernels must add a row — tools/kernel_audit.py KA001
#: cross-checks the table against the traced bodies.
RING_FAMILIES = {
    "ring_offsets": 2,   # (exclusive_prefix, total)
    "elect_min": 1,
    "fused_election": 1,
}

#: worst-case same-shape VMEM buffer copies of any ring family
WORST_RING_COPIES = 1 + max(RING_FAMILIES.values()) + COMM_SLOTS

_INT32_BYTES = 4


def ring_buffer_copies(n_out: int) -> int:
    """Simultaneous whole-payload VMEM buffers of one ring program."""
    return 1 + n_out + COMM_SLOTS


def derive_max_election_elems(
    target: str | None = None, copies: int = WORST_RING_COPIES
) -> int:
    """Largest power-of-two padded int32 element count E whose worst-case
    ring footprint (`copies` same-shape buffers) fits the target VMEM
    budget. Power of two: the (8, 128)-tiled padded buffers bucket
    compile shapes, and a non-power threshold would re-bucket every call
    site on a budget-table tweak."""
    budget = VMEM_BUDGET_BYTES[target or VMEM_TARGET]
    cap = budget // (copies * _INT32_BYTES)
    if cap < 1:
        raise ValueError(
            f"VMEM budget {budget} cannot hold {copies} int32 buffers"
        )
    elems = 1
    while elems * 2 <= cap:
        elems *= 2
    return elems


def max_election_elems() -> int:
    """The solver-gate threshold: derived from the envelope model, with
    the SPT_PALLAS_MAX_ELECTION_ELEMS escape hatch for experiments (the
    kernel auditor refuses to write a manifest under an override — the
    committed number is always the derived one)."""
    override = os.environ.get("SPT_PALLAS_MAX_ELECTION_ELEMS")
    if override is not None:
        return int(override)
    return derive_max_election_elems()
