"""Declared static magnitude bounds on the solver's input families
(ISSUE 18, KA003).

The repo's exactness story rests on two documented numeric facts that
until now lived only in comments (`ops/numa.py`, `ops/assign.py`,
`parallel/kernels.py`): float64 arithmetic on integers is EXACT below
2^53, and every resource-quantity aggregation the solver performs stays
below that line. `tools/kernel_audit.py` turns the second fact into a
checked one: it propagates the bounds declared HERE through the traced
programs (casts, sums, cumsums, dot_generals, scan carries) with an
interval lattice and flags any float64 accumulation of exact integer
quantities — or any int32 demotion — it cannot prove in-range.

Two kinds of declaration:

- **per-element bounds** (`LABEL_BOUNDS`): a regex over input-leaf
  provenance labels (`tools/jaxpr_audit.label_leaves` vocabulary —
  `snap.pods.req`, `state.free`, ...) → the max-abs bound of one
  element. Resource quantities are int64 in reference units (cpu
  millicores, memory bytes); `QUANTITY_ELEM_MAX` = 2^38 caps one
  element at 256 GiB / 274M cores — beyond any single node the
  reference supports. int32/bool leaves need no row (their dtype is
  the bound); int64/float leaves without a row audit as UNKNOWN and
  cannot prove anything downstream.
- **the aggregation invariant** (`QUANTITY_SUM_MAX`): sums, prefix
  sums and shard-psums of DISJOINT quantity elements stay < 2^53
  because the cluster total does — quota caps and the capacity audit
  enforce `used <= quota max <= sum(capacity)` at runtime, and
  2^53 reference units is ~9 PB / 9T millicores of cluster. When the
  naive interval product (elements x axis length) overflows 2^53 on a
  quantity aggregation, the auditor substitutes this declared cap and
  RECORDS THE ASSUMPTION in docs/kernel_audit.json — the manifest
  shows exactly which claims rest on the invariant rather than on
  arithmetic.

Blessed exactness helpers (`EXACT_FN_BOUNDS`): jitted helpers whose
exactness argument is structural, not interval-provable — base-2^18
limb recombination reconstructs the ORIGINAL < 2^53 value even though
the naive interval on `l2 * 2^36` overflows. They are audited at the
call boundary (declared result bound, assumption recorded) and are the
only sanctioned way to cast unproven int64 quantities to float64
(graft_lint GL013 enforces the source-level half of that contract).
"""

from __future__ import annotations

import re

__all__ = [
    "QUANTITY_ELEM_MAX",
    "QUANTITY_SUM_MAX",
    "F64_EXACT_MAX",
    "I32_MAX",
    "NUMA_DISTANCE_MAX",
    "NETWORK_COST_MAX",
    "LABEL_BOUNDS",
    "EXACT_FN_BOUNDS",
    "leaf_bound",
    "is_quantity_label",
]

#: float64 represents every integer strictly below 2^53 exactly
F64_EXACT_MAX = 1 << 53
#: int32 range (the demotion-safety line for KA003's second check)
I32_MAX = 1 << 31

#: one resource-quantity element (int64 reference units): 2^38 covers a
#: 256 GiB node memory row or 274M millicores — no single element the
#: reference's quantity parsing produces exceeds it
QUANTITY_ELEM_MAX = 1 << 38

#: the declared aggregation invariant: any sum of disjoint quantity
#: elements is bounded by the cluster total, kept < 2^53 by the runtime
#: quota/capacity caps (ops/assign.py, ops/numa.py document the same
#: fact per call site; kernels.py's limb scheme is sized to it)
QUANTITY_SUM_MAX = (1 << 53) - 1

#: NUMA distance matrix entries are SLIT-style small ints (<= 100;
#: ops/numa.py documents the table), declared tighter than their int32
#: dtype so distance-weighted sums stay provable
NUMA_DISTANCE_MAX = 100

#: network cost thresholds / cost-table entries: ops/network.py keeps
#: tallies in int32 and float32 dot_generals and documents "every tally
#: is bounded by MAX_COST * total placed pods, far inside int32" — that
#: argument needs the per-entry cost cap declared here
NETWORK_COST_MAX = 1 << 24

#: (label regex, max-abs bound, kind) — kind "elem" marks the leaf a
#: per-element resource quantity (eligible for the aggregation
#: invariant AND in scope for KA003's flags); kind "plain" is a bound
#: with no quantity semantics. First match wins; labels are the
#: `label_leaves` vocabulary. Keep rows FULL-label anchored — a loose
#: suffix match that silently blesses a new field defeats the audit.
LABEL_BOUNDS = (
    # -- per-element resource quantities (int64 reference units) --------
    (r"^(snap|state)\.nodes\.(alloc|capacity|requested|nonzero_requested"
     r"|limits)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^snap\.pods\.(req|container_req|limits|predicted_cpu_millis)$",
     QUANTITY_ELEM_MAX, "elem"),
    (r"^snap\.quota\.(min|max|used|nom_req)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^snap\.numa\.(allocatable|available)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^snap\.ranks\.(rank_req|quota_max)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^snap\.gangs\.(min_resources|cluster_slack)$",
     QUANTITY_ELEM_MAX, "elem"),
    # network max-cost thresholds are CONFIG cost caps compared against
    # the small zone/region cost tables — not resource quantities. The
    # bound backs ops/network.py's int32 internals and its "f32 tallies
    # are exact (counts < 2^24)" precondition.
    (r"^snap\.network\.(dep_max_cost|cls_dep_max_cost)$",
     NETWORK_COST_MAX, "plain"),
    (r"^state\.(free|eq_used|gang_inflight)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^state\.side\.(gang_slack|quota_used)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^state\.numa_avail$", QUANTITY_ELEM_MAX, "elem"),
    # serving delta/upsert columns (the packed int64 quantity columns of
    # serving_delta_apply / serving_side_apply)
    (r"^up\.(alloc|capacity)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^d\.(requested|nonzero|limits)$", QUANTITY_ELEM_MAX, "elem"),
    (r"^sd\.(g_slack|q_used)$", QUANTITY_ELEM_MAX, "elem"),
    # ring-election payloads (the pallas kernel programs' positional
    # args): exact quantities or quantity prefix sums by contract —
    # already aggregated once, so declared at the SUM cap, kind elem
    # keeps them in KA003 scope
    (r"^elect\.", QUANTITY_SUM_MAX, "elem"),
    # -- bounded non-quantity int64 families ----------------------------
    (r"^snap\.pods\.priority$", I32_MAX - 1, "plain"),
    (r"^snap\.(pods|gangs)\.creation_ms$", 1 << 45, "plain"),
    (r"^snap\.scheduling\.(pref_score|tol_prefer|waff_weight|track_base"
     r"|spread_max_skew|spread_min_domains)$", I32_MAX - 1, "plain"),
    (r"^snap\.numa\.distances$", NUMA_DISTANCE_MAX, "plain"),
    (r"^state\.sel_dom_counts$", I32_MAX - 1, "plain"),
    # its node-space view and the symmetric carriers': the same counts of
    # pods, one per node where the table has one per domain
    (r"^state\.(sel_dom_view|sym_view)$", I32_MAX - 1, "plain"),
    # the resident selector tables and their packed +-1 rows
    # (serving_selector_apply): counts of pods, never quantities
    (r"^state\.sel\[\d\]$", I32_MAX - 1, "plain"),
    (r"^sel\.delta$", I32_MAX - 1, "plain"),
    # plugin weight vectors ride the aux channel as small int64 config
    # scalars (profile weights are <= 2^20 by construction — framework
    # normalizes weights to the reference's int32 plugin-weight range)
    (r"^aux\.weights$", 1 << 20, "plain"),
    (r"^aux(\.|\[)", QUANTITY_ELEM_MAX, "plain"),
    # cfg6 raw score tensor: plugin scores are weight * normalized-score
    # products, bounded well under 2^45 by the weight cap above
    (r"^score_raw$", 1 << 45, "plain"),
)

_COMPILED = tuple(
    (re.compile(pat), bound, kind) for pat, bound, kind in LABEL_BOUNDS
)

#: blessed exactness helpers: jitted-function name -> declared max-abs
#: result bound. The auditor assigns the declared bound (exact integer,
#: quantity kind) at the jit call boundary and records the assumption;
#: graft_lint GL013 blesses the same names at the source level.
EXACT_FN_BOUNDS = {
    # base-2^18 limb recombination (parallel/kernels.py join_limbs):
    # reconstructs the original value, which is a quantity prefix sum
    # < 2^53 by the aggregation invariant; the naive interval on
    # l2 * 2^36 cannot see that
    "join_limbs": QUANTITY_SUM_MAX,
    # utils/intmath.py exact_f64: the sanctioned int64 -> float64 cast
    # for values the caller asserts are quantity-scale (< 2^53)
    "exact_f64": QUANTITY_SUM_MAX,
    # ops/allocatable.py demote_scores_int32: the order-preserving int64
    # -> int32 score demotion — its < 2^23 result magnitude is enforced
    # by a DYNAMIC right shift, structural rather than interval-provable
    "demote_scores_int32": 1 << 24,
}


def _dtype_cap(dtype: str):
    if dtype == "bool":
        return 1
    if dtype in ("int32", "uint32"):
        return I32_MAX - 1
    if dtype in ("int8", "uint8", "int16", "uint16"):
        return (1 << 16) - 1
    return None


def leaf_bound(label: str, dtype: str):
    """(max-abs bound or None, kind) for one input leaf: the tighter of
    the declared row and the dtype's own range (a declared quantity row
    on an int32 leaf keeps the int32 cap). int64/float leaves without a
    row are UNKNOWN (bound None) — nothing downstream of them can be
    proven exact."""
    declared, kind = None, "plain"
    for rx, bound, k in _COMPILED:
        if rx.match(label):
            declared, kind = bound, k
            break
    cap = _dtype_cap(dtype)
    if declared is None:
        return cap, kind
    if cap is None:
        return declared, kind
    return min(declared, cap), kind


def is_quantity_label(label: str) -> bool:
    """True when the label is a declared per-element resource quantity
    (the taint family KA003's flags are scoped to)."""
    for rx, _bound, kind in _COMPILED:
        if rx.match(label):
            return kind == "elem"
    return False
