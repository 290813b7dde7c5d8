"""NodeResourcesAllocatable score (Score-only plugin).

Reference behavior (/root/reference/pkg/noderesources/allocatable.go:117-168,
resource_allocation.go:49-100): per node,

    nodeScore = ( sum_r sign * allocatable_r * weight_r ) / sum_r weight_r

with sign = -1 for Least mode, +1 for Most, Go integer division (truncates
toward zero — scores are negative in Least mode), then min-max normalized to
[0, 100]. Default weights: cpu(milli) 1<<20, memory(bytes) 1
(resource_allocation.go:36). The score depends only on node allocatables, so
the whole (P, N) matrix is one broadcast row per cycle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from scheduler_plugins_tpu.ops.normalize import minmax_normalize
from scheduler_plugins_tpu.utils.intmath import go_div

MODE_LEAST = -1
MODE_MOST = 1


def allocatable_scores(alloc, weights, mode_sign=MODE_LEAST):
    """(N, R) allocatable x (R,) weights -> (N,) raw scores (pre-normalize)."""
    alloc = jnp.asarray(alloc)
    weights = jnp.asarray(weights, dtype=jnp.int64)
    weight_sum = jnp.maximum(weights.sum(), 1)
    node_score = (mode_sign * alloc * weights[None, :]).sum(axis=-1)
    return go_div(node_score, weight_sum)


@jax.jit
def demote_scores_int32(raw):
    """Order-preserving demotion of raw int64 scores to int32 for the heavy
    (P, N) normalize (int64 is emulated u32 pairs on TPU): a dynamic right
    shift squeezes magnitudes under 2^23 so (score - lo) * 100 cannot
    overflow int32 for ANY weight configuration. Shifting may merge
    near-ties; the sequential parity path stays full int64.

    A named jit boundary ON PURPOSE (XLA inlines it — no runtime cost):
    the < 2^23 result bound is enforced by the DYNAMIC shift, which an
    interval lattice cannot see, so `tools/kernel_audit.py` KA003
    blesses the jit call by name via `api.bounds.EXACT_FN_BOUNDS`
    (declared result bound 2^24) instead of flagging the demotion."""
    max_abs = jnp.max(jnp.abs(raw))
    bits = jnp.ceil(jnp.log2(max_abs.astype(jnp.float64) + 1.0))
    shift = jnp.maximum(bits - 23, 0).astype(jnp.int64)
    return (raw >> shift).astype(jnp.int32)


def allocatable_score_matrix(alloc, weights, mode_sign, feasible):
    """Full plugin output: (P, N) normalized scores given (P, N) feasibility.

    Normalization runs per pod over that pod's feasible nodes, mirroring the
    framework calling NormalizeScore on each pod's NodeScoreList.
    """
    raw = allocatable_scores(alloc, weights, mode_sign)  # (N,)
    per_pod = jnp.broadcast_to(raw[None, :], feasible.shape)
    return minmax_normalize(per_pod, feasible)
