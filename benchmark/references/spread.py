"""NodeResourcesAllocatable (Least) with PodTopologySpread, as upstream
kube-scheduler defines the latter (pkg/scheduler/framework/plugins/
podtopologyspread: filtering.go, scoring.go), one pod at a time.

For every constraint of the pod, with `count[d]` the pods its selector
matches in topology domain `d` (the recorded cycle's `track_base`, plus
this cycle's earlier placements), over the domains some node carries
(`domain_exists`):

    DoNotSchedule: a node is refused when it lacks the constraint's key, or
        count[domain(node)] + self_match - min(count over the domains)
        exceeds maxSkew; with fewer domains than minDomains the minimum
        counts as 0;
    ScheduleAnyway: the node scores the summed count[domain(node)] of such
        constraints, fewer is better (100 - count x 100 // the largest
        over the nodes the pod fits; 100 everywhere when that is 0).

The allocatable score, its normalisation over the nodes the pod fits (the
spread filter included) and the tie-break are `references/allocatable.py`'s.
A placement takes the node's capacity and adds one to every track whose
selector the pod matches (`pend_match`), in the node's domain under the
track's key.

The arrays come under their dotted paths (`scheduling.*`, `nodes.*`,
`pods.*`), in whatever row order and padding the cycle recorded: a padded
track is in no pod's constraint, a padded domain does not exist. A cycle
without `scheduling.spread_track` is an allocatable cycle.
"""

from __future__ import annotations

import numpy as np

from references import allocatable
from references.common import (
    MAX_NODE_SCORE, MIN_NODE_SCORE, NO_NODE, PODS, go_div, plugin_args,
)

BIG = np.int64(1) << 62


def _plugin_weight(profile: dict, plugin: str) -> int:
    weights = profile.get("weights")
    if weights is None:
        return 1
    return int(weights[profile["plugins"].index(plugin)])


def solve(x: dict, profile: dict) -> dict:
    if "scheduling.spread_track" not in x:
        return allocatable.solve(x, profile)
    args = plugin_args(profile, "NodeResourcesAllocatable")
    weights = np.zeros(x["nodes.alloc"].shape[1], np.int64)
    for name, weight in (
        args.get("resources") or allocatable.DEFAULT_WEIGHTS
    ).items():
        weights[allocatable.AXIS[name]] = weight
    sign = -1 if args.get("mode", "Least") == "Least" else 1
    raw = go_div(
        (sign * x["nodes.alloc"] * weights[None, :]).sum(axis=-1),
        max(int(weights.sum()), 1),
    )
    span = MAX_NODE_SCORE - MIN_NODE_SCORE
    w_alloc = _plugin_weight(profile, "NodeResourcesAllocatable")
    w_spread = _plugin_weight(profile, "PodTopologySpread")

    topo_code = x["scheduling.topo_code"]
    topo_has = x["scheduling.topo_has"]
    domain_exists = x["scheduling.domain_exists"]
    track_sel = x["scheduling.track_sel"]
    track_topo = x["scheduling.track_topo"]
    pend_match = x["scheduling.pend_match"]
    s_track = x["scheduling.spread_track"]
    s_topo = x["scheduling.spread_topo"]
    s_skew = x["scheduling.spread_max_skew"]
    s_hard = x["scheduling.spread_hard"]
    s_self = x["scheduling.spread_self"]
    s_mask = x["scheduling.spread_mask"]
    s_min_domains = x["scheduling.spread_min_domains"]
    counts = x["scheduling.track_base"].astype(np.int64).copy()

    free = x["nodes.alloc"] - x["nodes.requested"]
    node_mask = x["nodes.mask"]
    P, CT = s_track.shape
    N = free.shape[0]
    assignment = np.full(P, -1, np.int32)
    admitted = np.zeros(P, bool)
    for p in range(P):
        admitted[p] = bool(x["pods.mask"][p]) and not bool(x["pods.gated"][p])
        if not admitted[p]:
            continue
        demand = x["pods.req"][p].copy()
        demand[PODS] = 1
        feasible = np.all(demand[None, :] <= free, axis=-1) & node_mask
        soft = np.zeros(N, np.int64)
        any_soft = False
        for c in range(CT):
            if not s_mask[p, c]:
                continue
            k = s_topo[p, c]
            here = counts[s_track[p, c]][np.maximum(topo_code[k], 0)]
            if not s_hard[p, c]:
                soft += np.where(topo_has[k], here, 0)
                any_soft = True
                continue
            exists = domain_exists[k]
            least = counts[s_track[p, c]][exists].min() if exists.any() else BIG
            if 0 < s_min_domains[p, c] and exists.sum() < s_min_domains[p, c]:
                least = 0
            feasible &= topo_has[k] & (
                here + int(s_self[p, c]) - least <= s_skew[p, c]
            )
        if not feasible.any():
            continue
        lo, hi = raw[feasible].min(), raw[feasible].max()
        if hi == lo:
            total = np.full(N, MIN_NODE_SCORE, np.int64) * w_alloc
        else:
            total = ((raw - lo) * span // (hi - lo) + MIN_NODE_SCORE) * w_alloc
        if any_soft:
            most = max(int(soft[feasible].max()), 0)
            scaled = soft * MAX_NODE_SCORE // max(most, 1) if most else 0
            total = total + w_spread * (MAX_NODE_SCORE - scaled)
        choice = int(np.argmax(np.where(feasible, total, NO_NODE)))
        assignment[p] = choice
        free[choice] -= demand
        # the commit: +1 on every track whose selector the pod matches
        domain = topo_code[track_topo, choice]
        counted = pend_match[track_sel, p] & (domain >= 0)
        counts[np.flatnonzero(counted), domain[counted]] += 1
    return {
        "assignment": assignment, "admitted": admitted,
        "wait": np.zeros(P, bool),
    }


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step must move for one pod: what an allocatable step
    moves, plus one `topo_code` row (int32 a node) with its key-presence
    bit, and the zones' counts read and one written back (a handful of
    int64: three domains here, counted as eight, the domain axis's
    bucket)."""
    return (
        allocatable.min_bytes_per_pod(n_nodes, n_resources)
        + n_nodes * (4 + 1) + 8 * 8 + 8
    )
