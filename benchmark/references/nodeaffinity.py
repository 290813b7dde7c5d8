"""NodeResourcesAllocatable (Least) with NodeAffinity, as upstream
kube-scheduler defines the latter (pkg/scheduler/framework/plugins/
nodeaffinity/node_affinity.go), one pod at a time in queue order.

With `ok[t]` the verdict of required spec t over the nodes (the recorded
cycle's `scheduling.node_term_ok`: nodeSelector AND the OR of the required
terms, evaluated on the host) and `pref[u]` the summed weights of the
preferred terms of spec u that each node matches (`scheduling.pref_score`),
a node takes pod p only if:

    it fits: the pod's request and one pod slot within its free capacity;
    required: ok[pod_node_term[p]] holds for it.

Its score is the allocatable score (raw score, min-max normalisation over
the nodes the pod fits and tie-break are `references/allocatable.py`'s)
plus NodeAffinity's: pref[pod_pref[p]], normalised as upstream's
DefaultNormalizeScore does, the maximum over the feasible nodes to 100 in
Go integer division, all zero where the maximum is zero; each times its
plugin's weight (1 unless the profile gives `weights`). The winner's
capacity is taken before the next pod is looked at.

What it does not implement it refuses: a cycle that holds a selector table
(`scheduling.pend_match`: topology spread, pod (anti-)affinity) or a
toleration row that refuses or penalises a node raises; nothing is ever
ignored. A cycle without `scheduling.node_term_ok` is an allocatable cycle.
The arrays come under their dotted paths, in whatever row order and padding
the cycle recorded.
"""

from __future__ import annotations

import numpy as np

from references import allocatable
from references.common import (
    MAX_NODE_SCORE, MIN_NODE_SCORE, NO_NODE, PODS, go_div, plugin_args,
)


def _plugin_weight(profile: dict, plugin: str) -> int:
    weights = profile.get("weights")
    if weights is None:
        return 1
    return int(weights[profile["plugins"].index(plugin)])


def _minmax(raw: np.ndarray, feasible: np.ndarray) -> np.ndarray:
    lo, hi = raw[feasible].min(), raw[feasible].max()
    if hi == lo:
        return np.full(raw.shape, MIN_NODE_SCORE, np.int64)
    span = MAX_NODE_SCORE - MIN_NODE_SCORE
    return (raw - lo) * span // (hi - lo) + MIN_NODE_SCORE


def _max_to_100(raw: np.ndarray, feasible: np.ndarray) -> np.ndarray:
    """DefaultNormalizeScore, not reversed: score * 100 / max."""
    top = int(raw[feasible].max())
    if top <= 0:
        return np.zeros(raw.shape, np.int64)
    return go_div(raw * MAX_NODE_SCORE, top)


def solve(x: dict, profile: dict) -> dict:
    if "scheduling.node_term_ok" not in x:
        return allocatable.solve(x, profile)
    if "scheduling.pend_match" in x:
        raise NotImplementedError("the cycle holds a selector table")
    if not x["scheduling.tol_ok"].all() or x["scheduling.tol_prefer"].any():
        raise NotImplementedError("a toleration row refuses or penalises")
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
    w_alloc = _plugin_weight(profile, "NodeResourcesAllocatable")
    w_affinity = _plugin_weight(profile, "NodeAffinity")

    ok = x["scheduling.node_term_ok"].astype(bool)
    pref = x["scheduling.pref_score"].astype(np.int64)
    pod_term = x["scheduling.pod_node_term"]
    pod_pref = x["scheduling.pod_pref"]

    free = x["nodes.alloc"] - x["nodes.requested"]
    node_mask = x["nodes.mask"]
    P = x["pods.req"].shape[0]
    assignment = np.full(P, -1, np.int32)
    admitted = np.zeros(P, bool)
    for p in range(P):
        admitted[p] = bool(x["pods.mask"][p]) and not bool(x["pods.gated"][p])
        if not admitted[p]:
            continue
        demand = x["pods.req"][p].copy()
        demand[PODS] = 1
        feasible = (
            np.all(demand[None, :] <= free, axis=-1) & node_mask
            & ok[pod_term[p]]
        )
        if not feasible.any():
            continue
        total = (
            _minmax(raw, feasible) * w_alloc
            + _max_to_100(pref[pod_pref[p]], feasible) * w_affinity
        )
        choice = int(np.argmax(np.where(feasible, total, NO_NODE)))
        assignment[p] = choice
        free[choice] -= demand
    return {
        "assignment": assignment, "admitted": admitted,
        "wait": np.zeros(P, bool),
    }


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step must move for one pod: what an allocatable step
    moves, plus the pod's verdict row (a byte a node) and its preference
    row (int64 a node)."""
    return (
        allocatable.min_bytes_per_pod(n_nodes, n_resources)
        + n_nodes + 8 * n_nodes
    )
