"""NodeResourcesAllocatable (Least) with InterPodAffinity's required terms,
as upstream kube-scheduler defines the latter (pkg/scheduler/framework/
plugins/interpodaffinity: filtering.go, scoring.go), one pod at a time.

With `count[t][d]` the pods track t's selector matches in topology domain
`d` (the recorded cycle's `track_base`, plus this cycle's earlier
placements), `carried[e][d]` whether a pod that carries required
anti-affinity term e sits in `d` (`exist_anti_base`, plus this cycle's),
and `domain(k, node)` the node's value of key k (`topo_code`; a node
without the key is in no domain), a node takes pod p only if:

    it fits: the pod's request and one pod slot within its free capacity;
    own anti terms: for each of the pod's required anti-affinity terms
        (track t, key k), the node lacks k or count[t][domain(k, node)] is
        0 — a node without the key passes, as upstream;
    symmetry: for each term e whose selector matches the pod
        (`exist_anti_match`), the node lacks e's key or no carrier of e
        sits in the node's domain;
    required affinity: for each of the pod's required affinity terms, the
        node has k and count[t][domain(k, node)] > 0, or no pod anywhere
        matches the term and the pod matches it itself (the first pod of
        a group is not held back by its own term).

The score is the allocatable score and, where pods carry required affinity
terms, upstream's symmetric hard-affinity score: over the rows e2 of
`sym_*` that are hard and whose selector matches the pod, the carriers in
the node's domain times `hardPodAffinityWeight` (default 1), min-max
normalised over the nodes the pod fits. The allocatable score, its
normalisation and the tie-break are `references/allocatable.py`'s.

A placement takes the node's capacity, adds one to every track whose
selector the pod matches (`pend_match`) in the node's domain under the
track's key, marks the pod's own carried terms there
(`exist_anti_carrier`) and adds its hard rows (`sym_carrier`).

Preferred terms (`waff_*`, the weighted rows of `sym_*`) and spread
constraints are not implemented: a cycle that holds one raises, it is
never ignored. The arrays come under their dotted paths (`scheduling.*`,
`nodes.*`, `pods.*`), in whatever row order and padding the cycle
recorded. A cycle without `scheduling.aff_track` is an allocatable cycle.
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


def solve(x: dict, profile: dict) -> dict:
    if "scheduling.aff_track" not in x:
        return allocatable.solve(x, profile)
    if x["scheduling.waff_mask"].any():
        raise NotImplementedError("a pod of the cycle has a preferred term")
    if "scheduling.spread_mask" in x and x["scheduling.spread_mask"].any():
        raise NotImplementedError("a pod of the cycle has a spread constraint")
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
    w_affinity = _plugin_weight(profile, "InterPodAffinity")
    hard_weight = int(plugin_args(profile, "InterPodAffinity").get(
        "hardPodAffinityWeight", 1
    ))

    topo_code = x["scheduling.topo_code"]
    topo_has = x["scheduling.topo_has"]
    domain_exists = x["scheduling.domain_exists"]
    domain = np.maximum(topo_code, 0)  # (K, N), read where topo_has only
    track_sel = x["scheduling.track_sel"]
    track_topo = x["scheduling.track_topo"]
    pend_match = x["scheduling.pend_match"]
    count = x["scheduling.track_base"].astype(np.int64).copy()
    aff = [x[f"scheduling.aff_{f}"] for f in ("track", "topo", "self", "mask")]
    anti = [x[f"scheduling.anti_{f}"] for f in ("track", "topo", "mask")]

    symmetric = "scheduling.exist_anti_sel" in x
    if symmetric:
        term_topo = x["scheduling.exist_anti_topo"]
        carried = x["scheduling.exist_anti_base"].astype(bool).copy()
        carrier = x["scheduling.exist_anti_carrier"]
        term_match = x["scheduling.exist_anti_match"]
    scored = "scheduling.sym_sel" in x
    if scored:
        sym_hard = x["scheduling.sym_hard"]
        sym_topo = x["scheduling.sym_topo"]
        sym_count = x["scheduling.sym_base"].astype(np.int64).copy()
        sym_carrier = x["scheduling.sym_carrier"]
        soft = ~sym_hard & (x["scheduling.sym_weight"] != 0)
        if (sym_count[soft] != 0).any() or sym_carrier[soft].any():
            raise NotImplementedError("a pod carries a preferred term")
        sym_match = pend_match[x["scheduling.sym_sel"]] & sym_hard[:, None]
        sym_weight = x["scheduling.sym_weight"] * hard_weight

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
        feasible = np.all(demand[None, :] <= free, axis=-1) & node_mask
        track, key, mask = (rows[p] for rows in anti)
        for t, k in zip(track[mask], key[mask]):
            feasible &= ~topo_has[k] | (count[t][domain[k]] == 0)
        if symmetric:
            for e in np.flatnonzero(term_match[:, p]):
                k = term_topo[e]
                feasible &= ~(topo_has[k] & carried[e][domain[k]])
        track, key, own, mask = (rows[p] for rows in aff)
        for t, k, matches_itself in zip(track[mask], key[mask], own[mask]):
            nobody = count[t][domain_exists[k]].sum() == 0
            feasible &= topo_has[k] & (
                (count[t][domain[k]] > 0) | bool(nobody and matches_itself)
            )
        if not feasible.any():
            continue
        total = _minmax(raw, feasible) * w_alloc
        if scored:
            pulls = np.zeros(free.shape[0], np.int64)
            for e2 in np.flatnonzero(sym_match[:, p]):
                k = sym_topo[e2]
                pulls += sym_weight[e2] * np.where(
                    topo_has[k], sym_count[e2][domain[k]], 0
                )
            total = total + _minmax(pulls, feasible) * w_affinity
        choice = int(np.argmax(np.where(feasible, total, NO_NODE)))
        assignment[p] = choice
        free[choice] -= demand
        # the commit: +1 on every track whose selector the pod matches, the
        # pod's own carried terms marked, its hard rows added
        here = topo_code[track_topo, choice]
        counted = pend_match[track_sel, p] & (here >= 0)
        count[np.flatnonzero(counted), here[counted]] += 1
        if symmetric:
            here = topo_code[term_topo, choice]
            marked = carrier[:, p] & (here >= 0)
            carried[np.flatnonzero(marked), here[marked]] = True
        if scored:
            here = topo_code[sym_topo, choice]
            keyed = here >= 0
            sym_count[np.flatnonzero(keyed), here[keyed]] += (
                sym_carrier[keyed, p]
            )
    return {
        "assignment": assignment, "admitted": admitted,
        "wait": np.zeros(P, bool),
    }


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step must move for one pod: what an allocatable step
    moves, plus one `topo_code` row (int32 a node) with its key-presence
    bit, the (track, domain) row of the pod's own term (int64 a domain) and
    the (term, domain) presence row of the term that matches it (a byte a
    domain), with a domain a node under the hostname key, and one count
    and one presence written back."""
    return (
        allocatable.min_bytes_per_pod(n_nodes, n_resources)
        + n_nodes * (4 + 1) + n_nodes * 8 + n_nodes + 8 + 1
    )
