"""NodeResourcesAllocatable + Coscheduling + CapacityScheduling, one pod at a
time in queue order, in plain numpy; nothing of the program is imported.

Per pod: PreFilter (Coscheduling: the gang has enough members, is not backed
off, not too many of them gated, and, with MinResources, the cluster has
room for it; CapacityScheduling: the namespace's used + request within its
quota's Max, and all quotas' used + request within the sum of their Min),
then the fit, the allocatable score, the lowest index on a tie, and the
commits: node capacity, the gang's placed count, the namespace's usage.
After the last pod, Permit: a placed member of a gang that is still short
of `min_member` (placed now + assigned before) waits.

Reads the dotted inputs `harness.checks.reference_inputs` gives:
`nodes.*`, `pods.req/mask/gated/gang/ns`, `gangs.*`, `quota.*`, over any
number of resource columns (cpu, memory, ephemeral-storage and pods come
first, extended resources such as `nvidia.com/gpu` after them) and any
number of gang and namespace rows: a row no PodGroup or namespace holds has
no member and no quota, and nothing here looks at it.

Promoted from the fixture `tests/fixtures/gangs-mini/`, which keeps its own
copy (PR 26 wrote it; `tests/test_fixture_gangs.py` holds it to the program).
"""

from __future__ import annotations

import numpy as np

from references.common import (
    CPU, MAX_NODE_SCORE, MEMORY, NO_NODE, PODS, go_div, plugin_args,
)

#: upstream's default: a millicore weighs as much as a MiB
DEFAULT_WEIGHTS = {"cpu": 1 << 20, "memory": 1}
AXIS = {"cpu": CPU, "memory": MEMORY}


def _allocatable_score(x: dict, profile: dict):
    """NodeResourcesAllocatable (pkg/noderesources/allocatable.go): the
    weighted sum of a node's allocatable over the sum of the weights,
    negated in mode Least, min-max normalised over the nodes the pod fits."""
    args = plugin_args(profile, "NodeResourcesAllocatable")
    weights = np.zeros(x["nodes.alloc"].shape[1], np.int64)
    for name, weight in (args.get("resources") or DEFAULT_WEIGHTS).items():
        weights[AXIS[name]] = weight
    sign = -1 if args.get("mode", "Least") == "Least" else 1
    raw = go_div((sign * x["nodes.alloc"] * weights[None, :]).sum(axis=-1),
                 max(int(weights.sum()), 1))

    def score(feasible):
        lo, hi = raw[feasible].min(), raw[feasible].max()
        if hi == lo:
            return np.zeros(raw.shape, np.int64)
        return (raw - lo) * MAX_NODE_SCORE // (hi - lo)

    return score


def solve(x: dict, profile: dict) -> dict:
    free = x["nodes.alloc"] - x["nodes.requested"]
    node_mask = x["nodes.mask"]
    req = x["pods.req"]
    P = req.shape[0]
    gang = x.get("pods.gang")
    has_gangs = "gangs.min_member" in x
    has_quota = "quota.max" in x
    if has_gangs:
        placed_now = np.zeros_like(x["gangs.assigned"])
        inflight = np.zeros_like(x["gangs.min_resources"])
    if has_quota:
        used = x["quota.used"].copy()
        with_quota = x["quota.has_quota"]
        agg_min = (x["quota.min"] * with_quota[:, None]).sum(axis=0)
        nominee_placed = np.zeros(x["quota.nom_req"].shape[0], bool)
    score = _allocatable_score(x, profile)
    assignment = np.full(P, -1, np.int32)
    admitted = np.zeros(P, bool)
    for p in range(P):
        ok = bool(x["pods.mask"][p]) and not bool(x["pods.gated"][p])
        g = int(gang[p]) if has_gangs else -1
        if ok and g >= 0:
            total, need = x["gangs.total_members"][g], x["gangs.min_member"][g]
            room = free.sum(axis=0) + x["gangs.cluster_slack"][g] + inflight[g]
            ok = bool(
                total >= need and not x["gangs.backed_off"][g]
                and total - x["gangs.gated"][g] >= need
                and (not x["gangs.has_min_resources"][g]
                     or (x["gangs.min_resources"][g] <= room).all())
            )
        ns = int(x["pods.ns"][p]) if has_quota else -1
        if ok and has_quota and with_quota[ns]:
            live = ~nominee_placed
            in_eq = req[p] + x["quota.nom_req"][
                x["quota.nom_in_eq_mask"][:, p] & live].sum(axis=0)
            in_all = req[p] + x["quota.nom_req"][
                x["quota.nom_total_mask"][:, p] & live].sum(axis=0)
            agg_used = (used * with_quota[:, None]).sum(axis=0)
            ok = not ((used[ns] + in_eq > x["quota.max"][ns]).any()
                      or (agg_used + in_all > agg_min).any())
        admitted[p] = ok
        demand = req[p].copy()
        demand[PODS] = 1
        feasible = np.all(demand[None, :] <= free, axis=-1) & node_mask
        if not ok or not feasible.any():
            continue
        choice = int(np.argmax(np.where(feasible, score(feasible), NO_NODE)))
        assignment[p] = choice
        free[choice] -= demand
        if g >= 0:
            placed_now[g] += 1
            inflight[g] += demand
        if has_quota:
            if with_quota[ns]:
                used[ns] += req[p]
            nominee_placed |= x["quota.nom_batch_idx"] == p
    wait = np.zeros(P, bool)
    if has_gangs:
        quorum = x["gangs.assigned"] + placed_now >= x["gangs.min_member"]
        in_gang = gang >= 0
        wait = (assignment >= 0) & in_gang & ~quorum[np.maximum(gang, 0)]
    return {"assignment": assignment, "admitted": admitted, "wait": wait}


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step of the sequential scan must move for one pod of a
    gang under a quota. As `allocatable`: read the free capacity of every
    node (N x R int64) and its node mask, read the node's static score
    (int64), write back one row. Besides, of the pod's own gang: its
    member counts and flags (min_member, total, gated: 3 int32; backed off,
    has MinResources: 2 bool), its MinResources, slack and in-flight rows
    (3 x R int64), and the write of the placed count (int32) and the
    in-flight row; of its namespace: the used, max, aggregate-used and
    aggregate-min rows (4 x R int64), the has-quota flag, and the write of
    the used row. Sums over all quotas can be carried, so they are not
    counted. The harness passes the canonical four columns
    (`readers/device_trace.py`); a configuration with an extended resource
    moves one column more than this says, so its share reads low, never
    high."""
    node_bytes = n_nodes * (n_resources * 8 + 1 + 8) + n_resources * 8
    gang_bytes = 3 * 4 + 2 + 3 * n_resources * 8 + 4 + n_resources * 8
    quota_bytes = 4 * n_resources * 8 + 1 + n_resources * 8
    return node_bytes + gang_bytes + quota_bytes
