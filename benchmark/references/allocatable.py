"""NodeResourcesAllocatable, as upstream scheduler-plugins defines it
(pkg/noderesources/allocatable.go, resource_allocation.go).

A node's raw score is the weighted sum of its allocatable resources over
the sum of the weights, negated in mode Least, in Go integer division.
Over the nodes a pod fits, raw scores are min-max normalised to [0, 100]
(all 0 when they are equal). The score rates the node, never the pod.
"""

from __future__ import annotations

import numpy as np

from references.common import (
    CPU, MAX_NODE_SCORE, MEMORY, MIN_NODE_SCORE, go_div, plugin_args,
    sequential_place,
)

#: upstream's default: a millicore weighs as much as a MiB
DEFAULT_WEIGHTS = {"cpu": 1 << 20, "memory": 1}
AXIS = {"cpu": CPU, "memory": MEMORY}


def solve(x: dict, profile: dict) -> dict:
    args = plugin_args(profile, "NodeResourcesAllocatable")
    weights = np.zeros(x["alloc"].shape[1], np.int64)
    for name, weight in args.get("resources") or DEFAULT_WEIGHTS.items():
        weights[AXIS[name]] = weight
    sign = -1 if args.get("mode", "Least") == "Least" else 1
    raw = go_div(
        (sign * x["alloc"] * weights[None, :]).sum(axis=-1),
        max(int(weights.sum()), 1),
    )
    span = MAX_NODE_SCORE - MIN_NODE_SCORE

    def score(_p, feasible):
        lo, hi = raw[feasible].min(), raw[feasible].max()
        if hi == lo:
            return np.full(raw.shape, MIN_NODE_SCORE, np.int64)
        return (raw - lo) * span // (hi - lo) + MIN_NODE_SCORE

    return sequential_place(x, score)


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step of the sequential scan must move for one pod: read
    the free capacity of every node (N x R int64) and its node mask, read
    the node's static score (int64), write back one row."""
    return n_nodes * (n_resources * 8 + 1 + 8) + n_resources * 8

