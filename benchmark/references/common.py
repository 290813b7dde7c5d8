"""What the plain references share: the resource axis and Go's arithmetic.

The references are the benchmark's own statement of what a cycle's
placements must be. They are plain numpy, one pod at a time, and import
nothing of the program: a later PR cannot change them.

A reference is given the recorded inputs of one scheduling cycle as a dict
of numpy arrays (N nodes, P pods, R resources, padded rows masked off):

    alloc, requested, capacity   (N, R) int64   node quantities
    node_mask                    (N,)   bool
    req                          (P, R) int64   pod requests
    pod_mask, gated              (P,)   bool
    predicted_cpu_millis         (P,)   int64   (load-aware profiles)
    cpu_tlp, cpu_avg, cpu_std, mem_avg, mem_std      (N,) float64, percent
    cpu_tlp_valid, cpu_valid, mem_valid              (N,) bool
    missing_cpu_millis                               (N,) int64

and returns {"assignment": (P,) int32 node index or -1,
             "admitted": (P,) bool, "wait": (P,) bool}.
"""

from __future__ import annotations

import numpy as np

#: the resource axis: cpu (milli), memory (bytes), ephemeral-storage, pods
CPU, MEMORY, EPHEMERAL, PODS = 0, 1, 2, 3
MAX_NODE_SCORE = 100
MIN_NODE_SCORE = 0
NO_NODE = np.int64(-(2 ** 62))


def plugin_args(profile: dict, plugin: str) -> dict:
    """The plugin's `args` in a {plugins, pluginConfig} profile, or {}."""
    for entry in profile.get("pluginConfig", []):
        if entry["name"] == plugin:
            return entry.get("args", {})
    return {}


def go_div(a, b):
    """Integer division truncating toward zero (Go), b > 0."""
    a = np.asarray(a)
    q = a // b
    r = a - q * b
    return np.where((a < 0) & (r != 0), q + 1, q).astype(a.dtype)


def go_round(x):
    """Go's math.Round: half away from zero, as int64."""
    x = np.asarray(x, np.float64)
    f = np.floor(x)
    pos = np.where(x - f >= 0.5, f + 1, f)
    c = np.ceil(x)
    neg = np.where(c - x >= 0.5, c - 1, c)
    return np.where(x >= 0, pos, neg).astype(np.int64)


def sequential_place(x: dict, score_fn) -> dict:
    """The scheduling cycle every reference shares: pods in queue order;
    a pod fits a node whose free capacity covers its request and one pod
    slot; among the nodes it fits, the highest total score wins, the
    lowest index on a tie; the winner's capacity is taken before the next
    pod is looked at. `score_fn(p, feasible) -> (N,) int64` is the
    profile's weighted, normalized score."""
    free = x["alloc"] - x["requested"]
    node_mask = x["node_mask"]
    P = x["req"].shape[0]
    assignment = np.full(P, -1, np.int32)
    admitted = np.zeros(P, bool)
    for p in range(P):
        admitted[p] = bool(x["pod_mask"][p]) and not bool(x["gated"][p])
        if not admitted[p]:
            continue
        demand = x["req"][p].copy()
        demand[PODS] = 1
        feasible = np.all(demand[None, :] <= free, axis=-1) & node_mask
        if not feasible.any():
            continue
        total = np.where(feasible, score_fn(p, feasible), NO_NODE)
        choice = int(np.argmax(total))
        assignment[p] = choice
        free[choice] -= demand
    return {
        "assignment": assignment, "admitted": admitted,
        "wait": np.zeros(P, bool),
    }
