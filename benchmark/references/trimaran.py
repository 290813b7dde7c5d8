"""TargetLoadPacking + LoadVariationRiskBalancing, as upstream
scheduler-plugins defines them (pkg/trimaran/targetloadpacking,
pkg/trimaran/loadvariationriskbalancing), at their default arguments
unless the profile says otherwise. Both score only; neither normalises.

TargetLoadPacking: predicted CPU utilisation of the node with the pod on
it, in percent of capacity; the score rises linearly from the target to 100
at the target utilisation, falls to 0 at 100 %, and is 0 beyond; a node
without a CPU metric scores 0.

LoadVariationRiskBalancing: risk = (mu + margin * sigma^(1/sensitivity)) / 2
for CPU and for memory; score = (1 - risk) * 100, the smaller of the two
where both metrics exist.
"""

from __future__ import annotations

import numpy as np

from references.common import (
    CPU, MEMORY, go_round, plugin_args, sequential_place,
)


def solve(x: dict, profile: dict) -> dict:
    tlp = plugin_args(profile, "TargetLoadPacking")
    lvrb = plugin_args(profile, "LoadVariationRiskBalancing")
    target = float(tlp.get("targetUtilization", 40))
    margin = float(lvrb.get("safeVarianceMargin", 1.0))
    sensitivity = float(lvrb.get("safeVarianceSensitivity", 1.0))

    cap_cpu = x["capacity"][:, CPU].astype(np.float64)
    alloc_cpu = x["alloc"][:, CPU]
    alloc_mem = x["alloc"][:, MEMORY]

    def score(p, _feasible):
        return _tlp(x, cap_cpu, float(x["predicted_cpu_millis"][p]), target) + (
            _lvrb(x, alloc_cpu, alloc_mem, x["req"][p], margin, sensitivity)
        )

    return sequential_place(x, score)


def _tlp(x, cap, pod_millis, target):
    used = x["cpu_tlp"] / 100.0 * cap
    with np.errstate(divide="ignore", invalid="ignore"):
        predicted = np.where(
            cap != 0,
            100.0 * (used + x["missing_cpu_millis"] + pod_millis)
            / np.maximum(cap, 1.0),
            0.0,
        )
    rising = go_round((100.0 - target) * predicted / target + target)
    falling = go_round(target * (100.0 - predicted) / (100.0 - target))
    score = np.where(
        predicted > target, np.where(predicted > 100.0, 0, falling), rising
    )
    return np.where(x["cpu_tlp_valid"], score, 0).astype(np.int64)


def _risk(avg_pct, std_pct, capacity, request, margin, sensitivity):
    cap = capacity.astype(np.float64)
    used = np.clip(avg_pct / 100.0 * cap, 0.0, cap)
    stdev = np.clip(std_pct / 100.0 * cap, 0.0, cap)
    request = max(float(request), 0.0)
    mu = np.clip((used + request) / np.maximum(cap, 1.0), 0.0, 1.0)
    sigma = np.clip(stdev / np.maximum(cap, 1.0), 0.0, 1.0)
    if sensitivity == 0:
        sigma = np.where(sigma >= 1.0, 1.0, 0.0)
    elif sensitivity > 0 and sensitivity != 1.0:
        sigma = (np.sqrt(sigma) if sensitivity == 2.0
                 else np.power(sigma, 1.0 / sensitivity))
    sigma = np.clip(sigma * margin, 0.0, 1.0)
    return np.where(cap > 0, (1.0 - (mu + sigma) / 2.0) * 100.0, 0.0)


def _lvrb(x, alloc_cpu, alloc_mem, req, margin, sensitivity):
    cpu = _risk(x["cpu_avg"], x["cpu_std"], alloc_cpu, req[CPU],
                margin, sensitivity)
    mem = _risk(x["mem_avg"], x["mem_std"], alloc_mem, req[MEMORY],
                margin, sensitivity)
    cpu = np.where(x["cpu_valid"], cpu, 0.0)
    mem = np.where(x["mem_valid"], mem, 0.0)
    both = x["cpu_valid"] & x["mem_valid"]
    return go_round(np.where(both, np.minimum(cpu, mem), np.maximum(cpu, mem)))


def min_bytes_per_pod(n_nodes: int, n_resources: int) -> int:
    """The least a step of the sequential scan must move for one pod: the
    free capacity and mask of every node as for any profile, the five
    load columns (float64) with their three validity masks, the missing
    CPU column, CPU capacity and the two allocatable columns the risk
    score reads (int64), and one row written back."""
    fit = n_nodes * (n_resources * 8 + 1)
    load = n_nodes * (5 * 8 + 3 + 8 + 3 * 8)
    return fit + load + n_resources * 8

