"""Host-side (numpy) sequential parity solve — the failover target.

When the device backend is gone (hung, or failing every dispatch),
retrying the jitted solve just hangs again: the only way the cycle loop
keeps serving is a solve that never touches the backend. This
module is that path for the profiles it supports: a pure-numpy mirror of
`framework.runtime._solve_step`'s scan body — PreFilter gates, built-in
fit against the carried free capacity, weighted min-max-normalized
scoring, argmax with the lowest-index tie-break, capacity commit — in
the same int64 reference units with the same Go integer division, so
its placements are bit-identical to the sequential parity path by
construction (gated by tests/test_resilience.py::TestHostSolveParity).

Scope: profiles whose every plugin is Score-only with a host twin
(`NodeResourcesAllocatable` — the serving profile) on snapshots without
side tables (no gangs/quota/NUMA/network/scheduling/nominees). That is
exactly the surface `serving.engine.ServeEngine.compatible` serves, so
degraded-mode serving keeps the resident-state workload alive end to
end. `supports()` gates; unsupported profiles raise
`watchdog.BackendUnavailable` to the caller instead of guessing.
"""

from __future__ import annotations

import numpy as np

from scheduler_plugins_tpu.ops import MAX_NODE_SCORE, MIN_NODE_SCORE, PODS_I


def _go_div_np(a, b):
    """Numpy twin of `utils.intmath.go_div` (trunc-toward-zero, b > 0) —
    the floor+remainder-correction form, never abs()."""
    a = np.asarray(a)
    q = a // b
    r = a - q * b
    return np.where((a < 0) & (r != 0), q + 1, q).astype(a.dtype)


def supports(scheduler, snap) -> bool:
    """True when the host mirror covers this (profile, snapshot): every
    plugin carries the `host_static_scores` twin and no side-table
    subsystem (which would need carries the mirror does not model) is
    present."""
    from scheduler_plugins_tpu.plugins.noderesources import (
        NodeResourcesAllocatable,
    )

    if not all(
        isinstance(p, NodeResourcesAllocatable)
        for p in scheduler.profile.plugins
    ):
        return False
    return (
        snap.gangs is None
        and snap.quota is None
        and snap.numa is None
        and snap.network is None
        and snap.scheduling is None
        and snap.nominees is None
    )


def host_sequential_solve(scheduler, snap):
    """(assignment, admitted, wait, failed_plugin) numpy arrays for the
    supported profile surface — the exact outputs `Scheduler.solve`
    would produce (tests/test_resilience.py holds the two bit-equal).
    Callers must gate on `supports()` first."""
    alloc = np.asarray(snap.nodes.alloc)
    requested = np.asarray(snap.nodes.requested)
    node_mask = np.asarray(snap.nodes.mask)
    req = np.asarray(snap.pods.req)
    pod_mask = np.asarray(snap.pods.mask)
    gated = np.asarray(snap.pods.gated)
    P, N = req.shape[0], alloc.shape[0]

    free = alloc - requested  # the ops.fit.free_capacity rule
    # static per-node raw scores, one row per plugin (allocatable scores
    # rate the node, never the pod — resource_allocation.go:49-76)
    plugin_rows = []
    for plugin in scheduler.profile.plugins:
        weights = np.asarray(plugin.aux(), np.int64)
        weight_sum = max(int(weights.sum()), 1)
        raw = _go_div_np(
            (plugin.mode_sign * alloc * weights[None, :]).sum(axis=-1),
            weight_sum,
        )
        plugin_rows.append((int(plugin.weight), raw))

    assignment = np.full(P, -1, np.int32)
    admitted = np.zeros(P, bool)
    failed = np.zeros(P, np.int32)
    span = MAX_NODE_SCORE - MIN_NODE_SCORE
    for p in range(P):
        ok0 = bool(pod_mask[p]) and not bool(gated[p])
        admitted[p] = ok0
        demand = req[p].copy()
        demand[PODS_I] = 1
        feasible = np.all(demand[None, :] <= free, axis=-1) & node_mask
        feasible &= ok0
        if not feasible.any():
            # same encoding as runtime._encode_fail's sequential fallback:
            # every failure on this profile surface decodes to the
            # built-in fit (code 0); placed pods carry -1
            failed[p] = 0
            continue
        total = np.zeros(N, np.int64)
        for weight, raw in plugin_rows:
            lo = raw[feasible].min()
            hi = raw[feasible].max()
            rng = hi - lo
            if rng == 0:
                col = np.full(N, MIN_NODE_SCORE, np.int64)
            else:
                # operands non-negative: `//` matches Go int division
                col = (raw - lo) * span // rng + MIN_NODE_SCORE
            total += weight * np.where(feasible, col, 0)
        masked = np.where(feasible, total, np.int64(-(2 ** 62)))
        choice = int(np.argmax(masked))  # first max == lowest index
        assignment[p] = choice
        failed[p] = -1
        free[choice] -= demand
    wait = np.zeros(P, bool)  # no gangs on the supported surface
    return assignment, admitted, wait, failed
