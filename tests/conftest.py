"""Test bootstrap: an 8-device virtual CPU platform, so the multi-chip
sharding tests run anywhere. Tests run on the CPU backend (`JAX_PLATFORMS=cpu`,
nothing else); the chip is exercised by `chip_smoke.py`, not the unit suite.
Both variables are set before jax is first imported."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def _eval_plugin(cluster, sched, pod, method):
    """Drive ONE pending pod through a single-plugin profile up to a raw
    per-node plugin vector (Score or Filter) — the unit-level harness the
    decision-table suites share, binding aux/presolve exactly as the
    solvers do (framework/runtime + parallel/solver both prepare_solve
    first). Returns (vector ndarray, meta)."""
    import numpy as np

    pending = sched.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    sched.prepare(meta, cluster)
    plugin = sched.profile.plugins[0]
    plugin.bind_aux(plugin.aux())
    plugin.bind_presolve(plugin.prepare_solve(snap))
    state = sched.initial_state(snap)
    i = meta.pod_names.index(pod.uid)
    return np.asarray(getattr(plugin, method)(state, snap, i)), meta


def raw_plugin_scores(cluster, sched, pod):
    """Raw (un-normalized) per-node Score vector for one pending pod."""
    return _eval_plugin(cluster, sched, pod, "score")


def raw_plugin_filter(cluster, sched, pod):
    """(N,) Filter verdicts for one pending pod."""
    return _eval_plugin(cluster, sched, pod, "filter")
