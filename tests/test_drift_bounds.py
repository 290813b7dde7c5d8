"""Drift regression guard (ISSUE 2 satellite): the batched throughput
mode's placement-quality drift vs the bit-faithful sequential path is a
DOCUMENTED trade, not a free variable — this pins it.

- cfg-2 (trimaran TLP+LVRB, the config whose batch mode trades quality for
  throughput) must stay within the −0.05 envelope (−0.04 counted at the
  full 5000-node shape; the reduced shape here,
  `problems.SMOKE_COMPARE_SHAPES`, uses the same generator/roster).
- The NUMA roster (cfg-3 shape) batch path is score-identical to
  sequential on its shared objective — drift exactly 0.0.
- Sequential mode is the anchor: drift 0.0 by definition (the shared
  definition `score_drift_vs_sequential` must return exactly 0.0 for the
  anchor against itself, and so must its host-side twin
  `tuning.quality.score_drift`).

All drifts are computed with `parallel.solver.score_drift_vs_sequential`.
"""

import numpy as np

from scheduler_plugins_tpu.framework import Profile, Scheduler
from scheduler_plugins_tpu.models.problems import SMOKE_COMPARE_SHAPES
from scheduler_plugins_tpu.parallel.solver import (
    profile_batch_solve,
    score_drift_vs_sequential,
)

#: the documented envelope for the cfg-2 batch drift (−0.04 at the full
#: shape; anything below −0.05 is a quality regression, not noise)
CFG2_DRIFT_ENVELOPE = -0.05


def _solve_both(cluster, plugins):
    sched = Scheduler(Profile(plugins=plugins))
    pending = sched.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    sched.prepare(meta, cluster)
    seq = np.asarray(sched.solve(snap).assignment)
    bat = np.asarray(profile_batch_solve(sched, snap)[0])
    drift, placed_seq, placed_bat = score_drift_vs_sequential(
        sched, snap, seq, bat
    )
    return drift, placed_seq, placed_bat


class TestDriftBounds:
    def test_cfg2_batch_drift_within_envelope(self):
        from scheduler_plugins_tpu import plugins as P
        from scheduler_plugins_tpu.models import trimaran_scenario

        cluster = trimaran_scenario(**SMOKE_COMPARE_SHAPES[2])
        drift, placed_seq, placed_bat = _solve_both(
            cluster, [P.TargetLoadPacking(), P.LoadVariationRiskBalancing()]
        )
        assert placed_bat >= placed_seq, (placed_seq, placed_bat)
        assert drift >= CFG2_DRIFT_ENVELOPE, (
            f"cfg-2 batch drift {drift:.4f} fell below the documented "
            f"{CFG2_DRIFT_ENVELOPE} envelope"
        )

    def test_numa_batch_drift_zero(self):
        from scheduler_plugins_tpu import plugins as P
        from scheduler_plugins_tpu.models import numa_scenario

        cluster = numa_scenario(**SMOKE_COMPARE_SHAPES[3])
        drift, placed_seq, placed_bat = _solve_both(
            cluster, [P.NodeResourceTopologyMatch()]
        )
        assert placed_bat >= placed_seq, (placed_seq, placed_bat)
        assert drift == 0.0, drift

    def test_sequential_anchor_exactly_zero(self):
        # the anchor against itself MUST be exactly 0.0, not merely close
        from scheduler_plugins_tpu import plugins as P
        from scheduler_plugins_tpu.tuning.quality import score_drift
        from scheduler_plugins_tpu.models import numa_scenario

        cluster = numa_scenario(n_nodes=64, n_pods=64, zones=4)
        sched = Scheduler(Profile(plugins=[P.NodeResourceTopologyMatch()]))
        pending = sched.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        sched.prepare(meta, cluster)
        seq = np.asarray(sched.solve(snap).assignment)
        drift, _, _ = score_drift_vs_sequential(sched, snap, seq, seq)
        assert drift == 0.0

        # the host-side definition obeys the same anchor identity
        scores = np.arange(64, dtype=np.int64).reshape(4, 16)
        ref = np.array([3, 1, -1, 2])
        assert score_drift(scores, ref.copy(), ref.copy()) == 0.0
