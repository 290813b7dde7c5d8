"""Counterfactual tuning observatory over flight-recorder corpora.

- `tuning.quality`: placement-quality objectives as tensor math
  (fragmentation, utilization imbalance, gang wait, unplaced fraction,
  drift) + the numpy twin `run_cycle` stamps on every report.
- `tuning.sweep`: K candidate weight vectors replayed through ONE
  vmapped sequential solve (zero per-candidate retraces).
- `tuning.gates`: numpy hard-constraint replay oracles (fit, queue-order
  quota, gang quorum) gating tuned-profile emission.
- `tuning.promotion`: THE one promotion-gate body (sweep a corpus, rank,
  disqualify, accept) shared by the offline tuner and the shadow lane.
- `tuning.shadow`: the online shadow lane (ROADMAP item 2) — background
  deadlined sweeps over the flight-recorder ring, gated live promotion
  through the aux channel, probation auto-rollback.

Drivers: `tools/tune.py` (corpus sweep + gated profile emission), the
serving daemon's `--tune` flag (`tuning.shadow.ShadowTuner`),
`tools/replay.py quality` (score a recorded bundle).
"""

from scheduler_plugins_tpu.tuning import gates, promotion, quality, sweep

__all__ = ["gates", "promotion", "quality", "sweep"]
