"""Synthetic cluster/workload scenario generators for the five BASELINE.md
configurations and for tests, and the seeded problem builders the audit
registry, `chip_smoke.py` and the tests share."""

from scheduler_plugins_tpu.models.problems import (  # noqa: F401
    FLAGSHIP_SHAPE,
    NORTH_STAR_SHAPE,
    PACK_SMOKE_SHAPE,
    SHARD_SMOKE_SHAPE,
    SMOKE_COMPARE_SHAPES,
    SMOKE_SHAPE,
    alloc_problem,
    config_problem,
    flagship_solve_stats,
    mega_problem,
    north_star_problem,
    packing_problem,
    pod_chunks,
)
from scheduler_plugins_tpu.models.scenarios import (  # noqa: F401
    allocatable_scenario,
    gang_quota_scenario,
    metric_affinity_scenario,
    mixed_scenario,
    network_scenario,
    numa_scenario,
    rank_gang_scenario,
    trimaran_scenario,
)
