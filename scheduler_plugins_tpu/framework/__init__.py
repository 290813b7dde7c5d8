"""The scheduling framework runtime.

Tensor-shaped mirror of the upstream scheduler framework's extension points
(QueueSort / PreFilter / Filter / Score / Normalize / Reserve / Permit /
PostFilter — see SURVEY.md §1 L1): plugins contribute masked tensor
transformations instead of per-node callbacks, and the cycle driver fuses them
into one jitted solve over the whole pending batch.
"""

from scheduler_plugins_tpu.framework.cycle import (  # noqa: F401
    CycleReport,
    run_cycle,
)
from scheduler_plugins_tpu.framework.pipeline_cycle import (  # noqa: F401
    CycleTimeline,
    PipelinedCycle,
)
from scheduler_plugins_tpu.framework.plugin import (  # noqa: F401
    Plugin,
    SolverState,
)
from scheduler_plugins_tpu.framework.runtime import (  # noqa: F401
    PackingConfig,
    Profile,
    Scheduler,
    SolveResult,
)
