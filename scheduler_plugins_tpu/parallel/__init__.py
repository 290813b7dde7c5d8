"""Multi-chip scaling: shard the (pods x nodes) scheduling problem over a
`jax.sharding.Mesh`.

The reference scales by fanning Filter/Score across 16 goroutines on one
process (SURVEY.md §2.9); here the problem tensors shard across TPU chips:
the node axis plays the tensor-parallel role (scores/feasibility split by
node shard, argmax/reductions ride XLA collectives over ICI) and the pod
axis the data-parallel role (independent pods in a wave). XLA inserts the
collectives from sharding annotations — no hand-written NCCL analog.
"""

from scheduler_plugins_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    make_node_mesh,
    pad_to_shards,
    snapshot_shardings,
)
from scheduler_plugins_tpu.parallel.solver import (  # noqa: F401
    sharded_batch_solve,
    sharded_profile_batch_solve,
    sharded_wave_chunk_solver,
    sharded_wave_solve,
)
