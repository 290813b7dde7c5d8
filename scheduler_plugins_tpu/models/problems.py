"""Seeded problem builders the tooling and the tests share.

Each builder wraps a `models.scenarios` generator (or builds tensors
directly, where a million Pod objects would spend the run on host-side
bookkeeping) and returns what a solve program takes. The audit registry
(`tools/tpu_lower.py PROGRAMS`, shared by the jaxpr, kernel and cost
audits), `chip_smoke.py`, `tools/replay.py`, `tools/trace_smoke.py` and
the tests all build their problems here, so the program a manifest digests
is the program the chip smoke runs: same arguments, same seed, the same
problem bit for bit. Constructions and defaults are frozen — the committed
manifests under `docs/` digest the programs they build.

The chunk PROGRAM the north-star problem feeds
(`north_star_chunk_solver`) lives in `parallel/pipeline.py`.
"""

import numpy as np


#: the north-star chunk-loop shapes (BASELINE.json headline scale): what
#: `chip_smoke.py` phase B runs and `tools/tpu_lower.py` certifies
NORTH_STAR_SHAPE = dict(n_nodes=10_240, n_pods=102_400, chunk=8192)
#: BASELINE config 1 (the allocatable flagship) and its reduced twin, the
#: registry's `bench_cfg1_flagship` / `bench_cfg0_tpu_smoke` shapes
FLAGSHIP_SHAPE = dict(n_nodes=1024, n_pods=8192)
SMOKE_SHAPE = dict(n_nodes=64, n_pods=256)

#: the sharded wave chunk programs' registry shape: a NON-shard-multiple
#: node count (1020 pads to 1024 over 8 shards — the mesh-padding edge
#: rides through the audits), cumulative capacity far below the 2^53
#: bit-parity bound so placements must match EXACTLY
SHARD_SMOKE_SHAPE = dict(n_nodes=1020, n_pods=8192, chunk=2048, devices=8)

#: the packing solve's registry shape — large enough that consolidation
#: measurably moves both packing gauges (tests/test_packing.py)
PACK_SMOKE_SHAPE = dict(
    n_nodes=96, demand_frac=0.8, empty_frac=0.1, budgets=(8, 32),
)

#: reduced config 2 / 3 shapes (same generators and rosters as the full
#: configs) the batch-vs-sequential drift bounds are stated at
#: (tests/test_drift_bounds.py)
SMOKE_COMPARE_SHAPES = {
    2: dict(n_nodes=1024, n_pods=512),
    3: dict(n_nodes=256, n_pods=256, zones=8),
}


def alloc_problem(n_nodes, n_pods):
    """(cluster, snap, meta, weights) for the allocatable-profile configs
    (0/1)."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import CPU, MEMORY
    from scheduler_plugins_tpu.models import allocatable_scenario

    cluster = allocatable_scenario(n_nodes=n_nodes, n_pods=n_pods)
    pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    weights = jnp.asarray(
        meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
    )
    return cluster, snap, meta, weights


def flagship_solve_stats(snap, weights):
    """The flagship jitted step (configs 0/1): the full batched solve with
    per-wave occupancy stats, so a change to wave count or per-wave cost
    shows in the audited program."""
    from scheduler_plugins_tpu.parallel.solver import batch_solve

    return batch_solve(snap, weights, max_waves=8, collect_stats=True)


def north_star_problem(n_nodes, n_pods, chunk):
    """(cluster, snap, meta, weights, raw, padded) for the chunked
    north-star run (`parallel.pipeline.north_star_chunk_solver`)."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import CPU, MEMORY
    from scheduler_plugins_tpu.models import allocatable_scenario
    from scheduler_plugins_tpu.ops.allocatable import (
        MODE_LEAST,
        allocatable_scores,
        demote_scores_int32,
    )

    cluster = allocatable_scenario(n_nodes=n_nodes, n_pods=n_pods)
    pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
    # pad to a chunk multiple so every chunk shares one compiled shape
    padded = ((n_pods + chunk - 1) // chunk) * chunk
    snap, meta = cluster.snapshot(pending, now_ms=0, pad_pods=padded)
    weights = jnp.asarray(meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64)
    raw = demote_scores_int32(
        allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
    ).astype(jnp.int64)
    return cluster, snap, meta, weights, raw, padded


def pod_chunks(snap, chunk):
    """The pod axis of a chunk-padded snapshot as `run_chunk_pipeline`'s
    `chunk_inputs`: one host-side `(req, mask)` pair per chunk."""
    req, mask = np.asarray(snap.pods.req), np.asarray(snap.pods.mask)
    return [
        (req[lo:lo + chunk], mask[lo:lo + chunk])
        for lo in range(0, req.shape[0], chunk)
    ]


def mega_problem(n_nodes, n_pods, chunk, seed=0):
    """Tensor-level problem dict for the sharded wave solve, CANONICAL axis
    order and reference units (cpu millicores, memory bytes, int64). Four
    heterogeneous node SKUs make the allocatable ranking non-degenerate
    (the wave election actually orders nodes); the pod distribution
    mirrors `models.scenarios._pods`. Pods pad to a chunk multiple so
    every chunk shares one compiled shape (mask False on padding)."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import (
        CANONICAL,
        CPU,
        MEMORY,
        ResourceIndex,
    )
    from scheduler_plugins_tpu.ops.allocatable import (
        MODE_LEAST,
        allocatable_scores,
        demote_scores_int32,
    )

    gib = 1 << 30
    rng = np.random.default_rng(seed)
    R = len(CANONICAL)
    # SKU columns follow CANONICAL (cpu, memory, ephemeral-storage, pods)
    skus = np.asarray(
        [
            [64_000, 256 * gib, 0, 256],
            [32_000, 128 * gib, 0, 220],
            [96_000, 384 * gib, 0, 256],
            [16_000, 64 * gib, 0, 128],
        ],
        dtype=np.int64,
    )
    alloc = skus[rng.integers(0, len(skus), size=n_nodes)]
    padded = ((n_pods + chunk - 1) // chunk) * chunk
    req = np.zeros((padded, R), np.int64)
    req[:n_pods, CANONICAL.index(CPU)] = rng.integers(100, 4000, n_pods)
    req[:n_pods, CANONICAL.index(MEMORY)] = rng.integers(
        256 << 20, 8 * gib, n_pods
    )
    mask = np.arange(padded) < n_pods
    weights = jnp.asarray(
        ResourceIndex().encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
    )
    free0 = jnp.asarray(alloc)  # nothing bound: free == allocatable
    raw = demote_scores_int32(
        allocatable_scores(free0, weights, MODE_LEAST)
    ).astype(jnp.int64)
    return {
        "alloc": alloc, "free0": free0, "req": req, "mask": mask,
        "node_mask": jnp.ones(n_nodes, bool), "weights": weights,
        "raw": raw, "padded": padded, "n_pods": n_pods,
    }


def packing_problem(n_nodes, demand_frac=0.8, empty_frac=0.1, seed=0):
    """(cluster, snap, meta, weights) for the packing solve: a mid-life
    cluster — `1 - empty_frac` of the nodes pre-loaded by residents at
    uneven 20-80% cpu fill across four heterogeneous SKUs (arriving
    bound, as a feed replay would deliver them), the remaining
    `empty_frac` standing EMPTY on the biggest SKU (freshly added
    capacity) — plus a pending batch sized to `demand_frac` of the
    cluster's free cpu. The Least-allocatable ranking fills the loaded
    fleet first and the batch tail spills lightly onto the big empty
    nodes (the rescue waves spray stragglers round-robin); the packing
    refinement drains that spill back into the loaded fleet's dust gaps,
    re-emptying whole big nodes — exactly the consolidation headroom the
    one-pass wave semantics cannot see."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import (
        CPU,
        MEMORY,
        PODS,
        ResourceIndex,
    )
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    rng = np.random.default_rng(seed)
    skus = [
        (64_000, 256 * gib, 256),
        (32_000, 128 * gib, 220),
        (96_000, 384 * gib, 256),
        (16_000, 64 * gib, 128),
    ]
    cluster = Cluster()
    serial = 0
    free_cpu = 0
    n_empty = max(1, int(n_nodes * empty_frac))
    for i in range(n_nodes):
        # the last n_empty nodes stand empty on the BIGGEST SKU: freshly
        # added capacity the Least-allocatable ranking scores worst, so
        # the wave touches it only as spill — the blocks packing re-empties
        empty = i >= n_nodes - n_empty
        sku = 2 if empty else int(rng.integers(0, len(skus)))
        cpu, mem, pods = skus[sku]
        cluster.add_node(Node(
            name=f"node-{i:05d}",
            allocatable={CPU: cpu, MEMORY: mem, PODS: pods},
        ))
        used = 0
        if not empty:
            # uneven resident fill: 20-80% of cpu in 100-2000m pieces
            target = int(cpu * rng.uniform(0.2, 0.8))
            while used < target:
                c = int(rng.integers(100, 2000))
                m = int(rng.integers(256 << 20, 2 * gib))
                pod = Pod(
                    name=f"bound-{serial:06d}", creation_ms=serial,
                    containers=[Container(requests={CPU: c, MEMORY: m})],
                )
                pod.node_name = f"node-{i:05d}"
                cluster.add_pod(pod)
                used += c
                serial += 1
        free_cpu += cpu - used
    base_ms = serial
    target_demand = int(free_cpu * demand_frac)
    demand = 0
    j = 0
    while demand < target_demand:
        c = int(rng.integers(100, 2000))
        cluster.add_pod(Pod(
            name=f"pend-{j:06d}", creation_ms=base_ms + j,
            containers=[Container(requests={
                CPU: c,
                MEMORY: int(rng.integers(256 << 20, 2 * gib))})],
        ))
        demand += c
        j += 1
    pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    weights = jnp.asarray(
        ResourceIndex().encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
    )
    return cluster, snap, meta, weights


def config_problem(config: int, shape: dict | None = None):
    """(cluster, plugins, detail) — the BASELINE config 2-5 scenario/roster
    table. The ONE copy of these shapes: `chip_smoke.py` phase C runs them
    and the audit registry (tools/tpu_lower.py) lowers them, so they cannot
    drift apart. `shape` overrides the scenario size (same generators at
    reduced N). Raises ValueError for a config outside 2-5."""
    from scheduler_plugins_tpu.models import (
        gang_quota_scenario,
        network_scenario,
        numa_scenario,
        trimaran_scenario,
    )
    from scheduler_plugins_tpu import plugins as P

    if config == 2:
        kw = shape or dict(n_nodes=5000, n_pods=2048)
        cluster = trimaran_scenario(**kw)
        plugins = [P.TargetLoadPacking(), P.LoadVariationRiskBalancing()]
        detail = f"{kw['n_nodes']} nodes, TLP+LVRB, sequential"
    elif config == 3:
        kw = shape or dict(n_nodes=1024, n_pods=512, zones=8)
        cluster = numa_scenario(**kw)
        plugins = [P.NodeResourceTopologyMatch()]
        detail = f"{kw['n_nodes']} nodes x {kw.get('zones', 8)} zones, sequential"
    elif config == 4:
        kw = shape or dict(n_gangs=32, gang_size=64, n_nodes=1024)
        cluster = gang_quota_scenario(**kw)
        plugins = [P.NodeResourcesAllocatable(), P.Coscheduling(), P.CapacityScheduling()]
        detail = f"{kw['n_gangs']} gangs x {kw['gang_size']}, {kw['n_nodes']} nodes, sequential"
    elif config == 5:
        kw = shape or dict(n_nodes=1024, n_pods=1024)
        cluster = network_scenario(**kw)
        plugins = [P.NetworkOverhead(), P.TopologicalSort()]
        detail = f"{kw['n_nodes']} nodes multi-region, sequential"
    else:
        raise ValueError(f"unknown config {config}")
    return cluster, plugins, detail
