#!/usr/bin/env python
"""AOT TPU compile-readiness gate: StableHLO lowering + landmine scan.

CI has no chip, so it keeps a check that needs none. This tool
cross-lowers every hot program to TPU StableHLO via `jax.export` on the CPU
backend (lowering is the platform-specific trace; the TPU compiler is NOT
run, so this proves nothing about what it accepts — `chip_smoke.py` does
that on a chip) and then scans the emitted module text for the landmine
patterns CLAUDE.md documents:

- `dot`/`dot_general` on i64 operands (int64 matmul is unsupported on TPU);
- `reduce_window` over i64 (the vmem-hungry lowering 2-D int64 `jnp.cumsum`
  takes on TPU — can hang compiles);
- convolutions fed by i64 operands.

Programs covered: BASELINE configs 0-6 — including the north-star chunk
loop — both sharded solves in `parallel/solver.py`, and
`__graft_entry__.entry()`. Problems come from `models/problems.py`. The
`bench_` prefix of the config programs' registry names is historical (the
builders lived in a root script once); the names are keys in the five
manifests under `docs/` and stay.

A digest manifest (`docs/tpu_lowering.json`: program -> StableHLO SHA-256 +
op histogram, loc-metadata stripped) is committed so program regressions
show up as diffs. Hash equality is only enforced when the running jax
version matches the manifest's (StableHLO text is jax-version-dependent);
on a different jax the gate still enforces the program set, lowering
success, and zero landmines.

Usage:
    python tools/tpu_lower.py              # lower all, scan, write manifest
    python tools/tpu_lower.py --check     # read-only verify against manifest
    python tools/tpu_lower.py --programs entry bench_cfg0_tpu_smoke
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
from collections import Counter
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
MANIFEST = REPO / "docs" / "tpu_lowering.json"

if str(REPO) not in sys.path:  # `python tools/tpu_lower.py` from anywhere
    sys.path.insert(0, str(REPO))

#: TPU platform string passed to jax.export.
TARGET_PLATFORM = "tpu"


def bootstrap(n_devices: int = 8) -> None:
    """Force an n-device virtual CPU platform BEFORE the first backend
    touch: the static tools trace and lower on the CPU whatever device the
    machine has. Delegates to
    `__graft_entry__._force_cpu_platform`, which also UPGRADES a
    pre-existing smaller `--xla_force_host_platform_device_count` in
    XLA_FLAGS — a stale 4-device export must not starve the 8-way sharded
    programs. Idempotent; must run before any jnp array is created.

    Also clears SPT_SANITIZE: program construction branches on it
    (checkify-instrumented solver builds), and the certification tools —
    this one and tools/jaxpr_audit.py, which shares this bootstrap — must
    always trace/lower the SHIPPED programs, never instrumented variants
    (a stray `export SPT_SANITIZE=1` would otherwise silently regenerate
    the committed manifests from the wrong programs)."""
    import __graft_entry__

    os.environ.pop("SPT_SANITIZE", None)
    __graft_entry__._force_cpu_platform(n_devices)
    # Pallas kernel bodies serialize into the tpu_custom_call payload as
    # MLIR *bytecode*, whose per-op locations the textual loc-stripper in
    # `canonical_text` cannot reach. With full tracebacks (the default)
    # those locations include THIS tool's call-stack frames, so any line
    # shift in this file silently drifted the three pallas program
    # digests. Single-frame locations pin the payload to the innermost
    # user frame (the kernel source itself) — digests track the kernels,
    # not the certification tool.
    import jax

    jax.config.update("jax_include_full_tracebacks_in_locations", False)


# ---------------------------------------------------------------------------
# StableHLO landmine scanner (pure text analysis — no jax required)
# ---------------------------------------------------------------------------

_OP_RE = re.compile(r'"?stablehlo\.([a-z_0-9]+)"?')
#: element-type i64 inside a tensor type: `tensor<8x8xi64>` / `tensor<i64>`
#: (`ui64` deliberately not matched: the landmines are signed-i64 ops).
_I64_ELT_RE = re.compile(r"(?:x|<)i64>")
#: ops where i64 operands are TPU landmines
_MATMUL_OPS = ("dot_general", "dot", "convolution")


def op_histogram(text: str) -> dict[str, int]:
    """{stablehlo op name: count} over the module text."""
    return dict(Counter(m.group(1) for m in _OP_RE.finditer(text)))


def _operand_signature(
    text: str, start: int, region_op: bool = False, window: int = 6000
) -> str:
    """The `(operand types)` of the op starting at `start`.

    Plain one-line ops (dot/dot_general/convolution) carry
    ` : (types) -> ...` or ` : type` on their OWN line — that form must be
    read first, or a nearby region op's closing signature gets
    mis-attributed. Region ops (reduce_window) close with
    `}) : (types) -> ...` a few lines down. Returns "" when not found."""
    chunk = text[start : start + window]
    if region_op:
        m = re.search(r"\}\)?\s*:\s*\(([^)]*)\)", chunk)
        return m.group(1) if m else ""
    line = chunk.split("\n", 1)[0]
    m = re.search(r":\s*\(([^)]*)\)", line)
    if m is None:
        m = re.search(r":\s*(tensor<[^>]*>)", line)
    return m.group(1) if m else ""


def scan_landmines(text: str) -> list[dict]:
    """CLAUDE.md TPU landmines in a StableHLO module: i64 `dot`/
    `dot_general`/`convolution` operands, and `reduce_window` over i64
    (what 2-D int64 cumsum lowers to on TPU). Returns finding dicts with
    the op name and its operand signature."""
    findings = []
    for m in _OP_RE.finditer(text):
        op = m.group(1)
        if op in _MATMUL_OPS:
            sig = _operand_signature(text, m.start())
            if _I64_ELT_RE.search(sig):
                findings.append(
                    {"op": op, "signature": sig.strip(), "offset": m.start()}
                )
        elif op == "reduce_window":
            sig = _operand_signature(text, m.start(), region_op=True)
            if _I64_ELT_RE.search(sig) and _max_tensor_rank(sig) >= 2:
                # 1-D i64 reduce_window is the standard TPU cumsum lowering
                # and benign; the CLAUDE.md landmine is the MULTI-DIM form
                # (2-D int64 cumsum), whose windows go vmem-pathological
                findings.append(
                    {"op": op, "signature": sig.strip(), "offset": m.start()}
                )
    return findings


def _max_tensor_rank(signature: str) -> int:
    """Highest tensor rank among `tensor<...>` types in a signature."""
    rank = 0
    for m in re.finditer(r"tensor<([^>]*)>", signature):
        dims = m.group(1).split("x")
        rank = max(rank, len(dims) - 1)  # last element is the dtype
    return rank


def canonical_text(text: str) -> str:
    """Module text with loc metadata stripped, so the digest tracks the
    PROGRAM (ops + types + structure) — not source line numbers, and not
    the process-global #locN counter (which shifts with whatever else was
    traced earlier in the process and made naive digests order-dependent).

    `loc(...)` attributes nest parens (`loc("f"(#loc3))`), so a balanced
    scanner removes them; any remaining bare #locN tokens and #locN
    definition lines are dropped too."""
    out = []
    i, n = 0, len(text)
    while i < n:
        j = text.find("loc(", i)
        # only strip the attribute form: start-of-token boundary
        while j > 0 and j < n and (text[j - 1].isalnum() or text[j - 1] == "_"):
            j = text.find("loc(", j + 1)
        if j == -1:
            out.append(text[i:])
            break
        out.append(text[i:j].rstrip(" "))
        depth, k = 0, j + 3
        while k < n:
            if text[k] == "(":
                depth += 1
            elif text[k] == ")":
                depth -= 1
                if depth == 0:
                    break
            k += 1
        i = k + 1
    text = "".join(out)
    text = re.sub(r"#loc\d*", "", text)
    return "\n".join(
        line.rstrip()
        for line in text.splitlines()
        if line.strip() not in ("", "=")
    )


def stablehlo_digest(text: str) -> str:
    return hashlib.sha256(canonical_text(text).encode()).hexdigest()


# ---------------------------------------------------------------------------
# Program registry: name -> builder returning (jitted_fn, args, mesh|None)
# ---------------------------------------------------------------------------


def _batch_solve_program(shape):
    """Configs 0/1: `problems.flagship_solve_stats` on
    `problems.alloc_problem` (wave-occupancy stats included)."""
    import jax

    from scheduler_plugins_tpu.models import problems

    _, snap, _, weights = problems.alloc_problem(**shape)
    return jax.jit(problems.flagship_solve_stats), (snap, weights), None


def build_entry():
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    return jax.jit(fn), args, None


def build_cfg0_tpu_smoke():
    from scheduler_plugins_tpu.models import problems

    return _batch_solve_program(problems.SMOKE_SHAPE)


def build_cfg1_flagship():
    from scheduler_plugins_tpu.models import problems

    return _batch_solve_program(problems.FLAGSHIP_SHAPE)


def _sequential_program(config):
    """Configs 2-5: the bit-faithful sequential solve on
    `problems.config_problem`'s scenario/roster table (the one copy of those
    shapes), traced with the TPU-path scan unroll (runtime._scan_unroll
    returns 8 on TPU device kinds — mirror that here so the digest covers
    the program the chip would run, not the CPU test trace)."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.models import problems

    cluster, plugins, _ = problems.config_problem(config)
    scheduler = Scheduler(Profile(plugins=plugins))
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    state0 = scheduler.initial_state(snap)
    auxes = tuple(p.aux() for p in scheduler.profile.plugins)
    fn = scheduler._make_solve(unroll=8)  # already jitted
    return fn, (snap, state0, auxes), None


def build_cfg2_trimaran_sequential():
    return _sequential_program(2)


def build_cfg3_numa_sequential():
    return _sequential_program(3)


def build_cfg4_gang_quota_sequential():
    return _sequential_program(4)


def build_cfg5_network_sequential():
    return _sequential_program(5)


def build_cfg6_north_star_chunk():
    """The north-star chunk loop body —
    `parallel.pipeline.north_star_chunk_solver()` (the DONATED jit:
    donation changes the exported calling convention, so the certified
    program must carry it), at the real node-count/chunk shapes from
    `problems.NORTH_STAR_SHAPE`, with the chunk-invariant tensors as
    arguments exactly as `chip_smoke.py` calls it (one pod chunk of cluster
    build suffices: every chunk shares this one compiled program)."""
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import (
        north_star_chunk_solver,
    )

    shape = problems.NORTH_STAR_SHAPE
    chunk = shape["chunk"]
    _, snap, meta, weights, raw, _ = problems.north_star_problem(
        shape["n_nodes"], chunk, chunk
    )
    free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    args = (
        raw,
        snap.nodes.mask,
        snap.pods.req[:chunk],
        snap.pods.mask[:chunk],
        free,
    )
    return north_star_chunk_solver(), args, None


def _mesh8():
    from scheduler_plugins_tpu.parallel.mesh import make_mesh

    return make_mesh(8)


def build_sharded_batch_solve():
    """`parallel.solver.sharded_batch_solve`'s jitted program on an 8-way
    ("pods", "nodes") mesh — the gang+quota allocatable flagship with the
    snapshot sharded per `snapshot_shardings` (the dryrun_multichip layout;
    XLA inserts the cross-shard collectives)."""
    import jax

    import __graft_entry__
    from scheduler_plugins_tpu.parallel.mesh import shard_snapshot
    from scheduler_plugins_tpu.parallel.solver import batch_solve

    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    mesh = _mesh8()
    pods_dim, nodes_dim = mesh.devices.shape
    scheduler, snap, meta = __graft_entry__._build_problem(
        n_nodes=16, n_pods=32, pad_nodes=16, pad_pods=32
    )
    assert 16 % nodes_dim == 0 and 32 % pods_dim == 0
    snap = shard_snapshot(snap, mesh)
    weights = jnp.asarray(
        meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
    )
    fn = jax.jit(lambda s, w: batch_solve(s, w, 8))
    return fn, (snap, weights), mesh


def build_sharded_profile_batch_solve():
    """`parallel.solver.sharded_profile_batch_solve`'s jitted program: the
    mixed plugin roster (allocatable + NUMA + network + topology-spread
    validators) under the same 8-way mesh — the full-roster multi-chip
    path, not just the flagship."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.models import mixed_scenario
    from scheduler_plugins_tpu.parallel.mesh import shard_snapshot
    from scheduler_plugins_tpu.parallel.solver import profile_batch_fn
    from scheduler_plugins_tpu.plugins import (
        NetworkOverhead,
        NodeResourcesAllocatable,
        NodeResourceTopologyMatch,
        PodTopologySpread,
    )

    mesh = _mesh8()
    cluster = mixed_scenario(n_nodes=16, n_pods=32)
    sched = Scheduler(
        Profile(
            plugins=[
                NodeResourcesAllocatable(),
                NodeResourceTopologyMatch(),
                NetworkOverhead(),
                PodTopologySpread(),
            ]
        )
    )
    for p in sched.profile.plugins:
        p.configure_cluster(cluster)
    pending = sched.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0, pad_nodes=16, pad_pods=32)
    sched.prepare(meta, cluster)
    snap = shard_snapshot(snap, mesh)
    fn, args = profile_batch_fn(sched, snap, max_waves=8)
    return fn, args, mesh


def build_serving_delta_apply():
    """`serving.deltas.delta_apply_program` — the donated O(changed)
    scatter-apply the resident-state serving engine folds each cycle's
    delta batch with (`serving.engine.ServeEngine._apply_batch`), at the
    reduced resident shape `serving.engine.lower_program_args` builds.
    The donated resident carry changes the exported calling convention,
    so the certified program must carry it (like cfg6's chunk solver)."""
    from scheduler_plugins_tpu.serving.engine import lower_program_args

    fn, args = lower_program_args()
    return fn, args, None


def build_serving_node_compact():
    """`serving.deltas.node_compact_program` — the donated row-shift
    gather the streaming serve engine replaces node-delete rebases with
    (`StreamingServeEngine._compact_row`), at the reduced resident shape
    `serving.engine.compact_lower_args` builds. Same donated-carry
    calling convention as serving_delta_apply."""
    from scheduler_plugins_tpu.serving.engine import compact_lower_args

    fn, args = compact_lower_args()
    return fn, args, None


def _sharded_wave_chunk_program(use_pallas: bool):
    """Shared staging for the two sharded-wave-chunk manifest entries —
    ONE copy of the reduced shard-smoke problem, mesh and
    `rank_order_inputs` pre-permutation (as `chip_smoke.py` phase D does), so
    the lax and pallas entries can never drift onto different shapes. The
    resident rank-ordered free carry is DONATED (the exported calling
    convention must carry it, like cfg6's chunk program)."""
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
    from scheduler_plugins_tpu.parallel.solver import (
        rank_order_inputs,
        sharded_wave_chunk_solver,
    )

    shape = problems.SHARD_SMOKE_SHAPE
    problem = problems.mega_problem(
        shape["n_nodes"], shape["n_pods"], shape["chunk"]
    )
    mesh = make_node_mesh(shape["devices"])
    node_ids, rank_free = rank_order_inputs(
        problem["raw"], problem["free0"], problem["node_mask"],
        shape["devices"],
    )
    chunk = shape["chunk"]
    fn = sharded_wave_chunk_solver(
        mesh, shape["n_nodes"], rescue_window=256,
        use_pallas=use_pallas, pallas_interpret=False,
    )
    args = (
        node_ids, problem["req"][:chunk], problem["mask"][:chunk], rank_free
    )
    return fn, args, mesh


def build_sharded_wave_chunk():
    """The sharded wave chunk program (`parallel.solver.
    sharded_wave_chunk_solver` — the shard_map ring-election waterfill the
    mega config 8 ships) on an 8-way ("nodes",) mesh at the reduced
    shard-smoke shapes. The lowering proves the per-wave ring/psum
    elections — never a full node-axis gather — lower to TPU collectives.
    use_pallas pinned False: this entry certifies the LAX collectives
    build — an ambient SPT_PALLAS=1 in the manifest-refresh shell must
    never silently swap which formulation carries this program's digest."""
    return _sharded_wave_chunk_program(use_pallas=False)


def build_sharded_wave_chunk_pallas():
    """The sharded wave chunk program with the PALLAS election path
    (`use_pallas=True, pallas_interpret=False` — the COMPILED kernels, not
    the CPU twins): same shapes/mesh as `sharded_wave_chunk` (shared
    staging), but every per-wave collective is a `parallel.kernels` ring
    program. Lowering this proves the whole solve — kernels under
    shard_map under the wave while_loops, Mosaic bodies included — exports
    to TPU StableHLO (`tpu_custom_call` with the serialized kernel
    payloads). `chip_smoke.py --devices 4` compiles and runs the same
    build on four chips."""
    return _sharded_wave_chunk_program(use_pallas=True)


def _node_mesh8():
    from scheduler_plugins_tpu.parallel.mesh import make_node_mesh

    return make_node_mesh(8)


def build_pallas_ring_offsets():
    """`parallel.kernels.ring_offsets_f64` standalone (compiled body, 8-way
    node mesh): the double-buffered `make_async_remote_copy` exclusive-
    scan ring at the lite wave's cumulative-free payload shape. The
    kernel-body op census (dma_start/dma_wait, semaphore ops) lives in
    docs/jaxpr_audit.json; this entry certifies the Mosaic body serializes
    into TPU StableHLO."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from scheduler_plugins_tpu.api.resources import CANONICAL
    from scheduler_plugins_tpu.parallel import kernels as pk
    from scheduler_plugins_tpu.parallel.mesh import NODES_AXIS

    mesh = _node_mesh8()
    S, R = 8, len(CANONICAL)

    def per_shard(x):
        return pk.ring_offsets_f64(
            x.reshape(R), NODES_AXIS, S, interpret=False
        )

    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=P(NODES_AXIS),
        out_specs=(P(NODES_AXIS), P(NODES_AXIS)), check_vma=False,
    ))
    x = jnp.arange(S * R, dtype=jnp.float64) * (1 << 30)
    return fn, (x,), mesh


def build_pallas_fused_election():
    """`parallel.kernels.fused_election` standalone (compiled body, 8-way
    node mesh) at the rescue-window election shape: min-rank keys plus the
    winner node-id/free-row payload in one ring program — the kernel that
    retires the packed admission-verdict psum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from scheduler_plugins_tpu.api.resources import CANONICAL
    from scheduler_plugins_tpu.parallel import kernels as pk
    from scheduler_plugins_tpu.parallel.mesh import NODES_AXIS

    mesh = _node_mesh8()
    S, R, W = 8, len(CANONICAL), 256
    HP = 1 + pk.N_LIMBS * R

    def per_shard(keys, payload):
        return pk.fused_election(
            keys.reshape(W), payload.reshape(HP, W), NODES_AXIS, S,
            interpret=False,
        )

    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(NODES_AXIS), P(NODES_AXIS)),
        out_specs=(P(), P(None, None)), check_vma=False,
    ))
    keys = jnp.zeros(S * W, jnp.int32)
    payload = jnp.zeros(S * HP * W, jnp.int32)
    return fn, (keys, payload), mesh


def _gang_problem():
    """Reduced rank-gang problem shared by the two gang programs: the
    config-10 scenario generators at smoke shape, lowered through the
    SAME `gangs.phase.build_rank_gang_problem` the shipped phase uses."""
    from scheduler_plugins_tpu.gangs.phase import build_rank_gang_problem
    from scheduler_plugins_tpu.models import rank_gang_scenario

    cluster = rank_gang_scenario(
        n_nodes=16, n_regions=2, zones_per_region=2, n_mpi=2, mpi_ranks=4,
        n_dl=1, dl_min=2, dl_desired=3, dl_max=4,
    )
    pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
    prob = build_rank_gang_problem(cluster, pending, now=0)
    assert prob is not None
    return prob


def build_rank_gang_solve():
    """`gangs.topology.gang_solve_body` — the topology-block waterfill
    gang solve (scan over gangs, carried free/eq_used/rank_nodes). The
    `SolverState.rank_nodes` carry is initialized from the resident
    assignment (`RankGangState.prev_assigned` — its CARRY_COUNTERPARTS
    snapshot twin), so the jaxpr audit's JA001 can prove the solve
    threads placements through the carry."""
    import jax
    import jax.numpy as jnp

    from scheduler_plugins_tpu.framework.plugin import SolverState
    from scheduler_plugins_tpu.gangs.topology import gang_solve_fn

    prob = _gang_problem()
    gangs = jax.tree.map(jnp.asarray, prob["gangs"])
    state0 = SolverState(
        free=jnp.asarray(prob["free0"]),
        eq_used=jnp.asarray(prob["eq_used0"]),
        rank_nodes=jnp.asarray(prob["gangs"].prev_assigned),
    )
    return gang_solve_fn(), (gangs, state0, jnp.asarray(prob["node_mask"])), None


def build_elastic_shrink():
    """`gangs.elastic.shrink_select` — the elastic shrink-selection
    program (highest-cost ranks released first) over the resident
    rank-assignment carry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scheduler_plugins_tpu.gangs.elastic import shrink_select

    prob = _gang_problem()
    gangs = prob["gangs"]
    G, M = gangs.rank_mask.shape
    # a resident assignment: every masked slot on some node (the shrink
    # program runs on LIVE gangs)
    rank_nodes = np.where(
        gangs.rank_mask, np.arange(M)[None, :] % prob["free0"].shape[0], -1
    ).astype(np.int32)
    args = (
        jnp.asarray(rank_nodes),
        jnp.asarray(gangs.rank_mask),
        jnp.asarray(gangs.node_block),
        jnp.asarray(gangs.block_cost),
        jnp.asarray(np.ones(G, np.int32)),
    )
    return jax.jit(shrink_select), args, None


def build_serving_side_apply():
    """`serving.deltas.side_apply_program` — the donated O(changed)
    scatter-apply maintaining the resident gang/quota side tables
    (`serving.engine.ServeEngine._apply_side`; ISSUE 12), at the reduced
    shape `serving.engine.side_lower_args` builds. Same donated-carry
    calling convention as serving_delta_apply."""
    from scheduler_plugins_tpu.serving.engine import side_lower_args

    fn, args = side_lower_args()
    return fn, args, None


def build_serving_selector_apply():
    """`serving.deltas.selector_apply_program` — the donated O(changed)
    scatter-add that keeps the resident selector tables: the (track,
    domain) matching-pod counts (ISSUE 32) and the (term, domain) carrier
    counts of pod (anti-)affinity terms with the presence derived from
    them (ISSUE 34), at the reduced shape
    `serving.engine.selector_lower_args` builds. Same donated-carry
    calling convention as serving_delta_apply."""
    from scheduler_plugins_tpu.serving.engine import selector_lower_args

    fn, args = selector_lower_args()
    return fn, args, None


def build_wave_gang_solve():
    """`gangs.waves.wave_solve_body` — one wave of the wave-batched gang
    solve: the sequential scan's own per-gang body
    (`gangs.topology.place_gang_one`) vmapped over a lane of independent
    gang ids against the wave-start state (the host validator owns the
    between-wave carries). Lowered at the reduced `_gang_problem` shape
    with an 8-lane wave."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scheduler_plugins_tpu.gangs.waves import wave_solve_fn

    prob = _gang_problem()
    gangs = jax.tree.map(jnp.asarray, prob["gangs"])
    G = prob["gangs"].rank_mask.shape[0]
    ids = jnp.asarray((np.arange(8) % G).astype(np.int32))
    args = (
        gangs, jnp.asarray(prob["free0"]), jnp.asarray(prob["eq_used0"]),
        jnp.asarray(prob["node_mask"]), ids,
    )
    return wave_solve_fn(), args, None


def build_packing_solve():
    """`parallel.solver.packing_solve_fn` — the jitted packing-mode
    flagship program (ISSUE 14: targeted waterfill wave placement +
    `ops.packing.packing_refine` consolidation rounds + the shared
    finalize tail) at the reduced pack-smoke shape. The iteration
    budget, fragmentation-price weight and temperature schedule are the
    traced `pack_aux` argument, so ONE program serves every budget the
    caller sweeps — the property the lowering certifies for
    TPU (the refinement's `lax.while_loop` bound is a traced scalar)."""
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.ops.packing import pack_aux_vector
    from scheduler_plugins_tpu.parallel.solver import packing_solve_fn

    shape = problems.PACK_SMOKE_SHAPE
    _, snap, _, weights = problems.packing_problem(
        shape["n_nodes"], shape["demand_frac"], shape["empty_frac"]
    )
    fn = packing_solve_fn(collect_stats=True)
    return fn, (snap, weights, pack_aux_vector(32, 4.0, 0.0, 0.5)), None


def build_sweep_solve():
    """The vmapped counterfactual weight sweep (`parallel.solver
    .sweep_solve_fn` — the tuning observatory's hot program): the
    bit-faithful sequential solve body vmapped over an 8-lane candidate
    weight bucket on the reduced tune-smoke trimaran roster
    (tools/tune.py SMOKE corpus roster at a smaller shape; candidate
    weights are traced per-lane arguments, so ONE program serves every
    candidate — the property the lowering certifies for TPU)."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu import plugins as P
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.models import trimaran_scenario
    from scheduler_plugins_tpu.parallel.solver import sweep_solve_fn
    from scheduler_plugins_tpu.tuning import sweep

    cluster = trimaran_scenario(n_nodes=64, n_pods=32, seed=0)
    scheduler = Scheduler(Profile(plugins=[
        P.TargetLoadPacking(), P.LoadVariationRiskBalancing(),
    ]))
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    W = sweep.pad_candidates(sweep.candidate_weights([1, 1], 8))
    auxes = tuple(p.aux() for p in scheduler.profile.plugins)
    fn = sweep_solve_fn(scheduler)
    args = (snap, scheduler.initial_state(snap), auxes, jnp.asarray(W))
    return fn, args, None


PROGRAMS = {
    "entry": build_entry,
    "serving_delta_apply": build_serving_delta_apply,
    "serving_node_compact": build_serving_node_compact,
    "sharded_wave_chunk": build_sharded_wave_chunk,
    "sharded_wave_chunk_pallas": build_sharded_wave_chunk_pallas,
    "pallas_ring_offsets": build_pallas_ring_offsets,
    "pallas_fused_election": build_pallas_fused_election,
    "sweep_solve": build_sweep_solve,
    "packing_solve": build_packing_solve,
    "rank_gang_solve": build_rank_gang_solve,
    "wave_gang_solve": build_wave_gang_solve,
    "elastic_shrink": build_elastic_shrink,
    "serving_side_apply": build_serving_side_apply,
    "serving_selector_apply": build_serving_selector_apply,
    "bench_cfg0_tpu_smoke": build_cfg0_tpu_smoke,
    "bench_cfg1_flagship": build_cfg1_flagship,
    "bench_cfg2_trimaran_sequential": build_cfg2_trimaran_sequential,
    "bench_cfg3_numa_sequential": build_cfg3_numa_sequential,
    "bench_cfg4_gang_quota_sequential": build_cfg4_gang_quota_sequential,
    "bench_cfg5_network_sequential": build_cfg5_network_sequential,
    "bench_cfg6_north_star_chunk": build_cfg6_north_star_chunk,
    "sharded_batch_solve": build_sharded_batch_solve,
    "sharded_profile_batch_solve": build_sharded_profile_batch_solve,
}


def lower_program(name: str) -> str:
    """Build + AOT-lower one registered program to TPU StableHLO text."""
    import jax
    import jax.export

    fn, args, mesh = PROGRAMS[name]()
    if mesh is not None:
        with jax.set_mesh(mesh):
            exported = jax.export.export(fn, platforms=(TARGET_PLATFORM,))(
                *args
            )
    else:
        exported = jax.export.export(fn, platforms=(TARGET_PLATFORM,))(*args)
    return exported.mlir_module()


def analyze(name: str) -> dict:
    text = lower_program(name)
    findings = scan_landmines(text)
    hist = op_histogram(text)
    return {
        "sha256": stablehlo_digest(text),
        "stablehlo_bytes": len(canonical_text(text)),
        "ops": {k: hist[k] for k in sorted(hist)},
        "landmines": findings,
    }


def run(names, check: bool) -> int:
    import jax

    prior = {}
    if MANIFEST.exists():
        prior = json.loads(MANIFEST.read_text())
    results, failures = {}, []
    for name in names:
        print(f"[tpu-lower] {name} ...", flush=True)
        try:
            results[name] = analyze(name)
        except Exception as exc:  # lowering failure IS the gate tripping
            failures.append(f"{name}: lowering failed: {exc!r}")
            continue
        mines = results[name]["landmines"]
        if mines:
            for f in mines:
                failures.append(
                    f"{name}: TPU landmine {f['op']} on ({f['signature']})"
                )
        print(
            f"[tpu-lower] {name}: "
            f"{results[name]['stablehlo_bytes']} bytes, "
            f"{sum(results[name]['ops'].values())} ops, "
            f"{len(mines)} landmines",
            flush=True,
        )

    manifest = {
        "jax": jax.__version__,
        "platform": TARGET_PLATFORM,
        "programs": {
            n: {
                "sha256": r["sha256"],
                "stablehlo_bytes": r["stablehlo_bytes"],
                "landmines": len(r["landmines"]),
                "ops": r["ops"],
            }
            for n, r in sorted(results.items())
        },
    }

    if check and not prior:
        # the gate must fail CLOSED: a missing/deleted manifest means there
        # is nothing to verify drift against
        failures.append(
            "docs/tpu_lowering.json missing: run `python tools/tpu_lower.py` "
            "and commit it"
        )
    if check and prior:
        prior_programs = prior.get("programs", {})
        # any checked program absent from the manifest is a coverage gap —
        # also for --programs subsets (a new program must not check green
        # before its digest is committed)
        missing = [n for n in names if n in PROGRAMS and n not in prior_programs]
        if missing:
            failures.append(
                f"manifest missing programs {missing}: run "
                "`python tools/tpu_lower.py` and commit docs/tpu_lowering.json"
            )
        if prior.get("jax") == jax.__version__:
            for n, r in results.items():
                want = prior_programs.get(n, {}).get("sha256")
                if want and want != r["sha256"]:
                    failures.append(
                        f"{n}: StableHLO digest drift "
                        f"(manifest {want[:12]}.., now {r['sha256'][:12]}..) "
                        "— intended? re-run `python tools/tpu_lower.py` and "
                        "commit the manifest diff"
                    )
        else:
            print(
                f"[tpu-lower] note: manifest was written under jax "
                f"{prior.get('jax')}, running {jax.__version__}; digest "
                "equality not enforced (lowering text is version-dependent), "
                "landmine/coverage gates still apply"
            )

    if not check and set(names) == set(PROGRAMS) and not failures:
        MANIFEST.write_text(json.dumps(manifest, indent=1, sort_keys=True) + "\n")
        print(f"[tpu-lower] wrote {MANIFEST.relative_to(REPO)}")
    elif not check:
        # a failed or partial run must never clobber the last-good manifest
        reason = "failures" if failures else "partial program set"
        print(f"[tpu-lower] {reason}: manifest NOT rewritten")

    for f in failures:
        print(f"[tpu-lower] FAIL: {f}", file=sys.stderr)
    if not failures:
        print(
            f"[tpu-lower] OK: {len(results)}/{len(names)} programs lower to "
            f"TPU StableHLO with zero landmines"
        )
    return 1 if failures else 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--check",
        action="store_true",
        help="read-only: verify against the committed manifest "
        "(digest equality enforced only under the manifest's jax version)",
    )
    parser.add_argument(
        "--programs",
        nargs="+",
        choices=sorted(PROGRAMS),
        default=sorted(PROGRAMS),
        help="subset of programs (default: all)",
    )
    args = parser.parse_args(argv)
    bootstrap()
    return run(args.programs, check=args.check)


if __name__ == "__main__":
    sys.exit(main())
