"""Benchmark: pods scheduled per second.

Default (`python bench.py`): the BASELINE config-1 flagship — allocatable-
scored placement, 1024 nodes x 8192 pods — on the wave-parallel batched
solver (the throughput mode). `--config 2..5` run the other BASELINE.md
scenarios on the bit-faithful sequential solve with the matching plugin
profiles (trimaran, NUMA, gang+quota, network-aware).

`baseline` is a pure-Python per-pod x per-node loop implementing the
reference's algorithmic shape (the Go hot loop; the reference publishes no
numbers of its own, BASELINE.md), measured on a subsample and extrapolated.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np


def python_baseline_pods_per_sec(cluster, sample=200):
    """Reference-shaped sequential loop: per pod, scan every node (filter:
    all resources fit; score: weighted allocatable, min-max normalize),
    commit the winner."""
    nodes = list(cluster.nodes.values())
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS

    free = {
        n.name: dict(n.allocatable) for n in nodes
    }
    pods = cluster.pending_pods()[:sample]
    wcpu, wmem = 1 << 20, 1
    # Allocatable scores are STATIC per node (reference scores allocatable,
    # not free capacity) — precompute once like the plugin does
    static_raw = {
        n.name: -(
            (n.allocatable.get(CPU, 0) * wcpu + n.allocatable.get(MEMORY, 0) * wmem)
            // (wcpu + wmem)
        )
        for n in nodes
    }
    start = time.perf_counter()
    for pod in pods:
        req = pod.effective_request()
        best, best_score = None, None
        raw = {}
        feasible = []
        for node in nodes:
            f = free[node.name]
            if all(f.get(r, 0) >= q for r, q in req.items()) and f.get(PODS, 0) >= 1:
                feasible.append(node.name)
                raw[node.name] = static_raw[node.name]
        if not feasible:
            continue
        lo = min(raw.values())
        hi = max(raw.values())
        for name in feasible:
            score = 0 if hi == lo else (raw[name] - lo) * 100 // (hi - lo)
            if best_score is None or score > best_score:
                best, best_score = name, score
        for r, q in req.items():
            free[best][r] = free[best].get(r, 0) - q
        free[best][PODS] -= 1
    elapsed = time.perf_counter() - start
    return len(pods) / elapsed


def _bench_span(name, **args):
    """Tracer span on the "bench" row (no-op unless `--trace out.json`
    enabled the global tracer) — so every config's timed phases land in
    the exported timeline, not just the chunk pipeline's rows."""
    from scheduler_plugins_tpu.utils import observability as obs

    return obs.tracer.span(name, tid="bench", **args)


def _device_columns():
    """{"platform", "device_kind", "devices"} stamped into every emitted
    line, read from JAX as it runs — a number is never printed without
    the device it came from."""
    from scheduler_plugins_tpu.obs import costmodel

    device = costmodel.device_identity()
    return {
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "devices": device["count"],
    }


def _pallas_attribution():
    """The `pallas` block stamped on every config-8 / shard-smoke /
    pallas-smoke JSON line (ISSUE 13): `enabled`
    is whether a solver built by THIS process resolves to the ring
    kernels (`SPT_PALLAS` opt-in AND not sanitize mode), `interpret`
    whether the kernels run as their CPU twins (None when disabled), and
    `kernels` the short StableHLO digests of the committed pallas program
    entries from docs/tpu_lowering.json (None when the manifest or its
    pallas entries are absent) — so a bench line is attributable to the
    exact certified kernel programs without reading the manifest."""
    from scheduler_plugins_tpu.parallel import kernels as pk
    from scheduler_plugins_tpu.utils import sanitize

    # mirror sharded_wave_solve's build-time gate (opt-in AND not
    # sanitize mode): the stamp must report what a solver built by
    # THIS process actually resolves, never the raw env var — under
    # SPT_SANITIZE=1 the lax build runs even with SPT_PALLAS=1
    enabled = pk.pallas_enabled() and not sanitize.enabled()
    interpret = pk.pallas_interpret() if enabled else None
    try:
        manifest = json.loads(
            (Path(__file__).resolve().parent / "docs" / "tpu_lowering.json")
            .read_text()
        )
    except (OSError, ValueError):
        manifest = {}
    digests = {
        name: prog["sha256"][:12]
        for name, prog in manifest.get("programs", {}).items()
        if "pallas" in name
    } or None
    return {"enabled": enabled, "interpret": interpret, "kernels": digests}


def _quality_cycle(snap, assignment, wait=None):
    """JSON-ready placement-quality dict for one solved snapshot (the
    jittable `tuning.quality` tensor core) — the quality columns every
    bench line carries next to `drift`."""
    import numpy as np

    from scheduler_plugins_tpu.tuning import quality as Q

    assignment = np.asarray(assignment)
    if wait is None:
        wait = np.zeros(assignment.shape[0], bool)
    q = Q.cycle_quality(snap, assignment, None, np.asarray(wait))
    return {k: round(v, 4) for k, v in q.items()}


def _quality_state(alloc, used, node_mask=None):
    """JSON-ready {fragmentation, util_imbalance} of an accumulated
    cluster state (the multi-cycle configs 7/8)."""
    from scheduler_plugins_tpu.tuning import quality as Q

    q = Q.state_quality(alloc, used, node_mask)
    return {k: round(v, 4) for k, v in q.items()}


#: bench metric -> (registered cost-model program, pods per compiled solve)
#: for the cost-digest column (ISSUE 20). Only configs whose EXACT solve
#: program is in the tools/tpu_lower.py registry (and therefore in
#: docs/cost_model.json) get a digest — the comparison arms (configs
#: 8-15) and the batch modes run shapes the registry doesn't pin, so
#: their columns stay null rather than borrow a near-miss digest.
COST_PROGRAMS = {
    "tpu_smoke_pods_per_sec": ("bench_cfg0_tpu_smoke", 256),
    "pods_scheduled_per_sec": ("bench_cfg1_flagship", 8192),
    "trimaran_pods_per_sec": ("bench_cfg2_trimaran_sequential", 2048),
    "numa_pods_per_sec": ("bench_cfg3_numa_sequential", 512),
    "gang_quota_pods_per_sec": ("bench_cfg4_gang_quota_sequential", 2048),
    "network_pods_per_sec": ("bench_cfg5_network_sequential", 1024),
    # per-chunk program: the north-star metric counts all 102400 pods but
    # the compiled solve (and its roofline floor) is one 8192-pod chunk
    "north_star_pods_per_sec": ("bench_cfg6_north_star_chunk", 8192),
}


def _cost_columns(metric, pods_per_sec=None):
    """The two static-cost columns every bench line carries (ISSUE 20):
    the solve program's `cost_digest` from docs/cost_model.json (a pure
    function of the committed tree) and `roofline_calibration`, the
    measured step time over the roofline FLOOR of one solve of the
    registered program ON THE DEVICE THAT RAN IT: the hardware row is
    looked up from the running device's `device_kind`
    (`parallel.vmem.target_for_device_kind`; an unknown TPU raises), and
    a CPU run prints null — there is no chip to hold the time against.
    The floor uses spec-sheet peaks, so the ratio is large by
    construction. Unknown metric or missing manifest give nulls."""
    from scheduler_plugins_tpu.obs import costmodel
    from scheduler_plugins_tpu.parallel import vmem

    cols = {"cost_digest": None, "roofline_calibration": None}
    entry = COST_PROGRAMS.get(metric)
    if entry is None:
        return cols
    program, pods_per_solve = entry
    row = costmodel.program_row(program)
    if not row:
        return cols
    cols["cost_digest"] = row.get("cost_digest")
    device = costmodel.device_identity()
    if (pods_per_sec and device["platform"] == "tpu"
            and row.get("flops") is not None):
        target = vmem.target_for_device_kind(device["device_kind"])
        floor_us = costmodel.roofline(
            row["flops"], row["bytes_accessed"], target=target
        )["step_floor_us"]
        measured_us = pods_per_solve / pods_per_sec * 1e6
        cols["roofline_calibration"] = {
            "measured_over_floor": round(measured_us / floor_us, 2),
            "floor_us": floor_us,
            "target": target,
        }
    return cols


def _emit(metric, pods_per_sec, detail, baseline, compiled=None, extra=None,
          drift=None, quality=None):
    """One JSON line. `vs_baseline` is the honest headline: measured against
    the COMPILED reference-shaped loop (`bridge/ref_baseline.cc`) when it is
    available — the reference is compiled Go, so a pure-Python denominator
    flatters every multiplier. The Python-loop ratio stays as a secondary
    column (`vs_python_baseline`).

    `drift` is the placement-quality column, present in EVERY line (null
    only when no anchor exists, e.g. native build unavailable): relative
    score-sum drift of the emitted placements vs the BIT-FAITHFUL
    sequential semantics on the shared cycle-initial objective. Sequential
    configs anchor at 0.0 by definition; the batched modes report their
    measured trade (cfg2's f32 curve knife edges); the flagship configs
    (0/1/6) anchor on the compiled alloc loop, which is placement-identical
    to the sequential path on the allocatable profile."""
    line = {
        "metric": metric,
        "value": round(pods_per_sec, 1),
        "unit": f"pods/s ({detail})",
        **_device_columns(),
        # the mesh the solve ran on: None = unsharded single-device
        # program (the sharded wave runs override it via `extra`)
        "mesh_shape": None,
        "drift": None if drift is None else round(drift, 4),
        # the placement-quality columns (tuning.quality): per-cycle
        # objectives for the single-solve configs, accumulated-state
        # fragmentation/balance for the multi-cycle ones; None only when
        # no solve ran (error/stale-capture lines)
        "quality": quality,
        # static-cost columns: the solve program's cost digest + the
        # measured-vs-roofline calibration ratio (nulls for configs the
        # registry doesn't pin)
        **_cost_columns(metric, pods_per_sec),
    }
    if compiled is not None and compiled > 0:
        line["vs_baseline"] = round(pods_per_sec / compiled, 2)
        line["vs_compiled_baseline"] = round(pods_per_sec / compiled, 2)
        line["compiled_baseline_pods_per_sec"] = round(compiled, 1)
        line["vs_python_baseline"] = round(pods_per_sec / baseline, 2)
    else:
        line["vs_baseline"] = round(pods_per_sec / baseline, 2)
        line["vs_python_baseline"] = round(pods_per_sec / baseline, 2)
    if extra:
        line.update(extra)
    print(json.dumps(line))


def _compiled_baseline(config, snap, meta, weights=None, plugins=None):
    """(pods/s, placements) of the compiled reference-shaped loop for this
    config's snapshot ((None, None) for a config that has none); raises
    when the native build fails. Real (node, pod) counts come from meta so
    the denominator scans the reference's cluster shape, not the
    snapshot's padded buckets. The placements feed the per-line `drift`
    column."""
    from scheduler_plugins_tpu.bridge import ref_baseline as rb

    kw = dict(n_nodes=len(meta.node_names), n_pods=len(meta.pod_names))
    if config in (0, 1, 6):
        rate, _, out = rb.compiled_alloc_baseline(snap, weights, **kw)
    elif config == 2:
        rate, _, out = rb.compiled_trimaran_baseline(snap, **kw)
    elif config == 3:
        rate, _, out = rb.compiled_numa_baseline(snap, **kw)
    elif config == 4:
        rate, _, out = rb.compiled_gang_quota_baseline(snap, weights, **kw)
    elif config == 5:
        net = next(
            p for p in plugins if type(p).__name__ == "NetworkOverhead"
        )
        rate, _, out = rb.compiled_network_baseline(
            snap, net._zone_cost, net._region_cost, **kw
        )
    else:
        return None, None
    return rate, out


def _score_sum_drift(scores, ours, ref):
    """Relative score-sum drift of `ours` vs `ref` placements on the
    flagship's pod-invariant (N,) static allocatable objective (the
    profile-general (P, N) form lives in
    `parallel.solver.score_drift_vs_sequential`); unplaced/padded slots
    carry -1 and contribute nothing. None when there are no reference
    placements to compare against."""
    if ref is None:
        return None
    ref = np.asarray(ref)
    ours = np.asarray(ours)[: len(ref)]

    def ssum(a):
        return int(scores[a[a >= 0]].sum())

    s_ref = ssum(ref)
    return (ssum(ours) - s_ref) / max(abs(s_ref), 1)


def alloc_problem(n_nodes, n_pods):
    """(cluster, snap, meta, weights) for the allocatable-profile configs —
    the single construction bench and the AOT gate (tools/tpu_lower.py)
    share."""
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
    per-wave occupancy stats — the program bench ships AND the one the AOT
    gate lowers, so perf PRs can see whether wave count or per-wave cost
    moved."""
    from scheduler_plugins_tpu.parallel.solver import batch_solve

    return batch_solve(snap, weights, max_waves=8, collect_stats=True)


def _trim_occupancy(occ, waves=None):
    """JSON-ready admitted-per-wave list: clipped to the executed wave
    count when known, trailing never-run zero slots dropped either way —
    the ONE formatting rule for every bench line's `wave_occupancy`."""
    occ = [int(x) for x in occ]
    if waves is not None:
        occ = occ[: max(waves, 1)]
    while len(occ) > 1 and occ[-1] == 0:
        occ.pop()
    return occ


def _wave_extra(stats):
    """JSON-ready per-wave occupancy from a waterfill stats dict."""
    waves = int(stats["waves"])
    return {
        "waves": waves,
        "wave_occupancy": _trim_occupancy(stats["occupancy"], waves),
    }


def main(n_nodes=None, n_pods=None):
    import jax

    n_nodes = n_nodes or FLAGSHIP_SHAPE["n_nodes"]
    n_pods = n_pods or FLAGSHIP_SHAPE["n_pods"]
    cluster, snap, meta, weights = alloc_problem(n_nodes, n_pods)

    solve = jax.jit(flagship_solve_stats)
    # warmup/compile, completed by a host transfer. The warmup solves the
    # UNPERTURBED snapshot: its placements anchor the drift column (the
    # timed runs perturb one request for cache busting)
    assignment, admitted, wait, stats = solve(snap, weights)
    warm_np = np.asarray(assignment)

    # median of fully-synchronized runs with perturbed inputs; completion is
    # forced by a host transfer of the assignment
    runs = 10
    times = []
    assignment_np = None
    for k in range(runs):
        snap_k = snap.replace(
            pods=snap.pods.replace(req=snap.pods.req.at[0, 0].add(k % 3))
        )
        np.asarray(snap_k.pods.req[0, 0])  # perturbation settled
        start = time.perf_counter()
        with _bench_span(f"flagship solve run {k}", pods=n_pods):
            assignment, _, _, stats = solve(snap_k, weights)
            assignment_np = np.asarray(assignment)
        times.append(time.perf_counter() - start)
    elapsed = sorted(times)[len(times) // 2]
    placed = int((assignment_np >= 0).sum())
    pods_per_sec = n_pods / elapsed

    baseline = python_baseline_pods_per_sec(cluster)
    compiled, ref_out = _compiled_baseline(1, snap, meta, weights=weights)
    _emit(
        "pods_scheduled_per_sec",
        pods_per_sec,
        f"{n_nodes} nodes x {n_pods} pods, {placed} placed",
        baseline,
        compiled=compiled,
        drift=_score_sum_drift(
            _alloc_objective(snap, weights), warm_np, ref_out
        ),
        quality=_quality_cycle(snap, warm_np),
        extra=_wave_extra(stats),
    )


def _alloc_objective(snap, weights):
    """(N,) static allocatable node scores — the flagship's pod-invariant
    cycle-initial objective (the reference scores allocatable, not free
    capacity), shared by the drift column of configs 0/1/6."""
    from scheduler_plugins_tpu.ops.allocatable import (
        MODE_LEAST,
        allocatable_scores,
    )

    return np.asarray(allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST))


#: the north-star chunk-loop shapes (BASELINE.json headline scale) — shared
#: with the AOT compile-readiness gate (tools/tpu_lower.py) so the program
#: it certifies is the program this file ships
NORTH_STAR_SHAPE = dict(n_nodes=10_240, n_pods=102_400, chunk=8192)
FLAGSHIP_SHAPE = dict(n_nodes=1024, n_pods=8192)
SMOKE_SHAPE = dict(n_nodes=64, n_pods=256)


def north_star_solve_chunk(raw, node_mask, req_chunk, mask_chunk, free0):
    """One north-star chunk: static allocatable scores -> targeted
    waterfill, O(P*R) per lite wave instead of the (P, N) matrix (masked
    nodes fit nothing with zeroed free capacity). rescue_window=256 halves
    the end-game (K, N) rescue cost at this scale (8 waves x 256 slots
    still drains every straggler, all pods placed).

    Returns ((assignment, wave_stats), free) — the pipeline calling
    convention (`parallel.pipeline.run_chunk_pipeline`): the free carry is
    DONATED at the jit boundary (`donated_chunk_solver`) so it threads
    chunk to chunk in place. Chunk-invariant tensors (raw scores, node
    mask) are ARGUMENTS, not jit closure captures, so the compiled program
    is exactly the one tools/tpu_lower.py lowers and digests."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted

    assignment, free, stats = waterfill_assign_targeted(
        raw, req_chunk, mask_chunk,
        jnp.where(node_mask[:, None], free0, 0), max_waves=8,
        rescue_window=256, collect_stats=True,
    )
    return (assignment, stats), free


def north_star_chunk_solver():
    """The jitted, carry-donating chunk program bench ships (and the AOT
    gate lowers): one constructor so the two cannot drift apart."""
    from scheduler_plugins_tpu.parallel.pipeline import donated_chunk_solver

    return donated_chunk_solver(north_star_solve_chunk, carry_argnum=4)


def north_star_problem(n_nodes, n_pods, chunk):
    """(snap, meta, weights, raw, padded) for the chunked north-star run —
    the single construction bench and the AOT gate share."""
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


def north_star(n_nodes=None, n_pods=None, chunk=None):
    """The BASELINE.json headline scale: 10k nodes x 100k pending pods.

    Pods stream through the batched waterfill in queue-order chunks with
    free capacity carried between chunks (chunk boundaries preserve the
    queue order the sequential semantics define), bounding the (P, N)
    working set to one chunk. The chunk loop is the donated, double-
    buffered pipeline (`parallel.pipeline.run_chunk_pipeline`): chunk
    k+1's inputs stage host->device and chunk k-1's assignments return
    device->host while chunk k solves, with the free carry donated in
    place — the device never idles at a chunk boundary, and the host
    stays at most one chunk behind (a bounded in-flight window)."""
    import jax

    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import run_chunk_pipeline

    n_nodes = n_nodes or NORTH_STAR_SHAPE["n_nodes"]
    n_pods = n_pods or NORTH_STAR_SHAPE["n_pods"]
    chunk = chunk or NORTH_STAR_SHAPE["chunk"]
    cluster, snap, meta, weights, raw, padded = north_star_problem(
        n_nodes, n_pods, chunk
    )
    node_mask = snap.nodes.mask

    solve_chunk = north_star_chunk_solver()
    # pod chunks as host buffers: the pipeline's H2D ingest is part of the
    # timed run (streaming arrival), staged one chunk ahead of the solve
    req_np = np.asarray(snap.pods.req)
    mask_np = np.asarray(snap.pods.mask)
    chunk_inputs = [
        (req_np[lo:lo + chunk], mask_np[lo:lo + chunk])
        for lo in range(0, padded, chunk)
    ]
    free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    # warm up compile on the first chunk shape (the free buffer is donated
    # by the warmup call; the timed loop below rebuilds it). Inputs are
    # staged as the pipeline stages them: a committed device array and a
    # host buffer are different jit cache keys, and the timed window must
    # not trace
    dev0 = tuple(jax.device_put(x) for x in chunk_inputs[0])
    (a, _), _ = solve_chunk(raw, node_mask, *dev0, free)
    np.asarray(a)

    # calibration: ONE synchronous chunk solve (compile already paid),
    # completion forced by host transfer — the device-busy yardstick the
    # pipeline-bubble metric scales by the per-chunk wave counters
    # (device time is never read from inside jit; CLAUDE.md / GL008)
    free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    cal_start = time.perf_counter()
    with _bench_span("calibration chunk", chunk=chunk):
        (a_cal, cal_stats), _ = solve_chunk(raw, node_mask, *dev0, free)
        np.asarray(a_cal)
    cal_s = time.perf_counter() - cal_start
    cal_waves = max(1, int(np.asarray(cal_stats["waves"])))

    free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    start = time.perf_counter()
    with _bench_span("north-star pipeline", chunks=len(chunk_inputs)):
        results, free, chunk_done_s, timeline = run_chunk_pipeline(
            solve_chunk, (raw, node_mask), chunk_inputs, free
        )
    elapsed = time.perf_counter() - start
    chunk_assignments = [a for a, _ in results]
    placed = int(sum((a >= 0).sum() for a in chunk_assignments))
    waves = sum(int(stats["waves"]) for _, stats in results)
    occ = np.sum([np.asarray(stats["occupancy"]) for _, stats in results],
                 axis=0)
    # BASELINE.json names p99 scheduling latency alongside throughput: a
    # pod's decision latency is its chunk's completion time since the
    # batch was submitted (pods stream through in queue order), so the
    # per-pod latency distribution is the chunk completion times weighted
    # by chunk size
    pod_latency_s = np.repeat(chunk_done_s, chunk)[:n_pods]
    # device-busy estimate: calibration chunk's synchronous solve time
    # scaled by the wave counters -> the pipeline-overlap report
    solve_est_ms = cal_s * 1000.0 * (waves / cal_waves)
    overlap = timeline.summary(solve_ms=solve_est_ms)
    baseline = python_baseline_pods_per_sec(cluster, sample=40)
    compiled, ref_out = _compiled_baseline(6, snap, meta, weights=weights)
    _emit(
        "north_star_pods_per_sec",
        n_pods / elapsed,
        f"{n_nodes} nodes x {n_pods} pods chunked x{chunk}, {placed} placed",
        baseline,
        compiled=compiled,
        drift=_score_sum_drift(
            _alloc_objective(snap, weights),
            np.concatenate(chunk_assignments)[:n_pods],
            ref_out,
        ),
        quality=_quality_cycle(
            snap, np.concatenate(chunk_assignments)[: snap.num_pods]
        ),
        extra={
            "pod_latency_p50_ms": round(
                float(np.percentile(pod_latency_s, 50)) * 1000, 1),
            "pod_latency_p99_ms": round(
                float(np.percentile(pod_latency_s, 99)) * 1000, 1),
            "chunks": len(chunk_inputs),
            "waves": waves,
            "wave_occupancy": _trim_occupancy(occ),
            "pipeline_bubble_ms": overlap["pipeline_bubble_ms"],
            "overlap_efficiency": overlap["overlap_efficiency"],
            "h2d_overlap_efficiency": overlap["h2d_overlap_efficiency"],
            "d2h_overlap_efficiency": overlap["d2h_overlap_efficiency"],
        },
    )


def tpu_smoke(n_nodes=None, n_pods=None):
    """Tiny-shape on-chip smoke: one `batch_solve` at 64x256 — seconds,
    not minutes — confirming the targeted waterfill's argsort/cummax/
    scatter chains compile on a TPU. Refuses to run anywhere else: a
    number under this metric's name comes from a chip. Same measurement
    discipline as the flagship (host-transfer timing)."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench --config 0 is the on-chip smoke; jax runs on {platform!r}"
        )
    n_nodes = n_nodes or SMOKE_SHAPE["n_nodes"]
    n_pods = n_pods or SMOKE_SHAPE["n_pods"]
    cluster, snap, meta, weights = alloc_problem(n_nodes, n_pods)

    solve = jax.jit(flagship_solve_stats)
    compile_start = time.perf_counter()
    assignment, _, _, stats = solve(snap, weights)
    warm_np = np.asarray(assignment)  # unperturbed placements: drift anchor
    compile_s = time.perf_counter() - compile_start

    times = []
    assignment_np = None
    for k in range(5):
        snap_k = snap.replace(
            pods=snap.pods.replace(req=snap.pods.req.at[0, 0].add(k % 3))
        )
        np.asarray(snap_k.pods.req[0, 0])
        start = time.perf_counter()
        with _bench_span(f"smoke solve run {k}", pods=n_pods):
            assignment, _, _, stats = solve(snap_k, weights)
            assignment_np = np.asarray(assignment)
        times.append(time.perf_counter() - start)
    elapsed = sorted(times)[len(times) // 2]
    placed = int((assignment_np >= 0).sum())
    baseline = python_baseline_pods_per_sec(cluster, sample=100)
    compiled, ref_out = _compiled_baseline(0, snap, meta, weights=weights)
    _emit(
        "tpu_smoke_pods_per_sec",
        n_pods / elapsed,
        f"{n_nodes} nodes x {n_pods} pods smoke, {placed} placed",
        baseline,
        compiled=compiled,
        drift=_score_sum_drift(
            _alloc_objective(snap, weights), warm_np, ref_out
        ),
        quality=_quality_cycle(snap, warm_np),
        extra={"compile_seconds": round(compile_s, 1), **_wave_extra(stats)},
    )


#: one source of truth for the config -> metric-name mapping (the error
#: path must emit the same names the success paths do)
CONFIG_METRICS = {
    1: "pods_scheduled_per_sec", 2: "trimaran_pods_per_sec",
    3: "numa_pods_per_sec", 4: "gang_quota_pods_per_sec",
    5: "network_pods_per_sec", 6: "north_star_pods_per_sec",
    0: "tpu_smoke_pods_per_sec", 7: "serving_churn_pods_per_sec",
    8: "mega_pods_per_sec", 9: "chaos_churn_pods_per_sec",
    10: "rank_gang_pods_per_sec", 11: "cluster_life_pods_per_sec",
    12: "mega_gang_ranks_per_sec", 13: "packing_frontier_pods_per_sec",
    14: "drifting_mix_pods_per_sec", 15: "lane_pods_per_sec",
}


# ---------------------------------------------------------------------------
# config 8: mega scale — shard_map ring-election wave solver on a host mesh
# ---------------------------------------------------------------------------

#: the mega scale (~10x north star): 100k nodes x 1M pods is the regime
#: placement systems actually live in ("Tesserae", arxiv 2508.04953).
#: Tensor-level construction — a million Pod objects would spend the run on
#: host-side bookkeeping the solver never sees. Runs on an 8-device
#: virtual CPU ("nodes",) mesh (XLA_FLAGS
#: --xla_force_host_platform_device_count) and its lines say `platform:
#: cpu`: a functional and count result at a shard count no single host of
#: chips offers. Four real chips run `chip_smoke.py --devices 4`.
MEGA_SHAPE = dict(n_nodes=100_000, n_pods=1_000_000, chunk=16_384, devices=8)
#: reduced mega for the `make shard-smoke` CI gate: a NON-shard-multiple
#: node count (1020 pads to 1024 over 8 shards — the mesh-padding edge
#: rides through CI), small enough for 2-core runners, cumulative capacity
#: far below the 2^53 bit-parity bound so placements must match EXACTLY
SHARD_SMOKE_SHAPE = dict(n_nodes=1020, n_pods=8192, chunk=2048, devices=8)


def _force_host_mesh(n_devices):
    """Pin the n-device virtual CPU platform AND the one-lane-per-device
    execution policy (`--xla_cpu_multi_thread_eigen=false`) for the mesh
    benches. With per-device intra-op thread pools, an oversubscribed host
    measures pool thrashing, not mesh scaling; one lane per device is the
    regime a real chip mesh executes in (a device never borrows its
    neighbor's ALUs), and BOTH arms of the mega comparison run under the
    same policy in the same process. Must run before the first backend
    touch."""
    import os

    import __graft_entry__

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_cpu_multi_thread_eigen" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_multi_thread_eigen=false"
        ).strip()
    __graft_entry__._force_cpu_platform(n_devices)


def mega_problem(n_nodes, n_pods, chunk, seed=0):
    """Tensor-level problem dict for the mega configs, CANONICAL axis order
    and reference units (cpu millicores, memory bytes, int64). Four
    heterogeneous node SKUs make the allocatable ranking non-degenerate
    (the wave election actually orders nodes); the pod distribution
    mirrors `models.scenarios._pods`. Pods pad to a chunk multiple so
    every chunk shares one compiled shape (mask False on padding)."""
    import jax.numpy as jnp

    from scheduler_plugins_tpu.api.resources import (
        CANONICAL,
        CPU,
        MEMORY,
        PODS,
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


def _mega_run(problem, shape, sharded: bool):
    """One full pass of the mega pod stream through the double-buffered
    chunk pipeline: the shard_map ring-election solver on the ("nodes",)
    host mesh when `sharded`, else the single-device wave path (the
    north-star chunk program — the same targeted waterfill, unsharded, on
    device 0). Returns (elapsed_s, assignment (n_pods,), waves, occ,
    done_s)."""
    import jax
    import jax.numpy as jnp

    from scheduler_plugins_tpu.parallel.pipeline import run_chunk_pipeline

    chunk = shape["chunk"]
    chunk_inputs = [
        (problem["req"][lo:lo + chunk], problem["mask"][lo:lo + chunk])
        for lo in range(0, problem["padded"], chunk)
    ]
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from scheduler_plugins_tpu.parallel.mesh import (
            NODES_AXIS,
            make_node_mesh,
        )
        from scheduler_plugins_tpu.parallel.solver import (
            rank_order_inputs,
            sharded_wave_chunk_solver,
        )

        mesh = make_node_mesh(shape["devices"])
        solve_chunk = sharded_wave_chunk_solver(
            mesh, shape["n_nodes"], rescue_window=256
        )
        node_ids, rank_free = rank_order_inputs(
            problem["raw"], problem["free0"], problem["node_mask"],
            shape["devices"],
        )
        carry_host = np.asarray(rank_free)  # donated away each pass
        carry_sharding = NamedSharding(mesh, P(NODES_AXIS, None))
        invariant = (
            jax.device_put(node_ids, NamedSharding(mesh, P(NODES_AXIS))),
        )

        def fresh_carry():
            return jax.device_put(carry_host, carry_sharding)
    else:
        solve_chunk = north_star_chunk_solver()
        invariant = (problem["raw"], problem["node_mask"])
        carry_host = np.asarray(problem["free0"])

        def fresh_carry():
            return jnp.asarray(carry_host)

    # warmup/compile on the first chunk shape (the warmup donates its own
    # fresh carry; the timed pipeline below gets another)
    out0, _ = solve_chunk(
        *invariant, *(jax.device_put(a) for a in chunk_inputs[0]),
        fresh_carry(),
    )
    np.asarray(out0[0])

    carry = fresh_carry()
    start = time.perf_counter()
    with _bench_span(
        "mega pipeline", chunks=len(chunk_inputs), sharded=sharded
    ):
        results, carry, done_s, _timeline = run_chunk_pipeline(
            solve_chunk, invariant, chunk_inputs, carry
        )
    elapsed = time.perf_counter() - start
    assignment = np.concatenate(
        [np.asarray(a) for a, _ in results]
    )[: problem["n_pods"]]
    waves = sum(int(np.asarray(s["waves"])) for _, s in results)
    occ = np.sum([np.asarray(s["occupancy"]) for _, s in results], axis=0)
    return elapsed, assignment, waves, occ, done_s


def _mega_capacity_violations(problem, assignment) -> int:
    """Hard-constraint audit: replay the placements against allocatable —
    (node, resource) cells over capacity, pods slot charged 1 per pod."""
    from scheduler_plugins_tpu.tuning.gates import pod_fit_demand_np

    used = np.zeros_like(problem["alloc"])
    dem = pod_fit_demand_np(problem["req"][: problem["n_pods"]])
    placed = assignment >= 0
    np.add.at(used, assignment[placed], dem[placed])
    return int((used > problem["alloc"]).sum())


def mega(shape=None, emit=True):
    """Config 8: the mega-scale sharded wave bench. Streams the pod set
    through the shard_map ring-election waterfill on an n-device ("nodes",)
    host mesh AND through the single-device wave path (the north-star chunk
    program) on the same tensors, so every line carries the measured mesh
    scaling (`vs_baseline` = sharded vs 1-device pods/s), an exact
    placement diff, and a replayed hard-constraint audit. Placements are
    expected bit-identical below the 2^53 cumulative-capacity bound (the
    smoke shape); at full mega scale the float64 bucket positions may
    round differently between shardings — a targeting heuristic only, so
    `placements_match` is reported and hard constraints stay exact either
    way."""
    shape = shape or MEGA_SHAPE
    # must run before the first backend touch (device count fixes at init)
    _force_host_mesh(shape["devices"])

    problem = mega_problem(shape["n_nodes"], shape["n_pods"], shape["chunk"])
    t_sh, a_sh, waves, occ, done_s = _mega_run(problem, shape, sharded=True)
    t_one, a_one, _, _, _ = _mega_run(problem, shape, sharded=False)

    match = bool((a_sh == a_one).all())
    violations = _mega_capacity_violations(problem, a_sh)
    placed = int((a_sh >= 0).sum())
    from scheduler_plugins_tpu.tuning.gates import pod_fit_demand_np

    used = np.zeros_like(problem["alloc"])
    dem = pod_fit_demand_np(problem["req"][: problem["n_pods"]])
    placed_mask = a_sh >= 0
    np.add.at(used, a_sh[placed_mask], dem[placed_mask])
    quality = _quality_state(problem["alloc"], used)
    pod_latency_s = np.repeat(done_s, shape["chunk"])[: shape["n_pods"]]
    line = {
        "devices": shape["devices"],
        "mesh_shape": {"nodes": shape["devices"]},
        "vs_single_device": round(t_one / t_sh, 2),
        "single_device_pods_per_sec": round(shape["n_pods"] / t_one, 1),
        "placements_match": match,
        "capacity_violations": violations,
        "chunks": problem["padded"] // shape["chunk"],
        "waves": waves,
        "wave_occupancy": _trim_occupancy(occ),
        "pod_latency_p50_ms": round(
            float(np.percentile(pod_latency_s, 50)) * 1000, 1),
        "pod_latency_p99_ms": round(
            float(np.percentile(pod_latency_s, 99)) * 1000, 1),
    }
    line["quality"] = quality
    line["pallas"] = _pallas_attribution()
    if emit:
        _emit(
            CONFIG_METRICS[8],
            shape["n_pods"] / t_sh,
            f"{shape['n_nodes']} nodes x {shape['n_pods']} pods chunked "
            f"x{shape['chunk']}, {placed} placed, "
            f"{shape['devices']}-device nodes mesh",
            baseline=shape["n_pods"] / t_one,
            drift=(0.0 if match else _score_sum_drift(
                np.asarray(problem["raw"]), a_sh, a_one
            )),
            quality=quality,
            extra=line,
        )
    return line


def shard_smoke():
    """CI gate (`make shard-smoke`): reduced mega config on an 8-host-device
    ("nodes",) mesh — the sharded wave placements must MATCH the single-
    device wave path bit-exactly (the reduced shape sits far below the 2^53
    cumulative-capacity bound, where parity is unconditional), the replayed
    hard-constraint audit must be clean, and the traced chunk program's
    collective census must stay O(shards) with ZERO all_gather/all_to_all
    equations (the silent way the ring election degrades back to a full
    gather; graft_lint GL009 is the source-level twin). One JSON line;
    rc 1 on any failure."""
    shape = SHARD_SMOKE_SHAPE
    _force_host_mesh(shape["devices"])
    import jax.numpy as jnp  # noqa: F401

    from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
    from scheduler_plugins_tpu.parallel.solver import (
        collective_census,
        rank_order_inputs,
        sharded_wave_chunk_solver,
    )

    line = mega(shape=shape, emit=False)

    # static collective census of the traced chunk program: the wave loops
    # are while_loops, so each wave body appears ONCE in the jaxpr and the
    # census bounds the per-wave collective count independent of trip count
    problem = mega_problem(shape["n_nodes"], shape["n_pods"], shape["chunk"])
    S = shape["devices"]
    mesh = make_node_mesh(S)
    node_ids, rank_free = rank_order_inputs(
        problem["raw"], problem["free0"], problem["node_mask"], S
    )
    chunk = shape["chunk"]
    # use_pallas pinned False: this gate's budget bounds the LAX psum/pmin
    # formulation — an ambient SPT_PALLAS=1 must not swap the censused
    # program (the pallas census has its own gate, pallas_smoke)
    census = collective_census(
        sharded_wave_chunk_solver(
            mesh, shape["n_nodes"], rescue_window=256, use_pallas=False
        ),
        node_ids, problem["req"][:chunk], problem["mask"][:chunk], rank_free,
    )
    gathers = sum(
        census.get(k, 0)
        for k in ("all_gather", "all_gather_invariant", "all_to_all")
    )
    total = sum(census.values())
    # 3 wave bodies (whole-queue lite, windowed lite, rescue), each a
    # handful of psum/pmin champion reductions — CONSTANT per wave at this
    # shard count (the slot-scatter scan; the ppermute ring takes over
    # above ops.assign.PSUM_SCAN_MAX_SHARDS at S-1 steps per scan), so the
    # budget is linear in S with room for either regime
    budget = 6 * S + 24
    ok = (
        line["placements_match"]
        and line["capacity_violations"] == 0
        and gathers == 0
        and 0 < total <= budget
    )
    print(json.dumps({
        "metric": "shard_smoke",
        **_device_columns(),
        "pallas": _pallas_attribution(),
        "collectives": census,
        "collective_total": total,
        "collective_budget": budget,
        "full_axis_gathers": gathers,
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


def pallas_smoke():
    """CI gate (`make pallas-smoke`, ISSUE 13): the Pallas-election sharded
    wave solve (interpret-mode CPU twins — `SPT_PALLAS=1`'s off-TPU build)
    must match the lax-collectives build BIT-EXACTLY on the reduced
    SHARD_SMOKE_SHAPE chunk stream: placements, the final resident
    rank-free carry, and a clean replayed capacity audit. The traced
    pallas program's census must show the collectives actually replaced —
    ring kernels present, ZERO framework psum/pmin/ppermute left in the
    wave bodies, zero full-axis gathers — and the kernel programs must be
    covered by the committed lowering manifest. One JSON line; rc 1 on
    any failure."""
    shape = SHARD_SMOKE_SHAPE
    _force_host_mesh(shape["devices"])
    import jax

    from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
    from scheduler_plugins_tpu.parallel.solver import (
        collective_census,
        rank_order_inputs,
        sharded_wave_chunk_solver,
    )

    S = shape["devices"]
    chunk = shape["chunk"]
    problem = mega_problem(shape["n_nodes"], shape["n_pods"], shape["chunk"])
    mesh = make_node_mesh(S)
    node_ids, rank_free0 = rank_order_inputs(
        problem["raw"], problem["free0"], problem["node_mask"], S
    )
    carry_host = np.asarray(rank_free0)

    def run_arm(use_pallas):
        solver = sharded_wave_chunk_solver(
            mesh, shape["n_nodes"], rescue_window=256,
            use_pallas=use_pallas, pallas_interpret=True,
        )
        rank_free = jax.numpy.asarray(carry_host)
        parts = []
        # warmup/compile on the first chunk (its own donated carry)
        out0, _ = solver(
            node_ids, problem["req"][:chunk], problem["mask"][:chunk],
            jax.numpy.asarray(carry_host),
        )
        np.asarray(out0[0])
        start = time.perf_counter()
        for lo in range(0, problem["padded"], chunk):
            (a, _stats), rank_free = solver(
                node_ids, problem["req"][lo:lo + chunk],
                problem["mask"][lo:lo + chunk], rank_free,
            )
            parts.append(np.asarray(a))
        elapsed = time.perf_counter() - start
        return (
            np.concatenate(parts)[: problem["n_pods"]],
            np.asarray(rank_free), elapsed, solver,
        )

    a_lax, f_lax, t_lax, _ = run_arm(False)
    a_pk, f_pk, t_pk, solver_pk = run_arm(True)

    census = collective_census(
        solver_pk, node_ids, problem["req"][:chunk],
        problem["mask"][:chunk], jax.numpy.asarray(carry_host),
    )
    gathers = sum(
        census.get(k, 0)
        for k in ("all_gather", "all_gather_invariant", "all_to_all")
    )
    framework_left = sum(
        census.get(k, 0) for k in ("psum", "pmin", "pmax", "ppermute")
    )
    pallas = _pallas_attribution()
    manifest_covers = bool(pallas["kernels"]) and {
        "pallas_ring_offsets", "pallas_fused_election",
        "sharded_wave_chunk_pallas",
    } <= set(pallas["kernels"])
    match = bool((a_pk == a_lax).all())
    carry_match = bool((f_pk == f_lax).all())
    violations = _mega_capacity_violations(problem, a_pk)
    ok = (
        match and carry_match and violations == 0
        and census.get("pallas_call", 0) > 0
        and framework_left == 0 and gathers == 0
        and manifest_covers
    )
    print(json.dumps({
        "metric": "pallas_smoke",
        **_device_columns(),
        "pallas": {**pallas, "enabled": True, "interpret": True},
        "placements_match": match,
        "carry_match": carry_match,
        "capacity_violations": violations,
        "collectives": census,
        "framework_collectives_left": framework_left,
        "full_axis_gathers": gathers,
        "manifest_covers_kernels": manifest_covers,
        "pods_per_sec": round(problem["n_pods"] / t_pk, 1),
        "vs_lax_collectives": round(t_lax / t_pk, 2),
        "ok": bool(ok),
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 7: sustained-churn serving (resident-state engine vs re-snapshot)
# ---------------------------------------------------------------------------

#: the serving headline shape: a large cluster with a deep bound
#: population (what makes per-cycle re-snapshotting expensive) under
#: Poisson pod arrivals/departures plus slow node add/remove churn
SERVING_SHAPE = dict(
    n_nodes=2000, prefill=12288, cycles=48, warmup=4,
    lam_arrive=48, lam_depart=24, node_add_every=16, node_remove_every=24,
)
#: reduced shape for the `make churn-smoke` CI gate (2-core runners).
#: Node counts sit BELOW their padding bucket (240 < 256, 2000 < 2048
#: above) so the bench's node adds grow within the resident padding
#: instead of crossing a bucket boundary and retracing the solve mid-run
CHURN_SMOKE_SHAPE = dict(
    n_nodes=240, prefill=2048, cycles=24, warmup=3,
    lam_arrive=16, lam_depart=8, node_add_every=9, node_remove_every=0,
)


def churn_cluster(n_nodes, prefill, seed=0):
    """Cluster with a deep ALREADY-BOUND pod population (arriving assigned,
    as a feed replay would deliver them) — the state a serving scheduler
    carries between decisions, and exactly what the full-resnapshot
    baseline must re-accumulate every cycle."""
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    rng = np.random.default_rng(seed)
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(Node(
            name=f"node-{i:05d}",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 256},
        ))
    cpus = rng.integers(100, 2000, size=prefill)
    mems = rng.integers(256 << 20, 2 * gib, size=prefill)
    for i in range(prefill):
        pod = Pod(
            name=f"bound-{i:06d}", creation_ms=i,
            containers=[Container(requests={
                CPU: int(cpus[i]), MEMORY: int(mems[i])})],
        )
        pod.node_name = f"node-{i % n_nodes:05d}"
        cluster.add_pod(pod)
    return cluster


def _churn_events(cluster, rng, shape, cycle, now, serial):
    """Apply one cycle's churn to `cluster`; returns the new pod serial.
    Every draw depends only on the rng stream and the cluster's bound set,
    so two runs with equal placements see IDENTICAL event sequences."""
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS

    gib = 1 << 30
    for _ in range(int(rng.poisson(shape["lam_arrive"]))):
        serial += 1
        cluster.add_pod(Pod(
            name=f"arr-{serial:06d}", creation_ms=now * 1000 + serial,
            containers=[Container(requests={
                CPU: int(rng.integers(100, 2000)),
                MEMORY: int(rng.integers(256 << 20, 2 * gib))})],
        ))
    n_dep = int(rng.poisson(shape["lam_depart"]))
    if n_dep:
        bound = sorted(
            uid for uid, p in cluster.pods.items()
            if p.node_name is not None and p.node_name in cluster.nodes
        )
        if bound:
            picks = rng.choice(
                len(bound), size=min(n_dep, len(bound)), replace=False
            )
            for i in sorted(int(x) for x in picks):
                cluster.remove_pod(bound[i])
    every = shape.get("node_add_every")
    if every and cycle % every == every - 1:
        cluster.add_node(Node(
            name=f"node-x{cycle:04d}",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 256},
        ))
    every = shape.get("node_remove_every")
    if every and cycle % every == every - 1 and len(cluster.nodes) > 1:
        # drain-then-delete (the kubectl drain shape): pods leave cleanly,
        # then the node row disappears (a serve-engine re-base)
        victim = next(iter(cluster.nodes))
        for uid in [
            u for u, p in cluster.pods.items() if p.node_name == victim
        ]:
            cluster.remove_pod(uid)
        cluster.remove_node(victim)
    return serial


def run_churn(cluster, scheduler, shape, seed=0, engine=None):
    """Drive `shape['cycles']` timed churn cycles (after `warmup` untimed
    ones) through `framework.cycle.run_cycle`, in serve mode when `engine`
    is given. Returns per-cycle wall times, per-cycle decision counts, and
    the accumulated uid -> node placements."""
    from scheduler_plugins_tpu.framework import run_cycle

    rng = np.random.default_rng(seed + 1)
    serial = 0
    times, decided = [], []
    placements = {}
    total_cycles = shape["warmup"] + shape["cycles"]
    for cycle in range(total_cycles):
        now = 1000 * (cycle + 1)
        serial = _churn_events(cluster, rng, shape, cycle, now, serial)
        start = time.perf_counter()
        with _bench_span(
            f"churn cycle {cycle}", mode="serve" if engine else "baseline"
        ):
            report = run_cycle(scheduler, cluster, now=now, serve=engine)
        elapsed = time.perf_counter() - start
        placements.update(report.bound)
        if cycle >= shape["warmup"]:
            times.append(elapsed)
            decided.append(len(report.bound) + len(report.failed))
    return {
        "times": times, "decided": decided, "placements": placements,
    }


def _churn_capacity_violations(cluster) -> int:
    """Hard-constraint audit after a churn run: nodes over allocatable on
    any resource (bound pods replayed against node capacity)."""
    from scheduler_plugins_tpu.api.resources import PODS

    used: dict = {name: {} for name in cluster.nodes}
    for pod in cluster.pods.values():
        if pod.node_name is None or pod.node_name not in used:
            continue
        bucket = used[pod.node_name]
        for r, q in pod.effective_request().items():
            bucket[r] = bucket.get(r, 0) + q
        bucket[PODS] = bucket.get(PODS, 0) + 1
    violations = 0
    for name, node in cluster.nodes.items():
        for r, q in used[name].items():
            if q > node.allocatable.get(r, 0):
                violations += 1
    return violations


def _cluster_state_matrices(cluster):
    """(alloc (N, R), used (N, R)) CANONICAL-axis matrices of a cluster's
    bound population — the accumulated end state the multi-cycle serving
    bench scores with `tuning.quality.state_quality`."""
    from scheduler_plugins_tpu.api.resources import CANONICAL, PODS

    names = list(cluster.nodes)
    pos = {n: i for i, n in enumerate(names)}
    R = len(CANONICAL)
    alloc = np.zeros((len(names), R), np.int64)
    used = np.zeros((len(names), R), np.int64)
    for i, name in enumerate(names):
        node = cluster.nodes[name]
        for r, q in node.allocatable.items():
            if r in CANONICAL:
                alloc[i, CANONICAL.index(r)] = q
    for pod in cluster.pods.values():
        i = pos.get(pod.node_name)
        if i is None:
            continue
        for r, q in pod.effective_request().items():
            if r in CANONICAL:
                used[i, CANONICAL.index(r)] += q
        used[i, CANONICAL.index(PODS)] += 1
    return alloc, used


def serving_churn(shape=None, emit=True):
    """Config 7: the sustained-churn serving bench. Runs the SAME Poisson
    event sequence twice — resident-state serve mode (delta ingest,
    `serving.engine.ServeEngine`) vs the full-resnapshot baseline
    (`Cluster.snapshot` every cycle) — both through the bit-faithful
    sequential solve, and reports p50/p99 decision latency, cycles/s and
    pods/s with the cycles/s ratio as `vs_baseline`. Placements must
    match exactly (drift 0.0): serve mode changes WHERE the solver input
    comes from, never what the solver decides."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
    from scheduler_plugins_tpu.serving import ServeEngine

    shape = shape or SERVING_SHAPE
    seed = 0

    serve_cluster = churn_cluster(shape["n_nodes"], shape["prefill"], seed)
    engine = ServeEngine().attach(serve_cluster)
    serve_sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    serve = run_churn(serve_cluster, serve_sched, shape, seed, engine=engine)

    base_cluster = churn_cluster(shape["n_nodes"], shape["prefill"], seed)
    base_sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    base = run_churn(base_cluster, base_sched, shape, seed)

    serve_s, base_s = sum(serve["times"]), sum(base["times"])
    n_cycles = len(serve["times"])
    n_decided = sum(serve["decided"])
    match = serve["placements"] == base["placements"]
    violations = _churn_capacity_violations(serve_cluster)
    # per-decision latency: a pod's decision latency is its cycle's wall
    # time (ingest -> host-visible bind), weighted by decisions per cycle
    lat = np.repeat(serve["times"], serve["decided"])
    p50 = float(np.percentile(lat, 50)) * 1000 if lat.size else 0.0
    p99 = float(np.percentile(lat, 99)) * 1000 if lat.size else 0.0
    ratio = (n_cycles / serve_s) / (n_cycles / base_s) if serve_s else 0.0
    line = {
        "cycles": n_cycles,
        "cycles_per_sec": round(n_cycles / serve_s, 2),
        "baseline_cycles_per_sec": round(n_cycles / base_s, 2),
        "vs_full_resnapshot": round(ratio, 2),
        "decision_latency_p50_ms": round(p50, 2),
        "decision_latency_p99_ms": round(p99, 2),
        "placements_match": bool(match),
        "capacity_violations": violations,
        "rebases": engine.rebases,  # engine-local (the metric is global)
        "resident_generation": engine.generation,
        "decisions": n_decided,
    }
    if emit:
        _emit(
            CONFIG_METRICS[7],
            n_decided / serve_s if serve_s else 0.0,
            f"{shape['n_nodes']} nodes, {shape['prefill']} bound, "
            f"{n_cycles} cycles Poisson churn "
            f"λ={shape['lam_arrive']}/{shape['lam_depart']}, serve mode",
            baseline=n_decided / base_s if base_s else 1.0,
            drift=(0.0 if match else None),
            quality=_quality_state(*_cluster_state_matrices(serve_cluster)),
            extra=line,
        )
    return line


def churn_smoke(min_ratio=1.5):
    """CI gate (`make churn-smoke`): reduced sustained-churn run — the
    delta path must beat the full-resnapshot baseline by >= `min_ratio`
    on cycles/s, place IDENTICALLY (the serve engine feeds the same
    bit-faithful solve), and leave zero hard-constraint violations. One
    JSON line; rc 1 on any failure."""
    line = serving_churn(shape=CHURN_SMOKE_SHAPE, emit=False)
    ok = (
        line["vs_full_resnapshot"] >= min_ratio
        and line["placements_match"]
        and line["capacity_violations"] == 0
    )
    print(json.dumps({
        "metric": "churn_smoke",
        "min_ratio": min_ratio,
        **_device_columns(),
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 9: chaos churn — the config-7 workload under a seeded fault plan
# ---------------------------------------------------------------------------

#: the chaos headline shape: the config-7 churn workload (same generators,
#: same Poisson streams) with the full `resilience.faults` classification
#: injected — hung solve, device error, garbage output, dropped/
#: duplicated/corrupted sink events, feed stall, crash-mid-cycle. The
#: claim under test (docs/ROBUSTNESS.md): zero hard-constraint
#: violations, bounded recovery, and EVERY cycle bit-identical to the
#: no-chaos run — the watchdog failover is bit-faithful by construction
#: and the anti-entropy window is pinned to one cycle, so faults cost
#: latency and rebases, never placements.
CHAOS_SHAPE = dict(
    n_nodes=500, prefill=4096, cycles=32, warmup=4,
    lam_arrive=32, lam_depart=16, node_add_every=10, node_remove_every=0,
    timeout_s=2.0, hang_seconds=3.0, stall_seconds=0.05, probe_every=1,
)
#: reduced shape for the `make chaos-smoke` CI gate (2-core runners);
#: node count below its padding bucket like CHURN_SMOKE_SHAPE
CHAOS_SMOKE_SHAPE = dict(
    n_nodes=120, prefill=1024, cycles=16, warmup=2,
    lam_arrive=12, lam_depart=6, node_add_every=7, node_remove_every=0,
    timeout_s=1.5, hang_seconds=2.5, stall_seconds=0.02, probe_every=1,
)
#: interleaved watchdog-on/off pairs for the fault-free overhead bound
#: (the replay-smoke pairing discipline: the statistic is the median of
#: PAIRED deltas, the floor is the off series' own p10-p90 spread)
CHAOS_OVERHEAD_PAIRS = 9


def _chaos_fault_plan(shape, seed=0):
    from scheduler_plugins_tpu.resilience import faults as F

    plan = F.FaultPlan.standard(
        seed, shape["cycles"], hang_seconds=shape["hang_seconds"],
        stall_seconds=shape["stall_seconds"],
    )
    for spec in plan.specs:
        if spec.site == F.DELTA_EVENT:
            # a delta fault can only fire when a sink event actually
            # passes through its cycle — sticky specs roll forward to
            # the first opportunity instead of silently missing
            spec.sticky = True
    return plan


def _chaos_resilience(shape, engine, seed=0):
    from scheduler_plugins_tpu.resilience import Resilience, SolveWatchdog

    return Resilience(
        watchdog=SolveWatchdog(
            timeout_s=shape["timeout_s"], max_attempts=2,
            backoff_base_s=0.01, seed=seed,
        ),
        probe_every=shape["probe_every"],
        engine=engine,
    )


def _run_chaos_arm(scheduler, shape, seed=0, plan=None):
    """One full chaos-churn run: the config-7 event stream through serve
    mode + the resilience layer, with `plan` installed (None = the
    no-chaos control arm — SAME engine/resilience configuration, so the
    two arms differ ONLY in injected faults). The anti-entropy window is
    pinned to ONE cycle (`verify_every=1`): every refresh digests the
    resident columns before the solve consumes them, which is what makes
    "every cycle bit-identical under faults" a provable claim instead of
    a lucky one. Returns per-cycle wall times, per-cycle bound maps, and
    the recovery/degradation bookkeeping."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.resilience import faults as F
    from scheduler_plugins_tpu.serving import ServeEngine

    cluster = churn_cluster(shape["n_nodes"], shape["prefill"], seed)
    engine = ServeEngine().attach(cluster)
    engine.verify_every = 1
    rz = _chaos_resilience(shape, engine, seed)
    rng = np.random.default_rng(seed + 1)
    serial = 0
    times, decided, bound_per_cycle = [], [], []
    degraded_cycles = 0
    crashes = 0
    #: accumulated across engine replacements (a crash swaps the engine
    #: object; its pre-crash counters must not vanish with it)
    rebases_acc = 0
    divergences_acc = 0
    rebases0 = engine.rebases
    recoveries: list = []
    checkpoint = None
    if plan is not None:
        F.install(plan)
    try:
        total = shape["warmup"] + shape["cycles"]
        for cycle in range(total):
            now = 1000 * (cycle + 1)
            timed_idx = cycle - shape["warmup"]
            if plan is not None:
                # warmup cycles are fault-free (timed_idx < 0 matches no
                # spec); the window also covers _churn_events' sink pushes
                plan.begin_cycle(timed_idx)
                stall = plan.fire(F.FEED_STALL)
                if stall is not None:
                    time.sleep(stall.seconds)  # a stalled feed costs
                    # latency; the cycle itself must be unaffected
            serial = _churn_events(cluster, rng, shape, cycle, now, serial)
            start = time.perf_counter()
            try:
                with _bench_span(
                    f"chaos cycle {cycle}", chaos=plan is not None
                ):
                    report = run_cycle(
                        scheduler, cluster, now=now, serve=engine,
                        resilience=rz,
                    )
                bound = dict(report.bound)
                failed = len(report.failed)
                degraded = report.degraded
            except F.CrashInjected as crash:
                # process death after bindings landed: the engine (its
                # resident tensors + undrained sink) and the watchdog
                # state die; the harness "restarts" from the last
                # checkpoint, and anti-entropy re-bases the stale base
                # within one window
                crashes += 1
                bound = dict(crash.report.bound)
                failed = len(crash.report.failed)
                degraded = rz.degraded
                recoveries.extend(rz.recoveries)
                if rz.degraded:
                    # the crash ends the open degradation window at the
                    # restart boundary — charge it now (the fresh process
                    # starts fast and re-measures if the backend is still
                    # sick) instead of silently dropping it with the old rz
                    recoveries.append((rz.degraded_at, rz.cycle))
                rebases_acc += engine.rebases - rebases0
                divergences_acc += engine.antientropy_divergences
                engine.detach()
                engine = ServeEngine().attach(cluster)
                engine.verify_every = 1
                rebases0 = engine.rebases
                if checkpoint is not None:
                    engine.restore_checkpoint(checkpoint)
                rz = _chaos_resilience(shape, engine, seed)
            elapsed = time.perf_counter() - start
            checkpoint = engine.checkpoint_bytes() or checkpoint
            if timed_idx >= 0:
                times.append(elapsed)
                decided.append(len(bound) + failed)
                bound_per_cycle.append(bound)
                degraded_cycles += 1 if degraded else 0
    finally:
        if plan is not None:
            F.clear()
    recoveries.extend(rz.recoveries)
    if rz.degraded:
        # never recovered within the run: charge the open window through
        # one past the end so the gate's recovery bound fails honestly
        recoveries.append((rz.degraded_at, rz.cycle + 1))
    return {
        "times": times, "decided": decided, "bound": bound_per_cycle,
        "cluster": cluster, "engine": engine, "resilience": rz,
        "degraded_cycles": degraded_cycles, "crashes": crashes,
        "rebases": rebases_acc + engine.rebases - rebases0,
        "divergences": divergences_acc + engine.antientropy_divergences,
        "recoveries": recoveries,
    }


def _chaos_overhead_pct(shape, seed=77):
    """Fault-free watchdog/failover overhead, measured the replay-smoke
    way: two identically-evolving serve clusters, one cycle each per
    pair (resilience OFF first, then ON), overhead = median of paired
    deltas, floor = the off series' p10-p90 spread. Two full passes over
    the same seeded event stream — the first untimed, so every jit shape
    a timed pair can hit (pod buckets vary with the Poisson draws) is
    already warm and the statistic times the WATCHDOG layer, never a
    compile. One shared scheduler across arms and passes for the same
    reason. Anti-entropy stays at its production cadence here — this
    bounds the per-cycle cost of the watchdog wrapping alone."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
    from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
    from scheduler_plugins_tpu.serving import ServeEngine

    scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    n_cycles = shape["warmup"] + CHAOS_OVERHEAD_PAIRS

    def one_cycle(arm, cycle):
        now = 1000 * (cycle + 1)
        arm["serial"] = _churn_events(
            arm["cluster"], arm["rng"], shape, cycle, now, arm["serial"]
        )
        start = time.perf_counter()
        run_cycle(
            scheduler, arm["cluster"], now=now, serve=arm["engine"],
            resilience=arm["resilience"],
        )
        return time.perf_counter() - start

    off, pair_pct = [], []
    for timed in (False, True):
        arms = {}
        for name in ("off", "on"):
            cluster = churn_cluster(
                shape["n_nodes"], shape["prefill"], seed
            )
            engine = ServeEngine().attach(cluster)
            arms[name] = dict(
                cluster=cluster, engine=engine,
                rng=np.random.default_rng(seed + 1), serial=0,
                resilience=(
                    None if name == "off"
                    else _chaos_resilience(shape, engine, seed)
                ),
            )
        for cycle in range(n_cycles):
            t_off = one_cycle(arms["off"], cycle)
            t_on = one_cycle(arms["on"], cycle)
            if timed and cycle >= shape["warmup"]:
                off.append(t_off)
                pair_pct.append(100.0 * (t_on - t_off) / t_off)
    median_off = sorted(off)[len(off) // 2]
    overhead_pct = sorted(pair_pct)[len(pair_pct) // 2]
    off_sorted = sorted(off)
    spread_pct = 100.0 * (
        off_sorted[int(0.9 * (len(off) - 1))]
        - off_sorted[int(0.1 * (len(off) - 1))]
    ) / median_off
    return overhead_pct, spread_pct


def chaos_churn(shape=None, emit=True, seed=0):
    """Config 9: the chaos bench. Runs the config-7 churn workload twice
    through serve mode + the resilience layer — once under the full
    seeded fault plan, once fault-free (the control) — and reports
    recovery windows, degraded-time fraction, violations, and the
    fault-free watchdog overhead. The headline claims (asserted by
    `chaos_smoke`): zero hard-constraint violations, every injected
    fault recovered within a bounded cycle count, EVERY cycle's bound
    set bit-identical to the no-chaos control, and fault-free-path
    watchdog overhead within max(2%, the run's own jitter floor)."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable

    shape = shape or CHAOS_SHAPE
    # ONE scheduler for both arms: the control arm walks the identical
    # event stream first, so every (pod-bucket, node-bucket) jit shape
    # the chaos arm's device solves and probation probes hit is warm —
    # the watchdog deadline then times the BACKEND, never a legit compile
    scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    control = _run_chaos_arm(scheduler, shape, seed=seed, plan=None)
    plan = _chaos_fault_plan(shape, seed=seed)
    chaos = _run_chaos_arm(scheduler, shape, seed=seed, plan=plan)

    cycles_match = sum(
        1 for a, b in zip(chaos["bound"], control["bound"]) if a == b
    )
    n_cycles = len(chaos["times"])
    cumulative_chaos: dict = {}
    cumulative_control: dict = {}
    for b in chaos["bound"]:
        cumulative_chaos.update(b)
    for b in control["bound"]:
        cumulative_control.update(b)
    violations = _churn_capacity_violations(chaos["cluster"])
    recovery_cycles = [b - a for a, b in chaos["recoveries"]]
    # delta faults recover within the pinned one-cycle anti-entropy
    # window BY CONSTRUCTION (verify_every=1, divergence => rebase before
    # the solve); solve faults measure their own windows via probation
    recovery_max = max(
        recovery_cycles + ([1] if chaos["divergences"] else [0])
    )
    overhead_pct, jitter_floor_pct = _chaos_overhead_pct(shape, seed + 77)
    serve_s, control_s = sum(chaos["times"]), sum(control["times"])
    n_decided = sum(chaos["decided"])
    lat = np.repeat(chaos["times"], chaos["decided"])
    line = {
        "cycles": n_cycles,
        "faults_injected": len(plan.log),
        "faults_unfired": len(plan.unfired()),
        "fault_log": [list(entry) for entry in plan.log],
        "cycles_bit_identical": cycles_match,
        "all_cycles_bit_identical": cycles_match == n_cycles,
        "cumulative_placements_match": (
            cumulative_chaos == cumulative_control
        ),
        "capacity_violations": violations,
        "recovery_cycles_max": recovery_max,
        "degraded_cycles": chaos["degraded_cycles"],
        "degraded_fraction": round(chaos["degraded_cycles"] / n_cycles, 4),
        "crashes": chaos["crashes"],
        "rebases": chaos["rebases"],
        "antientropy_divergences": chaos["divergences"],
        "watchdog_overhead_pct": round(overhead_pct, 2),
        "overhead_jitter_floor_pct": round(jitter_floor_pct, 2),
        "decision_latency_p50_ms": round(
            float(np.percentile(lat, 50)) * 1000, 2) if lat.size else 0.0,
        "decision_latency_p99_ms": round(
            float(np.percentile(lat, 99)) * 1000, 2) if lat.size else 0.0,
        "decisions": n_decided,
    }
    if emit:
        _emit(
            CONFIG_METRICS[9],
            n_decided / serve_s if serve_s else 0.0,
            f"{shape['n_nodes']} nodes, {shape['prefill']} bound, "
            f"{n_cycles} cycles chaos churn x {len(plan.specs)} faults, "
            "serve+resilience",
            baseline=n_decided / control_s if control_s else 1.0,
            drift=(0.0 if line["all_cycles_bit_identical"] else None),
            quality=_quality_state(
                *_cluster_state_matrices(chaos["cluster"])
            ),
            extra=line,
        )
    return line


def _tuner_chaos_check(seed=5):
    """The chaos gate's tuner-fault phase (ISSUE 15): drive the drifting
    -mix workload twice — a no-tuner control, then a shadow tuner under
    injected `tune.sweep` (hang past the deadline, garbage sweep output)
    and `tune.promote` (crash on EVERY application attempt) faults — and
    prove the robustness contract: every injected tuner fault leaves the
    LIVE per-cycle placements bit-identical to the control (a sick
    shadow lane can cost tuning, never a placement), and the tuner
    either keeps sweeping or disables itself. The hang is injected after
    the sweep program is warm, against a lowered deadline, so the
    timeout exercises the abandonment path, not a compile."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.resilience import faults as F
    from scheduler_plugins_tpu.tuning.shadow import ShadowTuner
    from scheduler_plugins_tpu.utils import flightrec

    shape = dict(
        TUNE_LIVE_SMOKE_SHAPE, n_nodes=32, arrivals=8, departs=3,
        warmup=2, cycles_a=2, cycles_b=12, regression_cycles=0,
        settle_cycles=0, candidates=8, corpus=2, sweep_every=2,
        confirm_sweeps=1,
    )
    script, _drift = _drift_script(shape, seed)
    total = len(script)

    def run_arm(with_tuner):
        cluster = _drift_cluster(shape, seed)
        scheduler = _drift_profile()
        tuner = None
        plan = None
        if with_tuner:
            flightrec.recorder.start(capacity=shape["corpus"] + 2)
            tuner = ShadowTuner(
                scheduler, candidates=shape["candidates"],
                corpus_cycles=shape["corpus"],
                sweep_every=shape["sweep_every"],
                confirm_sweeps=shape["confirm_sweeps"],
                tolerance=shape["tolerance"], sync=True, seed=seed,
            )
            plan = F.FaultPlan(seed=seed)
            plan.specs = [
                # garbage sweep output on the first post-drift sweeps:
                # the numpy oracles must disqualify every corrupted lane
                F.FaultSpec(site=F.TUNE_SWEEP, cycle=5, kind="garbage",
                            sticky=True),
                # hang fired later, once the sweep program is warm (the
                # deadline is lowered right before — see the loop)
                F.FaultSpec(site=F.TUNE_SWEEP, cycle=9, kind="hang",
                            seconds=5.0, sticky=True),
            ] + [
                # EVERY promotion application crashes (one spec per
                # cycle: a consumed sticky spec does not re-arm):
                # nothing the sweeps stage may ever reach live weights
                F.FaultSpec(site=F.TUNE_PROMOTE, cycle=cc, kind="crash")
                for cc in range(total)
            ]
            F.install(plan)
        bound_per_cycle = []
        try:
            for c, (phase, arrivals, departs) in enumerate(script):
                now = 1000 * (c + 1)
                _drift_apply_events(cluster, arrivals, departs, now)
                _drift_metrics(cluster, shape, phase)
                if plan is not None:
                    plan.begin_cycle(c)
                if tuner is not None:
                    if c == 9:
                        # sweep program warm by now: a hang must trip
                        # the deadline, not masquerade as a slow compile
                        tuner.deadline_s = 2.0
                    tuner.begin_cycle(now_ms=now)
                report = run_cycle(scheduler, cluster, now=now)
                if tuner is not None:
                    tuner.observe_report(report)
                bound_per_cycle.append(dict(report.bound))
        finally:
            if with_tuner:
                F.clear()
                flightrec.recorder.stop()
        if with_tuner:
            # let the abandoned hang worker (5s sleep + one warm sweep)
            # drain before the process can exit: a daemon thread dying
            # inside XLA at interpreter teardown aborts the process
            time.sleep(6.0)
        return bound_per_cycle, tuner, plan

    control, _t, _p = run_arm(False)
    chaos, tuner, plan = run_arm(True)
    st = tuner.status()
    cycles_match = sum(1 for a, b in zip(chaos, control) if a == b)
    promote_attempts = sum(
        1 for entry in plan.log if entry[1] == F.TUNE_PROMOTE
    )
    fired_sites = {entry[1] for entry in plan.log}
    line = {
        "cycles": total,
        "cycles_bit_identical": cycles_match,
        "all_cycles_bit_identical": cycles_match == total,
        "fault_log": [list(entry) for entry in plan.log],
        "sweep_hang_fired": (F.TUNE_SWEEP in fired_sites and any(
            e[1] == F.TUNE_SWEEP and e[2] == "hang" for e in plan.log
        )),
        "sweep_garbage_fired": any(
            e[1] == F.TUNE_SWEEP and e[2] == "garbage" for e in plan.log
        ),
        "promote_crashes": promote_attempts,
        "promotions": st["promotions"],
        "sweeps": st["sweeps"],
        "sweep_failures": st["sweep_failures"],
        "tuner_state": st["state"],
        # the static profile weights (tlp 1 / lvrb 20) must still rule
        "weights_unchanged": (
            st["active_weights"] == [1, 20]
            and st["last_known_good"] == [1, 20]
        ),
    }
    line["ok"] = bool(
        line["all_cycles_bit_identical"]
        and line["sweep_hang_fired"]
        and line["sweep_garbage_fired"]
        and line["promote_crashes"] >= 1
        and line["promotions"] == 0
        and line["weights_unchanged"]
        # recovered (kept sweeping after the faults) or self-disabled
        and (line["tuner_state"] in ("idle", "cooldown", "disabled"))
        and line["sweep_failures"] >= 1
    )
    return line


def chaos_smoke(bound_pct=2.0, recovery_bound=4):
    """CI gate (`make chaos-smoke`): reduced chaos config under the FULL
    seeded fault plan — zero hard-constraint violations, every fault
    fired and recovered within `recovery_bound` cycles, every cycle
    bit-identical to the no-chaos control, and fault-free watchdog
    overhead within max(`bound_pct`%, the run's own jitter floor) — plus
    the tuner-fault phase (`_tuner_chaos_check`): injected tune.sweep /
    tune.promote faults leave live placements bit-identical to a
    no-tuner control and the tuner recovers or disables itself. One JSON
    line; rc 1 on any failure."""
    line = chaos_churn(shape=CHAOS_SMOKE_SHAPE, emit=False)
    tuner_chaos = _tuner_chaos_check()
    overhead_bound = max(bound_pct, line["overhead_jitter_floor_pct"])
    ok = (
        line["capacity_violations"] == 0
        and line["faults_unfired"] == 0
        and line["faults_injected"] >= 8
        and line["all_cycles_bit_identical"]
        and line["cumulative_placements_match"]
        and line["recovery_cycles_max"] <= recovery_bound
        and line["crashes"] >= 1
        # one divergence per delta fault that poisoned resident state
        # (drop/dup/corrupt) plus the post-crash stale-checkpoint detect
        and line["antientropy_divergences"] >= 3
        and line["watchdog_overhead_pct"] <= overhead_bound
        and tuner_chaos["ok"]
    )
    print(json.dumps({
        "metric": "chaos_smoke",
        **_device_columns(),
        "overhead_bound_pct": round(overhead_bound, 2),
        "recovery_bound_cycles": recovery_bound,
        "ok": bool(ok),
        "tuner_chaos": tuner_chaos,
        **line,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 10: rank-aware gangs — topology-cost gang solves + elastic DL jobs
# ---------------------------------------------------------------------------

#: the config-10 headline shape: heterogeneous MPI gangs + elastic DL jobs
#: on a 3-level (node / zone-block / region) topology — the rank-aware arm
#: runs the gang phase (gangs.phase.GangPhase, jit solve with the numpy
#: twin cross-checked every cycle), the baseline arm the SAME event stream
#: through quorum-only Coscheduling
RANK_GANG_SHAPE = dict(
    n_nodes=384, n_regions=2, zones_per_region=3, n_mpi=24, mpi_ranks=8,
    n_dl=8, dl_min=2, dl_desired=4, dl_max=8,
)
#: reduced shape for the `make gang-smoke` CI gate (2-core runners)
GANG_SMOKE_SHAPE = dict(
    n_nodes=48, n_regions=2, zones_per_region=2, n_mpi=4, mpi_ranks=6,
    n_dl=2, dl_min=2, dl_desired=3, dl_max=6,
)

#: bench cycles advance wall-clock by this much so per-pod requeue
#: backoffs (seeded jittered exponential, initial ~1s) never stall a
#: parked gang across the measured window
GANG_CYCLE_MS = 10_000


def _gang_placement_costs(cluster):
    """Audit a cluster's CURRENT rank-gang placements: per-gang max/sum
    inter-rank cost + the `tuning.quality.rank_gang_quality` objectives,
    computed from bound members' nodes against the scenario's own
    NetworkTopology weights — the SAME scoring for both arms, so the
    quorum-only baseline is measured with the rank-aware yardstick."""
    from scheduler_plugins_tpu.gangs import phase as GP
    from scheduler_plugins_tpu.gangs import topology as GT
    from scheduler_plugins_tpu.tuning import quality as Q

    # the solver's own lowering (gangs.phase.block_cost_view): both arms
    # are measured with the identical yardstick by construction
    node_pos, zones, block_cost = GP.block_cost_view(cluster)
    groups = [
        pg for _, pg in sorted(cluster.pod_groups.items())
        if getattr(pg, "rank_aware", False)
    ]
    rows, per_gang = [], {}
    M = 1
    for pg in groups:
        bound = [
            node_pos[p.node_name] for p in cluster.gang_members(pg)
            if p.node_name in node_pos
        ]
        rows.append((pg.full_name, bound))
        M = max(M, len(bound))
    rank_nodes = np.full((max(len(rows), 1), M), -1, np.int32)
    rank_mask = np.zeros((max(len(rows), 1), M), bool)
    for g, (_, bound) in enumerate(rows):
        rank_nodes[g, : len(bound)] = bound
        rank_mask[g, : len(bound)] = True
    max_cost, sum_cost = GT.gang_cost_stats(
        rank_nodes, rank_mask, zones, block_cost
    )
    for g, (name, bound) in enumerate(rows):
        per_gang[name] = {
            "ranks": len(bound),
            "max_cost": int(max_cost[g]),
            "sum_cost": int(sum_cost[g]),
        }
    quality = Q.rank_gang_quality(rank_nodes, rank_mask, zones, block_cost)
    return per_gang, quality


def _gang_violations(cluster) -> dict:
    """Hard-constraint replay over the bound population: node capacity
    (`_churn_capacity_violations`), ElasticQuota max per namespace, and
    the rank-gang quorum/zero-partial invariant (a rank-aware gang's
    bound member count is either 0 or >= min_member)."""
    from scheduler_plugins_tpu.api.resources import PODS  # noqa: F401

    quota_violations = 0
    used: dict = {}
    for pod in cluster.pods.values():
        if pod.node_name is None:
            continue
        bucket = used.setdefault(pod.namespace, {})
        for r, q in pod.effective_request().items():
            bucket[r] = bucket.get(r, 0) + q
    for eq in cluster.quotas.values():
        bucket = used.get(eq.namespace, {})
        for r, cap in eq.max.items():
            if bucket.get(r, 0) > cap:
                quota_violations += 1
    quorum_violations = 0
    for pg in cluster.pod_groups.values():
        if not getattr(pg, "rank_aware", False):
            continue
        bound = sum(
            1 for p in cluster.gang_members(pg) if p.node_name is not None
        )
        if 0 < bound < pg.min_member:
            quorum_violations += 1
    return {
        "capacity": _churn_capacity_violations(cluster),
        "quota": quota_violations,
        "quorum": quorum_violations,
    }


def _run_gang_arm(shape, phase, seed=0, max_cycles=8):
    """One arm of the config-10 comparison: the scenario cluster driven
    through `run_cycle` (with the gang phase when `phase` is given) until
    the queue drains or `max_cycles`. Returns the cluster/scheduler plus
    per-gang admission latency in cycles and the wall time."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
    from scheduler_plugins_tpu.models import rank_gang_scenario
    from scheduler_plugins_tpu import plugins as P

    cluster = rank_gang_scenario(seed=seed, **shape)
    scheduler = Scheduler(Profile(plugins=[
        P.NodeResourcesAllocatable(), P.Coscheduling(),
        P.CapacityScheduling(),
    ]))
    first_pending: dict = {}
    admitted_at: dict = {}
    decided = 0
    start = time.perf_counter()
    for cycle in range(max_cycles):
        now = GANG_CYCLE_MS * (cycle + 1)
        pending_gangs = {
            pg.full_name
            for p in cluster.pending_pods()
            if (pg := cluster.pod_group_of(p)) is not None
        }
        for name in pending_gangs:
            first_pending.setdefault(name, cycle)
        report = run_cycle(scheduler, cluster, now=now, gangs=phase)
        decided += len(report.bound) + len(report.failed)
        for pg in cluster.pod_groups.values():
            name = pg.full_name
            if name in admitted_at or name not in first_pending:
                continue
            bound = sum(
                1 for p in cluster.gang_members(pg)
                if p.node_name is not None
            )
            if bound >= pg.min_member:
                admitted_at[name] = cycle - first_pending[name]
        if not cluster.pending_pods():
            break
    elapsed = time.perf_counter() - start
    return {
        "cluster": cluster, "scheduler": scheduler,
        "latencies": admitted_at, "first_pending": first_pending,
        "decided": decided, "elapsed": elapsed,
        "cycles": cycle + 1,
    }


def _elastic_transition(cluster, scheduler, phase, set_desired, start_now,
                        max_cycles=4):
    """Apply `set_desired(pg) -> int` to every elastic rank-aware gang
    (through `add_pod_group`, so PodGroup/Update events fire) and run
    cycles until every one's LIVE width equals its clamped desired.
    Returns the convergence cycle count (max_cycles + 1 = did not
    converge)."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.gangs import elastic_bounds

    targets = {}
    for pg in list(cluster.pod_groups.values()):
        if getattr(pg, "rank_aware", False) and pg.desired_replicas is not None:
            pg.desired_replicas = set_desired(pg)
            cluster.add_pod_group(pg)  # PodGroup/Update (api.events)
            targets[pg.full_name] = elastic_bounds(pg)[1]

    def converged():
        for name, want in targets.items():
            pg = cluster.pod_groups[name]
            live = sum(
                1 for p in cluster.gang_members(pg)
                if p.node_name is not None
            )
            if live != want:
                return False
        return True

    for k in range(max_cycles):
        if converged():
            return k
        run_cycle(
            scheduler, cluster, now=start_now + GANG_CYCLE_MS * (k + 1),
            gangs=phase,
        )
    return max_cycles if converged() else max_cycles + 1


def rank_gangs(shape=None, emit=True, seed=0):
    """Config 10: the rank-aware gang bench (ISSUE 10; docs/GANGS.md).

    Two arms on the same scenario stream: the gang phase (topology-block
    waterfill, jit solve cross-checked against the numpy sequential twin
    every cycle — `drift` is 0.0 iff they stayed bit-identical) vs
    quorum-only Coscheduling. Reports gang admission latency, max/p99
    inter-rank cost for BOTH arms, elastic grow/shrink convergence, and
    the hard-constraint audit."""
    from scheduler_plugins_tpu.gangs import GangPhase
    from scheduler_plugins_tpu.tuning.quality import (
        elastic_satisfaction_quality,
    )

    shape = shape or RANK_GANG_SHAPE

    phase = GangPhase(check_twin=True)
    with _bench_span("rank-aware arm"):
        rank = _run_gang_arm(shape, phase, seed=seed)
    admit_now = GANG_CYCLE_MS * (rank["cycles"] + 1)
    with _bench_span("elastic grow"):
        grow_cycles = _elastic_transition(
            rank["cluster"], rank["scheduler"], phase,
            lambda pg: min(pg.max_replicas or 10**6,
                           (pg.desired_replicas or pg.min_member) + 2),
            admit_now,
        )
    with _bench_span("elastic shrink"):
        shrink_cycles = _elastic_transition(
            rank["cluster"], rank["scheduler"], phase,
            lambda pg: pg.min_member,
            admit_now + GANG_CYCLE_MS * 8,
        )
    rank_costs, rank_quality = _gang_placement_costs(rank["cluster"])
    rank_violations = _gang_violations(rank["cluster"])

    with _bench_span("quorum-only baseline arm"):
        base = _run_gang_arm(shape, None, seed=seed,
                             max_cycles=rank["cycles"] + 2)
    base_costs, base_quality = _gang_placement_costs(base["cluster"])

    lat = list(rank["latencies"].values())
    elastic_sat = elastic_satisfaction_quality([
        {
            name: {
                "resident": sum(
                    1 for p in rank["cluster"].gang_members(pg)
                    if p.node_name is not None
                ),
                "placed_new": 0,
                "desired": pg.desired_replicas or pg.min_member,
            }
            for name, pg in rank["cluster"].pod_groups.items()
            if getattr(pg, "rank_aware", False)
        }
    ])
    line = {
        "gangs": len(rank_costs),
        "gangs_admitted": len(lat),
        "gang_admission_latency_cycles": (
            round(float(np.mean(lat)), 2) if lat else None
        ),
        "max_inter_rank_cost": rank_quality["rank_cost_max"],
        "baseline_max_inter_rank_cost": base_quality["rank_cost_max"],
        "rank_cost_p99": rank_quality["rank_cost_p99"],
        "baseline_rank_cost_p99": base_quality["rank_cost_p99"],
        "gang_spread_cost": round(rank_quality["gang_spread_cost"], 2),
        "baseline_gang_spread_cost": round(
            base_quality["gang_spread_cost"], 2
        ),
        "elastic_grow_convergence_cycles": grow_cycles,
        "elastic_shrink_convergence_cycles": shrink_cycles,
        "elastic_satisfaction": round(elastic_sat, 4),
        "violations": rank_violations,
        "baseline_violations": _gang_violations(base["cluster"]),
        # the WORST jit-vs-twin drift over every solved cycle (admission
        # + grow + shrink): 0.0 iff the two stayed bit-identical all run
        "twin_drift": phase.max_drift,
        "serve_gang_fallback_documented": True,
    }
    if emit:
        _emit(
            CONFIG_METRICS[10],
            rank["decided"] / rank["elapsed"] if rank["elapsed"] else 0.0,
            f"{shape['n_nodes']} nodes x {len(rank_costs)} rank gangs "
            f"(3-level topology), gang phase vs quorum-only",
            baseline=(
                base["decided"] / base["elapsed"] if base["elapsed"] else 1.0
            ),
            drift=phase.max_drift,
            quality={
                **{k: round(v, 4) for k, v in rank_quality.items()},
                "elastic_satisfaction": round(elastic_sat, 4),
            },
            extra=line,
        )
    return line


def gang_smoke(max_convergence=2):
    """CI gate (`make gang-smoke`): reduced config-10 run — the gang
    phase's max inter-rank cost must sit STRICTLY below the quorum-only
    Coscheduling baseline on the same event stream, the jit solve must
    stay bit-identical to its numpy sequential twin (drift 0.0), the
    hard-constraint replay must be clean (capacity/quota/quorum all 0),
    every gang must admit, and elastic grow/shrink must converge within
    `max_convergence` cycles. One JSON line; rc 1 on any failure."""
    line = rank_gangs(shape=GANG_SMOKE_SHAPE, emit=False)
    ok = (
        line["max_inter_rank_cost"] < line["baseline_max_inter_rank_cost"]
        and line["twin_drift"] == 0.0
        and all(v == 0 for v in line["violations"].values())
        and line["gangs_admitted"] == line["gangs"]
        and line["elastic_grow_convergence_cycles"] <= max_convergence
        and line["elastic_shrink_convergence_cycles"] <= max_convergence
        and line["elastic_satisfaction"] == 1.0
    )
    print(json.dumps({
        "metric": "gang_smoke",
        **_device_columns(),
        "max_convergence_cycles": max_convergence,
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 11: cluster life — endurance composition, pipelined vs serial engine
# ---------------------------------------------------------------------------

#: the cluster-life endurance composition (ISSUE 11 / ROADMAP item 5):
#: ONE long run per arm over the same seeded event stream, phased —
#:   churn: exactly the config-7 Poisson workload (the >= 2x ratio claim
#:          is measured on THIS phase's cycles);
#:   gangs: churn + Coscheduling gang arrivals with elastic member
#:          resizes (PodGroups force the serve engines into full-snapshot
#:          fallback — the measured cost of gangs on a serving daemon —
#:          and serving resumes when the gangs drain at phase end);
#:   chaos: churn under a seeded fault-plan subset (solve garbage +
#:          dropped/duplicated/corrupted sink deltas) with the resilience
#:          watchdog attached and the anti-entropy window tightened;
#:   waves: node remove/add waves (drain-then-delete) under churn — the
#:          serial engine re-bases O(cluster) per delete, the streaming
#:          engine row-compacts O(changed).
#: Arms: pipelined = PipelinedCycle + StreamingServeEngine; serial = the
#: unchanged run_cycle + ServeEngine. Both share ONE scheduler so jit
#: caches are shared; the PIPELINED arm runs FIRST and eats every
#: first-shape compile, making the reported ratio conservative.
CLUSTER_LIFE_SHAPE = dict(
    n_nodes=2000, prefill=12288, warmup=4, seed=0,
    churn=dict(cycles=48, lam_arrive=48, lam_depart=24,
               node_add_every=16, node_remove_every=24),
    gangs=dict(cycles=12, lam_arrive=24, lam_depart=12, gang_every=4,
               gang_size=4, grow_by=2),
    chaos=dict(cycles=16, lam_arrive=32, lam_depart=16, verify_every=1,
               timeout_s=5.0),
    waves=dict(cycles=16, lam_arrive=24, lam_depart=24,
               node_add_every=3, node_remove_every=2),
)
#: reduced shape for the `make endurance-smoke` CI gate (2-core runners);
#: node count below its padding bucket like CHURN_SMOKE_SHAPE
ENDURANCE_SMOKE_SHAPE = dict(
    n_nodes=500, prefill=4096, warmup=3, seed=0,
    churn=dict(cycles=16, lam_arrive=24, lam_depart=12,
               node_add_every=9, node_remove_every=5),
    gangs=dict(cycles=6, lam_arrive=12, lam_depart=6, gang_every=3,
               gang_size=3, grow_by=1),
    chaos=dict(cycles=8, lam_arrive=16, lam_depart=8, verify_every=1,
               timeout_s=5.0),
    waves=dict(cycles=8, lam_arrive=12, lam_depart=12,
               node_add_every=3, node_remove_every=2),
)

#: the phase order is part of the workload definition
CLUSTER_LIFE_PHASES = ("churn", "gangs", "chaos", "waves")


def _life_fault_plan(shape, seed):
    """Seeded chaos subset for the cluster-life run: solve garbage plus
    the three sink-delta corruptions (sticky — they fire at the first
    delta after their slot). Hang/crash stay in config 9's dedicated
    harness: a multi-second hang would dominate the endurance timing
    and a crash needs config 9's restart machinery."""
    from scheduler_plugins_tpu.resilience import faults as F

    cycles = shape["chaos"]["cycles"]
    rng = np.random.default_rng(seed + 17)
    kinds = [
        (F.SOLVE_DISPATCH, "garbage", False),
        (F.DELTA_EVENT, "drop", True),
        (F.DELTA_EVENT, "dup", True),
        (F.DELTA_EVENT, "corrupt", True),
    ]
    slots = rng.choice(np.arange(1, cycles - 1), size=len(kinds),
                       replace=False)
    plan = F.FaultPlan(seed=seed)
    for (site, kind, sticky), cycle in zip(
        kinds, sorted(int(s) for s in slots)
    ):
        plan.specs.append(
            F.FaultSpec(site=site, cycle=cycle, kind=kind, sticky=sticky)
        )
    return plan


def _life_gang_events(cluster, phase_cycle, shape, now, roster):
    """Deterministic gang lifecycle for the gangs phase: arrivals every
    `gang_every` cycles, an elastic GROW (+`grow_by` members) two cycles
    in, an elastic SHRINK (-1 bound member, quorum kept) three cycles
    later, completion (members + group removed) after eight. `roster`
    carries {gang name: (birth cycle, next member serial)} across
    cycles."""
    from scheduler_plugins_tpu.api.objects import (
        Container, Pod, PodGroup, POD_GROUP_LABEL,
    )
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    gib = 1 << 30
    cfg = shape["gangs"]

    def add_member(gname, m):
        cluster.add_pod(Pod(
            name=f"{gname}-m{m}", namespace="life", creation_ms=now + m,
            labels={POD_GROUP_LABEL: gname},
            containers=[Container(
                requests={CPU: 1500, MEMORY: 3 * gib}
            )],
        ))

    if phase_cycle % cfg["gang_every"] == 0:
        gname = f"lg{phase_cycle:03d}"
        cluster.add_pod_group(PodGroup(
            name=gname, namespace="life",
            min_member=cfg["gang_size"] - 1, creation_ms=now,
        ))
        for m in range(cfg["gang_size"]):
            add_member(gname, m)
        roster[gname] = (phase_cycle, cfg["gang_size"])
    for gname, (birth, serial) in list(roster.items()):
        age = phase_cycle - birth
        if age == 2:
            # elastic grow: desired width increased
            for m in range(serial, serial + cfg["grow_by"]):
                add_member(gname, m)
            roster[gname] = (birth, serial + cfg["grow_by"])
        elif age == 5:
            # elastic shrink: release the highest-serial BOUND member
            # (stays >= quorum: grow_by extra members exist by now)
            members = sorted(
                (p.uid for p in cluster.pods.values()
                 if p.namespace == "life" and p.pod_group() == gname
                 and p.node_name is not None),
                reverse=True,
            )
            if members:
                cluster.remove_pod(members[0])
        elif age >= 8:
            # gang completes: workload done, members and group leave
            for uid in [
                p.uid for p in cluster.pods.values()
                if p.namespace == "life" and p.pod_group() == gname
            ]:
                cluster.remove_pod(uid)
            cluster.pod_groups.pop(f"life/{gname}", None)
            roster.pop(gname, None)


def _drain_life_gangs(cluster, roster):
    """End of the gangs phase: every remaining gang completes, so the
    serve engines re-engage (the compatibility gate re-opens once the
    PodGroups drain away)."""
    for gname in list(roster):
        for uid in [
            p.uid for p in cluster.pods.values()
            if p.namespace == "life" and p.pod_group() == gname
        ]:
            cluster.remove_pod(uid)
        cluster.pod_groups.pop(f"life/{gname}", None)
        roster.pop(gname, None)


class _LifeArm:
    """One cluster-life arm as an externally-stepped state machine.
    `cluster_life` steps the two timed arms INTERLEAVED (pipelined cycle
    k, then serial cycle k) so environment noise — this class of shared
    2-core hosts stalls a whole process for hundreds of ms at a time —
    lands on both arms of every compared window instead of whichever arm
    happened to be running (the replay-smoke pairing discipline, at arm
    granularity). Determinism contract: every random draw comes from the
    seeded stream + the cluster's bound set, so two arms with equal
    placements see IDENTICAL event sequences (the `_churn_events`
    discipline), and the chaos plan is seeded and installed around each
    arm's OWN tick — the arms differ only in engine."""

    def __init__(self, scheduler, shape, pipelined, seed=0):
        from scheduler_plugins_tpu.framework.pipeline_cycle import (
            PipelinedCycle,
        )
        from scheduler_plugins_tpu.serving import (
            ServeEngine,
            StreamingServeEngine,
        )

        self.scheduler = scheduler
        self.shape = shape
        self.pipelined = pipelined
        self.seed = seed
        self.cluster = churn_cluster(
            shape["n_nodes"], shape["prefill"], seed
        )
        self.engine = (
            StreamingServeEngine() if pipelined else ServeEngine()
        ).attach(self.cluster)
        self.pipe = (
            PipelinedCycle(scheduler, self.cluster, serve=self.engine)
            if pipelined else None
        )
        self.rng = np.random.default_rng(seed + 1)
        self.serial = 0
        self.cycle = 0
        self.times = {name: [] for name in CLUSTER_LIFE_PHASES}
        self.decided = {name: [] for name in CLUSTER_LIFE_PHASES}
        self.placements: dict = {}
        self.report_digests: list = []
        self.gang_roster: dict = {}
        # per-cycle phase schedule (warmup rides the churn generators,
        # untimed — covers the resident base build + hot compile shapes)
        self.schedule = (
            ["churn"] * (shape["warmup"] + shape["churn"]["cycles"])
            + ["gangs"] * shape["gangs"]["cycles"]
            + ["chaos"] * shape["chaos"]["cycles"]
            + ["waves"] * shape["waves"]["cycles"]
        )
        self.gang_phase_start = shape["warmup"] + shape["churn"]["cycles"]
        self.chaos_start = (
            self.gang_phase_start + shape["gangs"]["cycles"]
        )
        self._events = {
            "churn": dict(shape["churn"]),
            "gangs": dict(shape["gangs"],
                          node_add_every=shape["gangs"].get(
                              "node_add_every", 0),
                          node_remove_every=shape["gangs"].get(
                              "node_remove_every", 0)),
            # the anti-entropy window is pinned to ONE refresh for the
            # chaos phase (the config-9 discipline): detection then
            # happens at the SAME refresh that applied the corruption in
            # BOTH arms — the periodic cadence counts only compatible
            # refreshes, and the two engines' counters drift (the serial
            # engine re-bases on node deletes where the streaming engine
            # compacts), which would move the corruption-recovery rebase
            # to different cycles and break the placement-identity gate
            "chaos": dict(shape["chaos"], node_add_every=0,
                          node_remove_every=0, probe_every=1),
            "waves": dict(shape["waves"]),
        }
        self.plan = _life_fault_plan(shape, seed)
        self.rz = _chaos_resilience(self._events["chaos"], self.engine, seed)
        self._old_verify = self.engine.verify_every
        self._prev_phase = None
        # per-ARM pod-lifecycle ledger (obs.ledger), swapped in around
        # this arm's events+tick via `podledger.use`: the interleaved
        # arms share pod uids by construction, so a process-global ledger
        # would interleave two engines' records — exactly the pollution
        # the scoped-metrics discipline exists to prevent. The two arms'
        # event SEQUENCES must come out identical (`cluster_life`'s
        # ledger gate, the placement-identity discipline extended to the
        # observability plane).
        from scheduler_plugins_tpu.obs import ledger as podledger

        self._podledger = podledger
        self.ledger = podledger.Ledger().start()

    @property
    def done(self) -> bool:
        return self.cycle >= len(self.schedule)

    def _transition(self, phase):
        if phase == self._prev_phase:
            return
        if self._prev_phase == "gangs":
            # gangs complete at phase end: serving re-engages
            if self.pipe is not None:
                self.pipe.flush()
            _drain_life_gangs(self.cluster, self.gang_roster)
            self.engine.verify_every = self._old_verify
        if self._prev_phase == "chaos":
            if self.pipe is not None:
                self.pipe.flush()
                self.pipe.resilience = None
            self.engine.verify_every = self._old_verify
        if phase == "gangs":
            # the periodic anti-entropy cadence is pinned OUT of the
            # short gang window (and back on afterwards): the two
            # engines' refresh counters drift across earlier phases (the
            # serial engine's node-delete rebases skip the counter where
            # the streaming engine compacts), so the periodic O(assigned)
            # verify lands on DIFFERENT arms' gang cycles run to run —
            # one ~100 ms maintenance spike inside a 12-cycle window
            # decides the phase ratio by lottery. Forced verifies
            # (note_fault) stay armed, and the anti-entropy cost is
            # measured where it is pinned SYMMETRICALLY: the chaos phase
            # runs both arms at verify_every=1.
            self.engine.verify_every = 0
        if phase == "chaos":
            if self.pipe is not None:
                self.pipe.resilience = self.rz
            self.engine.verify_every = (
                self.shape["chaos"]["verify_every"]
            )
        self._prev_phase = phase

    def step(self):
        """Run ONE cycle (events + tick) of this arm's schedule."""
        prev = self._podledger.use(self.ledger)
        try:
            self._step()
        finally:
            # the pipelined arm's bind flusher is quiesced inside `_step`
            # (the fence runs in the timed window), so no hook can fire
            # against the wrong arm's ledger after this restore
            self._podledger.use(prev)

    def _step(self):
        from scheduler_plugins_tpu.framework import run_cycle
        from scheduler_plugins_tpu.resilience import faults as F

        phase = self.schedule[self.cycle]
        self._transition(phase)
        now = 1000 * (self.cycle + 1)
        self.serial = _churn_events(
            self.cluster, self.rng, self._events[phase], self.cycle, now,
            self.serial,
        )
        if phase == "gangs":
            _life_gang_events(
                self.cluster, self.cycle - self.gang_phase_start,
                self.shape, now, self.gang_roster,
            )
        chaos = phase == "chaos"
        if chaos:
            # each arm's OWN plan is live only around its own tick (the
            # registry is process-global and the arms interleave)
            F.install(self.plan)
            self.plan.begin_cycle(self.cycle - self.chaos_start)
        start = time.perf_counter()
        try:
            with _bench_span(
                f"life cycle {self.cycle}", phase=phase,
                mode="pipelined" if self.pipelined else "serial",
            ):
                if self.pipelined:
                    report = self.pipe.tick(now)
                    # decision latency = ingest boundary -> host-visible
                    # binds: fence inside the timed window (the bench's
                    # event generator needs the bound set anyway)
                    self.pipe.fence()
                else:
                    report = run_cycle(
                        self.scheduler, self.cluster, now=now,
                        serve=self.engine,
                        resilience=self.rz if chaos else None,
                    )
        finally:
            if chaos:
                F.clear()
        elapsed = time.perf_counter() - start
        self.placements.update(report.bound)
        if self.cycle >= self.shape["warmup"]:
            self.times[phase].append(elapsed)
            self.decided[phase].append(
                len(report.bound) + len(report.failed)
            )
            self.report_digests.append((
                tuple(sorted(report.bound.items())),
                tuple(sorted(report.reserved.items())),
                tuple(sorted(report.failed)),
                tuple(sorted(report.rejected_gangs)),
            ))
        self.cycle += 1

    def finish(self) -> dict:
        prev = self._podledger.use(self.ledger)
        try:
            if self.pipe is not None:
                self.pipe.flush()
                self.pipe.close()
        finally:
            self._podledger.use(prev)
        out = {
            "sli": self.ledger.sli_summary(),
            "ledger_sequence": self.ledger.sequence(),
            "ledger_decomposition_errors":
                len(self.ledger.decomposition_errors()),
            "times": self.times,
            "decided": self.decided,
            "placements": self.placements,
            "report_digests": self.report_digests,
            "final_state": {
                uid: p.node_name
                for uid, p in sorted(self.cluster.pods.items())
            },
            "violations": _churn_capacity_violations(self.cluster),
            "state_matrices": _cluster_state_matrices(self.cluster),
            "rebases": self.engine.rebases,
            "compactions": getattr(self.engine, "compactions", 0),
            "gang_fallbacks": self.engine.gang_fallbacks,
            "antientropy_divergences": self.engine.antientropy_divergences,
            "faults_fired": len(self.plan.log),
            "degraded_end": self.rz.degraded,
        }
        if self.pipe is not None:
            tls = [t.as_dict() for t in self.pipe.timelines]
            out["overlap_efficiency_mean"] = (
                round(float(np.mean(
                    [t["overlap_efficiency"] for t in tls]
                )), 4) if tls else None
            )
            out["pipeline_bubble_ms_mean"] = (
                round(float(np.mean(
                    [t["pipeline_bubble_ms"] for t in tls]
                )), 3) if tls else None
            )
            out["late_binds"] = sum(
                1 for t in self.pipe.timelines if t.late_bind
            )
        return out


def _cluster_life_arm(scheduler, shape, pipelined, seed=0):
    """One full cluster-life run to completion (the prewarm pass and any
    standalone use; the timed comparison steps two `_LifeArm`s
    interleaved instead — see `cluster_life`)."""
    arm = _LifeArm(scheduler, shape, pipelined, seed)
    while not arm.done:
        arm.step()
    return arm.finish()


def cluster_life(shape=None, emit=True):
    """Config 11: the cluster-life endurance bench. ONE seeded event
    stream (Poisson churn + gang arrivals/elastic resizes + seeded chaos
    faults + node add/remove waves) run twice — the concurrent pipeline
    engine (`framework.pipeline_cycle.PipelinedCycle` +
    `serving.engine.StreamingServeEngine`) vs the serial `run_cycle` +
    base `ServeEngine` — sharing one scheduler (warm jit caches; the
    pipelined arm runs first and eats the first-shape compiles).
    Headline: sustained cycles/s and p99 decision latency, with the
    >= 2x claim measured on the churn phase (exactly the config-7
    workload) and every hard gate checked: identical per-cycle
    placements, bit-identical final cluster state, zero capacity
    violations in the replayed audit."""
    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.plugins import (
        Coscheduling,
        NodeResourcesAllocatable,
    )

    shape = shape or CLUSTER_LIFE_SHAPE
    seed = shape.get("seed", 0)
    scheduler = Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(),
        Coscheduling(permit_waiting_seconds=30),
    ]))

    # untimed prewarm: one full pipelined pass over the SAME seeded
    # stream compiles every shape both timed arms will hit (the two
    # arms' cluster states are bit-identical cycle for cycle, so their
    # jit signatures are too) — the comparison then times the overlap,
    # not compiles. The pipelined arm still runs first: any residual
    # first-shape compile lands there, keeping the ratio conservative.
    import gc

    _cluster_life_arm(scheduler, shape, pipelined=True, seed=seed)
    # the timed arms run INTERLEAVED (pipelined cycle k, serial cycle
    # k): on a shared host, episodic slowdowns then land on both arms
    # of every compared window instead of poisoning whichever arm
    # happened to be running
    pipe = _LifeArm(scheduler, shape, pipelined=True, seed=seed)
    ser = _LifeArm(scheduler, shape, pipelined=False, seed=seed)
    # bench hygiene, applied identically to both timed arms: move the
    # prewarm's surviving objects AND both timed arms' prefill
    # populations out of the collector's scan set — the freeze must
    # happen AFTER the arms exist, or the ~25k-pod populations stay in
    # the unfrozen set and the first gen-2 collection lands as a
    # 100-200 ms pause on whichever timed cycle triggers it (measured:
    # it deterministically hit the 12-cycle gang phase and decided that
    # phase's ratio by itself)
    gc.collect()
    gc.freeze()
    try:
        while not pipe.done:
            pipe.step()
            ser.step()
        pipe_arm = pipe.finish()
        serial_arm = ser.finish()
    finally:
        gc.unfreeze()

    def cps(arm, phase=None):
        ts = (
            arm["times"][phase] if phase
            else [t for name in CLUSTER_LIFE_PHASES for t in
                  arm["times"][name]]
        )
        return len(ts) / sum(ts) if ts else 0.0

    phases = {}
    for name in CLUSTER_LIFE_PHASES:
        p, s = cps(pipe_arm, name), cps(serial_arm, name)
        phases[name] = {
            "cycles": len(pipe_arm["times"][name]),
            "cycles_per_sec": round(p, 2),
            "serial_cycles_per_sec": round(s, 2),
            "vs_serial": round(p / s, 2) if s else 0.0,
        }
    all_p, all_s = cps(pipe_arm), cps(serial_arm)

    def cps_phases(arm, names):
        ts = [t for name in names for t in arm["times"][name]]
        return len(ts) / sum(ts) if ts else 0.0

    # the serve-mode phases (churn + node waves) — the workload the
    # pipelined engine's O(changed) ingest targets; the composite is the
    # smoke gate's statistic because a single phase's ratio at reduced
    # scale swings with the serial arm's per-rebase cost
    serve_p = cps_phases(pipe_arm, ("churn", "waves"))
    serve_s = cps_phases(serial_arm, ("churn", "waves"))
    pipe_times = np.array(
        [t for name in CLUSTER_LIFE_PHASES for t in pipe_arm["times"][name]]
    )
    # per-decision latency: a pod's decision latency is its cycle's wall
    # time (ingest -> host-visible bind), weighted by decisions per cycle
    # — the config-7 convention, so the columns compare directly
    weights = np.array([
        d for name in CLUSTER_LIFE_PHASES
        for d in pipe_arm["decided"][name]
    ])
    lat = np.repeat(pipe_times, weights) \
        if pipe_times.size else np.array([])
    p50 = float(np.percentile(lat, 50)) * 1000 if lat.size else 0.0
    p99 = float(np.percentile(lat, 99)) * 1000 if lat.size else 0.0

    placements_match = pipe_arm["placements"] == serial_arm["placements"]
    n_decided = int(weights.sum())
    cycles_match = (
        pipe_arm["report_digests"] == serial_arm["report_digests"]
    )
    state_match = pipe_arm["final_state"] == serial_arm["final_state"]
    total_s = pipe_times.sum()
    line = {
        "cycles": int(len(pipe_times)),
        "cycles_per_sec": round(all_p, 2),
        "serial_cycles_per_sec": round(all_s, 2),
        "vs_serial": round(all_p / all_s, 2) if all_s else 0.0,
        "churn_vs_serial": phases["churn"]["vs_serial"],
        "serve_phases_vs_serial": (
            round(serve_p / serve_s, 2) if serve_s else 0.0
        ),
        "phases": phases,
        "decision_latency_p50_ms": round(p50, 2),
        "decision_latency_p99_ms": round(p99, 2),
        "placements_match": bool(placements_match),
        "per_cycle_reports_match": bool(cycles_match),
        "final_state_identical": bool(state_match),
        "capacity_violations": int(pipe_arm["violations"]),
        "overlap_efficiency_mean": pipe_arm["overlap_efficiency_mean"],
        "pipeline_bubble_ms_mean": pipe_arm["pipeline_bubble_ms_mean"],
        "late_binds": pipe_arm["late_binds"],
        "rebases": int(pipe_arm["rebases"]),
        "serial_rebases": int(serial_arm["rebases"]),
        "compactions": int(pipe_arm["compactions"]),
        "gang_fallbacks": int(pipe_arm["gang_fallbacks"]),
        "antientropy_divergences": int(
            pipe_arm["antientropy_divergences"]
        ),
        "faults_fired": int(pipe_arm["faults_fired"]),
        "decisions": int(n_decided),
        # pod-lifecycle SLO ledger (obs.ledger): the pipelined arm's SLI
        # block (e2e percentiles + stage decomposition), the engine-
        # identity gate (serial and pipelined arms must record the SAME
        # event sequence on the shared stream) and the decomposition
        # invariant (stage sums == e2e for every retired pod)
        "sli": pipe_arm["sli"],
        "ledger_sequence_identical": bool(
            pipe_arm["ledger_sequence"] == serial_arm["ledger_sequence"]
        ),
        "ledger_decomposition_errors": int(
            pipe_arm["ledger_decomposition_errors"]
            + serial_arm["ledger_decomposition_errors"]
        ),
    }
    if emit:
        _emit(
            CONFIG_METRICS[11],
            n_decided / total_s if total_s else 0.0,
            f"{shape['n_nodes']} nodes, {shape['prefill']} bound, "
            f"{line['cycles']} cycles cluster life "
            "(churn+gangs+chaos+waves), pipelined vs serial engine",
            baseline=(
                n_decided / sum(
                    t for name in CLUSTER_LIFE_PHASES
                    for t in serial_arm["times"][name]
                )
            ),
            drift=(0.0 if placements_match else None),
            quality=_quality_state(*pipe_arm["state_matrices"]),
            extra=line,
        )
    return line


def endurance_smoke(min_ratio=1.5):
    """CI gate (`make endurance-smoke`): reduced cluster-life run — the
    pipelined engine must beat the serial engine >= `min_ratio` on
    cycles/s over the serve-mode phases (churn + node waves: the
    composite is robust against the run-to-run cost variance of the
    serial arm's individual rebases at reduced scale; the full-shape
    config-7 churn ratio is the headline claim, not the CI statistic),
    produce IDENTICAL per-cycle placements and a bit-identical final
    cluster state, and leave a clean replayed capacity audit. ISSUE 12
    adds the gang-phase gate: zero serve fallbacks across the gang phase
    (the resident gang side tables own the roster) and gang-phase
    cycles/s >= `min_ratio` x the serial arm. One JSON line; rc 1 on
    any failure."""
    line = cluster_life(shape=ENDURANCE_SMOKE_SHAPE, emit=False)
    ok = (
        line["serve_phases_vs_serial"] >= min_ratio
        and line["placements_match"]
        and line["per_cycle_reports_match"]
        and line["final_state_identical"]
        and line["capacity_violations"] == 0
        # ISSUE 12 gang-phase gate: the resident gang side tables must
        # keep the serve engines OFF the O(cluster) fallback for the
        # whole gang phase (zero fallbacks — the roster is compatible)
        # and the pipelined engine must beat the serial engine on
        # gang-phase cycles/s now that both serve resident
        and line["gang_fallbacks"] == 0
        and line["phases"]["gangs"]["vs_serial"] >= min_ratio
        # ISSUE 19 ledger gates: the serial and pipelined arms must
        # record the SAME pod-lifecycle event sequence on the shared
        # stream, and every retired pod's stage decomposition must sum
        # to its e2e exactly
        and line["ledger_sequence_identical"]
        and line["ledger_decomposition_errors"] == 0
    )
    print(json.dumps({
        "metric": "endurance_smoke",
        "min_ratio": min_ratio,
        **_device_columns(),
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 12: mega gangs — wave-batched gang solve at 10k nodes x 1k gangs
# ---------------------------------------------------------------------------

#: the mega gang scale (ROADMAP item 3 / ISSUE 12): 10k nodes x 1k gangs
#: is the regime Tesserae (arxiv 2508.04953) says DL placement must scale
#: to. Tensor-level construction like config 8 (8k Pod objects would
#: dominate the run). The workload is the STEADY-STATE RECONCILE a
#: serving scheduler actually loops on: `resident_frac` of the gangs are
#: elastic jobs anchored on their resident topology block with 1-2
#: pending grow/repair ranks, the rest fresh admissions — the regime
#: where independent gangs spread across blocks and the wave validator
#: accepts long runs (a cold-cluster admission storm serializes through
#: the host-resolve path instead; docs/GANGS.md documents both).
MEGA_GANG_SHAPE = dict(
    n_nodes=10_240, n_gangs=1_024, max_ranks=8, blocks=256, regions=8,
    quota_ns=32, resident_frac=0.8, wave=64, seed=0,
)


def mega_gang_problem(shape):
    """Tensor-level `RankGangState` + initial state for the mega gang
    configs: heterogeneous node SKUs over `blocks` zone-blocks grouped
    into regions (same-region spill 10, cross-region 40, same-block 1),
    heterogeneous rank demand (launcher 2x), half the namespaces quota-
    capped, elastic residents anchored per `resident_frac`."""
    from scheduler_plugins_tpu.gangs.topology import RankGangState

    rng = np.random.default_rng(shape["seed"])
    N, G, M, B = (shape["n_nodes"], shape["n_gangs"], shape["max_ranks"],
                  shape["blocks"])
    Q, regions = shape["quota_ns"], shape["regions"]
    R = 3  # cpu, memory, pods-style axis (the gang solve is axis-agnostic)
    node_block = (np.arange(N) * B // N).astype(np.int32)
    free0 = np.zeros((N, R), np.int64)
    sku = rng.integers(0, 4, N)
    # synthetic 3-slot axis local to this problem (NOT the CANONICAL
    # layout — the gang solve is axis-order agnostic, like the gang
    # differential's oracle axis)
    free0[:, 0] = np.array([32_000, 48_000, 64_000, 96_000])[sku]  # graft-lint: ignore[GL005]
    free0[:, 1] = np.array([128, 192, 256, 384])[sku]  # graft-lint: ignore[GL005]
    free0[:, 2] = 48  # graft-lint: ignore[GL005]
    zone_region = (np.arange(B) * regions // B)
    block_cost = np.where(
        zone_region[:, None] == zone_region[None, :], 10, 40
    ).astype(np.int32)
    np.fill_diagonal(block_cost, 1)
    rank_req = np.zeros((G, M, R), np.int64)
    rank_mask = np.zeros((G, M), bool)
    prev = np.full((G, M), -1, np.int32)
    min_ranks = np.zeros(G, np.int32)
    nodes_of_block = [np.where(node_block == b)[0] for b in range(B)]
    for g in range(G):
        k = int(rng.integers(max(4, M // 2), M + 1))
        rank_mask[g, :k] = True
        cpu = int(rng.integers(1_000, 4_000))
        rank_req[g, :k, 0] = cpu
        rank_req[g, 0, 0] = 2 * cpu  # MPI launcher wants double
        rank_req[g, :k, 1] = int(rng.integers(4, 16))
        rank_req[g, :k, 2] = 1
        if rng.random() < shape["resident_frac"]:
            # resident elastic gang: anchored ranks on one block, 1-2
            # pending grow/repair ranks
            b = int(rng.integers(0, B))
            pend = int(rng.integers(1, 3))
            block_nodes = nodes_of_block[b]
            prev[g, : k - pend] = block_nodes[
                rng.integers(0, len(block_nodes), k - pend)
            ]
            min_ranks[g] = max(2, k - pend)
        else:
            min_ranks[g] = k if rng.random() < 0.7 else max(2, k - 2)
    quota_max = np.full((Q, R), np.iinfo(np.int64).max, np.int64)
    quota_has = np.zeros(Q, bool)
    quota_has[: Q // 2] = True
    quota_max[: Q // 2, 0] = rng.integers(400_000, 4_000_000, Q // 2)
    quota_max[: Q // 2, 1] = rng.integers(4_000, 40_000, Q // 2)
    quota_max[: Q // 2, 2] = rng.integers(400, 4_000, Q // 2)
    gangs = RankGangState(
        rank_req=rank_req, rank_mask=rank_mask, prev_assigned=prev,
        min_ranks=min_ranks,
        gang_ns=rng.integers(-1, Q, G).astype(np.int32),
        gang_mask=np.ones(G, bool),
        node_block=node_block, block_cost=block_cost,
        quota_max=quota_max, quota_has=quota_has,
    )
    return {
        "gangs": gangs, "free0": free0,
        "eq_used0": np.zeros((Q, R), np.int64),
        "node_mask": np.ones(N, bool),
    }


def _mega_gang_violations(problem, rank_nodes, admitted, placed_new):
    """Independent replay of the gang hard constraints over the emitted
    placements (the TestRankGangDifferential oracle, vectorized): fit
    (new demand per node within free0, schedulable nodes only), quota
    caps, quorum/zero-partial."""
    gangs = problem["gangs"]
    free0 = problem["free0"]
    node_mask = problem["node_mask"]
    G, M, R = gangs.rank_req.shape
    new = (rank_nodes >= 0) & (gangs.prev_assigned < 0) & gangs.rank_mask
    fit = quota = quorum = 0
    used = np.zeros_like(free0)
    g_idx, m_idx = np.nonzero(new)
    nodes = rank_nodes[g_idx, m_idx]
    if not node_mask[nodes].all():
        fit += int((~node_mask[nodes]).sum())
    np.add.at(used, nodes, gangs.rank_req[g_idx, m_idx])
    fit += int((used > free0).any(axis=1).sum())
    for q in range(gangs.quota_max.shape[0]):
        if not gangs.quota_has[q]:
            continue
        sel = gangs.gang_ns[g_idx] == q
        dem = gangs.rank_req[g_idx[sel], m_idx[sel]].sum(axis=0)
        if ((problem["eq_used0"][q] + dem) > gangs.quota_max[q]).any():
            quota += 1
    resident = ((gangs.prev_assigned >= 0) & gangs.rank_mask).sum(axis=1)
    n_new = new.sum(axis=1)
    quorum += int((
        admitted & (resident + n_new < gangs.min_ranks)
    ).sum())
    quorum += int((~admitted & (n_new > 0)).sum())
    quorum += int((admitted & (n_new != placed_new)).sum())
    return {"fit": int(fit), "quota": quota, "quorum": quorum}


def mega_gangs(shape=None, emit=True):
    """Config 12: the mega gang bench (ISSUE 12; docs/GANGS.md). One
    problem, three solvers: the sequential jit gang scan (PR 10's
    `gang_solve_body` — the parity anchor), the wave-batched solve
    (`gangs.waves.wave_gang_solve`), and the numpy sequential twin
    (`gang_solve_np` — the bit-identity oracle). Headline: newly placed
    ranks/s of the wave path; the gate is placements BIT-IDENTICAL to
    the twin across all three, drift 0.0, zero fit/quota/quorum
    violations in the independent replay."""
    import jax
    import jax.numpy as jnp

    from scheduler_plugins_tpu.framework.plugin import SolverState
    from scheduler_plugins_tpu.gangs.topology import (
        gang_solve_fn,
        gang_solve_np,
    )
    from scheduler_plugins_tpu.gangs.waves import wave_gang_solve

    shape = shape or MEGA_GANG_SHAPE
    problem = mega_gang_problem(shape)
    gangs = problem["gangs"]

    fn = gang_solve_fn()
    gangs_dev = jax.tree.map(jnp.asarray, gangs)
    state0 = SolverState(
        free=jnp.asarray(problem["free0"]),
        eq_used=jnp.asarray(problem["eq_used0"]),
        rank_nodes=jnp.asarray(gangs.prev_assigned),
    )
    mask_dev = jnp.asarray(problem["node_mask"])
    with _bench_span("mega-gang sequential scan"):
        out = fn(gangs_dev, state0, mask_dev)
        np.asarray(out[0])  # warm (compile)
        t0 = time.perf_counter()
        out = fn(gangs_dev, state0, mask_dev)
        rn_seq = np.asarray(out[0])
        adm_seq = np.asarray(out[1])
        t_seq = time.perf_counter() - t0

    wave_args = (gangs, problem["free0"], problem["eq_used0"],
                 problem["node_mask"])
    with _bench_span("mega-gang wave solve"):
        wave_gang_solve(*wave_args, wave=shape["wave"])  # warm
        stats: dict = {}
        t0 = time.perf_counter()
        rn_w, adm_w, pn_w, free_w, eq_w = wave_gang_solve(
            *wave_args, wave=shape["wave"], stats=stats
        )
        t_wave = time.perf_counter() - t0

    with _bench_span("mega-gang numpy twin"):
        rn_np, adm_np, pn_np, free_np, eq_np = gang_solve_np(*wave_args)

    twin_match = (
        (rn_w == rn_np).all() and (adm_w == adm_np).all()
        and (pn_w == pn_np).all() and (free_w == free_np).all()
        and (eq_w == eq_np).all()
    )
    seq_match = (rn_seq == rn_np).all() and (adm_seq == adm_np).all()
    violations = _mega_gang_violations(problem, rn_w, adm_w, pn_w)
    placed = int(pn_w.sum())
    line = {
        "gangs": int(gangs.gang_mask.sum()),
        "gangs_admitted": int(adm_w.sum()),
        "ranks_placed": placed,
        "wave_seconds": round(t_wave, 3),
        "sequential_scan_seconds": round(t_seq, 3),
        "wave_vs_sequential_scan": round(t_seq / t_wave, 2) if t_wave
        else 0.0,
        "waves": stats.get("waves"),
        "wave_width": shape["wave"],
        "host_resolves": stats.get("host_solves"),
        "placements_match_twin": bool(twin_match),
        "sequential_matches_twin": bool(seq_match),
        "violations": violations,
        "resident_frac": shape["resident_frac"],
    }
    if emit:
        _emit(
            CONFIG_METRICS[12],
            placed / t_wave if t_wave else 0.0,
            f"{shape['n_nodes']} nodes x {line['gangs']} gangs "
            f"({shape['blocks']} blocks), wave-batched vs sequential "
            "gang scan",
            baseline=placed / t_seq if t_seq else 1.0,
            drift=(0.0 if twin_match and seq_match else None),
            quality=None,
            extra=line,
        )
    return line


# ---------------------------------------------------------------------------
# config 13: packing frontier — the packing solve mode vs the wave path
# ---------------------------------------------------------------------------

#: the packing-frontier shape (ISSUE 14): a mid-life cluster — heterogeneous
#: SKUs, ~70% of nodes carrying an uneven resident load — where the static
#: allocatable score order diverges from the fill order, so the one-pass
#: wave placement leaves free-capacity dust the packing refinement can
#: consolidate. `budgets` is the iteration-budget sweep (0 is always run
#: first as the wave-parity anchor).
PACKING_SHAPE = dict(
    n_nodes=768, demand_frac=0.92, empty_frac=0.05, budgets=(8, 32, 128),
)
#: reduced shape for the `make pack-smoke` CI gate — small enough for
#: 2-core runners, large enough that consolidation measurably moves both
#: packing gauges
PACK_SMOKE_SHAPE = dict(
    n_nodes=96, demand_frac=0.8, empty_frac=0.1, budgets=(8, 32),
)


def packing_problem(n_nodes, demand_frac=0.8, empty_frac=0.1, seed=0):
    """(cluster, snap, meta, weights) for the packing configs: a mid-life
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


def _packing_arms(snap, weights, budgets, runs=3):
    """Run the wave-parity anchor (budget 0) + the budget sweep through
    the ONE jitted packing program (`parallel.solver.packing_solve_fn` —
    budgets ride the traced pack_aux argument, so the sweep shares a
    single compile). Returns (wave_arm, [arm per budget]) where each arm
    is {assignment, wait, seconds, stats}."""
    from scheduler_plugins_tpu.ops.packing import pack_aux_vector
    from scheduler_plugins_tpu.parallel.solver import packing_solve_fn

    solve = packing_solve_fn(collect_stats=True)

    def run_arm(budget):
        aux = pack_aux_vector(budget, 4.0, 0.0, 0.5)
        times = []
        out = None
        for _ in range(runs):
            t0 = time.perf_counter()
            with _bench_span(f"packing solve budget {budget}"):
                assignment, admitted, wait, stats = solve(
                    snap, weights, aux
                )
                out = (
                    np.asarray(assignment), np.asarray(wait),
                    {k: int(v) for k, v in stats["packing"].items()},
                )
            times.append(time.perf_counter() - t0)
        return {
            "assignment": out[0], "wait": out[1], "stats": out[2],
            "seconds": sorted(times)[len(times) // 2],
        }

    run_arm(0)  # warm: one compile serves every budget
    wave = run_arm(0)
    return wave, [(b, run_arm(b)) for b in budgets]


def packing_frontier(shape=None, emit=True, seed=0):
    """Config 13: the packing-frontier bench (ISSUE 14; docs/PACKING.md).
    One mid-life cluster problem; arms = the wave path (the packing
    program at iteration budget 0 — proven bit-identical to `batch_solve`
    per run) and the packing mode at each `budgets` entry. The emitted
    line carries the full utilization-vs-drift-vs-latency frontier: per
    budget, the placement-quality objectives (packed_utilization,
    fragmentation, util_imbalance), score-sum drift vs the wave
    placements, solve latency and the refinement counters — with the
    `tuning.gates` replay oracles certifying ZERO hard-constraint
    violations on every arm. Headline value: pods/s of the largest
    budget (quality costs latency; the frontier is the point)."""
    from scheduler_plugins_tpu.parallel.solver import batch_solve
    from scheduler_plugins_tpu.tuning.gates import hard_violations

    shape = shape or PACKING_SHAPE
    cluster, snap, meta, weights = packing_problem(
        shape["n_nodes"], shape["demand_frac"], shape["empty_frac"],
        seed=seed,
    )
    wave, arms = _packing_arms(snap, weights, shape["budgets"])
    # budget 0 must BE the wave path (the acceptance anchor)
    a_ref, _, w_ref = batch_solve(snap, weights)
    wave_parity = bool(
        (np.asarray(a_ref) == wave["assignment"]).all()
        and (np.asarray(w_ref) == wave["wait"]).all()
    )
    from scheduler_plugins_tpu.tuning import quality as Q

    objective = _alloc_objective(snap, weights)

    def raw_quality(arm):
        # unrounded objectives for the gain columns: at full scale a real
        # fragmentation gain is smaller than the 4-decimal display
        # rounding of the per-arm quality dicts
        return Q.cycle_quality(
            snap, arm["assignment"], None, arm["wait"]
        )

    q_wave_raw = raw_quality(wave)
    q_wave = {k: round(v, 4) for k, v in q_wave_raw.items()}
    v_wave = hard_violations(snap, wave["assignment"], wave["wait"])
    frontier = [{
        "budget": 0, "quality": q_wave, "drift": 0.0,
        "solve_seconds": round(wave["seconds"], 4),
        "violations": v_wave["total"], **wave["stats"],
    }]
    total_violations = v_wave["total"]
    q_best_raw = q_wave_raw
    for budget, arm in arms:
        q_raw = raw_quality(arm)
        q_best_raw = q_raw
        v = hard_violations(snap, arm["assignment"], arm["wait"])
        total_violations += v["total"]
        frontier.append({
            "budget": budget,
            "quality": {k: round(v_, 4) for k, v_ in q_raw.items()},
            "drift": round(_score_sum_drift(
                objective, arm["assignment"], wave["assignment"]
            ), 4),
            "solve_seconds": round(arm["seconds"], 4),
            "violations": v["total"], **arm["stats"],
        })
    best = arms[-1][1]
    q_best = frontier[-1]["quality"]
    placed = int((best["assignment"] >= 0).sum())
    line = {
        "frontier": frontier,
        "wave_parity_at_budget_0": wave_parity,
        "violations": total_violations,
        "packed_utilization_gain": round(
            q_best_raw["packed_utilization"]
            - q_wave_raw["packed_utilization"], 6
        ),
        "fragmentation_gain": round(
            q_wave_raw["fragmentation"] - q_best_raw["fragmentation"], 6
        ),
        "budgets": list(shape["budgets"]),
    }
    if emit:
        _emit(
            CONFIG_METRICS[13],
            placed / best["seconds"] if best["seconds"] else 0.0,
            f"{shape['n_nodes']} nodes x {snap.num_pods} pods packing "
            f"frontier, budgets {list(shape['budgets'])}",
            baseline=placed / wave["seconds"] if wave["seconds"] else 1.0,
            drift=frontier[-1]["drift"],
            quality=q_best,
            extra=line,
        )
    return line


def pack_smoke(min_gain=1e-4, drift_bound=0.15):
    """CI gate (`make pack-smoke`): on the reduced shape, the packing
    mode must STRICTLY improve packed_utilization AND fragmentation over
    the wave path at its largest budget, with zero hard-constraint
    violations on every arm (the `tuning.gates` replay oracles), budget-0
    placements bit-identical to the wave path, and |drift| bounded."""
    line = packing_frontier(shape=PACK_SMOKE_SHAPE, emit=False)
    checks = {
        "wave_parity_at_budget_0": line["wave_parity_at_budget_0"],
        "zero_violations": line["violations"] == 0,
        "packed_utilization_strictly_improves":
            line["packed_utilization_gain"] > min_gain,
        "fragmentation_strictly_improves":
            line["fragmentation_gain"] > min_gain,
        "drift_bounded": all(
            abs(arm["drift"]) <= drift_bound for arm in line["frontier"]
        ),
    }
    ok = all(checks.values())
    print(json.dumps({
        "smoke": "pack", "ok": ok, "checks": checks,
        "packed_utilization_gain": line["packed_utilization_gain"],
        "fragmentation_gain": line["fragmentation_gain"],
        "frontier": line["frontier"],
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 14: drifting mix — online self-tuning serving vs the static profile
# ---------------------------------------------------------------------------

#: the config-14 headline shape (ISSUE 15 / ROADMAP item 2): a trimaran
#: pair (TargetLoadPacking + LoadVariationRiskBalancing) serving a
#: sustained-churn workload whose MIX DRIFTS mid-run — a hot/cold node
#: fleet whose formerly-quiet class turns metric-noisy (colocated batch
#: jobs) while the pod-size mix goes bimodal, so the LVRB variance term
#: starts steering pods AWAY from the emptiest nodes and the static
#: profile's weight split stops being the right one. Four arms/phases:
#: tuned-vs-static quality over the drift, an interleaved-pairs
#: shadow-lane overhead bound, an injected-regression phase where the
#: probation auto-rollback is observed, and a no-flap settle window.
TUNE_LIVE_SHAPE = dict(
    n_nodes=96, hot_frac=0.25, hot_util=0.62, cold_util=0.15,
    arrivals=24, departs=10,
    warmup=8, cycles_a=8, cycles_b=14, regression_cycles=12,
    settle_cycles=4,
    candidates=16, corpus=3, sweep_every=2, confirm_sweeps=2,
    probation_cycles=8, baseline_window=8, baseline_min=2,
    baseline_recent=3, hysteresis=0.002, regress_cycles=2, cooldown=16,
    tolerance=0.01,
    deadline_s=60.0, inject=(1, 64),
)
#: reduced shape for the `make tune-live-smoke` CI gate (2-core runners)
TUNE_LIVE_SMOKE_SHAPE = dict(
    n_nodes=48, hot_frac=0.25, hot_util=0.62, cold_util=0.15,
    arrivals=16, departs=6,
    warmup=8, cycles_a=6, cycles_b=12, regression_cycles=12,
    settle_cycles=4,
    candidates=12, corpus=3, sweep_every=2, confirm_sweeps=2,
    probation_cycles=8, baseline_window=8, baseline_min=2,
    baseline_recent=3, hysteresis=0.002, regress_cycles=2, cooldown=16,
    tolerance=0.01,
    deadline_s=60.0, inject=(1, 64),
)
#: interleaved lane-on/lane-off pairs for the shadow overhead bound (the
#: chaos/replay pairing discipline: statistic = median of PAIRED deltas,
#: floor = the off series' own p10-p90 spread)
TUNE_OVERHEAD_PAIRS = 9

#: probation objectives (the per-cycle quality gauges the tuned-vs-static
#: comparison and the rollback detection both read) — must equal
#: `tuning.shadow.PROBATION_OBJECTIVES` (asserted by
#: `tuned_drifting_mix`; stated literally here because bench.py imports
#: the package lazily, after `apply_platform_override`)
TUNE_OBJECTIVES = (
    "fragmentation", "util_imbalance", "gang_wait_frac", "unplaced_frac",
)


def _drift_cluster(shape, seed=0):
    """Hot/cold fleet with an imbalanced ALREADY-BOUND base load: the
    first `hot_frac` of nodes prefilled to `hot_util` of cpu, the rest to
    `cold_util` — the imbalance the load-aware profile is there to work
    against, and the request distribution the per-cycle metrics mirror."""
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    cluster = Cluster()
    n = shape["n_nodes"]
    hot = max(1, int(n * shape["hot_frac"]))
    serial = 0
    for i in range(n):
        cluster.add_node(Node(
            name=f"node-{i:05d}",
            allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 512},
        ))
        target = shape["hot_util"] if i < hot else shape["cold_util"]
        filled = 0
        while filled < int(64_000 * target):
            serial += 1
            pod = Pod(
                name=f"base-{serial:06d}", creation_ms=serial,
                containers=[Container(requests={
                    CPU: 2000, MEMORY: 4 * gib})],
            )
            pod.node_name = f"node-{i:05d}"
            cluster.add_pod(pod)
            filled += 2000
    return cluster


def _drift_script(shape, seed=0):
    """(script, drift_at): the per-cycle event script — (phase, arrivals
    [(name, cpu, mem)], departures [names]) — generated ONCE from the rng
    stream alone, fully independent of placements, so every arm (static,
    tuned, lane-on, lane-off) replays the identical workload and quality
    deltas are attributable to the weights, never the stream. Departures
    draw only from pods that arrived in EARLIER cycles."""
    rng = np.random.default_rng(seed + 1)
    gib = 1 << 30
    total = (shape["warmup"] + shape["cycles_a"] + shape["cycles_b"]
             + shape["regression_cycles"] + shape["settle_cycles"])
    drift_at = shape["warmup"] + shape["cycles_a"]
    serial = 0
    live: list = []
    script = []
    for c in range(total):
        phase = "a" if c < drift_at else "b"
        departs = []
        k = min(shape["departs"], len(live))
        if k > 0:
            picks = sorted(
                int(x) for x in
                rng.choice(len(live), size=k, replace=False)
            )
            departs = [live[i] for i in picks]
            live = [nm for i, nm in enumerate(live) if i not in set(picks)]
        arrivals = []
        for _ in range(shape["arrivals"]):
            serial += 1
            if phase == "a":
                cpu = int(rng.integers(800, 1600))
                mem = int(rng.integers(gib, 2 * gib))
            else:
                # bimodal post-drift mix: sidecar dust + fat batch pods
                if rng.random() < 0.5:
                    cpu, mem = 600, gib // 2
                else:
                    cpu, mem = 4200, 3 * gib
            name = f"arr-{serial:06d}"
            arrivals.append((name, cpu, mem))
            live.append(name)
        script.append((phase, arrivals, departs))
    return script, drift_at


def _drift_metrics(cluster, shape, phase) -> None:
    """Refresh `cluster.node_metrics` for one cycle: cpu/mem averages
    mirror the ACTUAL requested utilization per node (a live
    load-watcher), while the variance term drifts with the phase — in
    phase "b" the cold class turns metric-noisy (cpu_std 60: colocated
    batch interference), which makes the LVRB risk curve steer pods away
    from exactly the nodes that balance the fleet. The drift is the
    tuning opportunity: phase "a" weights stop being right."""
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    n = len(cluster.nodes)
    hot = max(1, int(n * shape["hot_frac"]))
    used_cpu: dict = {}
    used_mem: dict = {}
    for pod in cluster.pods.values():
        if pod.node_name is None:
            continue
        req = pod.effective_request()
        used_cpu[pod.node_name] = used_cpu.get(pod.node_name, 0) + req.get(
            CPU, 0
        )
        used_mem[pod.node_name] = used_mem.get(pod.node_name, 0) + req.get(
            MEMORY, 0
        )
    metrics = {}
    for i, (name, node) in enumerate(cluster.nodes.items()):
        cpu_pct = 100.0 * used_cpu.get(name, 0) / max(
            node.allocatable.get(CPU, 1), 1
        )
        mem_pct = 100.0 * used_mem.get(name, 0) / max(
            node.allocatable.get(MEMORY, 1), 1
        )
        noisy = phase == "b" and i >= hot
        metrics[name] = {
            "cpu_avg": min(cpu_pct, 100.0),
            "cpu_std": 60.0 if noisy else 3.0,
            "mem_avg": min(mem_pct, 100.0),
            "mem_std": 8.0 if noisy else 2.0,
        }
    cluster.node_metrics = metrics


def _drift_profile():
    """The static serving profile: a trimaran pair whose weights TRUST
    the variance signal (LVRB 20 : TLP 1 — the right call in phase "a",
    where metric noise really does flag bad nodes). The phase-"b" drift
    makes exactly that trust misleading: the noisy-but-empty cold class
    is where pods SHOULD go, and the static profile starts steering
    arrivals onto the already-hot nodes — the regression the online
    tuner exists to close."""
    from scheduler_plugins_tpu import plugins as P
    from scheduler_plugins_tpu.framework import Profile, Scheduler

    tlp = P.TargetLoadPacking()
    lvrb = P.LoadVariationRiskBalancing()
    lvrb.weight = 20
    return Scheduler(Profile(plugins=[tlp, lvrb]))


def _drift_apply_events(cluster, arrivals, departs, now) -> None:
    from scheduler_plugins_tpu.api.objects import Container, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    for name in departs:
        uid = f"default/{name}"
        if uid in cluster.pods:
            cluster.remove_pod(uid)
    for name, cpu, mem in arrivals:
        cluster.add_pod(Pod(
            name=name, creation_ms=now,
            containers=[Container(requests={CPU: cpu, MEMORY: mem})],
        ))


def _sense_quality_win(tuned_rows, static_rows) -> float:
    """Sense-adjusted placement-quality delta, positive = tuned better:
    sum over the per-cycle objectives of SENSE * (mean_tuned -
    mean_static) in each objective's own dimensionless units (the
    promotion gate's own ranking rule, applied between arms)."""
    from scheduler_plugins_tpu.tuning.quality import SENSE

    if not tuned_rows or not static_rows:
        return 0.0
    win = 0.0
    for name in TUNE_OBJECTIVES:
        t = [q[name] for q in tuned_rows if name in q]
        s = [q[name] for q in static_rows if name in q]
        if t and s:
            win += SENSE[name] * (float(np.mean(t)) - float(np.mean(s)))
    return win


def _run_drift_arm(shape, seed=0, tuned=False):
    """One full drifting-mix run. `tuned=False` is the static-profile
    control; `tuned=True` arms the flight recorder + a synchronous
    ShadowTuner (sweeps deadlined inline at the cycle boundary — the
    seam order production uses, with the sweep wall time accounted
    SEPARATELY from the cycle timing: in the daemon the sweep runs on a
    background worker, and the per-tick lane overhead has its own
    interleaved-pairs phase) and drives the injected-regression phase.
    Returns per-cycle times/decisions/quality plus the tuner ledger."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.tuning.quality import SENSE
    from scheduler_plugins_tpu.tuning.shadow import ShadowTuner
    from scheduler_plugins_tpu.utils import flightrec

    script, drift_at = _drift_script(shape, seed)
    b_end = drift_at + shape["cycles_b"]
    inject_at = b_end
    cluster = _drift_cluster(shape, seed)
    scheduler = _drift_profile()
    tuner = None
    if tuned:
        flightrec.recorder.start(capacity=shape["corpus"] + 2)
        tuner = ShadowTuner(
            scheduler,
            candidates=shape["candidates"],
            corpus_cycles=shape["corpus"],
            sweep_every=shape["sweep_every"],
            confirm_sweeps=shape["confirm_sweeps"],
            tolerance=shape["tolerance"],
            probation_cycles=shape["probation_cycles"],
            baseline_window=shape["baseline_window"],
            baseline_min=shape["baseline_min"],
            baseline_recent=shape["baseline_recent"],
            hysteresis=shape["hysteresis"],
            regress_cycles=shape["regress_cycles"],
            cooldown_cycles=shape["cooldown"],
            deadline_s=shape["deadline_s"],
            sync=True, seed=seed,
        )
    out = {
        "times": [], "decided": [], "quality": [], "violations": 0,
        "promotions": [], "sweep_wall_s": 0.0, "weights_by_cycle": [],
        "rollback": None, "regress_seen_at": None, "injected_at": None,
    }
    try:
        promotions_seen = 0
        for c, (phase, arrivals, departs) in enumerate(script):
            now = 1000 * (c + 1)
            _drift_apply_events(cluster, arrivals, departs, now)
            _drift_metrics(cluster, shape, phase)
            if tuner is not None:
                st = tuner.status()
                if (
                    out["injected_at"] is None and c >= inject_at
                    and st["state"] == "idle"
                    and st["promotions"] > st["rollbacks"]
                    and st["active_weights"] == st["last_known_good"]
                ):
                    # the injected-regression phase, armed only once the
                    # REAL promotion has been confirmed: stage a
                    # known-bad vector past the gates (the documented
                    # harness-only hook) — the probation window must
                    # catch it and roll back to the confirmed weights
                    tuner.inject_promotion(shape["inject"])
                    out["injected_at"] = c
                    out["rollbacks_pre_inject"] = st["rollbacks"]
                sweep_t0 = time.perf_counter()
                tuner.begin_cycle(now_ms=now)
                out["sweep_wall_s"] += time.perf_counter() - sweep_t0
                st = tuner.status()
                if st["promotions"] > promotions_seen:
                    promotions_seen = st["promotions"]
                    out["promotions"].append(
                        {"cycle": c, "weights": st["active_weights"],
                         # the injected promotion may apply a cycle or
                         # two after staging (probation/inflight
                         # deferral) — identify it by its weights
                         "injected": (
                             out["injected_at"] is not None
                             and st["active_weights"]
                             == list(shape["inject"])
                         )}
                    )
            start = time.perf_counter()
            with _bench_span(f"drift cycle {c}", phase=phase, tuned=tuned):
                report = run_cycle(scheduler, cluster, now=now)
            elapsed = time.perf_counter() - start
            if tuner is not None:
                tuner.observe_report(report)
                st = tuner.status()
                if (
                    out["injected_at"] is not None
                    and c >= out["injected_at"]
                    and st["state"] == "probation"
                    and st["baseline"] and report.quality is not None
                    and out["regress_seen_at"] is None
                ):
                    # first cycle the injected regression is DETECTABLE:
                    # any probation objective past the hysteresis band
                    for name in TUNE_OBJECTIVES:
                        if name not in st["baseline"]:
                            continue
                        delta = SENSE[name] * (
                            report.quality[name] - st["baseline"][name]
                        )
                        if delta < -shape["hysteresis"]:
                            out["regress_seen_at"] = c
                            break
                if (
                    out["rollback"] is None
                    and out["injected_at"] is not None
                    and st["rollbacks"] > out.get("rollbacks_pre_inject", 0)
                ):
                    out["rollback"] = {
                        "cycle": c,
                        "reason": st["last_rollback_reason"],
                        "restored_weights": st["active_weights"],
                    }
            out["weights_by_cycle"].append(
                [int(p.weight) for p in scheduler.profile.plugins]
            )
            out["times"].append(elapsed)
            out["decided"].append(len(report.bound) + len(report.failed))
            out["quality"].append(dict(report.quality or {}))
            out["violations"] += _churn_capacity_violations(cluster)
    finally:
        if tuned:
            flightrec.recorder.stop()
    out["tuner"] = tuner.status() if tuner is not None else None
    out["drift_at"] = drift_at
    out["b_end"] = b_end
    out["inject_at"] = inject_at
    return out


def _tune_overhead_pct(shape, seed=77):
    """Per-tick shadow-lane overhead, the replay/chaos pairing way: two
    identically-evolving drift clusters sharing ONE scheduler, one cycle
    each per pair (lane OFF first, then lane ON = flight-recorder
    capture + tuner hooks in observe-only mode with the sweep worker in
    its production background shape). Two passes over the same seeded
    script — the first untimed, warming every jit shape AND letting the
    background sweep program compile; the timed pass then suppresses new
    sweep dispatches so the statistic bounds the ALWAYS-ON per-tick lane
    cost (hook + ring capture + worker poll; background sweep wall time
    is reported separately by the main arm). Returns (overhead_pct,
    jitter_floor_pct, placements_match) — the observe-only lane must
    never change a placement."""
    from scheduler_plugins_tpu.framework import run_cycle
    from scheduler_plugins_tpu.tuning.shadow import ShadowTuner
    from scheduler_plugins_tpu.utils import flightrec

    script, _ = _drift_script(shape, seed)
    n_cycles = shape["warmup"] + TUNE_OVERHEAD_PAIRS
    script = script[:n_cycles]
    scheduler = _drift_profile()
    off, pair_pct = [], []
    placements_match = True
    for timed in (False, True):
        arms = {
            name: {"cluster": _drift_cluster(shape, seed)}
            for name in ("off", "on")
        }
        flightrec.recorder.start(capacity=shape["corpus"] + 2)
        flightrec.recorder.stop()  # armed per on-cycle via resume()
        tuner = ShadowTuner(
            scheduler,
            candidates=shape["candidates"],
            corpus_cycles=shape["corpus"],
            sweep_every=shape["sweep_every"],
            deadline_s=shape["deadline_s"],
            observe_only=True, sync=False, seed=seed,
        )
        for c, (phase, arrivals, departs) in enumerate(script):
            now = 1000 * (c + 1)
            if timed and c == shape["warmup"]:
                # timed pairs bound the always-on per-tick cost: no NEW
                # sweep dispatches mid-measurement, and the one in
                # flight (if any) drains first
                tuner.sweep_every = 10 ** 9
                tuner.quiesce(shape["deadline_s"])

            def one(arm_name):
                arm = arms[arm_name]
                _drift_apply_events(
                    arm["cluster"], arrivals, departs, now
                )
                _drift_metrics(arm["cluster"], shape, phase)
                lane = arm_name == "on"
                if lane:
                    flightrec.recorder.resume()
                    start = time.perf_counter()
                    tuner.begin_cycle(now_ms=now)
                    report = run_cycle(scheduler, arm["cluster"], now=now)
                    tuner.observe_report(report)
                    elapsed = time.perf_counter() - start
                    flightrec.recorder.stop()
                else:
                    start = time.perf_counter()
                    report = run_cycle(scheduler, arm["cluster"], now=now)
                    elapsed = time.perf_counter() - start
                return elapsed, dict(report.bound)

            t_off, bound_off = one("off")
            t_on, bound_on = one("on")
            if bound_off != bound_on:
                placements_match = False
            if timed and c >= shape["warmup"]:
                off.append(t_off)
                pair_pct.append(100.0 * (t_on - t_off) / t_off)
        tuner.quiesce(shape["deadline_s"])
    flightrec.recorder.stop()
    off_sorted = sorted(off)
    median_off = off_sorted[len(off) // 2]
    overhead_pct = sorted(pair_pct)[len(pair_pct) // 2]
    spread_pct = 100.0 * (
        off_sorted[int(0.9 * (len(off) - 1))]
        - off_sorted[int(0.1 * (len(off) - 1))]
    ) / median_off
    return overhead_pct, spread_pct, placements_match


def tuned_drifting_mix(shape=None, emit=True, seed=0):
    """Config 14: the drifting-mix bench. Runs the SAME drifting event
    script twice — the static profile vs the online-tuned lane
    (flight-recorder ring + ShadowTuner: deadlined shadow sweeps, gated
    promotion through the aux channel, probation auto-rollback) — then
    measures the shadow lane's per-tick overhead with interleaved pairs
    and drives an injected-regression phase where the rollback is
    observed. Headline claims (asserted by `tune_live_smoke`): the tuned
    lane beats the static profile on the placement-quality gauges over
    the drifted mix with ZERO hard-constraint violations, lane overhead
    within max(5%, the jitter floor), rollback within
    `regress_cycles` (<= 2) cycles of the first detectable regression,
    and no flapping afterwards."""
    from scheduler_plugins_tpu.tuning.shadow import PROBATION_OBJECTIVES
    from scheduler_plugins_tpu.utils import observability as obs_

    assert TUNE_OBJECTIVES == PROBATION_OBJECTIVES
    shape = shape or TUNE_LIVE_SHAPE
    # scoped view over the process-global registry: the arm-vs-arm run
    # reads only what IT moved, not whatever earlier benches in this
    # process accumulated (Metrics.scoped — the snapshot/diff discipline)
    scope = obs_.metrics.scoped()
    static = _run_drift_arm(shape, seed=seed, tuned=False)
    tuned = _run_drift_arm(shape, seed=seed, tuned=True)
    sweep_compiles = scope.get(obs_.JIT_CACHE_MISS, program="sweep_solve")

    drift_at, b_end = tuned["drift_at"], tuned["b_end"]
    warmup = shape["warmup"]
    # timed window: post-warmup through the end of phase B (the
    # regression/settle phases exist to demonstrate rollback, not to
    # pollute the throughput or quality comparison)
    t_idx = list(range(warmup, b_end))
    serve_s = sum(tuned["times"][i] for i in t_idx)
    static_s = sum(static["times"][i] for i in t_idx)
    n_decided = sum(tuned["decided"][i] for i in t_idx)

    real_promos = [p for p in tuned["promotions"] if not p["injected"]]
    promo_cycle = real_promos[0]["cycle"] if real_promos else None
    post_idx = (
        list(range(max(promo_cycle, drift_at), b_end))
        if promo_cycle is not None and promo_cycle < b_end
        else list(range(drift_at, b_end))
    )
    win_post = _sense_quality_win(
        [tuned["quality"][i] for i in post_idx],
        [static["quality"][i] for i in post_idx],
    )
    win_overall = _sense_quality_win(
        [tuned["quality"][i] for i in t_idx],
        [static["quality"][i] for i in t_idx],
    )

    rollback = tuned["rollback"]
    tuner_final = tuned["tuner"]
    regress_at = tuned["regress_seen_at"]
    detect_cycles = (
        tuner_final["last_rollback_detect_cycles"]
        if rollback is not None else None
    )
    # no flapping: after the rollback the controller must hold the
    # last-known-good weights through the settle window — no further
    # promotion, the injected vector blocked
    flapped = bool(
        rollback is not None and (
            any(p["cycle"] > rollback["cycle"] for p in tuned["promotions"])
            or tuner_final["active_weights"]
            != tuner_final["last_known_good"]
        )
    )
    overhead_pct, jitter_floor_pct, lane_placements_match = (
        _tune_overhead_pct(shape, seed + 77)
    )

    line = {
        "cycles": len(t_idx),
        "drift_at_cycle": drift_at,
        "promotions": len(real_promos),
        "promotion_cycle": promo_cycle,
        "promoted_weights": (
            real_promos[0]["weights"] if real_promos else None
        ),
        "static_weights": static["weights_by_cycle"][0],
        "quality_win_post_promotion": round(win_post, 6),
        "quality_win_overall": round(win_overall, 6),
        "tuned_quality_post": {
            name: round(float(np.mean(
                [tuned["quality"][i][name] for i in post_idx]
            )), 6)
            for name in TUNE_OBJECTIVES
        },
        "static_quality_post": {
            name: round(float(np.mean(
                [static["quality"][i][name] for i in post_idx]
            )), 6)
            for name in TUNE_OBJECTIVES
        },
        "capacity_violations": tuned["violations"] + static["violations"],
        "sweeps": tuner_final["sweeps"],
        "sweep_failures": tuner_final["sweep_failures"],
        "sweep_compiles": int(sweep_compiles),
        "shadow_sweep_wall_s": round(tuned["sweep_wall_s"], 3),
        "overhead_pct": round(overhead_pct, 2),
        "overhead_jitter_floor_pct": round(jitter_floor_pct, 2),
        "observe_only_placements_match": bool(lane_placements_match),
        "injected_weights": list(shape["inject"]),
        "injected_at_cycle": tuned["injected_at"],
        "regression_detected_cycle": regress_at,
        "rollback": rollback,
        "rollback_detect_cycles": detect_cycles,
        "rollbacks_total": tuner_final["rollbacks"],
        "flapped": flapped,
        "tuner_state_final": tuner_final["state"],
        "decisions": n_decided,
    }
    if emit:
        _emit(
            CONFIG_METRICS[14],
            n_decided / serve_s if serve_s else 0.0,
            f"{shape['n_nodes']} nodes drifting mix, {len(t_idx)} cycles, "
            f"tuned lane (shadow sweeps + guarded rollout) vs static "
            f"profile",
            baseline=(
                sum(static['decided'][i] for i in t_idx) / static_s
                if static_s else 1.0
            ),
            # the tuned lane solves through the bit-faithful sequential
            # parity path under its live weights — drift vs that
            # semantics is 0 by definition; the quality columns carry
            # the tuned-vs-static comparison
            drift=0.0,
            quality=line["tuned_quality_post"],
            extra=line,
        )
    return line


def tune_live_smoke(bound_pct=5.0, rollback_bound=2):
    """CI gate (`make tune-live-smoke`): reduced drifting-mix run — the
    tuned lane must promote (through the shared gates) and beat the
    static profile on the placement-quality gauges over the drifted mix,
    with zero hard-constraint violations, per-tick shadow-lane overhead
    within max(`bound_pct`%, the run's own jitter floor), observe-only
    lane placements bit-identical to the lane-off control, ONE vmapped
    sweep compile, and the injected-regression phase rolling back within
    `rollback_bound` cycles of first detectability with no flapping.
    One JSON line; rc 1 on any failure."""
    line = tuned_drifting_mix(shape=TUNE_LIVE_SMOKE_SHAPE, emit=False)
    overhead_bound = max(bound_pct, line["overhead_jitter_floor_pct"])
    checks = {
        "promoted": line["promotions"] >= 1,
        "tuned_beats_static": line["quality_win_post_promotion"] > 0,
        "tuned_not_worse_overall": line["quality_win_overall"] >= -0.002,
        "zero_violations": line["capacity_violations"] == 0,
        "overhead_bounded": line["overhead_pct"] <= overhead_bound,
        "observe_lane_placements_identical":
            line["observe_only_placements_match"],
        # one vmapped compile per pod-count bucket (arrivals + retries
        # land on a couple of power-of-two buckets over the run)
        "sweep_compiles_bounded": 0 < line["sweep_compiles"] <= 6,
        "no_sweep_failures": line["sweep_failures"] == 0,
        "rollback_observed": line["rollback"] is not None,
        "rollback_within_bound": (
            line["rollback_detect_cycles"] is not None
            and line["rollback_detect_cycles"] <= rollback_bound
        ),
        "no_flapping": not line["flapped"],
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "tune_live_smoke",
        **_device_columns(),
        "overhead_bound_pct": round(overhead_bound, 2),
        "rollback_bound_cycles": rollback_bound,
        "checks": checks,
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# config 15: K-lane optimistic concurrency — one conflict fence
# ---------------------------------------------------------------------------

#: the K-lane headline shape: zoned disjoint-tenant steady-state churn.
#: 64 tenants spread over 8 zone extended resources (R = 12 axes) on 64
#: deep nodes that never fill — the regime the lane screen certifies
#: wholesale — plus an ADVERSARIAL contended tail: `hot_bidders` pods
#: from distinct tenants (= distinct lanes) race `hot_slots` units of one
#: node's scarce extended resource every contended cycle, forcing real
#: cross-lane conflicts through the fence. Arrival/departure counts are
#: FIXED (not Poisson): the pending axis then lands on one padding bucket
#: every cycle, so no arm ever pays a retrace inside a timed cycle.
LANE_SHAPE = dict(
    n_nodes=64, zones=8, tenants=64, prefill=12288,
    cycles=10, warmup=2, lam_arrive=12288, lam_depart=12288,
    contend_cycles=3, hot_slots=8, hot_bidders=16,
    ks=(1, 2, 4, 8), headline_k=4, reps=3,
)
#: reduced shape for the `make lane-smoke` CI gate (2-core runners): same
#: zone/tenant structure, fewer cycles. The pending axis stays deep
#: (1536/cycle) — the lane claim is about amortizing the per-pod serial
#: scan, and a shallow queue measures fence fixed cost instead.
LANE_SMOKE_SHAPE = dict(
    n_nodes=48, zones=8, tenants=64, prefill=2048,
    cycles=5, warmup=2, lam_arrive=6144, lam_depart=6144,
    contend_cycles=2, hot_slots=4, hot_bidders=8,
    ks=(1, 2, 4), headline_k=4, reps=3,
)


def _lane_cluster(shape, seed=0):
    """Zoned multi-tenant cluster + one scarce 'hot' node. Prefill pods
    arrive bound (the serving steady state); every bound pod's zone
    request matches its node's zone so the end-of-run capacity audit
    (`_churn_capacity_violations`) starts clean by construction."""
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    rng = np.random.default_rng(seed)
    cluster = Cluster()
    Z = shape["zones"]
    for i in range(shape["n_nodes"]):
        cluster.add_node(Node(
            name=f"node-{i:04d}",
            allocatable={CPU: 256_000, MEMORY: 1024 * gib, PODS: 1024,
                         f"example.com/zone-{i % Z}": 100_000},
        ))
    cluster.add_node(Node(
        name="node-hot",
        allocatable={CPU: 64_000, MEMORY: 256 * gib, PODS: 512,
                     "example.com/hot": shape["hot_slots"]},
    ))
    for i in range(shape["prefill"]):
        j = i % shape["n_nodes"]
        pod = Pod(
            name=f"bound-{i:06d}", creation_ms=i,
            namespace=f"tenant-{i % shape['tenants']:03d}",
            containers=[Container(requests={
                CPU: int(rng.integers(100, 900)),
                MEMORY: int(rng.integers(256 << 20, 1 * gib)),
                f"example.com/zone-{j % Z}": 1,
            })],
        )
        pod.node_name = f"node-{j:04d}"
        cluster.add_pod(pod)
    return cluster


def lane_scaling(shape=None, emit=True):
    """Config 15: the K-lane optimistic-concurrency bench. Drives the
    zoned churn through BOTH arms on the same snapshot every cycle — the
    bit-faithful sequential solve (the defined serial order) and
    `parallel.lanes.LaneSolver` at every K in `shape['ks']` — and gates
    on per-cycle digest identity (assignment + admitted + wait) at every
    K, including the contended tail where lanes genuinely collide and
    the fence re-resolves.

    Throughput accounting (the PR 7 discipline — this host exposes ONE
    core, so K lanes time-slice instead of running concurrently):

    - `ratio` (headline, the ISSUE gate): serial solve wall over the
      laned SOLVE BOUNDARY, max(lane_ms) + fence_ms — the critical path
      K independent schedulers would pay, measured per-lane under the
      'sequential' dispatch so each lane's scan is a real wall time.
    - `ratio_full`: adds partition_ms. The partition is the serial
      coordinator prologue; a sharded deployment amortizes it into
      watch ingest (each arrival is keyed once at the filter), so it is
      reported, not hidden, but kept out of the headline.
    - `ratio_wall`: honest in-process wall over wall — <= 1 on a 1-core
      host by construction; documented, never gated.

    Timed cycles cover only the disjoint-tenant phase (the ISSUE's
    throughput claim); contended cycles assert identity + conflicts."""
    import hashlib

    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.parallel.lanes import LaneSolver
    from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable

    shape = shape or LANE_SHAPE
    gib = 1 << 30
    T, Z = shape["tenants"], shape["zones"]
    ks = list(shape["ks"])
    cluster = _lane_cluster(shape)
    cluster.enable_pending_index()
    sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    solvers = {
        k: LaneSolver(sched, k=k, partition="namespace",
                      dispatch="sequential")
        for k in ks
    }
    rng = np.random.default_rng(1)
    serial_no = 0
    total = shape["warmup"] + shape["cycles"]
    contended_from = total - shape["contend_cycles"]
    serial_s = 0.0
    decided = 0
    timed_cycles = 0
    acc = {k: dict(boundary=0.0, full=0.0, wall=0.0, conflicts=0,
                   re_resolved=0, fallbacks=0, partition=0.0,
                   fence=0.0, max_lane=0.0)
           for k in ks}
    contended = dict(cycles=0, conflicts=0, re_resolved=0)
    digests_ok = True
    mismatches = []

    def _arrive(n, hot=False):
        nonlocal serial_no
        from scheduler_plugins_tpu.api.objects import Container, Pod
        from scheduler_plugins_tpu.api.resources import CPU, MEMORY

        for _ in range(n):
            serial_no += 1
            t = serial_no % T
            req = {CPU: int(rng.integers(100, 900)),
                   MEMORY: int(rng.integers(256 << 20, 1 * gib))}
            if hot:
                req["example.com/hot"] = 1
            else:
                req[f"example.com/zone-{t % Z}"] = 1
            cluster.add_pod(Pod(
                name=f"{'hot' if hot else 'arr'}-{serial_no:06d}",
                namespace=f"tenant-{t:03d}",
                creation_ms=1_000_000 + serial_no,
                containers=[Container(requests=req)],
            ))

    for cycle in range(total):
        now = 1000 * (cycle + 1)
        in_contention = cycle >= contended_from
        if in_contention:
            # reset the hot population (bound AND last round's losers),
            # then race hot_bidders distinct-tenant pods for hot_slots
            for uid in [u for u, p in cluster.pods.items()
                        if p.name.startswith("hot-")]:
                cluster.remove_pod(uid)
            _arrive(shape["lam_arrive"] - shape["hot_bidders"])
            _arrive(shape["hot_bidders"], hot=True)
        else:
            _arrive(shape["lam_arrive"])
        bound = sorted(
            u for u, p in cluster.pods.items()
            if p.node_name is not None and p.name.startswith(("bound", "arr"))
        )
        picks = rng.choice(
            len(bound), size=min(shape["lam_depart"], len(bound)),
            replace=False,
        )
        for i in sorted(int(x) for x in picks):
            cluster.remove_pod(bound[i])

        pending = cluster.pending_pods()
        P = len(pending)
        snap, meta = cluster.snapshot(pending, now_ms=now)
        sched.prepare(meta, cluster)

        timed = cycle >= shape["warmup"] and not in_contention
        # min over reps: both arms re-solve the SAME snapshot; the
        # minimum is the standard estimator against preemption noise on
        # an oversubscribed host (the replay-smoke pairing discipline's
        # cousin), and it biases NEITHER arm — each takes its own min
        reps = shape.get("reps", 1) if timed else 1

        serial_dt = None
        for _ in range(reps):
            t0 = time.perf_counter()
            res = sched.solve(snap, mode="sequential")
            a_ser = np.asarray(res.assignment)
            ok_ser = np.asarray(res.admitted)
            w_ser = np.asarray(res.wait)
            dt = time.perf_counter() - t0
            serial_dt = dt if serial_dt is None else min(serial_dt, dt)
        digest = hashlib.sha256(
            a_ser[:P].tobytes() + ok_ser[:P].tobytes() + w_ser[:P].tobytes()
        ).hexdigest()[:16]

        if timed:
            serial_s += serial_dt
            timed_cycles += 1
            decided += P
        if in_contention:
            contended["cycles"] += 1
        for k in ks:
            best = None
            for rep in range(reps):
                t0 = time.perf_counter()
                a, ok, w, codes, st = solvers[k].solve(
                    snap, pending, cluster, meta=meta
                )
                wall = time.perf_counter() - t0
                boundary = (
                    max(st.lane_ms) + st.fence_ms
                    if st.lane_ms else st.solve_ms
                )
                if rep == 0:
                    # identity + conflict accounting from the first rep;
                    # later reps only tighten the timing estimate (the
                    # partition column stays rep-0 COLD — the key cache
                    # is warm on re-solves of the same queue)
                    d = hashlib.sha256(
                        np.asarray(a)[:P].tobytes()
                        + np.asarray(ok)[:P].tobytes()
                        + np.asarray(w)[:P].tobytes()
                    ).hexdigest()[:16]
                    if d != digest:
                        digests_ok = False
                        mismatches.append({"cycle": cycle, "k": k})
                    conflicts = sum(st.conflicts or [])
                    acc[k]["conflicts"] += conflicts
                    acc[k]["re_resolved"] += st.re_resolved
                    if k > 1 and st.path == "serial":
                        acc[k]["fallbacks"] += 1
                    if in_contention and k > 1:
                        contended["conflicts"] += conflicts
                        contended["re_resolved"] += st.re_resolved
                    partition0 = st.partition_ms
                if best is None or boundary < best[0]:
                    best = (boundary, wall, st.fence_ms,
                            max(st.lane_ms) if st.lane_ms else 0.0)
            if timed:
                boundary, wall, fence, max_lane = best
                a_k = acc[k]
                a_k["boundary"] += boundary / 1000.0
                a_k["full"] += (boundary + partition0) / 1000.0
                a_k["wall"] += wall
                a_k["partition"] += partition0
                a_k["fence"] += fence
                a_k["max_lane"] += max_lane

        # commit the serial arm's placements (identical at every K by the
        # digest gate) through the store's bind mutator
        for i, pod in enumerate(pending):
            if ok_ser[i] and a_ser[i] >= 0:
                cluster.bind(
                    pod.uid, meta.node_names[int(a_ser[i])], now_ms=now
                )

    for solver in solvers.values():
        solver.close()
    violations = _churn_capacity_violations(cluster)
    hk = shape["headline_k"]
    curve = []
    for k in ks:
        a_k = acc[k]
        n = max(1, timed_cycles)
        curve.append({
            "k": k,
            "ratio": round(serial_s / a_k["boundary"], 2)
            if a_k["boundary"] else None,
            "ratio_full": round(serial_s / a_k["full"], 2)
            if a_k["full"] else None,
            "ratio_wall": round(serial_s / a_k["wall"], 2)
            if a_k["wall"] else None,
            "pods_per_sec": round(decided / a_k["boundary"], 1)
            if a_k["boundary"] else None,
            "conflicts": a_k["conflicts"],
            "re_resolved": a_k["re_resolved"],
            "serial_fallbacks": a_k["fallbacks"],
            "partition_ms_mean": round(a_k["partition"] / n, 3),
            "max_lane_ms_mean": round(a_k["max_lane"] / n, 3),
            "fence_ms_mean": round(a_k["fence"] / n, 3),
        })
    head = next(c for c in curve if c["k"] == hk)
    line = {
        "lanes": {
            "ks": ks, "headline_k": hk, "dispatch": "sequential",
            "partition": "namespace",
            "timed_cycles": timed_cycles, "decisions": decided,
            "serial_ms_total": round(serial_s * 1000, 3),
            "curve": curve,
            "contended": dict(contended),
            "digest_mismatches": mismatches[:8],
        },
        "lane_ratio": head["ratio"],
        "lane_ratio_full": head["ratio_full"],
        "lane_ratio_wall": head["ratio_wall"],
        "digests_match": bool(digests_ok),
        "conflicts": contended["conflicts"],
        "re_resolved": contended["re_resolved"],
        "serial_fallbacks": sum(a["fallbacks"] for a in acc.values()),
        "capacity_violations": violations,
    }
    if emit:
        _emit(
            CONFIG_METRICS[15],
            decided / acc[hk]["boundary"] if acc[hk]["boundary"] else 0.0,
            f"{shape['n_nodes']} nodes, {T} tenants / {Z} zones, "
            f"{timed_cycles} cycles x {shape['lam_arrive']} pods, "
            f"K={hk} lanes (solve boundary) vs defined serial order",
            baseline=decided / serial_s if serial_s else 1.0,
            drift=(0.0 if digests_ok else None),
            quality=_quality_state(*_cluster_state_matrices(cluster)),
            extra=line,
        )
    return line


def lane_smoke(min_ratio=1.5):
    """CI gate (`make lane-smoke`): reduced K-lane run — every K's
    placements bit-identical to the defined serial order on EVERY cycle
    (contended tail included), zero hard-constraint violations, zero
    serial fallbacks, the contended phase actually forcing cross-lane
    conflicts through the fence, and the headline-K solve-boundary ratio
    >= `min_ratio` (the full config-15 shape targets the ISSUE's 2x; the
    smoke bound absorbs 2-core CI runners, the shard-smoke precedent).
    One JSON line; rc 1 on any failure."""
    line = lane_scaling(shape=LANE_SMOKE_SHAPE, emit=False)
    checks = {
        "digests_match": line["digests_match"],
        "zero_violations": line["capacity_violations"] == 0,
        "no_serial_fallbacks": line["serial_fallbacks"] == 0,
        "contention_exercised": line["conflicts"] > 0,
        "contention_re_resolved": line["re_resolved"] > 0,
        "ratio_at_headline_k": (
            line["lane_ratio"] is not None
            and line["lane_ratio"] >= min_ratio
        ),
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "lane_smoke",
        "min_ratio": min_ratio,
        **_device_columns(),
        "checks": checks,
        "ok": bool(ok),
        **line,
    }))
    return 0 if ok else 1


#: the columns every line `_emit` prints carries — THE one schema
#: statement, shared with the tests, so a new config cannot ship a line
#: missing the attribution columns
LINE_SCHEMA_KEYS = (
    "metric", "value", "unit", "vs_baseline", "platform", "device_kind",
    "devices", "mesh_shape", "drift", "quality", "cost_digest",
    "roofline_calibration",
)


def metric_name(config: int, mode: str = "sequential") -> str:
    metric = CONFIG_METRICS.get(config, CONFIG_METRICS[1])
    if config in (2, 3, 4, 5) and mode == "batch":
        metric = metric.replace("_pods_per_sec", "_batch_pods_per_sec")
    return metric


def config_problem(config: int, shape: dict | None = None):
    """(cluster, plugins, detail) — the BASELINE config 2-5 scenario/roster
    table. The ONE copy of these shapes: bench runs them and the AOT gate
    (tools/tpu_lower.py) lowers them, so they cannot drift apart. `shape`
    overrides the scenario size (the smoke-compare gate runs the same
    scenario generators at reduced N)."""
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
        raise SystemExit(f"unknown config {config}")
    return cluster, plugins, detail


def sequential_config(config: int, mode: str = "sequential",
                      record_dir: str | None = None):
    """BASELINE configs 2-5 on the bit-faithful sequential solve, or the
    profile-generic batched throughput mode (--mode batch). `record_dir`
    saves the measured cycle as a flight-recorder bundle (full solver
    inputs + outputs + drift; replay with tools/replay.py)."""
    import jax  # noqa: F401

    from scheduler_plugins_tpu.framework import Profile, Scheduler

    cluster, plugins, detail = config_problem(config)
    metric = metric_name(config, mode)

    scheduler = Scheduler(Profile(plugins=plugins))
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    n_pods = len(pending)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    import jax.numpy as jnp
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY

    weights = jnp.asarray(
        meta.index.encode({CPU: 1 << 20, MEMORY: 1}), jnp.int64
    )

    wave_stats = {}
    if mode == "batch":
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

        detail = detail.replace("sequential", "batched")

        def run():
            out = profile_batch_solve(scheduler, snap, collect_stats=True)
            wave_stats["stats"] = out[3]
            wave_stats["wait"] = out[2]
            return out[0]
    else:
        def run():
            result = scheduler.solve(snap)
            wave_stats["wait"] = result.wait
            return result.assignment

    np.asarray(run())  # compile
    times = []
    assignment = None
    for k in range(3):
        start = time.perf_counter()
        with _bench_span(f"{metric} run {k}", pods=n_pods):
            assignment = np.asarray(run())  # forces completion
        times.append(time.perf_counter() - start)
    elapsed = sorted(times)[len(times) // 2]
    placed = int((assignment >= 0).sum())
    baseline = python_baseline_pods_per_sec(cluster, sample=100)
    compiled, _ = _compiled_baseline(
        config, snap, meta, weights=weights, plugins=plugins
    )
    # sequential mode IS the bit-faithful quality anchor: drift 0 by
    # definition; batch mode reports its measured drift below
    drift = 0.0
    extra = None
    if mode == "batch":
        # placement-quality cost of the throughput path, surfaced per run
        # (VERDICT r3 item 8): relative score-sum drift on the shared
        # cycle-initial objective vs the bit-faithful sequential solve
        # (untimed — quality metric, not part of the throughput number;
        # same definition the drift-bound test asserts on)
        from scheduler_plugins_tpu.parallel.solver import (
            score_drift_vs_sequential,
        )

        seq = np.asarray(scheduler.solve(snap).assignment)
        drift, placed_seq, _ = score_drift_vs_sequential(
            scheduler, snap, seq, assignment
        )
        extra = {
            "score_drift_vs_sequential": round(drift, 4),
            "placed_sequential": placed_seq,
            **_wave_extra(wave_stats["stats"]),
        }
    if record_dir:
        _record_bench_cycle(scheduler, snap, meta, mode, record_dir, drift)
    _emit(metric, n_pods / elapsed, f"{detail}, {placed}/{n_pods} placed",
          baseline, compiled=compiled, drift=drift,
          quality=_quality_cycle(
              snap, assignment, np.asarray(wave_stats["wait"])
          ),
          extra=extra)


def _record_bench_cycle(scheduler, snap, meta, mode, record_dir, drift):
    """`--record dir/`: persist the measured cycle's full solver inputs +
    outputs as a flight-recorder bundle (the solves are cached — this
    re-invokes the already-compiled program once, outside the timing)."""
    from scheduler_plugins_tpu.utils import flightrec

    flightrec.recorder.start(capacity=1)
    flightrec.recorder.seed = 0  # config_problem scenarios are seed-0
    rec = flightrec.recorder.begin(now_ms=0, profile=scheduler.profile.name)
    rec.capture_inputs(snap, meta, scheduler)
    if mode == "batch":
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

        # collect_stats=True matches the timed run's jit-cache key — this
        # re-invokes the SAME compiled program the emitted numbers came from
        a, admitted, wait = profile_batch_solve(
            scheduler, snap, collect_stats=True
        )[:3]
        rec.capture_outputs("batch", a, admitted, wait)
    else:
        result = scheduler.solve(snap)
        rec.capture_outputs(
            "sequential", result.assignment, result.admitted, result.wait,
            failed_plugin=result.failed_plugin,
        )
    rec.commit(drift=drift)
    summary = flightrec.recorder.save(record_dir)
    flightrec.recorder.stop()
    print(f"# flight recorder bundle: {json.dumps(summary)}",
          file=sys.stderr)


#: reduced scenario shapes for the CI smoke gate (compile time bounded on
#: 2-core runners; same generators/rosters as the full configs)
SMOKE_COMPARE_SHAPES = {
    2: dict(n_nodes=1024, n_pods=512),
    3: dict(n_nodes=256, n_pods=256, zones=8),
}


def smoke_compare(configs, noise_floor=0.9, runs=5):
    """CI gate (`make bench-smoke`): on reduced config shapes, the batched
    throughput mode must schedule at least `noise_floor` x the sequential
    parity path's pods/s — the batched mode is the scale default, so a
    change that flips the batch-vs-sequential split must fail the build;
    the 10% floor absorbs small-runner timing noise. One JSON line per
    config; rc 1 on any failure."""
    import jax  # noqa: F401

    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

    failed = False
    for config in configs:
        cluster, plugins, _ = config_problem(
            config, shape=SMOKE_COMPARE_SHAPES.get(config)
        )
        scheduler = Scheduler(Profile(plugins=plugins))
        pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
        n_pods = len(pending)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        scheduler.prepare(meta, cluster)

        def timed(fn):
            np.asarray(fn())  # compile
            times = []
            for _ in range(runs):
                start = time.perf_counter()
                np.asarray(fn())  # host transfer forces completion
                times.append(time.perf_counter() - start)
            return n_pods / sorted(times)[len(times) // 2]

        seq = timed(lambda: scheduler.solve(snap).assignment)
        bat = timed(lambda: profile_batch_solve(scheduler, snap)[0])
        ratio = bat / seq
        ok = bool(ratio >= noise_floor)
        failed |= not ok
        print(json.dumps({
            "metric": f"bench_smoke_cfg{config}",
            "sequential_pods_per_sec": round(seq, 1),
            "batch_pods_per_sec": round(bat, 1),
            "ratio": round(ratio, 3),
            "noise_floor": noise_floor,
            **_device_columns(),
            "ok": ok,
        }))
    return 1 if failed else 0


def sanitize_smoke(configs, chunk_shape=(64, 256, 128)):
    """CI gate (`make sanitize-smoke`): run the checkify-instrumented
    solvers (SPT_SANITIZE=1, utils.sanitize) at reduced shapes and fail on
    ANY checkify error — index OOB on the commit scatters, NaN, or
    div-by-zero that the production jits would silently clamp or
    propagate. Coverage spans the three sanitizer wrap points: the batched
    profile solve per config, the donated chunk pipeline (reduced
    north-star shape), and the checkified `entry()` program. One JSON line
    per program; rc 1 on any error."""
    import os

    os.environ["SPT_SANITIZE"] = "1"
    import jax  # noqa: F401

    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.parallel.solver import profile_batch_solve
    from scheduler_plugins_tpu.utils import sanitize

    assert sanitize.enabled()
    failed = False

    def flush(program, detail):
        nonlocal failed
        reports = sanitize.drain()
        errors = [r for r in reports if not r["ok"]]
        failed |= bool(errors) or not reports
        print(json.dumps({
            "metric": f"sanitize_smoke_{program}",
            "detail": detail,
            "checked_calls": len(reports),
            "checkify_errors": [r.get("error") for r in errors],
            **_device_columns(),
            "ok": bool(reports) and not errors,
        }))

    for config in configs:
        cluster, plugins, detail = config_problem(
            config, shape=SMOKE_COMPARE_SHAPES.get(config)
        )
        # the gate exercises the BATCHED checkified solver, not the
        # sequential parity path config_problem's detail string names
        detail = detail.replace("sequential", "batched")
        scheduler = Scheduler(Profile(plugins=plugins))
        pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
        snap, meta = cluster.snapshot(pending, now_ms=0)
        scheduler.prepare(meta, cluster)
        out = profile_batch_solve(scheduler, snap)
        placed = int((np.asarray(out[0]) >= 0).sum())
        flush(f"cfg{config}", f"{detail}, {placed}/{len(pending)} placed")

    # donated chunk pipeline (the north-star loop body) at reduced shape
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import run_chunk_pipeline

    n_nodes, n_pods, chunk = chunk_shape
    _, snap, meta, weights, raw, padded = north_star_problem(
        n_nodes, n_pods, chunk
    )
    solve_chunk = north_star_chunk_solver()  # sanitized under SPT_SANITIZE
    req_np = np.asarray(snap.pods.req)
    mask_np = np.asarray(snap.pods.mask)
    chunk_inputs = [
        (req_np[lo:lo + chunk], mask_np[lo:lo + chunk])
        for lo in range(0, padded, chunk)
    ]
    free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    results, _, _, _ = run_chunk_pipeline(
        solve_chunk, (raw, snap.nodes.mask), chunk_inputs, free
    )
    placed = int(sum((np.asarray(a) >= 0).sum() for a, _ in results))
    flush("chunk_pipeline",
          f"{n_nodes} nodes x {n_pods} pods chunked x{chunk}, {placed} placed")

    # the checkified entry() program ((error, result) contract)
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    err, result = jax.jit(fn)(*args)
    sanitize.report("entry", err)
    placed = int((np.asarray(result.assignment) >= 0).sum())
    flush("entry", f"fused solve, {placed} placed")
    return 1 if failed else 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=int, default=1,
                        help="BASELINE.md scenario (1-5; 6 = 10k-node x "
                             "100k-pod north-star scale; 0 = tiny-shape "
                             "tpu smoke; 7 = sustained-churn serving, "
                             "resident-state vs full-resnapshot; 8 = "
                             "100k-node x 1M-pod mega scale on the "
                             "shard_map ring-election wave solver, "
                             "8-host-device mesh vs 1 device; 9 = chaos "
                             "churn: the config-7 workload under the "
                             "full seeded fault plan, serve+resilience "
                             "vs the no-chaos control; 10 = rank-aware "
                             "gangs: topology-cost gang solves + elastic "
                             "DL jobs vs quorum-only Coscheduling; 12 = "
                             "10k-node x 1k-gang mega gangs, wave-"
                             "batched gang solve vs the sequential gang "
                             "scan, bit-identical placements; 13 = "
                             "packing frontier: the packing solve mode "
                             "vs the wave path over iteration budgets — "
                             "utilization vs drift vs latency; 14 = "
                             "drifting mix: online self-tuned serving "
                             "(shadow sweeps + guarded rollout + "
                             "probation auto-rollback) vs the static "
                             "profile over a workload mix that drifts "
                             "mid-run); "
                             "default flagship")
    parser.add_argument("--mode", choices=["sequential", "batch"],
                        default="sequential",
                        help="configs 2-5: bit-faithful scan or batched waves")
    parser.add_argument("--trace", default=None, metavar="OUT",
                        help="OUT ending in .json: record the cycle tracer "
                             "(utils.observability) and write a Perfetto-"
                             "loadable Chrome-trace JSON with the host "
                             "extension-point spans and the chunk "
                             "pipeline's H2D/solve/D2H rows; otherwise a "
                             "directory for a jax profiler trace "
                             "(op-level data for tuning rounds)")
    parser.add_argument("--record", default=None, metavar="DIR",
                        help="configs 2-5: save the measured cycle as a "
                             "flight-recorder bundle under DIR (full "
                             "solver inputs + placements; replay/explain "
                             "offline with tools/replay.py)")
    parser.add_argument("--smoke-compare", default=None, metavar="CFGS",
                        help="CI gate: comma-separated configs (e.g. 2,3) "
                             "run at reduced shapes in BOTH modes; fails "
                             "when batch < 0.9x sequential pods/s")
    parser.add_argument("--sanitize-smoke", default=None, metavar="CFGS",
                        help="CI gate: comma-separated configs run at "
                             "reduced shapes under SPT_SANITIZE=1 "
                             "(checkify); fails on any checkify error")
    parser.add_argument("--shard-smoke", action="store_true",
                        help="CI gate: reduced mega config on an 8-host-"
                             "device nodes mesh; fails unless sharded "
                             "placements match the single-device wave "
                             "path bit-exactly, the capacity audit is "
                             "clean, and the program's collective census "
                             "stays O(shards) with zero all_gathers")
    parser.add_argument("--pallas-smoke", action="store_true",
                        help="CI gate: the Pallas-election sharded wave "
                             "solve (interpret twins) bit-identical to "
                             "the lax collectives build on the reduced "
                             "mega shape — placements + resident carry + "
                             "clean capacity audit, ring kernels present "
                             "with zero framework collectives left and "
                             "manifest-covered kernel programs")
    parser.add_argument("--churn-smoke", action="store_true",
                        help="CI gate: reduced sustained-churn run; fails "
                             "unless the resident-state delta path beats "
                             "the full-resnapshot baseline >= 1.5x on "
                             "cycles/s with identical placements and "
                             "zero hard-constraint violations")
    parser.add_argument("--gang-smoke", action="store_true",
                        help="CI gate: reduced rank-gang config-10 run; "
                             "fails unless the gang phase's max inter-"
                             "rank cost is strictly below the quorum-"
                             "only baseline, the jit solve bit-matches "
                             "its numpy twin (drift 0.0), the hard-"
                             "constraint audit is clean, and elastic "
                             "grow/shrink converge within 2 cycles")
    parser.add_argument("--endurance-smoke", action="store_true",
                        help="CI gate: reduced cluster-life config-11 "
                             "run (churn+gangs+chaos+waves, one seeded "
                             "stream); fails unless the pipelined cycle "
                             "engine beats the serial engine >= 1.5x on "
                             "serve-phase (churn+waves) AND gang-phase "
                             "cycles/s with zero serve gang fallbacks "
                             "(resident gang/quota side tables), "
                             "identical "
                             "per-cycle placements, a bit-identical "
                             "final cluster state and a clean replayed "
                             "capacity audit")
    parser.add_argument("--pack-smoke", action="store_true",
                        help="CI gate: reduced packing-frontier run; "
                             "fails unless the packing mode strictly "
                             "improves packed_utilization AND "
                             "fragmentation over the wave path with "
                             "zero hard-constraint violations, budget-0 "
                             "bit-parity with the wave placements, and "
                             "bounded drift")
    parser.add_argument("--tune-live-smoke", action="store_true",
                        help="CI gate: reduced drifting-mix config-14 "
                             "run; fails unless the online-tuned lane "
                             "promotes through the shared gates and "
                             "beats the static profile on placement "
                             "quality with zero violations, bounded "
                             "shadow-lane overhead, and the injected-"
                             "regression phase rolling back within 2 "
                             "cycles with no flapping")
    parser.add_argument("--lane-smoke", action="store_true",
                        help="CI gate: reduced K-lane config-15 run; "
                             "fails unless every K's placements are "
                             "bit-identical to the defined serial order "
                             "on every cycle (contended tail included), "
                             "zero hard-constraint violations, zero "
                             "serial fallbacks, the contended phase "
                             "forces real cross-lane conflicts through "
                             "the fence, and the headline-K solve-"
                             "boundary ratio clears the bound")
    parser.add_argument("--chaos-smoke", action="store_true",
                        help="CI gate: reduced chaos-churn run under the "
                             "full seeded fault plan (hung solve, device "
                             "error, garbage output, dropped/dup/corrupt "
                             "deltas, feed stall, crash mid-cycle); fails "
                             "unless zero hard-constraint violations, "
                             "bounded recovery, every cycle bit-identical "
                             "to the no-chaos control, and watchdog "
                             "overhead within max(2%, jitter floor)")
    args = parser.parse_args()
    from scheduler_plugins_tpu.utils import compile_cache

    compile_cache.configure()
    # the CI gates and comparison configs: each compares two arms in this
    # one process on whatever device JAX runs, and exits on its own verdict
    for flag, gate in (
        ("shard_smoke", shard_smoke), ("pallas_smoke", pallas_smoke),
        ("churn_smoke", churn_smoke), ("chaos_smoke", chaos_smoke),
        ("gang_smoke", gang_smoke), ("endurance_smoke", endurance_smoke),
        ("pack_smoke", pack_smoke), ("tune_live_smoke", tune_live_smoke),
        ("lane_smoke", lane_smoke),
    ):
        if getattr(args, flag):
            sys.exit(gate())
    for flag, gate in (
        ("sanitize_smoke", sanitize_smoke), ("smoke_compare", smoke_compare),
    ):
        if getattr(args, flag):
            sys.exit(gate(
                [int(c) for c in getattr(args, flag).split(",") if c]
            ))
    comparison = {
        8: mega, 9: chaos_churn, 10: rank_gangs, 11: cluster_life,
        12: mega_gangs, 13: packing_frontier, 14: tuned_drifting_mix,
        15: lane_scaling,
    }
    if args.config in comparison:
        comparison[args.config]()
        sys.exit(0)
    trace_json = bool(args.trace) and args.trace.endswith(".json")
    if trace_json:
        from scheduler_plugins_tpu.utils import observability as obs

        obs.tracer.start()
    elif args.trace:
        import jax

        jax.profiler.start_trace(args.trace)
    try:
        if args.config == 0:
            tpu_smoke()
        elif args.config == 1:
            main()
        elif args.config == 6:
            north_star()
        elif args.config == 7:
            serving_churn()
        else:
            sequential_config(args.config, args.mode,
                              record_dir=args.record)
        if args.record and args.config in (0, 1, 6):
            print("# --record applies to plugin-profile configs 2-5 "
                  "(the flagship/north-star solves run no plugin "
                  "profile); nothing recorded", file=sys.stderr)
    finally:
        if trace_json:
            obs.tracer.stop()
            obs.tracer.write(args.trace)
        elif args.trace:
            jax.profiler.stop_trace()
