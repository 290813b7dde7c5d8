"""Placement: turn per-pod feasibility + scores into node assignments.

Three modes, all returning assignment = (P,) int32 node index (-1 =
unschedulable):

- `greedy_assign` — bit-faithful to the reference's one-pod-at-a-time cycle:
  a `lax.scan` over the pod queue where each step filters/scores against the
  *current* free capacity and commits the winner before the next pod runs
  (SURVEY.md §7 "sequential semantics"). Tie-break: lowest node index (the
  upstream framework randomizes among equals; we pin determinism instead).

- `waterfill_assign` — the TPU-throughput default: queue-ranked pods spread
  across score-ordered nodes by estimated per-node capacity per wave, with
  EXACT queue-order admission; converges in a few waves even when scores tie.

- `wave_assign` — the simpler argmax-per-pod wave variant (one node fills
  per wave under tied scores; kept for comparison and tests).

Wave placements can differ from sequential mode in tie-breaking; hard
constraints hold in all modes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from scheduler_plugins_tpu.ops.fit import pod_fit_demand

#: signature: (free (N,R), pod_index int32) -> (feasible (N,) bool, score (N,) int64)
StepFn = Callable

def _segment_prefix(values_sorted, first):
    """Inclusive per-segment prefix sums of NON-NEGATIVE (P, R) float values
    WITHOUT a (P, N) cumsum (int64 2-D cumsums lower to vmem-hungry
    reduce-windows on TPU and compile pathologically): 1-D cumsums over the
    sorted axis, rebased per segment with a forward-filled running maximum
    (cummax works because the exclusive cumsum is non-decreasing)."""
    csum = jnp.cumsum(values_sorted, axis=0)
    exclusive = csum - values_sorted
    base = jax.lax.cummax(jnp.where(first[:, None], exclusive, -1.0), axis=0)
    return csum - base


def _cumulative_demand_positions(dem, free, order_n):
    """(W,) first score-ordered node index whose CUMULATIVE free capacity
    covers each row's inclusive cumulative demand, per resource (max over
    R) — the cumulative-demand waterfill bucketing shared by the generic
    wave core and the targeted lite waves (exact under heterogeneous
    demands, unlike a mean-demand pods-per-node estimate: a queue of small
    pods fills the preferred nodes first instead of stampeding the one big
    node, mirroring sequential packing order). `dem` must already be
    masked to the active/window rows (inactive rows charge 0). R 1-D
    float64 cumsums + R searchsorteds — exact below 2^53."""
    cumdem = jnp.cumsum(dem.astype(jnp.float64), axis=0)  # (W, R) inclusive
    cumfree = jnp.cumsum(
        jnp.clip(free[order_n], 0, None).astype(jnp.float64), axis=0
    )  # (N, R) in score order
    return jnp.max(
        jax.vmap(
            lambda cf, cd: jnp.searchsorted(cf, cd, side="left"),
            in_axes=(1, 1), out_axes=1,
        )(cumfree, cumdem),
        axis=1,
    )


def _queue_order_admission_choice(choice, demand, free):
    """(P,) bool: pod admitted iff its chosen node still fits after all
    earlier same-wave choosers of that node (exact sorted-segment prefix
    sums in float64 — exact below 2^53). `choice` is (P,) int32 node
    indices with -1 = no choice; never materializes a (P, N) onehot."""
    P = choice.shape[0]
    N = free.shape[0]
    seg_choice = jnp.where(choice >= 0, choice, N)
    order = jnp.argsort(
        seg_choice.astype(jnp.int64) * P + jnp.arange(P)
    )  # stable (choice, queue); int64 keys — N*P can exceed int32
    seg = seg_choice[order]
    first = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    dem_sorted = demand[order].astype(jnp.float64)  # (P, R)
    within = _segment_prefix(dem_sorted, first)  # inclusive per-segment
    free_row = free[jnp.minimum(seg, N - 1)].astype(jnp.float64)  # (P, R)
    ok_sorted = jnp.all(within <= free_row, axis=1) & (seg < N)
    return jnp.zeros(P, bool).at[order].set(ok_sorted)


def _queue_order_admission(onehot, demand, free):
    """`_queue_order_admission_choice` for callers holding a (P, N) onehot."""
    choice = jnp.where(
        onehot.any(axis=1), jnp.argmax(onehot, axis=1).astype(jnp.int32), -1
    )
    return _queue_order_admission_choice(choice, demand, free)


def _pick(feasible, scores):
    """argmax score among feasible nodes, lowest index on ties; -1 if none."""
    masked = jnp.where(feasible, scores, jnp.int64(-(2**62)))
    best = jnp.argmax(masked)
    return jnp.where(feasible.any(), best.astype(jnp.int32), jnp.int32(-1))


def _straggler_window(demand, pod_mask, assignment, hopeless, W):
    """First W still-active pods in queue order: (idx (W,), valid (W,),
    dem (W, R)) — rank-compaction scatter into a W+1 buffer (slot W is
    the overflow trash slot), no P-length sort. Deliberately NOT
    `jnp.nonzero(size=)`: jax pads that via a bincount scatter whose
    out-of-bounds writes rely on drop semantics, which the SPT_SANITIZE
    checkify gate rightly flags; this form is in-bounds by construction
    at the same O(P) scatter cost. Shared by the single-device targeted
    waterfill and the shard_map sharded variant (pod-axis state is
    replicated there, so the same code runs per shard)."""
    P = pod_mask.shape[0]
    active = (assignment == -1) & pod_mask & ~hopeless
    rank = jnp.cumsum(active) - 1  # (P,) inclusive rank among active
    slot = jnp.where(active & (rank < W), rank, W).astype(jnp.int32)
    idx = jnp.full(W + 1, P, jnp.int32).at[slot].min(
        jnp.arange(P, dtype=jnp.int32)
    )[:W]
    valid = idx < P
    dem_w = jnp.where(valid[:, None], demand[jnp.minimum(idx, P - 1)], 0)
    return idx, valid, dem_w


def ring_exclusive_scan(x, axis_name, n_shards: int):
    """Exclusive prefix sum of `x` over the mesh axis `axis_name` (shard s
    receives the sum of x from shards < s) via an (S-1)-step `lax.ppermute`
    ring — O(shards) collectives of O(|x|) payload each, never a full-axis
    gather (tools/graft_lint.py GL009 forbids `all_gather` over the node
    axis: it silently degrades the ring election back to a full gather).
    After k ring steps each shard holds the value of shard (idx - k) mod S;
    summing the steps with k <= idx yields the exclusive prefix."""
    if n_shards == 1:
        return jnp.zeros_like(x)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    acc = jnp.zeros_like(x)
    recv = x
    for k in range(1, n_shards):
        recv = jax.lax.ppermute(recv, axis_name, perm)
        acc = acc + jnp.where(k <= idx, recv, jnp.zeros_like(recv))
    return acc


#: shard count above which the sharded wave's block-offset scans switch
#: from the one-psum slot-scatter (payload O(S·|x|), ONE barrier) to the
#: ppermute ring (payload O(|x|) per step, S-1 barriers): barriers are the
#: expensive resource on small meshes (XLA's in-process CPU collectives
#: spin-wait at every rendezvous), payload is on large ones.
PSUM_SCAN_MAX_SHARDS = 64


def block_exclusive_offsets(x, axis_name, n_shards: int):
    """(exclusive_prefix, total) of the per-shard values `x` over the mesh
    axis — the cross-shard reduction behind both wave elections (cumulative
    free-capacity bases, rescue feasible-count offsets). Reduces per-shard
    CHAMPIONS only (an (S, ...) table of block aggregates), never the node
    axis itself.

    Two exact formulations, picked by shard count:

    - S <= `PSUM_SCAN_MAX_SHARDS`: each shard scatters its value into its
      own slot of an (S, ...) zero table and ONE `lax.psum` assembles all
      block aggregates everywhere (slots are disjoint, so the sum is exact
      for any dtype); the exclusive prefix and the total then fall out of
      one local cumsum over the tiny S axis.
    - larger S: the (S-1)-step `ring_exclusive_scan` plus one psum for the
      total — O(|x|) payload per step when S·|x| tables would outgrow the
      win of fewer barriers.

    Both orderings sum blocks left-to-right, so results are bit-identical
    to each other and to the single-device cumsum decomposition whenever
    the values are exact (integers below 2^53 in float64 — the documented
    parity bound)."""
    if n_shards == 1:
        return jnp.zeros_like(x), x
    if n_shards > PSUM_SCAN_MAX_SHARDS:
        return (
            ring_exclusive_scan(x, axis_name, n_shards),
            jax.lax.psum(x, axis_name),
        )
    shard = jax.lax.axis_index(axis_name)
    slots = jnp.zeros((n_shards,) + x.shape, x.dtype).at[shard].set(x)
    blocks = jax.lax.psum(slots, axis_name)  # (S, ...) every block's value
    csum = jnp.cumsum(blocks, axis=0)
    return (csum - blocks)[shard], csum[-1]


@partial(jax.jit, static_argnames=("step_fn",))
def greedy_assign(step_fn: StepFn, req, pod_mask, free0):
    """Sequential greedy placement.

    step_fn computes this pod's (feasible, scores) against current free
    capacity; the scan then commits `req` (with the pod-count slot set to 1)
    to the chosen node.
    """
    demand = pod_fit_demand(req)  # (P, R)
    P = req.shape[0]

    def body(free, p):
        feasible, scores = step_fn(free, p)
        choice = _pick(feasible & pod_mask[p], scores)
        delta = jnp.where(
            (jnp.arange(free.shape[0]) == choice)[:, None], demand[p], 0
        )
        return free + jnp.where(choice >= 0, -delta, 0), choice

    free, assignment = jax.lax.scan(body, free0, jnp.arange(P))
    return assignment, free


@partial(jax.jit, static_argnames=("batch_fn", "max_waves"))
def waterfill_assign(batch_fn, req, pod_mask, free0, max_waves: int = 4):
    """Capacity-aware wave placement: queue-ranked pods spread across
    score-ordered nodes by estimated per-node capacity, so a wave fills MANY
    nodes (plain `wave_assign` fills one node per wave when scores tie —
    e.g. the homogeneous-cluster Least-allocatable case, where the sequential
    reference semantics pack node after node).

    Per wave: rank active pods in queue order; order nodes by mean score
    (desc, index tie-break); estimate each node's capacity in pods as
    min_r floor(free_r / mean-demand_r); send pod rank k to the node whose
    cumulative-capacity bucket contains k (falling back to the pod's argmax
    when that node is infeasible for it); validate with the exact queue-order
    prefix admission and retry the rest next wave.

    Stateless front-end of `waterfill_assign_stateful` (one shared wave
    body): no plugin carry, no guards.
    """
    assignment, free, _ = waterfill_assign_stateful(
        lambda f, _state, active: batch_fn(f, active),
        lambda state, _placed, _choice: state,
        (),
        (),
        req,
        pod_mask,
        free0,
        jnp.int32(0),
        max_waves=max_waves,
    )
    return assignment, free


def waterfill_assign_stateful(
    batch_fn,
    commit_fn,
    guards,
    guard_demands,
    req,
    pod_mask,
    free0,
    state0,
    max_waves: int = 4,
    validate_fn=None,
    validate_commit_fn=None,
    capacity_fns=(),
    initial_batch=None,
    sub_batch_fn=None,
    straggler_cap: int = 256,
    collect_stats: bool = False,
):
    """`waterfill_assign` with a plugin-state carry for STATE-DEPENDENT
    filters (NUMA zone availability, network placement tallies): the carries
    the sequential scan threads per pod are re-evaluated per WAVE here, so
    hard plugin constraints hold against committed placements instead of the
    cycle-initial snapshot.

    - ``batch_fn(free, state, active) -> (feasible (P,N), scores (P,N))`` is
      re-invoked every wave with the carried state (per-wave re-filtering).
    - ``commit_fn(state, placed (P,) bool, choice (P,) int32) -> state``
      folds a whole wave's placements into the carry (must be
      order-independent — the framework's carries are sums).
    - ``guards`` / ``guard_demands``: per-plugin exact WITHIN-wave admission.
      Each guard is ``fn(state, p, node, prefix (R_g,)) -> bool`` evaluated
      in queue order with ``prefix`` = the exclusive per-(wave, node) sum of
      ``guard_demands[i]`` (a (P, R_g) non-negative float array) over earlier
      same-wave choosers of the same node. A pod whose guard fails retries
      next wave against the committed state. Prefixes include earlier
      choosers that were themselves rejected — conservative (never violates
      hard constraints; may defer a feasible pod to the next wave), matching
      `_queue_order_admission`'s capacity semantics.
    - ``validate_fn(state, q, choice) -> bool`` /
      ``validate_commit_fn(state, q, choice) -> state``: per-wave SEQUENTIAL
      validation for hard constraints that span nodes (topology-domain
      counting): after guard admission, the wave's winners are re-checked
      one at a time in queue order against the live carry, committing (via
      ``validate_commit_fn``) only the kept ones; a demoted pod re-enters
      the next wave against the committed state. ``commit_fn`` must then
      EXCLUDE the carries ``validate_commit_fn`` maintains. The scan body
      is a handful of gathers per pod — this is for O(1)-per-pod checks,
      not (N,)-wide filters.

    ``initial_batch``: optional (feasible0 (P,N), scores0 (P,N)) — the
    cycle-initial filter/score tensors the caller already computed (the
    profile solver's per-pod pass evaluates every plugin filter against
    state0 for normalization anyway). Wave 0 then reuses them instead of
    paying ``batch_fn`` a second time on the unchanged initial state; waves
    1+ always re-evaluate against the committed carry.

    ``sub_batch_fn(free, state, idx (S,), act_sub (S,)) -> (feasible (S,N),
    scores (S,N))``: optional SPARSE straggler waves — requires
    ``initial_batch``. Waves after the dense wave 0 gather the first
    ``straggler_cap`` still-unplaced pods (queue order) and re-filter only
    those rows, so a straggler wave costs O(S·N), not O(P·N). Guard
    prefixes, queue-order admission, and the validate scan all run inside
    the subset — exact, because subset rows preserve queue order and a
    wave admits only subset pods. A sparse wave that places NOTHING
    escalates to one dense wave over all active pods (a head cohort of
    more than ``straggler_cap`` infeasible pods must not starve placeable
    pods behind it); only a stalled dense wave ends the loop early.

    ``collect_stats``: also return per-wave occupancy — a
    ``{"occupancy": (max_waves,) int32 admitted-per-wave, "waves": int32
    executed-wave-count}`` dict (wave 0 is slot 0) — so perf work can see
    whether wave count or per-wave cost moved. Adds one O(max_waves)
    scatter per wave; placements are unchanged.

    Not jitted itself: designed to run inside a caller's jit (the closures
    are trace-local). Returns (assignment, free, state), plus the stats
    dict when ``collect_stats``.
    """
    P, R = req.shape
    demand = pod_fit_demand(req)
    N = free0.shape[0]
    S = min(straggler_cap, P)
    if sub_batch_fn is not None and initial_batch is None:
        raise ValueError("sub_batch_fn requires initial_batch (dense wave 0)")

    def wave_core(free, assignment, state, idx, feasible, scores):
        """One wave over the pod rows `idx` (ascending = queue order);
        `feasible`/`scores` are the (S, N) rows for those pods. The dense
        wave passes idx = arange(P)."""
        Ssub = idx.shape[0]
        active_full = (assignment == -1) & pod_mask
        active = active_full[idx]
        dem = demand[idx]
        feasible = feasible & active[:, None]
        neg_inf = jnp.iinfo(scores.dtype).min // 2

        # int64 accumulator over a possibly-int32 score matrix: exact, at
        # half the (P, N) read traffic when the caller demoted scores
        mean_score = jnp.sum(
            jnp.where(active[:, None], scores, 0), axis=0, dtype=jnp.int64
        )
        order_n = jnp.argsort(-mean_score, stable=True)  # (N,)
        # cumulative-demand bucketing (`_cumulative_demand_positions`, the
        # targeted waterfill's exact formulation): a mean-demand
        # pods-per-node estimate misroutes heterogeneous big/small queues
        # and leaves stragglers for extra re-filtered waves
        pos = _cumulative_demand_positions(
            jnp.where(active[:, None], dem, 0), free, order_n
        )  # (S,) first score-ordered node covering the demand prefix
        # plugin capacity refinements (NUMA zones, ...): pods-per-node caps
        # the resource cumsums cannot see — bucket pod rank against the
        # cumulative cap and take the more conservative position
        rank = jnp.cumsum(active, dtype=jnp.int32) - 1
        for cap_fn in capacity_fns:
            extra = cap_fn(state, active_full)
            if extra is not None:
                cap = jnp.clip(extra.astype(jnp.int32), 0, Ssub)
                ccap = jnp.cumsum(cap[order_n], dtype=jnp.int32)
                pos = jnp.maximum(
                    pos, jnp.searchsorted(ccap, rank, side="right")
                )
        target = order_n[jnp.minimum(pos, N - 1)]
        target_ok = jnp.take_along_axis(
            feasible, target[:, None], axis=1
        ).squeeze(1)
        masked = jnp.where(feasible, scores, neg_inf)
        fallback = jnp.argmax(masked, axis=1).astype(jnp.int32)
        choice = jnp.where(
            target_ok, target.astype(jnp.int32),
            jnp.where(feasible.any(axis=1), fallback, -1),
        )
        choice = jnp.where(active, choice, -1)

        # queue-order segment layout straight from `choice` — never
        # materializes the (S, N) onehot the selection math doesn't need
        seg_choice = jnp.where(choice >= 0, choice, N)
        order = jnp.argsort(
            seg_choice.astype(jnp.int64) * Ssub + jnp.arange(Ssub)
        )
        seg = seg_choice[order]
        first = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
        dem_sorted = dem[order].astype(jnp.float64)
        within = _segment_prefix(dem_sorted, first)
        free_row = free[jnp.minimum(seg, N - 1)].astype(jnp.float64)
        ok_sorted = jnp.all(within <= free_row, axis=1) & (seg < N)
        node_sorted = jnp.minimum(seg, N - 1)
        for guard, gdem in zip(guards, guard_demands):
            gd_sorted = gdem[idx][order].astype(jnp.float64)
            g_within = _segment_prefix(gd_sorted, first)
            g_excl = g_within - gd_sorted  # exclusive: earlier choosers only
            ok_sorted &= jax.vmap(
                lambda j, n, pre: guard(state, idx[j], n, pre)
            )(order, node_sorted, g_excl)
        admitted = (choice >= 0) & jnp.zeros(Ssub, bool).at[order].set(
            ok_sorted
        )

        if validate_fn is not None:
            # cross-node hard constraints: sequential queue-order re-check
            # of this wave's winners against the live carry; kept pods
            # commit immediately so later pods in the same wave see them
            # explicit int32-counter while_loop, not lax.scan: with x64 on,
            # scan lowers its xs-slicing/ys-stacking through an i64 loop
            # counter, and an i64 dynamic-slice start on these POD-SHARDED
            # rows trips older XLA spmd partitioners (s64 index vs s32
            # shard-offset compare fails the HLO verifier)
            def vstep(carry):
                vstate, kept, j = carry
                act = admitted[j]
                q = idx[j].astype(jnp.int32)
                ok = act & validate_fn(vstate, q, choice[j])
                kept_choice = jnp.where(ok, choice[j], jnp.int32(-1))
                vstate = validate_commit_fn(vstate, q, kept_choice)
                return vstate, kept.at[j].set(ok), j + 1

            state, kept, _ = jax.lax.while_loop(
                lambda c: c[2] < Ssub,
                vstep,
                (state, jnp.zeros(Ssub, bool), jnp.int32(0)),
            )
            admitted = kept

        new_assignment = assignment.at[idx].set(
            jnp.where(admitted, choice, assignment[idx])
        )
        # (N, R) usage via an (S,)-row segment sum — R * (S, N) masked
        # multiply passes collapse into one S*R-element scatter
        used = jax.ops.segment_sum(
            jnp.where(admitted[:, None], dem, 0),
            jnp.where(admitted, choice, N),
            num_segments=N + 1,
        )[:N]
        placed_full = jnp.zeros(P, bool).at[idx].set(admitted)
        choice_full = jnp.full(P, -1, jnp.int32).at[idx].set(choice)
        state = commit_fn(state, placed_full, choice_full)
        return free - used, new_assignment, state, admitted.sum()

    dense_idx = jnp.arange(P)

    def dense_wave(free, assignment, state):
        active = (assignment == -1) & pod_mask
        feasible, scores = batch_fn(free, state, active)
        return wave_core(free, assignment, state, dense_idx, feasible, scores)

    def sparse_wave(free, assignment, state):
        active = (assignment == -1) & pod_mask
        # first S active pods in queue order (stable argsort: inactive
        # rows sink with key P)
        idx = jnp.argsort(jnp.where(active, dense_idx, P))[:S]
        feasible, scores = sub_batch_fn(free, state, idx, active[idx])
        return wave_core(free, assignment, state, idx, feasible, scores)

    assignment0 = jnp.full(P, -1, jnp.int32)
    occ0 = jnp.zeros(max_waves, jnp.int32)

    if sub_batch_fn is None:
        def cond(loop_state):
            _, assignment, _, wave_idx, progressed, _ = loop_state
            # stop on wave budget, on a no-progress wave, or — cheaper —
            # when nothing is left to place (otherwise a fully-placed
            # batch pays one whole extra wave to discover quiescence)
            return (
                (wave_idx < max_waves)
                & progressed
                & ((assignment == -1) & pod_mask).any()
            )

        def body(loop_state):
            free, assignment, state, wave_idx, _, occ = loop_state
            free, assignment, state, n = dense_wave(free, assignment, state)
            return (
                free, assignment, state, wave_idx + 1, n > 0,
                occ.at[wave_idx].set(n.astype(jnp.int32)),
            )

        if initial_batch is not None:
            feasible0, scores0 = initial_batch
            free_w, assignment_w, state_w, n0 = wave_core(
                free0, assignment0, state0, dense_idx, feasible0, scores0
            )
            init = (
                free_w, assignment_w, state_w, jnp.int32(1), n0 > 0,
                occ0.at[0].set(n0.astype(jnp.int32)),
            )
        else:
            init = (free0, assignment0, state0, jnp.int32(0),
                    jnp.bool_(True), occ0)
        free, assignment, state, waves, _, occ = jax.lax.while_loop(
            cond, body, init
        )
        if collect_stats:
            return assignment, free, state, {"occupancy": occ, "waves": waves}
        return assignment, free, state

    # sparse mode machine: 0 = sparse straggler wave, 1 = dense retry,
    # 2 = stop. A stalled sparse wave does NOT end the loop — a head
    # cohort of >straggler_cap infeasible pods would otherwise starve
    # placeable pods behind it — it escalates to one dense wave over ALL
    # active pods; only a stalled dense wave proves quiescence. A
    # productive wave of either kind returns to sparse.
    MODE_SPARSE, MODE_DENSE, MODE_STOP = jnp.int32(0), jnp.int32(1), jnp.int32(2)

    def cond(loop_state):
        _, assignment, _, wave_idx, mode, _ = loop_state
        return (
            (wave_idx < max_waves)
            & (mode < MODE_STOP)
            & ((assignment == -1) & pod_mask).any()
        )

    def body(loop_state):
        free, assignment, state, wave_idx, mode, occ = loop_state
        free, assignment, state, n = jax.lax.cond(
            mode == MODE_SPARSE,
            lambda args: sparse_wave(*args),
            lambda args: dense_wave(*args),
            (free, assignment, state),
        )
        new_mode = jnp.where(
            n > 0,
            MODE_SPARSE,
            jnp.where(mode == MODE_SPARSE, MODE_DENSE, MODE_STOP),
        )
        return (
            free, assignment, state, wave_idx + 1, new_mode,
            occ.at[wave_idx].set(n.astype(jnp.int32)),
        )

    # wave 0 is always dense (initial_batch is required with sub_batch_fn)
    feasible0, scores0 = initial_batch
    free_w, assignment_w, state_w, n0 = wave_core(
        free0, assignment0, state0, dense_idx, feasible0, scores0
    )
    # a stalled dense wave 0 already proves quiescence
    init = (
        free_w, assignment_w, state_w, jnp.int32(1),
        jnp.where(n0 > 0, MODE_SPARSE, MODE_STOP),
        occ0.at[0].set(n0.astype(jnp.int32)),
    )
    free, assignment, state, waves, _, occ = jax.lax.while_loop(
        cond, body, init
    )
    if collect_stats:
        return assignment, free, state, {"occupancy": occ, "waves": waves}
    return assignment, free, state


@partial(jax.jit,
         static_argnames=("max_waves", "rescue_window", "lite_window",
                          "collect_stats"))
def waterfill_assign_targeted(raw_scores, req, pod_mask, free0,
                              max_waves: int = 8,
                              rescue_window: int = 512,
                              lite_window: int = 1024,
                              collect_stats: bool = False):
    """Waterfill for STATIC per-node scores (the allocatable flagship and the
    north-star scale): per wave, each active pod checks fit against a
    handful of target nodes — the capacity-bucket choice plus next-fit
    probes — in O(W*R) gathers, never materializing the (P, N)
    feasibility/score matrix the generic waterfill recomputes every wave.
    At 100k pods x 10k nodes that matrix is ~4B int64 compares per wave.

    Caller contract: `raw_scores` must already be the desired node ranking —
    the caller's normalization must be MONOTONE in the raw score and its
    weight positive (true of minmax_normalize and the single-scoring-plugin
    fast-path gate in parallel.solver), because this path orders by the raw
    vector and never runs normalize().

    Wave structure (every retry wave runs on a bounded straggler WINDOW —
    the first W still-active pods in queue order via a rank-compaction
    scatter — so late waves sort/scan W elements, not P; at north-star
    scale the
    per-wave queue-order admission sort over the full 8k-pod chunk was the
    dominant fixed cost of the ~7-wave tail):

    1. one whole-queue lite wave: cumulative-demand bucket targets + next-
       fit probes, O(P·R);
    2. sparse lite waves (`lite_window` pods each) to quiescence;
    3. sparse rescue waves (`rescue_window` pods each): a dense (K, N)
       feasibility row per window pod, feasible ones spread round-robin
       over their own feasible sets, and window pods with NO feasible node
       are retired as hopeless (sound within one solve — free capacity
       only shrinks here, so infeasible-now is infeasible-later), so junk
       pods cannot starve the window for feasible stragglers behind them.

    Correctness: scores are static, so the node ranking never changes.
    Queue-order per-node admission is the same exact sorted-segment prefix
    check the generic waterfill runs — exact on a window because only
    window pods choose in that wave and window order IS queue order.
    Completeness matches `waterfill_assign` UP TO THE WAVE BUDGET: phases
    2 and 3 each run at most `max_waves` waves (2*max_waves + 1 total),
    draining at least their window per productive wave. Hard constraints
    (fit, node queue-order admission) hold identically in all cases.

    Mirrors the reference's scoring semantics for allocatable
    (/root/reference/pkg/noderesources/resource_allocation.go:49-76) at
    wave granularity."""
    P, R = req.shape
    N = free0.shape[0]
    demand = pod_fit_demand(req)
    order_n = jnp.argsort(-raw_scores, stable=True)  # static node ranking

    #: next-fit probe depth per lite wave: a pod whose bucket node cannot
    #: fit it individually (fragmentation — cumulative coverage is
    #: necessary, not sufficient) probes the next few score-ordered nodes
    #: in the SAME O(W*R) wave instead of stalling into the dense rescue
    #: phase.
    LITE_PROBES = 4

    def window_of(free, assignment, hopeless, W):
        """First W still-active pods in queue order — the shared
        `_straggler_window` rank-compaction scatter (one copy with the
        sharded waterfill, so the window rule cannot drift)."""
        return _straggler_window(demand, pod_mask, assignment, hopeless, W)

    def lite_choice(free, idx, valid, dem_w):
        # cumulative-demand waterfill over the window (the shared
        # `_cumulative_demand_positions` bucketing; dem_w rows are already
        # masked to valid window pods)
        pos = _cumulative_demand_positions(dem_w, free, order_n)
        choice = jnp.full(idx.shape[0], -1, jnp.int32)
        for probe in range(LITE_PROBES):
            cand = order_n[jnp.minimum(pos + probe, N - 1)].astype(jnp.int32)
            fit = jnp.all(dem_w <= free[cand], axis=1)
            choice = jnp.where((choice < 0) & valid & fit, cand, choice)
        # lite misses prove nothing about true feasibility: no hopeless delta
        return choice, jnp.zeros(idx.shape[0], bool)

    def rescue_choice(free, idx, valid, dem_w):
        # dense rescue wave: straggler k takes the (k mod |feasible_k|)-th
        # best node of ITS OWN feasible set in score order. Plain argmax
        # stampedes one tied-score node (admission then drains a node's
        # worth per wave — O(stragglers/node-capacity) waves at the
        # fragmented end-game); round-robin over each pod's feasible set
        # drains the residue in O(1) dense waves. Rank 0 still gets its
        # argmax, so the common one-straggler case keeps reference scoring.
        W = idx.shape[0]
        feasible = jnp.all(
            dem_w[:, None, :] <= free[None, :, :], axis=2
        ) & valid[:, None]
        feas_sorted = feasible[:, order_n]  # score-desc node order
        counts = jnp.cumsum(feas_sorted.astype(jnp.int32), axis=1)
        total = counts[:, -1]
        k = jnp.where(total > 0, jnp.arange(W) % jnp.maximum(total, 1), 0)
        pos = jax.vmap(
            lambda c, kk: jnp.searchsorted(c, kk, side="right")
        )(counts, k)  # first score-ordered index with counts > k
        choice = jnp.where(
            valid & (total > 0),
            order_n[jnp.minimum(pos, N - 1)].astype(jnp.int32),
            -1,
        )
        # window pods with NO feasible node retire as hopeless so they stop
        # occupying the window (free only shrinks within a solve, so the
        # verdict cannot go stale)
        return choice, valid & (total == 0)

    def wave(free, assignment, hopeless, W, choice_fn):
        # O(W·R + W log W): admission runs on the (W,) window choice vector
        # via sorted segments (`_queue_order_admission_choice`) — exact,
        # because only window pods choose and window order is queue order —
        # and commits via scatter-add; never the (P, N) onehot/winners
        # matrices (at north-star scale ~84M-element temporaries per wave)
        idx, valid, dem_w = window_of(free, assignment, hopeless, W)
        choice_w, hopeless_w = choice_fn(free, idx, valid, dem_w)
        admitted = (choice_w >= 0) & _queue_order_admission_choice(
            choice_w, dem_w, free
        )
        # scatter-ADD commits (not set-with-drop): adds of zero from the
        # clamped fill rows are harmless under duplication AND partition
        # cleanly when the pod axis is sharded (the SPMD partitioner
        # mishandles windowed set-scatters)
        safe_idx = jnp.minimum(idx, P - 1)
        placed_plus = jnp.zeros(P, jnp.int32).at[safe_idx].add(
            jnp.where(admitted, choice_w + 1, 0)
        )
        assignment = jnp.where(placed_plus > 0, placed_plus - 1, assignment)
        hop_add = jnp.zeros(P, jnp.int32).at[safe_idx].add(
            hopeless_w.astype(jnp.int32)
        )
        hopeless = hopeless | (hop_add > 0)
        used = jnp.zeros_like(free).at[jnp.where(admitted, choice_w, N - 1)].add(
            jnp.where(admitted[:, None], dem_w, 0)
        )
        return (
            free - used,
            assignment,
            hopeless,
            admitted.sum(),
            hopeless_w.sum(),
        )

    # `occ` records ADMITTED pods per executed wave (whole-queue wave in
    # slot 0, then lite/rescue waves in execution order); retirements count
    # as progress but not occupancy.
    def run(free, assignment, hopeless, W, choice_fn, occ, base, budget):
        def cond(ls):
            free, assignment, hopeless, wave_idx, progressed, _ = ls
            return (
                (wave_idx < budget)
                & progressed
                & ((assignment == -1) & pod_mask & ~hopeless).any()
            )

        def body(ls):
            free, assignment, hopeless, wave_idx, _, occ = ls
            free, assignment, hopeless, adm, retired = wave(
                free, assignment, hopeless, W, choice_fn
            )
            return (
                free, assignment, hopeless, wave_idx + 1,
                (adm + retired) > 0,
                occ.at[base + wave_idx].set(adm.astype(jnp.int32)),
            )

        return jax.lax.while_loop(
            cond, body,
            (free, assignment, hopeless, jnp.int32(0), jnp.bool_(True), occ),
        )

    assignment0 = jnp.full(P, -1, jnp.int32)
    hopeless0 = jnp.zeros(P, bool)
    occ0 = jnp.zeros(2 * max_waves + 1, jnp.int32)
    Wl = min(P, lite_window)
    K = min(P, rescue_window)
    # phase 1: one whole-queue lite wave
    free, assignment, hopeless, adm0, _ = wave(
        free0, assignment0, hopeless0, P, lite_choice
    )
    occ = occ0.at[0].set(adm0.astype(jnp.int32))
    # phase 2: sparse lite waves over straggler windows
    free, assignment, hopeless, w_lite, _, occ = run(
        free, assignment, hopeless, Wl, lite_choice, occ, jnp.int32(1),
        max_waves,
    )
    # phase 3: sparse rescue waves
    free, assignment, _, w_full, _, occ = run(
        free, assignment, hopeless, K, rescue_choice, occ, 1 + w_lite,
        max_waves,
    )
    if collect_stats:
        return assignment, free, {
            "occupancy": occ, "waves": 1 + w_lite + w_full
        }
    return assignment, free


@partial(jax.jit, static_argnames=("batch_fn", "max_waves"))
def wave_assign(batch_fn, req, pod_mask, free0, max_waves: int = 8):
    """Wave-parallel placement.

    batch_fn: (free (N,R), active (P,) bool) -> (feasible (P,N), scores (P,N)).
    Per wave every still-unassigned pod picks its argmax node; within a wave,
    pods that chose the same node are admitted in queue order while the node's
    capacity lasts (an exclusive running sum per node), the rest retry next
    wave.
    """
    P, R = req.shape
    demand = pod_fit_demand(req)

    def wave(carry, _):
        free, assignment = carry
        active = (assignment == -1) & pod_mask
        feasible, scores = batch_fn(free, active)
        feasible &= active[:, None]
        masked = jnp.where(feasible, scores, jnp.int64(-(2**62)))
        choice = jnp.where(
            feasible.any(axis=1), jnp.argmax(masked, axis=1).astype(jnp.int32), -1
        )
        # queue-order admission: pod p wins iff node still fits after all
        # earlier winners of the same wave on the same node (sorted-segment
        # exact prefix sums)
        onehot = (choice[:, None] == jnp.arange(free.shape[0])[None, :]) & (
            choice[:, None] >= 0
        )  # (P, N)
        admitted = (choice >= 0) & _queue_order_admission(onehot, demand, free)
        new_assignment = jnp.where(admitted, choice, assignment)
        winners = onehot & admitted[:, None]  # (P, N)
        # per-resource masked sums (int64 matmul is unsupported on TPU)
        used = jnp.stack(
            [(winners * demand[:, r][:, None]).sum(axis=0) for r in range(R)],
            axis=-1,
        )  # (N, R)
        return (free - used, new_assignment), admitted.sum()

    (free, assignment), _ = jax.lax.scan(
        wave, (free0, jnp.full(P, -1, jnp.int32)), None, length=max_waves
    )
    return assignment, free


# ---------------------------------------------------------------------------
# Sharded targeted waterfill (shard_map body): node axis sharded, per-wave
# winner election via ring collectives
# ---------------------------------------------------------------------------


def waterfill_targeted_sharded(rank_free, node_ids, req, pod_mask,
                               axis_name: str, n_shards: int,
                               n_real: int,
                               max_waves: int = 8,
                               rescue_window: int = 512,
                               lite_window: int = 1024,
                               collect_stats: bool = False,
                               use_pallas: bool = False,
                               pallas_interpret: bool = True):
    """Shard-local body of `waterfill_assign_targeted` — runs INSIDE a
    `shard_map` with the NODE axis sharded over `axis_name` (S = `n_shards`
    shards). The node axis arrives in GLOBAL SCORE-RANK ORDER (the caller
    permutes once per solve via `parallel.solver.rank_order_inputs`), so
    shard s owns the contiguous rank block [s*BS, (s+1)*BS) and the static
    ranking `order_n` of the single-device path becomes the identity: all
    wave math happens in rank space, and the winning shard maps its rank
    back to the original node index through its `node_ids` rows.

    Per-wave cross-shard traffic is O(shards) collectives of O(window)
    payload — never a gather of the node axis:

    - cumulative-free bases for the demand buckets: per-shard block totals
      combined with an (S-1)-step `ring_exclusive_scan` (`lax.ppermute`);
    - winner election: each shard proposes its local champion RANK (or N =
      "no candidate") and `lax.pmin` elects the global minimum — the
      min-rank key reproduces the single-device searchsorted/first-fit
      choice exactly, because rank order IS score order with the
      lowest-index tie-break baked in by the stable pre-sort;
    - admission/committal: the queue-order sorted-segment prefix check runs
      replicated on the (W,) window, each shard verifies the pods that
      chose ITS nodes against its local free rows, and one `lax.psum`
      ORs the per-shard verdicts; commits then scatter ONLY into the
      owning shard's resident `rank_free` block.

    Padded rank rows (node_ids -1, zero capacity) can never win an
    election: every valid pod's fit demand carries a pods-slot of 1, so a
    zero-capacity row fails both the lite fit probes and the rescue
    feasibility row (tests/test_shard_wave.py gates the edge).

    Placements are BIT-IDENTICAL to `waterfill_assign_targeted` at any
    shard count while every cumulative-capacity float64 sum stays exact
    (< 2^53 — all test/gate shapes; beyond it, block-decomposed summation
    can round bucket POSITIONS differently than the single-device cumsum:
    a targeting heuristic only — the per-node admission sums stay exact at
    any scale, so hard constraints never depend on the bound). The
    degenerate 1-shard program emits no ring steps and is bit-identical by
    construction.

    Arguments (per shard): `rank_free` (BS, R) local block of score-rank-
    ordered free capacity (the resident carry — returned updated),
    `node_ids` (BS,) original rank-row node index (-1 = padding),
    `req` (P, R) and `pod_mask` (P,) replicated. `n_real` is the PRE-
    PADDING rank count (the single-device path's N): probe clamps must
    saturate at the worst REAL node, exactly as the unsharded
    `jnp.minimum(pos + probe, N - 1)` does — clamping into the padding
    tail would silently drop overflow pods the single-device path still
    probes against rank N-1. Returns (assignment (P,) original node
    indices, replicated; rank_free (BS, R); stats dict when
    `collect_stats`).

    Under `use_pallas` (the `SPT_PALLAS=1` opt-in, ISSUE 13) every
    cross-shard exchange runs as a `parallel.kernels` Pallas ring program
    instead of a framework collective: `ring_offsets_*` replaces
    `block_exclusive_offsets`, `elect_min` the bucket-position `pmin`, and
    `fused_election` folds the min-rank champion reduction AND the
    admission-verdict resolution into ONE kernel — the winning shard
    attaches its node id and pre-wave free row to the election payload, so
    the queue-order admission check runs REPLICATED on every shard
    (`_admission_replicated`) and the packed verdict `psum` disappears. A
    rescue wave then costs 2 fused collective programs and a lite wave 3,
    versus the 3/3 framework collectives of the lax formulation.
    Placements are bit-identical either way (same elections, same f64
    admission sums — the kernels move exact-integer limbs); call sites
    whose padded payload would exceed the kernel VMEM envelope
    (`kernels.PALLAS_MAX_ELECTION_ELEMS` — the mega whole-queue wave)
    statically keep the lax collectives, and `stats["pallas_sites"]` says
    which did. `pallas_interpret` selects the CPU interpret twins (the
    CI/differential path) versus the compiled on-chip kernels.
    """
    P, R = req.shape
    BS = rank_free.shape[0]
    N = BS * n_shards  # padded global rank count ("no candidate" sentinel)
    demand = pod_fit_demand(req)
    shard = jax.lax.axis_index(axis_name)
    block_start = shard * BS

    LITE_PROBES = 4

    pk = None
    if use_pallas and n_shards > 1:
        from scheduler_plugins_tpu.parallel import kernels as pk  # noqa: N813

    #: payload rows of one fused election: winner node id + the winner's
    #: free-capacity row as exact base-2^18 limbs
    PAYLOAD_ROWS = 1 + (pk.N_LIMBS * R if pk is not None else 0)

    def pallas_wave(W: int) -> bool:
        """Static per-call-site gate: this window's elections ride the
        Pallas kernels only when every buffer fits the VMEM envelope —
        otherwise the wave keeps the lax collectives (bit-identical)."""
        return (
            pk is not None
            and pk.fits_election_budget(1 + PAYLOAD_ROWS, W)
            and pk.fits_election_budget(R, W)
        )

    def elect_min_rank(ranks):
        """`lax.pmin` of candidate ranks, as int32: ranks are bounded by
        the padded node count, and the TPU compiler lowers a 64-bit
        all-reduce only for sums ("Supported lowering only of Sum all
        reduce" on an s64 minimum)."""
        return jax.lax.pmin(ranks.astype(jnp.int32), axis_name)

    def winner_payload(prop_rank, free_l):
        """(1 + 3R, W) int32 payload for the shard's own proposal
        `prop_rank` (global rank in MY block, or >= N): node id + 1 and
        my pre-wave free row for that rank as limbs; zeros when not
        proposing (the sentinel key ties everywhere with zero payload)."""
        local = prop_rank - block_start
        has = (local >= 0) & (local < BS) & (prop_rank < N)
        safe = jnp.clip(local, 0, BS - 1)
        nid = jnp.where(has, node_ids[safe].astype(jnp.int32) + 1, 0)
        row = jnp.where(has[:, None], free_l[safe], 0)  # (W, R) int64
        limb_rows = pk.split_limbs(row).transpose(0, 2, 1).reshape(
            pk.N_LIMBS * R, -1
        )
        return jnp.concatenate([nid[None, :], limb_rows], axis=0)

    def unpack_payload(rows):
        """(nid (W,) int32, win_row (W, R) float64) from the elected
        payload — the winner's free row recombines exactly (limb sums are
        selected, not summed, so each limb is still < 2^18)."""
        nid = rows[0]
        limbs = rows[1:].reshape(pk.N_LIMBS, R, -1).transpose(0, 2, 1)
        return nid, pk.join_limbs(limbs)

    def lite_choice(free_l, idx, valid, dem_w):
        """Cumulative-demand bucket targets + next-fit probes, elected
        across shards: per-resource global bucket position = pmin over the
        shards' local searchsorted candidates (exact — the global cumfree
        is nondecreasing, so the first covering index lives in exactly one
        shard), then the first fitting probe = min fitting rank."""
        W = idx.shape[0]
        cumfree_l = jnp.cumsum(
            jnp.clip(free_l, 0, None).astype(jnp.float64), axis=0
        )  # (BS, R) local inclusive
        if pallas_wave(W):
            base, _ = pk.ring_offsets_f64(
                cumfree_l[-1], axis_name, n_shards,
                interpret=pallas_interpret,
            )
        else:
            base, _ = block_exclusive_offsets(
                cumfree_l[-1], axis_name, n_shards
            )  # (R,)
        abs_cf = cumfree_l + base[None, :]
        cumdem = jnp.cumsum(dem_w.astype(jnp.float64), axis=0)  # (W, R)
        loc = jax.vmap(
            lambda cf, cd: jnp.searchsorted(cf, cd, side="left"),
            in_axes=(1, 1), out_axes=1,
        )(abs_cf, cumdem)  # (W, R) local positions
        cand = jnp.where(loc < BS, block_start + loc, N)
        if pallas_wave(W):
            pos = jnp.max(
                pk.elect_min(
                    cand.T.astype(jnp.int32), axis_name, n_shards,
                    interpret=pallas_interpret,
                ),
                axis=0,
            )  # (W,) global
        else:
            pos = jnp.max(elect_min_rank(cand), axis=1)  # (W,)
        ranks = jnp.minimum(
            pos[None, :] + jnp.arange(LITE_PROBES)[:, None], n_real - 1
        )  # (LP, W) — saturate at the worst REAL rank, never the padding
        local = ranks - block_start
        mine = (local >= 0) & (local < BS)
        row = free_l[jnp.clip(local, 0, BS - 1)]  # (LP, W, R)
        fit_l = mine & valid[None, :] & jnp.all(
            dem_w[None, :, :] <= row, axis=2
        )
        # first fitting probe == min fitting rank (ranks nondecreasing in
        # probe order; equal only when clamped to the same node): each
        # shard proposes its min fitting OWNED rank, pmin elects — a (W,)
        # champion reduction instead of a (LP, W) verdict exchange
        prop = jnp.min(jnp.where(fit_l, ranks, N), axis=0)  # (W,) mine
        if pallas_wave(W):
            fit_rank, pay = pk.fused_election(
                prop.astype(jnp.int32), winner_payload(prop, free_l),
                axis_name, n_shards, interpret=pallas_interpret,
            )
            choice = jnp.where(
                valid & (fit_rank < N), fit_rank.astype(jnp.int32), -1
            )
            return choice, jnp.zeros(W, bool), unpack_payload(pay)
        fit_rank = elect_min_rank(prop)  # (W,)
        choice = jnp.where(
            valid & (fit_rank < N), fit_rank.astype(jnp.int32), -1
        )
        # lite misses prove nothing about true feasibility: no hopeless delta
        return choice, jnp.zeros(idx.shape[0], bool), None

    def rescue_choice(free_l, idx, valid, dem_w):
        """Dense rescue wave, sharded: each shard counts its local feasible
        nodes per window pod; a ring scan turns the counts into global
        score-order offsets (rank blocks ARE score order), the shard whose
        range covers the pod's round-robin slot k proposes its k-local-th
        feasible rank, and pmin elects it (exactly one shard proposes)."""
        W = idx.shape[0]
        feasible_l = jnp.all(
            dem_w[:, None, :] <= free_l[None, :, :], axis=2
        ) & valid[:, None]  # (W, BS)
        counts_l = feasible_l.sum(axis=1, dtype=jnp.int32)  # (W,)
        if pallas_wave(W):
            base_l, total = pk.ring_offsets_i32(
                counts_l, axis_name, n_shards, interpret=pallas_interpret,
            )
        else:
            base_l, total = block_exclusive_offsets(
                counts_l, axis_name, n_shards
            )  # (W,) each — ONE collective serves both the round-robin
            # offsets and the global feasible totals
        k = jnp.where(total > 0, jnp.arange(W) % jnp.maximum(total, 1), 0)
        k_local = (k - base_l).astype(jnp.int32)
        c_l = jnp.cumsum(feasible_l.astype(jnp.int32), axis=1)  # (W, BS)
        locpos = jax.vmap(
            lambda c, kk: jnp.searchsorted(c, kk, side="right")
        )(c_l, k_local)  # first local idx with count > k_local
        mine = (k_local >= 0) & (k_local < counts_l)
        cand = jnp.where(
            mine & valid & (total > 0), block_start + locpos, N
        )
        if pallas_wave(W):
            # whenever total > 0 some shard proposes the k-th feasible
            # rank (k < total), so the elected rank is always a REAL
            # feasible node and the n_real clamp below is a no-op there —
            # the payload (proposer's node id + free row) stays consistent
            rank, pay = pk.fused_election(
                cand.astype(jnp.int32), winner_payload(cand, free_l),
                axis_name, n_shards, interpret=pallas_interpret,
            )
            choice = jnp.where(
                valid & (total > 0),
                jnp.minimum(rank, n_real - 1).astype(jnp.int32), -1,
            )
            return choice, valid & (total == 0), unpack_payload(pay)
        rank = elect_min_rank(cand)  # (W,)
        choice = jnp.where(
            valid & (total > 0),
            jnp.minimum(rank, n_real - 1).astype(jnp.int32), -1,
        )
        # window pods with NO feasible node anywhere retire as hopeless
        # (free only shrinks within a solve, so the verdict cannot go stale)
        return choice, valid & (total == 0), None

    def _admission_segments(choice, dem_w):
        """The ONE copy of the queue-order admission sort/segment math
        both formulations below share — lax-vs-pallas bit-identity rests
        on these staying byte-equivalent, so neither path may inline its
        own: (order, seg, within) where `order` is the stable
        choice-then-queue-position sort, `seg` the sorted chosen ranks
        (N for unchosen), and `within` the inclusive per-segment f64
        demand prefix."""
        W = choice.shape[0]
        seg_choice = jnp.where(choice >= 0, choice, N)
        order = jnp.argsort(
            seg_choice.astype(jnp.int64) * W + jnp.arange(W)
        )
        seg = seg_choice[order]
        first = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
        within = _segment_prefix(dem_w[order].astype(jnp.float64), first)
        return order, seg, within

    def queue_admission_local(choice, dem_w, free_l):
        """`_queue_order_admission_choice` with the free rows sharded: the
        sorted-segment prefix math is replicated (choice/demand are), each
        shard checks the pods whose chosen rank lies in its block against
        its local rows. Returns the LOCAL sorted-order verdicts + the sort
        permutation — the wave ORs the verdicts across shards in the same
        psum that elects the winner node ids (each chosen rank is owned by
        exactly one shard, so a sum is an OR)."""
        order, seg, within = _admission_segments(choice, dem_w)
        local = seg - block_start
        mine = (local >= 0) & (local < BS) & (seg < N)
        free_row = free_l[jnp.clip(local, 0, BS - 1)].astype(jnp.float64)
        ok_l = mine & jnp.all(within <= free_row, axis=1)
        return ok_l, order

    def _admission_replicated(choice, dem_w, win_row):
        """`queue_admission_local` + verdict psum collapsed to REPLICATED
        math (the pallas path): the winner's pre-wave free row arrived
        with the election payload, so every shard evaluates the same
        sorted-segment prefix check against the same f64 rows — identical
        verdicts to the owner-checks-then-psum formulation, zero
        collectives."""
        Wn = choice.shape[0]
        order, seg, within = _admission_segments(choice, dem_w)
        ok_sorted = (seg < N) & jnp.all(within <= win_row[order], axis=1)
        return (choice >= 0) & jnp.zeros(Wn, bool).at[order].set(ok_sorted)

    def wave(free_l, assignment, hopeless, W, choice_fn):
        idx, valid, dem_w = _straggler_window(
            demand, pod_mask, assignment, hopeless, W
        )
        choice, hopeless_w, payload = choice_fn(free_l, idx, valid, dem_w)
        Wn = choice.shape[0]
        local = choice - block_start
        own = (choice >= 0) & (local >= 0) & (local < BS)
        if payload is not None:
            # pallas path: the fused election already delivered the
            # winner's node id and free row — admission is replicated
            # math, no further collective this wave
            nid, win_row = payload
            admitted = _admission_replicated(choice, dem_w, win_row)
        else:
            ok_l, order = queue_admission_local(choice, dem_w, free_l)
            # rank -> original node id: the owning shard contributes id+1
            # for its owned CHOICES (independent of admission, so it packs
            # into the same collective; -1 padding rows can never be
            # chosen, so id+1 >= 1 on every elected winner)
            nid_l = jnp.where(
                own,
                node_ids[jnp.clip(local, 0, BS - 1)].astype(jnp.int32) + 1,
                0,
            )
            # ONE barrier elects admission verdicts (sorted order) AND
            # winner node ids (window order): psum is elementwise, the two
            # rows just ride together
            packed = jax.lax.psum(
                jnp.stack([ok_l.astype(jnp.int32), nid_l]), axis_name
            )
            admitted = (choice >= 0) & jnp.zeros(Wn, bool).at[order].set(
                packed[0] > 0
            )
            nid = packed[1]  # (W,) node_id + 1, replicated
        ownc = admitted & own
        safe_idx = jnp.minimum(idx, P - 1)
        placed_plus = jnp.zeros(P, jnp.int32).at[safe_idx].add(
            jnp.where(admitted, nid, 0)
        )
        assignment = jnp.where(placed_plus > 0, placed_plus - 1, assignment)
        hop_add = jnp.zeros(P, jnp.int32).at[safe_idx].add(
            hopeless_w.astype(jnp.int32)
        )
        hopeless = hopeless | (hop_add > 0)
        # commit scatters ONLY into the owning shard's resident block
        used_l = jnp.zeros_like(free_l).at[
            jnp.where(ownc, jnp.clip(local, 0, BS - 1), BS - 1)
        ].add(jnp.where(ownc[:, None], dem_w, 0))
        return (
            free_l - used_l, assignment, hopeless,
            admitted.sum(), hopeless_w.sum(),
        )

    def run(free_l, assignment, hopeless, W, choice_fn, occ, base, budget):
        """Wave loop to `budget` — the loop state is replicated except the
        local free block, so every shard takes identical trips."""
        def cond(ls):
            free_l, assignment, hopeless, wave_idx, progressed, _ = ls
            return (
                (wave_idx < budget)
                & progressed
                & ((assignment == -1) & pod_mask & ~hopeless).any()
            )

        def body(ls):
            free_l, assignment, hopeless, wave_idx, _, occ = ls
            free_l, assignment, hopeless, adm, retired = wave(
                free_l, assignment, hopeless, W, choice_fn
            )
            return (
                free_l, assignment, hopeless, wave_idx + 1,
                (adm + retired) > 0,
                occ.at[base + wave_idx].set(adm.astype(jnp.int32)),
            )

        return jax.lax.while_loop(
            cond, body,
            (free_l, assignment, hopeless, jnp.int32(0), jnp.bool_(True),
             occ),
        )

    assignment0 = jnp.full(P, -1, jnp.int32)
    hopeless0 = jnp.zeros(P, bool)
    occ0 = jnp.zeros(2 * max_waves + 1, jnp.int32)
    Wl = min(P, lite_window)
    K = min(P, rescue_window)
    # phase 1: one whole-queue lite wave
    free_l, assignment, hopeless, adm0, _ = wave(
        rank_free, assignment0, hopeless0, P, lite_choice
    )
    occ = occ0.at[0].set(adm0.astype(jnp.int32))
    # phase 2: sparse lite waves over straggler windows
    free_l, assignment, hopeless, w_lite, _, occ = run(
        free_l, assignment, hopeless, Wl, lite_choice, occ, jnp.int32(1),
        max_waves,
    )
    # phase 3: sparse rescue waves
    free_l, assignment, _, w_full, _, occ = run(
        free_l, assignment, hopeless, K, rescue_choice, occ, 1 + w_lite,
        max_waves,
    )
    if collect_stats:
        return assignment, free_l, {
            "occupancy": occ, "waves": 1 + w_lite + w_full,
            # which election sites (whole-queue lite, windowed lite,
            # rescue) ride the Pallas ring kernels: a site that statically
            # gave way to the lax collectives reads False here
            "pallas_sites": jnp.array(
                [pallas_wave(P), pallas_wave(Wl), pallas_wave(K)]
            ),
        }
    return assignment, free_l
