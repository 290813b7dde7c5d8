"""Packing optimizer: iterative consolidation rounds over a wave placement.

The third solve mode ("Priority Matters", arxiv 2511.08373; ROADMAP
item 1): both existing solve paths — the bit-faithful sequential scan and
the wave/waterfill throughput path — are ONE-PASS greedy over queue
order, which leaves cluster utilization on the table: residual free
capacity ends up as dust spread over many partially-filled nodes
(`tuning.quality.fragmentation`), and lightly-loaded nodes stay pinned by
a handful of pods a better assignment would consolidate elsewhere.

`packing_refine` climbs that frontier: a jittable `lax.while_loop` of
reassignment rounds over the SAME int64 reference-unit quantities. Per
round:

1. **Donor election** — the emptiest still-occupied schedulable node (by
   float64 fill fraction over cpu+memory) that still holds batch pods and
   was not frozen by a failed earlier round.
2. **Bids** — each batch pod on the donor bids for every other occupied
   node: ``bid(n) = score_frac(n) + price_weight * fill(n)``, where
   `score_frac` is the profile's static node ranking min-max-normalized
   to [0, 1] (the same raw vector the targeted waterfill ranks by) and
   `fill(n)` is the node's cpu/mem fill fraction — a FRAGMENTATION PRICE
   on each node's remaining free vector: emptier targets are expensive,
   so pods prefer to densify already-full nodes (auction-style bidding
   with a static per-round price vector). A decaying temperature
   (`temperature * decay^round`) sets the minimum fill EDGE a target must
   have over the donor — early rounds take only clearly-packing moves,
   later rounds accept marginal ones.
3. **Commit** — the movers' choices run through the EXISTING sorted-
   segment queue-order admission (`ops.assign._queue_order_admission_
   choice`): a move is admitted only if the target still fits the mover's
   demand after every earlier same-round mover of that target, so
   resource fit holds BY CONSTRUCTION at every intermediate state.
   Admitted movers scatter their demand off the donor and onto the
   target; the donor is frozen when a round moves nothing.

Moves never change WHICH pods are placed — only where — so namespace
quota usage and gang quorum counts are untouched by refinement, and the
caller's `finalize_assignment` tail (queue-order quota prefix + Permit
quorum) enforces those families exactly as the wave path does. The
`tuning.gates` numpy replay oracles certify the packing solves in
tests/test_packing.py.

Why this strictly improves the packing objectives: an emptied donor
removes its (large) free vector from the packed numerator of
`packed_utilization` — since the donor was the emptiest occupied node,
its free fraction exceeds the occupied average, so dropping it raises
packed utilization strictly; its freed capacity also consolidates into
one whole-node block, growing the largest free block `fragmentation`
measures. Targets are restricted to OCCUPIED nodes, so refinement never
spreads load onto empty nodes.

Knobs (iteration budget, price weight, temperature schedule) ride a
traced float64 aux vector (`pack_aux`), NOT closure constants — one
compile serves every budget/weight the tuner sweeps (CLAUDE.md
aux-channel discipline; the budget bounds a `lax.while_loop`, so budget 0
returns the wave placement bit-identically).

`packing_refine_np` is the bit-exact numpy twin (identical op order,
identical float64 arithmetic, lowest-index tie-breaks) — the differential
gate in tests/test_packing.py holds the two together the way
`gangs.topology.gang_solve_np` gates the gang solve.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from scheduler_plugins_tpu.ops import CPU_I, MEMORY_I, PODS_I
from scheduler_plugins_tpu.ops.assign import _queue_order_admission_choice
from scheduler_plugins_tpu.ops.fit import pod_fit_demand

#: pack_aux slots: [iterations, price_weight, temperature, decay] — one
#: traced float64 vector (see `pack_aux_vector`), so knob changes never
#: recompile. Kept as a module constant so the config surface
#: (`framework.runtime.PackingConfig`) and the solvers agree on the layout.
PACK_AUX_SLOTS = ("iterations", "price_weight", "temperature", "decay")


def pack_aux_vector(iterations, price_weight, temperature, decay):
    """The (4,) float64 traced knob vector `packing_refine` consumes."""
    return jnp.asarray(
        [float(iterations), float(price_weight), float(temperature),
         float(decay)],
        jnp.float64,
    )


def _fill_fraction(free, alloc, node_mask):
    """(N,) float64 cpu/mem fill fraction (used / allocatable, averaged
    over the two core resources); -1.0 on masked rows so they can never
    be elected donor nor priced as a target."""
    allocf = alloc[:, (CPU_I, MEMORY_I)].astype(jnp.float64)
    freef = free[:, (CPU_I, MEMORY_I)].astype(jnp.float64)
    util = jnp.where(
        allocf > 0, (allocf - freef) / jnp.maximum(allocf, 1.0), 0.0
    )
    fill = (util[:, 0] + util[:, 1]) / 2.0
    return jnp.where(node_mask, fill, -1.0)


def _score_fraction(raw_scores, node_mask):
    """(N,) float64 min-max normalization of the static node ranking to
    [0, 1] over schedulable nodes — the score term of the bid (raw int64
    scores have arbitrary scale; the price term needs a comparable
    unit)."""
    raw = raw_scores.astype(jnp.float64)
    lo = jnp.min(jnp.where(node_mask, raw, jnp.inf))
    hi = jnp.max(jnp.where(node_mask, raw, -jnp.inf))
    span = jnp.maximum(hi - lo, 1.0)
    frac = jnp.where(node_mask, (raw - lo) / span, 0.0)
    return frac


def packing_refine(raw_scores, req, pod_mask, alloc, node_mask, free0,
                   assignment0, pack_aux, mover_cap: int = 128):
    """Refine a wave placement by consolidation rounds (module docstring).

    Arguments: `raw_scores` (N,) int64 static node ranking (the targeted
    waterfill's caller contract), `req` (P, R) int64 requests, `pod_mask`
    (P,) admitted batch rows, `alloc` (N, R) allocatable, `node_mask`
    (N,) schedulable, `free0` (N, R) free AFTER the wave placement
    (consistent with `assignment0`), `assignment0` (P,) int32 the wave
    placements, `pack_aux` the (4,) traced knob vector
    (`pack_aux_vector`). `mover_cap` (static) bounds the per-round mover
    window — a donor holding more batch pods drains over several rounds.

    Returns (assignment, free, stats) with stats = {"rounds", "moves",
    "emptied"} (int32 scalars). Budget 0 returns the inputs unchanged —
    bit-identical to the wave path by construction. Not jitted itself
    (runs inside the caller's jit, like `waterfill_assign_stateful`).
    """
    P, R = req.shape
    N = free0.shape[0]
    W = min(mover_cap, P)
    demand = pod_fit_demand(req)
    n_iters = pack_aux[0]
    price_weight = pack_aux[1]
    temperature = pack_aux[2]
    decay = pack_aux[3]
    score_frac = _score_fraction(raw_scores, node_mask)
    # alloc pods-slot minus free pods-slot counts resident pods (the
    # requested base the solve free was derived from charges 1 per bound
    # pod, and every batch placement charges 1 more)
    alloc_pods = alloc[:, PODS_I]

    def occupied_of(free):
        return node_mask & (alloc_pods - free[:, PODS_I] > 0)

    def batch_count_of(assignment):
        placed = (assignment >= 0) & pod_mask
        return jnp.zeros(N + 1, jnp.int32).at[
            jnp.where(placed, assignment, N)
        ].add(1)[:N]

    def round_body(carry):
        free, assignment, frozen, it, theta, moves, done = carry
        fill = _fill_fraction(free, alloc, node_mask)
        occupied = occupied_of(free)
        eligible = occupied & ~frozen & (batch_count_of(assignment) > 0)
        any_donor = eligible.any()
        # donor = emptiest eligible node (lowest fill; ties -> lowest
        # index via argmin)
        d = jnp.argmin(jnp.where(eligible, fill, jnp.inf)).astype(jnp.int32)
        fill_d = fill[d]

        # mover window: first W batch pods on the donor, queue order
        # (rank-compaction scatter — the _straggler_window shape)
        on_donor = (assignment == d) & pod_mask & any_donor
        rank = jnp.cumsum(on_donor) - 1
        slot = jnp.where(on_donor & (rank < W), rank, W).astype(jnp.int32)
        idx = jnp.full(W + 1, P, jnp.int32).at[slot].min(
            jnp.arange(P, dtype=jnp.int32)
        )[:W]
        valid = idx < P
        dem_w = jnp.where(valid[:, None], demand[jnp.minimum(idx, P - 1)], 0)

        # bids: score + fragmentation price, over occupied fitting
        # targets with the decaying fill-edge guard (theta is carried and
        # decayed multiplicatively — a pow() here could round differently
        # between the XLA and numpy builds)
        target_ok = (
            occupied
            & (jnp.arange(N) != d)
            & (fill >= fill_d + theta)
        )
        fit = jnp.all(
            dem_w[:, None, :] <= free[None, :, :], axis=2
        )  # (W, N)
        cand = fit & target_ok[None, :] & valid[:, None]
        bid = score_frac + price_weight * fill  # (N,) static per round
        masked_bid = jnp.where(cand, bid[None, :], -jnp.inf)
        best = jnp.argmax(masked_bid, axis=1).astype(jnp.int32)
        choice = jnp.where(cand.any(axis=1), best, -1)

        # queue-order sorted-segment admission against the round-start
        # free rows (movers' own demand still sits on the donor, which is
        # never a target, so target headroom is exact)
        admitted = (choice >= 0) & _queue_order_admission_choice(
            choice, dem_w, free
        )

        safe_idx = jnp.minimum(idx, P - 1)
        placed_plus = jnp.zeros(P, jnp.int32).at[safe_idx].add(
            jnp.where(admitted, choice + 1, 0)
        )
        assignment = jnp.where(placed_plus > 0, placed_plus - 1, assignment)
        moved_dem = jnp.where(admitted[:, None], dem_w, 0)
        used_t = jnp.zeros_like(free).at[
            jnp.where(admitted, choice, N - 1)
        ].add(moved_dem)
        free = free - used_t
        free = free.at[d].add(moved_dem.sum(axis=0))
        n_moved = admitted.sum().astype(jnp.int32)
        frozen = frozen.at[d].set(
            jnp.where(any_donor, n_moved == 0, frozen[d])
        )
        return (
            free, assignment, frozen, it + 1, theta * decay,
            moves + n_moved, ~any_donor,
        )

    def cond(carry):
        _, _, _, it, _, _, done = carry
        # floor the traced budget: the numpy twin's `int(n_iters)` floors,
        # so a fractional budget (a continuous tuner proposal) must run
        # the SAME round count on both builds — `it < 1.5` would run one
        # round more here than there and break the bit-parity anchor
        return (it.astype(jnp.float64) < jnp.floor(n_iters)) & ~done

    occupied0 = occupied_of(free0)
    init = (
        free0, assignment0, jnp.zeros(N, bool), jnp.int32(0),
        temperature, jnp.int32(0), jnp.bool_(False),
    )
    free, assignment, _, rounds, _, moves, _ = jax.lax.while_loop(
        cond, round_body, init
    )
    emptied = (occupied0 & ~occupied_of(free)).sum().astype(jnp.int32)
    stats = {"rounds": rounds, "moves": moves, "emptied": emptied}
    return assignment, free, stats


# ---------------------------------------------------------------------------
# numpy twin (bit-exact: identical op order, float64 arithmetic, ties)
# ---------------------------------------------------------------------------


def _queue_order_admission_choice_np(choice, demand, free):
    """Numpy twin of `ops.assign._queue_order_admission_choice` — the
    sorted-segment queue-order admission check, identical float64 prefix
    arithmetic (cumsum minus own value, cummax rebase)."""
    P = choice.shape[0]
    N = free.shape[0]
    seg_choice = np.where(choice >= 0, choice, N)
    order = np.argsort(seg_choice.astype(np.int64) * P + np.arange(P))
    seg = seg_choice[order]
    first = np.concatenate([[True], seg[1:] != seg[:-1]])
    dem_sorted = demand[order].astype(np.float64)
    csum = np.cumsum(dem_sorted, axis=0)
    exclusive = csum - dem_sorted
    base = np.maximum.accumulate(
        np.where(first[:, None], exclusive, -1.0), axis=0
    )
    within = csum - base
    free_row = free[np.minimum(seg, N - 1)].astype(np.float64)
    ok_sorted = np.all(within <= free_row, axis=1) & (seg < N)
    out = np.zeros(P, bool)
    out[order] = ok_sorted
    return out


def packing_refine_np(raw_scores, req, pod_mask, alloc, node_mask, free0,
                      assignment0, pack_aux, mover_cap: int = 128):
    """Bit-exact numpy sequential twin of `packing_refine` (same rounds,
    same elections, same commits) — the differential anchor and the
    degraded-mode/host certification path."""
    raw_scores = np.asarray(raw_scores)
    req = np.asarray(req)
    pod_mask = np.asarray(pod_mask).astype(bool)
    alloc = np.asarray(alloc)
    node_mask = np.asarray(node_mask).astype(bool)
    free = np.asarray(free0).copy()
    assignment = np.asarray(assignment0).copy()
    pack_aux = np.asarray(pack_aux, np.float64)
    P, R = req.shape
    N = free.shape[0]
    W = min(mover_cap, P)
    demand = req.copy()
    demand[:, PODS_I] = 1
    n_iters, price_weight, temperature, decay = (
        float(pack_aux[0]), float(pack_aux[1]), float(pack_aux[2]),
        float(pack_aux[3]),
    )

    def fill_fraction(free):
        allocf = alloc[:, (CPU_I, MEMORY_I)].astype(np.float64)
        freef = free[:, (CPU_I, MEMORY_I)].astype(np.float64)
        util = np.where(
            allocf > 0, (allocf - freef) / np.maximum(allocf, 1.0), 0.0
        )
        fill = (util[:, 0] + util[:, 1]) / 2.0
        return np.where(node_mask, fill, -1.0)

    raw = raw_scores.astype(np.float64)
    lo = np.min(np.where(node_mask, raw, np.inf))
    hi = np.max(np.where(node_mask, raw, -np.inf))
    span = max(hi - lo, 1.0)
    score_frac = np.where(node_mask, (raw - lo) / span, 0.0)
    alloc_pods = alloc[:, PODS_I]

    def occupied_of(free):
        return node_mask & (alloc_pods - free[:, PODS_I] > 0)

    occupied0 = occupied_of(free)
    frozen = np.zeros(N, bool)
    moves = 0
    rounds = 0
    theta = temperature
    while rounds < int(n_iters):
        fill = fill_fraction(free)
        occupied = occupied_of(free)
        placed = (assignment >= 0) & pod_mask
        batch_count = np.zeros(N + 1, np.int32)
        np.add.at(batch_count, np.where(placed, assignment, N), 1)
        eligible = occupied & ~frozen & (batch_count[:N] > 0)
        if not eligible.any():
            rounds += 1
            break
        d = int(np.argmin(np.where(eligible, fill, np.inf)))
        fill_d = fill[d]
        on_donor = np.nonzero((assignment == d) & pod_mask)[0][:W]
        dem_w = demand[on_donor]
        target_ok = (
            occupied & (np.arange(N) != d) & (fill >= fill_d + theta)
        )
        fit = np.all(dem_w[:, None, :] <= free[None, :, :], axis=2)
        cand = fit & target_ok[None, :]
        bid = score_frac + price_weight * fill
        masked_bid = np.where(cand, bid[None, :], -np.inf)
        best = np.argmax(masked_bid, axis=1).astype(np.int32)
        choice = np.where(cand.any(axis=1), best, -1)
        admitted = (choice >= 0) & _queue_order_admission_choice_np(
            choice, dem_w, free
        )
        for j, p in enumerate(on_donor):
            if admitted[j]:
                assignment[p] = choice[j]
                free[choice[j]] -= demand[p]
                free[d] += demand[p]
                moves += 1
        if not admitted.any():
            frozen[d] = True
        theta *= decay
        rounds += 1
    emptied = int((occupied0 & ~occupied_of(free)).sum())
    return assignment, free, {
        "rounds": rounds, "moves": moves, "emptied": emptied,
    }


__all__ = [
    "PACK_AUX_SLOTS",
    "pack_aux_vector",
    "packing_refine",
    "packing_refine_np",
]
