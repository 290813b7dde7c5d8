"""Wave-batched gang solve: independent gangs solved together, bit-exact.

PR 10's `gang_solve_body` is a sequential `lax.scan` over gangs — at
Tesserae scale (arxiv 2508.04953: placement policy work must scale with
the cluster) a G-length scan of per-gang block scoring is the bottleneck
the cluster-life bench named (ROADMAP item 3). This module batches gangs
into **waves**: one jit solves a whole wave of gangs in parallel against
the wave-start state (`gangs.topology.place_gang_one`, the SAME per-gang
body the sequential scan runs), then a host validator walks the wave in
queue order, committing every lane whose speculative result is provably
identical to the sequential solve and resolving the conflicted lanes
in place with the shared per-gang host body (`place_gang_np` — the
numpy twin's own step). A wave therefore costs exactly ONE device
dispatch however the workload serializes; G gangs always take
ceil(G/W) dispatches.

Why the accepted prefix is bit-exact (docs/GANGS.md "conflict
detection") — gang g's solve against the sequential state S_{i-1}
equals its wave-start solve against S0 because commits only DECREASE
free and only INCREASE quota usage, which makes the first-fit scan
monotone. Two host-side checks per gang, against the commits accepted
earlier in the wave:

1. **Primary-block invariance** — block spill order depends only on the
   primary block (the cost matrix is static). Resident-anchored gangs
   pick their primary from `prev_assigned` (free-independent); for the
   rest the validator recomputes packed-rank capacity under the
   accepted block-level free deltas (`packed_rank_capacity_np` — the
   solve's own scoring, shared with the numpy twin) and requires the
   argmax to be unchanged, which pins the whole node order.
2. **Choice replay** — with the node order pinned, replay g's tentative
   (PRE-revert) choices against the current host state: every node
   ordered before a chosen node was infeasible at S0 under the gang's
   own in-scan depletion, and free(S_{i-1}) <= free(S0) pointwise, so
   it STAYS infeasible — the sequential scan can only pick the same
   node or fail. The replay therefore just re-checks, rank by rank in
   scan order, that the chosen node still fits the rank's demand and
   the quota row still clears (committing both into the simulation as
   it goes). A rank that found NO node at S0 finds none under smaller
   free either, so dead-prefix semantics replay for free. Quorum-failed
   gangs revalidate the same way — their no-op revert is only
   guaranteed equal if the whole scan replays.

The first gang of every wave validates trivially (no commits yet, so
its wave-start state IS its sequential state). A conflicted lane is
re-solved host-side against the committed state — bit-exact by
construction (it IS the twin's step) — and validation continues, so
the worst case degrades to the numpy sequential twin plus G/W device
dispatches, while the common case (steady-state reconcile: gangs
anchored across blocks, contention localized) validates most lanes and
turns G sequential scan steps into G/W parallel dispatches.

`wave_gang_solve` is gated bit-identical to `gang_solve_np` (and hence
to the sequential jit scan) by tests/test_differential.py. The wave
axis optionally shards over a ("gangs",) device mesh via shard_map —
free/eq/problem tensors replicate, gang lanes shard, zero collectives.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from scheduler_plugins_tpu.gangs import topology as T
from scheduler_plugins_tpu.utils.intmath import bucket_size

I64 = np.int64
I32 = np.int32

#: mesh axis name for the wave (gang-lane) dimension — NOT the node axis
#: (GL009 guards "nodes"; the wave solve never gathers over nodes at all)
GANGS_AXIS = "gangs"

#: default wave width: lanes solved per parallel dispatch. Bounds the
#: worst-case wasted work (every consecutive gang conflicting costs one
#: W-lane dispatch per accepted gang) while keeping the dispatch big
#: enough to amortize — the mega bench's acceptance runs are ~W long.
DEFAULT_WAVE = 64


def wave_solve_body(gangs: T.RankGangState, free, eq_used, node_mask, ids):
    """One wave: solve `ids` (W,) gangs independently against the SAME
    (`free`, `eq_used`) wave-start state — a vmap of the sequential
    scan's own per-gang body (`topology.place_gang_one`). Returns
    per-lane (choices (W, M), admitted (W,), q_new (W,), primary (W,),
    has_res (W,)); the post-placement free/eq of each lane stay internal
    (the host validator recommits accepted lanes exactly)."""
    import jax

    def lane(g):
        (choices, admitted, q_new, _free_l, _eq_l, _resident, primary,
         has_res) = T.place_gang_one(gangs, g, free, eq_used, node_mask)
        return choices, admitted, q_new, primary, has_res

    return jax.vmap(lane)(ids)


#: (shape-key, sharded) -> jitted wave program; equal shapes share one
#: compile like every other padded program in this repo
_WAVE_PROGRAMS: dict = {}


def wave_solve_fn(mesh=None):
    """The jitted wave program — one constructor shared by the solve
    loop, the bench, and the AOT/jaxpr certification gates
    (tools/tpu_lower.py `wave_gang_solve`). With a ("gangs",) `mesh` the
    wave axis shards over the devices via shard_map (problem tensors and
    the free/eq state replicate; the per-lane solve needs no
    collectives), so a wave of W gangs runs W/S per device."""
    import jax

    from scheduler_plugins_tpu.utils import observability as obs

    key = None if mesh is None else tuple(mesh.devices.flat)
    if key in _WAVE_PROGRAMS:
        return _WAVE_PROGRAMS[key]
    if mesh is None:
        fn = jax.jit(wave_solve_body)
    else:
        from jax.sharding import PartitionSpec as P

        lanes = P(GANGS_AXIS)
        rep = P()

        def sharded(gangs, free, eq_used, node_mask, ids):
            body = jax.shard_map(
                wave_solve_body,
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: rep, gangs), rep, rep, rep,
                    lanes,
                ),
                out_specs=(lanes, lanes, lanes, lanes, lanes),
                check_vma=False,
            )
            return body(gangs, free, eq_used, node_mask, ids)

        fn = jax.jit(sharded)
    _WAVE_PROGRAMS[key] = obs.compile_watch(fn, program="wave_gang_solve")
    return _WAVE_PROGRAMS[key]


def _primary_invariant(gangs, g, block_free, packed_dev_primary):
    """True when gang g's primary-block choice is unchanged under the
    accepted commits' block deltas: recompute packed-rank capacity with
    the solve's own scoring (`packed_rank_capacity_np`) and compare the
    argmax to the device solve's wave-start primary."""
    dem = np.where(
        (gangs.rank_mask[g] & (gangs.prev_assigned[g] < 0))[:, None],
        gangs.rank_req[g], 0,
    )
    cumdem = np.cumsum(dem.astype(np.float64), axis=0)
    packed = T.packed_rank_capacity_np(cumdem, block_free)
    return int(np.argmax(packed)) == int(packed_dev_primary)


def wave_gang_solve(gangs: T.RankGangState, free0, eq_used0, node_mask,
                    wave: int = DEFAULT_WAVE, mesh=None,
                    stats: Optional[dict] = None):
    """Wave-batched gang solve, bit-identical to `gang_solve_np` /
    `gang_solve_body` (see module doc for the proof sketch). Returns
    (rank_nodes (G, M) int32, admitted (G,) bool, placed_new (G,) int32,
    free (N, R) int64, eq_used (Q, R) int64) — the numpy twin's exact
    output contract. `stats`, when given, collects {"waves", "accepted"}
    (dispatch count and per-wave acceptance runs)."""
    import jax.numpy as jnp

    rank_req = np.asarray(gangs.rank_req)
    rank_mask = np.asarray(gangs.rank_mask)
    prev = np.asarray(gangs.prev_assigned)
    gang_ns = np.asarray(gangs.gang_ns)
    gang_mask = np.asarray(gangs.gang_mask)
    node_block = np.asarray(gangs.node_block)
    quota_has = np.asarray(gangs.quota_has)
    node_mask_np = np.asarray(node_mask)

    G, M, R = rank_req.shape
    B = np.asarray(gangs.block_cost).shape[0]
    blocked = (node_block >= 0) & node_mask_np
    blk = np.maximum(node_block, 0)

    free = np.asarray(free0).astype(I64).copy()
    eq_used = np.asarray(eq_used0).astype(I64).copy()
    rank_nodes = prev.astype(I32).copy()
    admitted = np.zeros(G, bool)
    placed_new = np.zeros(G, I32)

    # queue order over the REAL gangs; pad slots (mask False) never solve
    # in the sequential scan either — their rows stay resident-only
    order = [g for g in range(G) if gang_mask[g]]
    for g in range(G):
        if not gang_mask[g]:
            rank_nodes[g] = np.where(rank_mask[g] & (prev[g] >= 0),
                                     prev[g], -1)

    fn = wave_solve_fn(mesh)
    W = wave
    if mesh is not None:
        n_dev = int(np.prod(mesh.devices.shape))
        W = max(W, n_dev)
        W = ((W + n_dev - 1) // n_dev) * n_dev
    # problem tensors staged to device ONCE — every wave re-reads them,
    # and re-staging (G, M, R) rank tensors per dispatch would double the
    # per-wave cost (measured; docs/SCALING.md)
    import jax

    gangs_dev = jax.tree.map(jnp.asarray, gangs)
    mask_dev = jnp.asarray(node_mask_np)
    quota_max = np.asarray(gangs.quota_max)

    i = 0
    n_waves = 0
    accepts: list[int] = []
    host_solves = 0
    while i < len(order):
        batch = order[i:i + W]
        ids = np.zeros(W, I32)  # pad lanes re-solve gang batch[0]: cheap,
        ids[:len(batch)] = batch  # ignored by the host acceptance loop
        ids[len(batch):] = batch[0]
        choices, adm, q_new, primary, has_res = (
            np.asarray(x) for x in fn(
                gangs_dev, jnp.asarray(free), jnp.asarray(eq_used),
                mask_dev, jnp.asarray(ids),
            )
        )
        n_waves += 1

        # wave-start block free totals (the scoring input), maintained
        # under accepted commits for the primary-invariance check
        freec = np.where(node_mask_np[:, None], np.clip(free, 0, None), 0)
        block_free = np.zeros((B, R), I64)
        np.add.at(block_free, blk[blocked], freec[blocked])

        accepted = 0
        dirty = False  # any committed placement since the wave dispatched
        for j, g in enumerate(batch):
            tentative = [
                (m, int(choices[j, m])) for m in range(M)
                if choices[j, m] >= 0
            ]
            ns = int(gang_ns[g])
            has_quota = ns >= 0 and bool(quota_has[ns])
            valid = True
            if dirty:  # the first lane of a wave validates trivially
                # 1. primary-block invariance pins the node order
                if not bool(has_res[j]) and not _primary_invariant(
                    gangs, g, block_free, primary[j]
                ):
                    valid = False
                else:
                    # 2. choice replay: each tentatively chosen node must
                    # still fit its rank's demand under the committed
                    # state (+ this gang's own earlier ranks), and the
                    # quota row must still clear — monotonicity covers
                    # everything else (see module doc)
                    sim_free: dict[int, np.ndarray] = {}
                    sim_eq = eq_used[ns].copy() if has_quota else None
                    for m, n in tentative:
                        d = rank_req[g, m]
                        fvec = sim_free.get(n)
                        if fvec is None:
                            fvec = free[n].copy()
                        if not (fvec >= d).all() or (
                            has_quota
                            and not (sim_eq + d <= quota_max[ns]).all()
                        ):
                            valid = False
                            break
                        sim_free[n] = fvec - d
                        if has_quota:
                            sim_eq = sim_eq + d
            if not valid:
                # conflicted lane: the wave-start speculation is stale —
                # resolve THIS gang exactly with the shared per-gang host
                # body (the numpy twin's own step) against the committed
                # state, and keep consuming the wave. No re-dispatch: a
                # wave costs exactly one device solve regardless of how
                # the workload serializes.
                host_solves += 1
                c_np, ok, qn, free_l, eq_l, resident = T.place_gang_np(
                    gangs, g, free, eq_used, node_mask_np
                )
                admitted[g] = ok
                placed_new[g] = qn if ok else 0
                row = np.where(resident, prev[g], c_np if ok else -1)
                rank_nodes[g] = row.astype(I32)
                if ok:
                    placed = [
                        (m, int(c_np[m])) for m in range(M) if c_np[m] >= 0
                    ]
                    free = free_l
                    eq_used = eq_l
                    for m, n in placed:
                        if blocked[n]:
                            block_free[blk[n]] -= rank_req[g, m]
                    if placed:
                        dirty = True
                continue
            # validated lane: commit the device solve — EXACTLY the
            # sequential semantics (revert on quorum failure — zero
            # partial ranks). A reverted gang committed NOTHING, so later
            # lanes only ever validate against genuinely committed state.
            ok = bool(adm[j])
            admitted[g] = ok
            placed_new[g] = int(q_new[j]) if ok else 0
            resident = rank_mask[g] & (prev[g] >= 0)
            row = np.where(
                resident, prev[g],
                choices[j].astype(I32) if ok else I32(-1),
            )
            rank_nodes[g] = row
            if ok:
                for m, n in tentative:
                    d = rank_req[g, m]
                    free[n] -= d
                    if blocked[n]:
                        block_free[blk[n]] -= d
                    if has_quota:
                        eq_used[ns] += d
                if tentative:
                    dirty = True
            accepted += 1
        accepts.append(accepted)
        i += len(batch)

    if stats is not None:
        stats["waves"] = n_waves
        stats["accepted"] = accepts
        stats["host_solves"] = host_solves
    return rank_nodes, admitted, placed_new, free, eq_used
