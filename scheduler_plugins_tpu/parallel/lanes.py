"""Optimistic-concurrency K-lane solve: speculate in parallel, commit
through one conflict fence.

The reference runs as a *second scheduler* beside kube-scheduler against
shared cluster state (SURVEY.md §L0, deploy/k8s.yaml): multiple actors
solve optimistically and the apiserver's bind serializes them. This
module reproduces that concurrency model INSIDE one process, against one
resident snapshot:

1. **Partition** (`partition_segments`): the sorted pending queue
   groups into SEGMENTS by a deterministic key — the PodGroup full name
   for gang members (a gang never splits across lanes), else the
   namespace (default) or the pod's admission serial
   (`Cluster.admission_serial`) — and segments pack onto K lanes by
   deterministic LPT (balance bounds the longest lane's scan, and the
   fence makes lane membership semantically irrelevant). Each lane's
   pods keep their global queue positions, so every lane is an
   order-preserving subsequence of the serial order.
2. **Speculate** (`lane_solve_fn`): every lane runs the bit-faithful
   sequential step (`framework.runtime._solve_step`) over ITS pods
   against the same cycle-initial state — one jit, vmapped over the lane
   axis (`dispatch="fused"`), K dispatches of the shared (1, L) program
   on named worker threads (`dispatch="threads"`), or the same K
   dispatches one-at-a-time with exact per-lane wall attribution
   (`dispatch="sequential"`).
3. **Fence** (`lane_screen_fn` + `_fence_refine`): pods commit in the
   DEFINED SERIAL ORDER (= global queue order, the exact order
   `run_cycle`'s scan commits). A compiled monotone screen (one jitted
   dispatch over the device-resident columns) first proves most pods
   order-independent wholesale; the (usually empty) remainder is
   re-checked exactly, in order, on host int64 twins of the device
   math. The first pod whose step would genuinely diverge triggers ONE
   whole-suffix re-solve against the committed state through the same
   program — so the result is bit-identical to the serial scan at
   every K, by construction.

Why the fence is exact (docs/SCALING.md has the long form, extending
docs/GANGS.md's monotone argument): under the fence-exact gate
(`fence_exact`) no profile Filter is live and no Score reads the carried
state, so pod p's step is a pure function of (admit verdicts, built-in
fit mask) — the step SIGNATURE. Equal signatures under the
lane-speculative and the committed state ⇒ identical feasible set ⇒
identical normalization, argmax choice, fail code and commits. Commits
move the carries MONOTONICALLY — `free` only shrinks, `eq_used` /
`gang_inflight` only grow (the GANGS.md direction) — and both states
pod p compares lie between the cycle-initial and the all-lanes-final
carries, differing only through OTHER lanes' commits. So a signature
component that agrees at those two precomputable extremes — restricted
to nodes/tables other lanes actually touched — is constant across the
whole interval (`lane_screen_fn`, ONE compiled dispatch, no per-pod
host work); only screen-flagged pods pay the exact per-pod twins
(`_fence_refine`). Disjoint-tenant lanes therefore validate wholesale
with an empty refine set; contended traffic degrades to the exact walk
plus one repair solve — never worse than serial by more than the
fence.
"""

from __future__ import annotations

import hashlib
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from scheduler_plugins_tpu.framework.plugin import Plugin, SolverState
from scheduler_plugins_tpu.framework.runtime import _solve_step
from scheduler_plugins_tpu.ops.fit import pod_fit_demand
from scheduler_plugins_tpu.tuning.gates import pod_fit_demand_np
from scheduler_plugins_tpu.utils import observability as obs

#: lane partition modes: gang members ALWAYS key on their PodGroup full
#: name (quorum accounting is per-gang state — splitting a gang across
#: lanes would let two lanes each count a partial quorum); non-members
#: key on the namespace (tenant traffic is naturally disjoint) or on the
#: admission serial (uniform spray, for single-tenant rosters)
PARTITION_MODES = ("namespace", "hash")

#: lane solver dispatch: "fused" = ONE jit, vmapped over the lane axis;
#: "threads" = K dispatches of the shared (1, L) program on named worker
#: threads ("spt-lane-w*", docs/race_audit.json) — same outputs, real
#: thread-level overlap when the backend releases the GIL AND the host
#: has cores to overlap onto; "sequential" = the same K dispatches on
#: the caller thread, one after another, each wall-timed into
#: `LaneStats.lane_ms` — the per-lane critical-path attribution mode
#: (on this repo's 1-core CI host threads cannot overlap, so sequential
#: is also the jitter-free way to measure what K independent scheduler
#: processes would each pay; see docs/SCALING.md)
DISPATCH_MODES = ("fused", "threads", "sequential")


def lane_key(pod, cluster, mode: str = "namespace") -> str:
    """The deterministic partition key for one pending pod."""
    pg = cluster.pod_group_of(pod) if cluster is not None else None
    if pg is not None:
        return "gang:" + pg.full_name
    if mode == "namespace":
        return "ns:" + pod.namespace
    serial = cluster.admission_serial(pod.uid) if cluster is not None else -1
    return "serial:%d" % serial


def lane_of(key: str, k: int) -> int:
    """Stable key -> lane hash (blake2b, not `hash()`: PYTHONHASHSEED
    must never affect it). `partition_segments` packs segments by
    balanced LPT rather than this modulo — the hash remains the
    run-independent spray an external sharder (e.g. a per-scheduler
    watch filter) would use, and tests key on its stability."""
    digest = hashlib.blake2b(key.encode(), digest_size=8).digest()
    return int.from_bytes(digest, "big") % k


def partition_segments(pending, cluster, k: int, mode: str = "namespace",
                       key_cache: dict | None = None):
    """(lanes, seg_of_pod, lane_of_seg, seg_keys) — the K lane index
    lists (each ascending: lanes are order-preserving subsequences of
    the serial order) plus the partition-KEY segmentation beneath them:
    pods with the same key (namespace / gang / serial) share a segment,
    every segment lives wholly inside one lane. The screen's fit
    certificate runs at segment grain — a lane is only as coarse as the
    tenants packed onto it, so certifying per segment keeps the
    certificate sharp when K is small (segment ids are first-seen
    ordered, deterministic for a given queue order).

    Segments pack onto lanes by deterministic LPT (longest first, ties
    by first-seen order; each to the least-loaded lane, ties to the
    lowest index) instead of key-hash modulo: the fence makes lane
    membership semantically irrelevant — bit-identity holds under ANY
    key-disjoint split — so the partition is free to chase balance. The
    critical path is the LONGEST lane's scan; a hash split leaves it
    ~30% over P/K at small K (measured: 1,070 of 3,600 pods on one of
    4 lanes), which a half-octave bucket then rounds UP again.

    `key_cache` (optional, caller-owned uid -> key dict) memoizes the
    per-pod key across cycles — pods persist until placed, so the
    steady-state cost is one dict hit per pod instead of a blake2b +
    group lookup (measured 6.1 ms -> sub-ms at P=3,600). A pod carrying
    a pod-group label whose PodGroup object is not registered YET is
    never cached: its key must flip to `gang:` the moment the group
    appears (a stale `ns:` key could split the gang across lanes).
    `fresh` lists the positions that MISSED the cache — for the caller
    these are exactly the pods not yet folded into any cross-cycle
    per-key aggregate keyed off this cache (all positions when no cache
    rides along)."""
    if mode not in PARTITION_MODES:
        raise ValueError(
            f"unknown lane partition mode {mode!r}; expected one of "
            f"{PARTITION_MODES}"
        )
    n = len(pending)
    seg_ids: dict = {}
    seg_list: list = []
    seg_keys: list = []
    fresh: list = []
    # the per-pod pass is THE serial prologue of the laned path — keep
    # it to one dict hit and one list append per pod (bulk-convert to
    # numpy after; per-element ndarray stores measured ~3x slower)
    cache_get = key_cache.get if key_cache is not None else None
    seg_get = seg_ids.get
    append = seg_list.append
    for i, pod in enumerate(pending):
        key = cache_get(pod.uid) if cache_get is not None else None
        if key is None:
            key = lane_key(pod, cluster, mode)
            if key_cache is not None and (
                key.startswith("gang:") or not pod.pod_group()
            ):
                key_cache[pod.uid] = key
            fresh.append(i)
        s = seg_get(key)
        if s is None:
            s = seg_ids[key] = len(seg_keys)
            seg_keys.append(key)
        append(s)
    S = len(seg_keys)
    seg_of_pod = (
        np.asarray(seg_list, np.int32) if n else np.zeros(0, np.int32)
    )
    lane_of_seg = np.zeros(max(1, S), np.int32)
    if k > 1 and S:
        sizes = np.bincount(seg_of_pod, minlength=S)
        load = [0] * k
        for s in np.argsort(-sizes, kind="stable"):
            j = min(range(k), key=load.__getitem__)
            lane_of_seg[s] = j
            load[j] += int(sizes[s])
    lane_of_pod = lane_of_seg[seg_of_pod]
    lanes = [np.flatnonzero(lane_of_pod == j).tolist() for j in range(k)]
    return lanes, seg_of_pod, lane_of_seg, seg_keys, fresh


def partition_lanes(pending, cluster, k: int, mode: str = "namespace"):
    """K lists of global queue positions (each ascending — lanes are
    order-preserving subsequences of the serial order)."""
    return partition_segments(pending, cluster, k, mode)[0]


def fence_exact(scheduler, snap):
    """(ok, reason) — whether the conflict fence's host validation is
    EXACT for this profile + snapshot. Outside the gate the laned path
    falls back to the sequential parity solve (counted by
    `scheduler_lane_serial_fallbacks_total`), never to a weaker fence:

    - side tables that arm profile Filters or state-dependent commits
      (scheduling / network / NUMA) break the "step is a pure function
      of (admit, fit)" argument;
    - preemption nominees make the built-in fit read nominee holds
      keyed on `placed_mask` — cross-lane state the fence's per-lane
      free mirror does not carry;
    - an admit plugin without a host twin here cannot be validated.
    """
    if snap.scheduling is not None:
        return False, "scheduling"
    if snap.network is not None:
        return False, "network"
    if snap.numa is not None:
        return False, "numa"
    if snap.nominees is not None:
        return False, "nominees"
    if snap.quota is not None:
        # the nominee axis is padded to M >= 1; only LIVE rows (nonzero
        # request or a set contribution mask) couple the quota admit to
        # the cross-lane placed_mask carry
        q = snap.quota
        if (
            np.asarray(q.nom_req).any()
            or np.asarray(q.nom_in_eq_mask).any()
            or np.asarray(q.nom_total_mask).any()
        ):
            return False, "quota_nominees"
    from scheduler_plugins_tpu.plugins import CapacityScheduling, Coscheduling

    for p in scheduler.profile.plugins:
        if type(p).admit is Plugin.admit:
            continue
        if not isinstance(p, (Coscheduling, CapacityScheduling)):
            return False, f"admit:{p.name}"
    return True, None


# ---------------------------------------------------------------------------
# The lane solver program
# ---------------------------------------------------------------------------


def lane_solve_fn(scheduler):
    """The speculative lane solve: vmap over the lane axis of a scan of
    THE parity step body (`_solve_step` — one copy, shared with
    `Scheduler.solve`, so a lane cannot drift from the serial scan).

    The throughput trick is pod-table RESIDENCY: each lane's pod rows
    are gathered ONCE, outside the scan (`pods_table[idx]`, one
    vectorized gather per column), and ride the scan `xs` — every step
    hands the body a one-pod snapshot view (`p = 0`, a static row
    select that compiles away). The step body therefore runs ZERO
    batched gathers: on CPU those lower to per-row scalar loops that
    made the per-step cost grow ~linearly with K (measured ~0.7 µs/K
    per step), capping fused lanes below 2x regardless of K; on TPU
    they are vmem-hostile dynamic slices (the CLAUDE.md gotcha).
    Padded slots fold `live` into the row's `mask`, so the step's own
    PreFilter gate makes them no-op carries emitting the "masked pod"
    outputs (-1 / False / 0) the serial scan produces for padded rows.

    Exactness note: the one-pod view relies on the fence-exact gate —
    every live table a plugin indexes by a POD axis lives in
    `snap.pods` (gathered here) or is pinned off (`snap.numa`'s
    presolve carries a pod axis; `fence_exact` rejects armed numa /
    scheduling / network / nominee tables). `SolverState.placed_mask`
    is written at the view-local index but never read under the gate
    (quota nominee rows are inert), and the serial-order fence ignores
    it.

    Signature: fn(snap, state0, auxes, idx, live) with idx/live shaped
    (K, L); returns ((K, L) int32 choice, (K, L) bool admitted,
    (K, L) int32 fail_code). The same program repairs conflicts at
    (1, L') — seeded with the committed state instead of state0."""
    plugins = tuple(scheduler.profile.plugins)
    unroll = scheduler._scan_unroll()

    def fn(snap, state0, auxes, idx, live):
        for plugin, aux in zip(plugins, auxes):
            plugin.bind_aux(aux)
        for plugin in plugins:
            plugin.bind_presolve(plugin.prepare_solve(snap))
        rows = jax.tree.map(lambda a: a[idx], snap.pods)
        rows = rows.replace(mask=rows.mask & live)

        def lane(lane_rows):
            def body(carry, r):
                step_snap = snap.replace(
                    pods=jax.tree.map(lambda a: a[None], r)
                )
                return _solve_step(plugins, carry, 0, step_snap)

            _, outs = jax.lax.scan(
                body, state0, lane_rows, unroll=unroll
            )
            return outs

        return jax.vmap(lane)(rows)

    return fn


def _cached_lane_fn(scheduler):
    """The jitted lane program, cached on the scheduler like every other
    solve-family program. The weight tuple rides the key (the lane scan
    BAKES `plugin.weight` trace constants, like explain/packing), so a
    live-weight swap retraces instead of serving stale scores — and
    `set_live_weights`' eviction sweep can find the entry."""
    key = ("lane_solve", scheduler._scan_unroll()) + scheduler.weights_key() \
        + tuple(p.static_key() for p in scheduler.profile.plugins)
    cache = scheduler._solve_cache
    if key not in cache:
        cache[key] = obs.compile_watch(
            jax.jit(lane_solve_fn(scheduler)), program="lane_solve"
        )
    return cache[key]


#: smallest lane scan bucket: sub-8 lane lengths all share one compiled
#: (K, 8) shape — masked padded steps cost microseconds, a fresh XLA
#: compile costs most of a second (and the tier-1 suite runs at the
#: budget cliff)
MIN_LANE_BUCKET = 8


def _pow2(n: int) -> int:
    return max(MIN_LANE_BUCKET, 1 << max(0, int(n - 1)).bit_length())


def _bucket(n: int) -> int:
    """Half-octave scan bucket: the next size in {8, 12, 16, 24, 32,
    48, ...} >= n. Pure power-of-two buckets waste up to 2x scan steps
    right above a boundary (a 1,070-pod lane would scan 2,048 padded
    steps); the intermediate 3·2^(m-2) sizes cap the waste at ~33% for
    at most 2x the compile-cache entries."""
    p = _pow2(n)
    h = (p * 3) // 4
    return h if n <= h and h >= MIN_LANE_BUCKET else p


# ---------------------------------------------------------------------------
# The conflict fence: host twins of the admit/commit math
# ---------------------------------------------------------------------------


def _lane_deficits(req, free0, assignment, lane_of_pod, k: int):
    """Shared screen prelude: per-lane speculative node deficits and the
    two state extremes. Sums ride float64 (exact below 2^53, the
    repo-wide dodge — int64 scatter-adds are the TPU gotcha); compares
    stay exact because every quantity is an integer-valued float64."""
    demand = pod_fit_demand(req)
    placed = assignment >= 0
    choice = jnp.maximum(assignment, 0)
    free0f = free0.astype(jnp.float64)
    N = free0f.shape[0]
    demf = demand.astype(jnp.float64)
    w = demf * placed[:, None]
    flat = lane_of_pod * N + choice
    lanedef = jax.ops.segment_sum(w, flat, num_segments=k * N)
    lanedef = lanedef.reshape(k, N, demand.shape[1])
    alldef = lanedef.sum(axis=0)
    othersdef = alldef[None] - lanedef
    free_fin = free0f - alldef
    return demf, placed, free0f, free_fin, othersdef, alldef


def lane_screen_fn(k: int, quota_on: bool, gang_on: bool):
    """The compiled fence stage 1 — the vectorized monotone screen as ONE
    jitted program over the device-resident snapshot columns, so the
    wholesale-commit fast path costs a single dispatch instead of a dozen
    device->host pulls plus O(P·N·R) numpy (measured 1.6 ms vs ~0.3 ms at
    P=1024, N=48 — the numpy screen alone out-weighed the K-lane solve it
    was validating).

    The math is the exact program `_fence_refine`'s docstring argument
    needs: per-lane speculative deficits -> the two state extremes
    (cycle-initial, all-lanes-final) -> a pod is flagged iff some
    signature component (fit row, quota admit, gang min-res admit)
    DISAGREES between the extremes restricted to nodes/tables OTHER
    lanes touched.

    The built-in fit component runs at SEGMENT granularity here (one
    segment per partition key — `partition_segments`), not pod
    granularity: `fit_unsafe` certifies per (segment, node) that no
    segment pod's fit bit at node n can flip, via three sufficient
    conditions (each one pins fits_hi == fits_lo for EVERY pod of the
    segment):

    - no OTHER lane committed onto n — then the committed and
      speculative columns for n are identical (the segment's own lane's
      commits appear in both), so there is no interval to cross;
    - the segment's axiswise MAX demand bound fits `free_fin[n]` — then
      every segment pod still fits at the low extreme (fits_lo true,
      and lo ⊆ hi);
    - the segment's axiswise MIN demand bound exceeds `free0[n]` on
      some axis — then no segment pod ever fit at the high extreme
      (tenant traffic on dedicated node groups certifies through this
      arm: a foreign group's extended-resource column is 0).

    The (S, R) demand extremes ride in as INPUTS (`seg_mx` / `seg_mn`),
    host-accumulated by `LaneSolver` over every pod ever seen with the
    key — a conservative SUPERSET of the live pods (max only grows, min
    only shrinks), so both arms stay sufficient while the O(P·R)
    segment reductions drop out of the per-cycle dispatch (measured:
    segment_max + segment_min alone were ~0.6 ms of a 1.65 ms dispatch
    at P=4,096). Padded segment rows carry the -inf/+inf identities and
    are trivially safe.

    That is O(S·N·R) compares instead of O(P·N·R) — the per-pod fit
    screen (`lane_screen_fit_fn`) dispatches ONLY when some (segment,
    node) pair stays unsafe, so disjoint-tenant traffic never pays it
    (measured: the P=3,600 per-pod screen alone cost ~2.5 ms, ~40% of
    the whole serial solve it was meant to beat).

    Args are three flat tuples (`core`, `quota`, `gang` — the latter
    two empty when the branch is off) of exactly the columns the
    branches read, NOT the snapshot/state pytrees: flattening the full
    snapshot per dispatch cost ~0.4 ms of host overhead at P=4,096.

    Returns (fit_unsafe: scalar bool, flagged: (P,) bool quota|gang
    component); the host ORs in the per-pod fit screen when unsafe and
    keeps `np.flatnonzero(flagged[:P_live])` as the refine candidate
    set — a conservative SUPERSET of true conflicts, empty on
    disjoint-lane traffic."""

    def fn(core, quota, gang_args):
        (req, pod_mask, gated, free0, node_mask, assignment,
         lane_of_pod, seg_mx, seg_mn, lane_of_seg) = core
        ok0 = pod_mask & ~gated
        demf, placed, free0f, free_fin, othersdef, alldef = _lane_deficits(
            req, free0, assignment, lane_of_pod, k
        )
        f64 = jnp.float64

        # segment-level fit certificates (see docstring)
        touched = (othersdef > 0).any(axis=2)  # (K, N)
        max_fits = (seg_mx[:, None, :] <= free_fin[None]).all(axis=2)
        min_fails = (seg_mn[:, None, :] > free0f[None]).any(axis=2)
        fit_unsafe = (
            touched[lane_of_seg] & ~max_fits & ~min_fails
            & node_mask[None]
        ).any()

        flagged = jnp.zeros(assignment.shape[0], bool)
        if quota_on:
            ns, qm, q_min, q_max, eq_used0 = quota
            reqf = req.astype(f64)
            hasq = qm[ns]
            contrib = placed & hasq
            eq0 = eq_used0.astype(f64)
            eq_fin = eq0 + jax.ops.segment_sum(
                reqf * contrib[:, None], ns, num_segments=eq0.shape[0]
            )
            eq_min = q_min.astype(f64)
            eq_max = q_max.astype(f64)
            agg_min = (eq_min * qm[:, None]).sum(axis=0)
            agg_hi = (eq0 * qm[:, None]).sum(axis=0)
            agg_lo = (eq_fin * qm[:, None]).sum(axis=0)
            pass_hi = (
                ~(eq0[ns] + reqf > eq_max[ns]).any(axis=1)
                & ~(agg_hi[None] + reqf > agg_min[None]).any(axis=1)
            )
            pass_lo = (
                ~(eq_fin[ns] + reqf > eq_max[ns]).any(axis=1)
                & ~(agg_lo[None] + reqf > agg_min[None]).any(axis=1)
            )
            lane_q = jax.ops.segment_sum(
                contrib.astype(f64), lane_of_pod, num_segments=k
            )
            others_q = lane_q.sum() - lane_q
            flagged |= (
                hasq & (others_q[lane_of_pod] > 0) & (pass_hi != pass_lo)
            )

        if gang_on:
            gang, g_slack, g_min_res, g_has_min_res, infl_used0 = gang_args
            g = jnp.maximum(gang, 0)
            total0 = free0f.sum(axis=0)
            total_fin = total0 - alldef.sum(axis=0)
            infl0 = infl_used0.astype(f64)
            ing = placed & (gang >= 0)
            infl_fin = infl0 + jax.ops.segment_sum(
                demf * ing[:, None], g, num_segments=infl0.shape[0]
            )
            lane_n = jax.ops.segment_sum(
                placed.astype(f64), lane_of_pod, num_segments=k
            )
            others_n = lane_n.sum() - lane_n
            slack = g_slack.astype(f64)
            min_res = g_min_res.astype(f64)
            cap_hi = total0[None] + slack[g] + infl0[g]
            cap_lo = total_fin[None] + slack[g] + infl_fin[g]
            pass_hi = (min_res[g] <= cap_hi).all(axis=1)
            pass_lo = (min_res[g] <= cap_lo).all(axis=1)
            flagged |= (
                (gang >= 0) & g_has_min_res[g]
                & (others_n[lane_of_pod] > 0) & (pass_hi != pass_lo)
            )

        # dead pods (masked / gated) decide (-1 / False / 0) under ANY
        # state — no flip can change their outputs or commits
        return fit_unsafe, flagged & ok0

    return fn


def lane_screen_fit_fn(k: int):
    """The per-pod fit screen — the O(P·N·R) refinement of the lane
    certificate, dispatched only when `lane_screen_fn` reports some
    (lane, node) pair fit-unsafe. A pod is flagged iff its fit bit flips
    between the extremes on a live node some OTHER lane committed onto —
    the exact per-pod form of the monotone-sandwich argument."""

    def fn(req, pod_mask, gated, free0, node_mask, assignment, lane_of_pod):
        ok0 = pod_mask & ~gated
        demf, _, free0f, free_fin, othersdef, _ = _lane_deficits(
            req, free0, assignment, lane_of_pod, k
        )
        fits_hi = (demf[:, None, :] <= free0f[None]).all(axis=2)
        fits_lo = (demf[:, None, :] <= free_fin[None]).all(axis=2)
        flipable = (othersdef > 0).any(axis=2)  # (K, N)
        flagged = (
            (fits_hi & ~fits_lo)
            & flipable[lane_of_pod] & node_mask[None]
        ).any(axis=1)
        return flagged & ok0

    return fn


def _cached_screen_fn(scheduler, k: int, quota_on: bool, gang_on: bool):
    """The jitted screen, cached beside the lane program. No weight
    dependence (the screen reads admit/fit inputs, never scores), so the
    key carries only the branch structure."""
    key = ("lane_screen", k, quota_on, gang_on)
    cache = scheduler._solve_cache
    if key not in cache:
        cache[key] = obs.compile_watch(
            jax.jit(lane_screen_fn(k, quota_on, gang_on)),
            program="lane_screen",
        )
    return cache[key]


def _cached_screen_fit_fn(scheduler, k: int):
    key = ("lane_screen_fit", k)
    cache = scheduler._solve_cache
    if key not in cache:
        cache[key] = obs.compile_watch(
            jax.jit(lane_screen_fit_fn(k)),
            program="lane_screen_fit",
        )
    return cache[key]


@dataclass
class _FenceState:
    """One actor's view of the in-cycle carried state, on host int64 —
    the committed truth, or one lane's speculative mirror. Mutations
    mirror `_solve_step`'s commits bit-exactly (trivially: int64 adds)."""

    free: np.ndarray  # (N, R)
    total_free: np.ndarray  # (R,) raw per-node sum, negatives included
    eq_used: np.ndarray | None  # (Q, R)
    gang_inflight: np.ndarray | None  # (G, R)

    def clone(self) -> "_FenceState":
        return _FenceState(
            self.free.copy(), self.total_free.copy(),
            None if self.eq_used is None else self.eq_used.copy(),
            None if self.gang_inflight is None else self.gang_inflight.copy(),
        )

    def commit(self, t: "_FenceTables", p: int, choice: int) -> None:
        if choice < 0:
            return  # failed pods mutate nothing (the scan's where-gates)
        d = t.demand[p]
        self.free[choice] -= d
        self.total_free -= d
        if self.eq_used is not None and t.has_quota[t.ns[p]]:
            self.eq_used[t.ns[p]] += t.req[p]
        g = t.gang[p]
        if self.gang_inflight is not None and g >= 0:
            self.gang_inflight[g] += d


@dataclass
class _FenceTables:
    """Host copies of the static snapshot columns the fence reads."""

    req: np.ndarray  # (P, R)
    demand: np.ndarray  # (P, R) — req with the pods slot forced to 1
    ns: np.ndarray  # (P,)
    gang: np.ndarray  # (P,)
    ok0: np.ndarray  # (P,) mask & ~gated
    node_mask: np.ndarray  # (N,)
    has_quota: np.ndarray | None  # (Q,)
    eq_min: np.ndarray | None  # (Q, R)
    eq_max: np.ndarray | None  # (Q, R)
    g_min_member: np.ndarray | None
    g_total: np.ndarray | None
    g_gated: np.ndarray | None
    g_backed_off: np.ndarray | None
    g_slack: np.ndarray | None  # (G, R)
    g_min_res: np.ndarray | None  # (G, R)
    g_has_min_res: np.ndarray | None
    g_assigned: np.ndarray | None
    #: admit twins in PROFILE ORDER ("gang" | "quota") — verdict
    #: equality must be compared per plugin, in order, or the
    #: attribution code could silently differ
    admit_plugins: list = field(default_factory=list)


def _fence_tables(scheduler, snap) -> _FenceTables:
    from scheduler_plugins_tpu.plugins import CapacityScheduling, Coscheduling

    req = np.asarray(snap.pods.req)
    t = _FenceTables(
        req=req,
        demand=np.asarray(pod_fit_demand(jnp.asarray(req))),
        ns=np.asarray(snap.pods.ns),
        gang=np.asarray(snap.pods.gang),
        ok0=np.asarray(snap.pods.mask) & ~np.asarray(snap.pods.gated),
        node_mask=np.asarray(snap.nodes.mask),
        has_quota=None, eq_min=None, eq_max=None,
        g_min_member=None, g_total=None, g_gated=None, g_backed_off=None,
        g_slack=None, g_min_res=None, g_has_min_res=None, g_assigned=None,
    )
    if snap.quota is not None:
        t.has_quota = np.asarray(snap.quota.has_quota)
        t.eq_min = np.asarray(snap.quota.min)
        t.eq_max = np.asarray(snap.quota.max)
    if snap.gangs is not None:
        t.g_min_member = np.asarray(snap.gangs.min_member)
        t.g_total = np.asarray(snap.gangs.total_members)
        t.g_gated = np.asarray(snap.gangs.gated)
        t.g_backed_off = np.asarray(snap.gangs.backed_off)
        t.g_slack = np.asarray(snap.gangs.cluster_slack)
        t.g_min_res = np.asarray(snap.gangs.min_resources)
        t.g_has_min_res = np.asarray(snap.gangs.has_min_resources)
        t.g_assigned = np.asarray(snap.gangs.assigned)
    for p in scheduler.profile.plugins:
        if isinstance(p, Coscheduling) and snap.gangs is not None:
            t.admit_plugins.append("gang")
        elif isinstance(p, CapacityScheduling) and snap.quota is not None:
            t.admit_plugins.append("quota")
    return t


def _gang_admit_np(t: _FenceTables, s: _FenceState, p: int) -> bool:
    """Numpy twin of `ops.gang.gang_admit` (gang_scheduled plays no role
    in admission — it only feeds the post-scan quorum reduction)."""
    g = int(t.gang[p])
    if g < 0:
        return True
    if t.g_total[g] < t.g_min_member[g]:
        return False
    if t.g_backed_off[g]:
        return False
    if t.g_total[g] - t.g_gated[g] < t.g_min_member[g]:
        return False
    if not t.g_has_min_res[g]:
        return True
    capacity = s.total_free + t.g_slack[g]
    if s.gang_inflight is not None:
        capacity = capacity + s.gang_inflight[g]
    return bool(np.all(t.g_min_res[g] <= capacity))


def _quota_admit_np(t: _FenceTables, s: _FenceState, p: int) -> bool:
    """Numpy twin of `ops.quota.quota_admit` with empty nominee
    aggregates (the fence-exact gate pins M == 0)."""
    ns = int(t.ns[p])
    if not t.has_quota[ns]:
        return True
    req = t.req[p]
    if np.any(s.eq_used[ns] + req > t.eq_max[ns]):
        return False
    agg_used = s.eq_used[t.has_quota].sum(axis=0)
    agg_min = t.eq_min[t.has_quota].sum(axis=0)
    return not np.any(agg_used + req > agg_min)


def _step_signature(t: _FenceTables, s: _FenceState, p: int):
    """Everything pod p's step depends on through the carried state,
    under the fence-exact gate: the per-plugin admit verdicts (profile
    order) and the built-in fit mask. Two states with equal signatures
    replay the step identically — equal feasible set ⇒ equal normalized
    scores ⇒ equal argmax/fail-code/commits."""
    verdicts = []
    for kind in t.admit_plugins:
        if kind == "gang":
            verdicts.append(_gang_admit_np(t, s, p))
        else:
            verdicts.append(_quota_admit_np(t, s, p))
    fit = np.all(t.demand[p] <= s.free, axis=1) & t.node_mask
    return verdicts, fit


def _fence_refine(t: _FenceTables, free0, eq0, infl0, assignment,
                  lane_of_pod, candidates, k: int):
    """Fence stage 2: exact serial-order validation of the screen's
    candidates. Every pod up to the last candidate replays its cheap
    int64 delta commits (the committed truth + each lane's speculative
    mirror); the expensive per-pod signature twins run ONLY at
    candidate indices. Returns (conflict_at, committed-state-at-
    conflict) — (-1, None) when every candidate validates, in which
    case screen + refine together prove the whole cycle conflict-free."""
    committed = _FenceState(
        free=free0.copy(), total_free=free0.sum(axis=0),
        eq_used=None if eq0 is None else eq0.copy(),
        gang_inflight=None if infl0 is None else infl0.copy(),
    )
    lane_states = [committed.clone() for _ in range(k)]
    cand = {int(c) for c in candidates}
    for p in range(max(cand) + 1):
        j = int(lane_of_pod[p])
        mine = lane_states[j]
        if p in cand:
            sig_lane = _step_signature(t, mine, p)
            sig_comm = _step_signature(t, committed, p)
            if sig_lane[0] != sig_comm[0] or not np.array_equal(
                sig_lane[1], sig_comm[1]
            ):
                return p, committed
        choice = int(assignment[p])
        committed.commit(t, p, choice)
        mine.commit(t, p, choice)
    return -1, None


# ---------------------------------------------------------------------------
# The orchestrator
# ---------------------------------------------------------------------------


@dataclass
class LaneStats:
    """One cycle's lane attribution (rides `CycleReport.lanes`)."""

    k: int
    path: str  # "laned" | "serial"
    sizes: list = field(default_factory=list)
    #: verbatim-committed pods per lane
    committed: list = field(default_factory=list)
    #: fence conflicts per lane (the lane whose pod first failed
    #: validation — at most one per cycle: the repair covers the rest)
    conflicts: list = field(default_factory=list)
    #: pods re-resolved against committed state by the repair solve
    re_resolved: int = 0
    serial_fallback_reason: str | None = None
    solve_ms: float = 0.0
    fence_ms: float = 0.0
    #: partition + segment-stat upkeep wall (ms): the serial coordinator
    #: prologue a K-process deployment pays before fanning out — counted
    #: INSIDE solve_ms, broken out so the critical path
    #: (partition_ms + max(lane_ms) + fence_ms) is honest
    partition_ms: float = 0.0
    #: per-lane dispatch wall (ms) — "sequential" mode times each lane's
    #: (1, L) program alone on the caller thread (exact per-lane
    #: attribution: max(lane_ms) + fence_ms is the critical path a
    #: K-core / K-process deployment pays); "threads" mode records the
    #: same spans but overlapping workers inflate each other's wall.
    #: Empty under "fused" (one program, no per-lane boundary).
    lane_ms: list = field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "k": self.k,
            "path": self.path,
            "sizes": list(self.sizes),
            "committed": list(self.committed),
            "conflicts": list(self.conflicts),
            "re_resolved": self.re_resolved,
            "serial_fallback_reason": self.serial_fallback_reason,
            "solve_ms": round(self.solve_ms, 3),
            "fence_ms": round(self.fence_ms, 3),
            "partition_ms": round(self.partition_ms, 3),
            "lane_ms": [round(m, 3) for m in self.lane_ms],
        }


class LaneSolver:
    """K speculative solver lanes over one scheduler, committed through
    the single conflict fence. `solve(snap, pending, cluster)` returns
    (assignment, admitted, wait, fail_codes) host arrays bit-identical
    to `Scheduler.solve`'s sequential scan, plus a `LaneStats`."""

    def __init__(self, scheduler, k: int = 4, partition: str = "namespace",
                 dispatch: str = "fused"):
        if k < 1:
            raise ValueError(f"lane count must be >= 1, got {k}")
        if dispatch not in DISPATCH_MODES:
            raise ValueError(
                f"unknown lane dispatch mode {dispatch!r}; expected one "
                f"of {DISPATCH_MODES}"
            )
        if partition not in PARTITION_MODES:
            raise ValueError(
                f"unknown lane partition mode {partition!r}; expected "
                f"one of {PARTITION_MODES}"
            )
        self.scheduler = scheduler
        self.k = k
        self.partition = partition
        self.dispatch = dispatch
        # cross-cycle partition + screen-input caches (pods persist
        # until placed, so steady-state upkeep is arrivals-only):
        # uid -> partition key, and key -> (axiswise max, axiswise min)
        # float64 (R,) demand extremes accumulated over every pod EVER
        # folded into the key — a conservative superset of any cycle's
        # live pods (max only grows, min only shrinks), which is
        # exactly the direction the screen's sufficient conditions
        # need. A pod folds exactly when it misses the key cache, so
        # the two caches prune together and the invariant "every cached
        # uid's demand is folded into its key's stats" holds by
        # construction. Invalidated wholesale whenever the snapshot's
        # resource axis changes (`_axis_sig`).
        self._key_cache: dict = {}
        self._seg_stats: dict = {}
        self._axis_sig = None
        self._pool = None
        if dispatch == "threads" and k > 1:
            # named per GL012: the race audit's entry table models these
            # workers (docs/race_audit.json "spt-lane-w*") — they only
            # EXECUTE the compiled lane program (tracing, which mutates
            # plugin bind state, happens on the caller thread first)
            self._pool = ThreadPoolExecutor(
                max_workers=k - 1, thread_name_prefix="spt-lane-w"
            )

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- speculation -----------------------------------------------------
    def _dispatch(self, snap, state0, auxes, idx2d, live2d, stats):
        """Runs the lane program and returns PER-LANE output rows:
        a list of (choice, ok, fail) 1-D arrays, one per lane, each at
        least the lane's length."""
        fn = _cached_lane_fn(self.scheduler)
        if self.dispatch == "fused" or self.k == 1:
            with obs.tracer.span("Lane/solve", tid="Lane/solve",
                                 k=self.k, bucket=int(idx2d.shape[1])):
                out = fn(
                    snap, state0, auxes, jnp.asarray(idx2d),
                    jnp.asarray(live2d),
                )
                out = tuple(np.asarray(o) for o in out)
                return [tuple(o[j] for o in out) for j in range(self.k)]
        # threads/sequential: K dispatches of the (1, L) program. Lane 0
        # (or every lane, sequential) runs on the caller thread FIRST —
        # the one trace (bind_aux / bind_presolve mutate the shared
        # plugin objects at trace time) must not race; workers then
        # execute compiled code only, all at the SHARED max bucket (one
        # shape -> one trace). Sequential mode instead rides each lane's
        # OWN half-octave bucket — per-lane shapes are safe on one
        # thread, and the shorter scans are exactly what K independent
        # scheduler processes would compile. lane_ms writes are
        # per-index disjoint (each worker owns slot j).
        stats.lane_ms = [0.0] * self.k
        seq = self._pool is None

        def one(j):
            t0 = time.perf_counter()
            pods_j = int(live2d[j].sum())
            b = _bucket(pods_j) if seq else live2d.shape[1]
            with obs.tracer.span("Lane/solve", tid=f"Lane/{j}",
                                 pods=pods_j, bucket=b):
                out = fn(
                    snap, state0, auxes,
                    jnp.asarray(idx2d[j:j + 1, :b]),
                    jnp.asarray(live2d[j:j + 1, :b]),
                )
                out = tuple(np.asarray(o)[0] for o in out)
            stats.lane_ms[j] = (time.perf_counter() - t0) * 1000.0
            return out

        if seq:
            outs = [one(j) for j in range(self.k)]
        else:
            first = one(0)
            futures = [
                self._pool.submit(one, j) for j in range(1, self.k)
            ]
            outs = [first] + [f.result() for f in futures]
        return outs

    def _repair(self, snap, auxes, committed: _FenceState, suffix,
                quota_present: bool, gangs_present: bool):
        """Re-solve the whole remaining suffix in ONE dispatch, seeded
        with the committed state — from the first conflict on, this IS
        the serial scan (same step body, same state, same order)."""
        fn = _cached_lane_fn(self.scheduler)
        state = SolverState(
            free=jnp.asarray(committed.free),
            eq_used=(
                jnp.asarray(committed.eq_used) if quota_present else None
            ),
            gang_scheduled=(
                jnp.zeros(self._num_gangs(snap), jnp.int32)
                if gangs_present else None
            ),
            gang_inflight=(
                jnp.asarray(committed.gang_inflight)
                if gangs_present else None
            ),
            placed_mask=(
                jnp.zeros(snap.num_pods, bool) if quota_present else None
            ),
        )
        bucket = _bucket(len(suffix))
        idx = np.zeros((1, bucket), np.int32)
        idx[0, : len(suffix)] = suffix
        live = np.zeros((1, bucket), bool)
        live[0, : len(suffix)] = True
        with obs.tracer.span("Lane/repair", tid="Lane/fence",
                             pods=len(suffix)):
            out = fn(snap, state, auxes, jnp.asarray(idx), jnp.asarray(live))
            return tuple(np.asarray(o)[0, : len(suffix)] for o in out)

    @staticmethod
    def _num_gangs(snap) -> int:
        return int(snap.gangs.min_member.shape[0])

    # -- screen inputs ---------------------------------------------------
    def _segment_extremes(self, snap, pending, seg_of_pod, seg_keys,
                          fresh, meta):
        """(S_b, R) float64 axiswise per-segment demand extremes for the
        screen's fit certificate, padded to the segment bucket with the
        -inf/+inf identities (padded rows are trivially safe).

        Accumulated on host across cycles over every pod EVER folded
        into the key — a conservative superset of this cycle's live
        segment pods, so both certificate arms stay sufficient (the
        accumulated max dominates the live max; the accumulated min is
        dominated by the live min). A pod folds exactly when it misses
        the partition's key cache (`fresh`), so steady-state upkeep is
        arrivals-only and the (P, R) demand pull happens only on cycles
        that have any. `meta.index.names` fingerprints the resource
        axis — a changed axis (new extended resource) drops both caches
        wholesale; without meta the axis LENGTH stands in (axis
        identity is then assumed stable across this solver's
        lifetime)."""
        R = int(snap.pods.req.shape[1])
        sig = tuple(meta.index.names) if meta is not None else ("R", R)
        if sig != self._axis_sig:
            self._axis_sig = sig
            self._key_cache.clear()
            self._seg_stats.clear()
            fresh = range(len(pending))
        stats = self._seg_stats
        dem = None
        if len(fresh):
            dem = pod_fit_demand_np(
                np.asarray(snap.pods.req)
            ).astype(np.float64)
            for i in fresh:
                key = seg_keys[seg_of_pod[i]]
                row = dem[i]
                cur = stats.get(key)
                if cur is None:
                    stats[key] = (row.copy(), row.copy())
                else:
                    np.maximum(cur[0], row, out=cur[0])
                    np.minimum(cur[1], row, out=cur[1])
        missing = {
            s for s, key in enumerate(seg_keys) if key not in stats
        }
        if missing:
            # backstop (externally-mutated cache): a key whose pods all
            # HIT the uid cache yet has no stats — fold every pod of
            # the stats-less segments so the certificate stays sound
            if dem is None:
                dem = pod_fit_demand_np(
                    np.asarray(snap.pods.req)
                ).astype(np.float64)
            for i in range(len(pending)):
                s = int(seg_of_pod[i])
                if s not in missing:
                    continue
                key = seg_keys[s]
                row = dem[i]
                cur = stats.get(key)
                if cur is None:
                    stats[key] = (row.copy(), row.copy())
                else:
                    np.maximum(cur[0], row, out=cur[0])
                    np.minimum(cur[1], row, out=cur[1])
        if len(self._key_cache) > 4 * len(pending) + 1024:
            # bound the caches on long-lived solvers: keep live uids
            # and live keys only. Dropping a departed uid is harmless —
            # it re-folds (a no-op: max/min accumulation is idempotent)
            # if it ever pends again — and a pruned KEY has no live
            # pods left to cover (every kept uid's key is in
            # `seg_keys`, so the fold invariant holds).
            live = {p.uid for p in pending}
            self._key_cache = {
                u: key for u, key in self._key_cache.items() if u in live
            }
            keep = set(seg_keys)
            self._seg_stats = {
                key: v for key, v in self._seg_stats.items()
                if key in keep
            }
        S_b = _bucket(max(1, len(seg_keys)))
        seg_mx = np.full((S_b, R), -np.inf)
        seg_mn = np.full((S_b, R), np.inf)
        for s, key in enumerate(seg_keys):
            mx, mn = stats[key]
            seg_mx[s] = mx
            seg_mn[s] = mn
        return seg_mx, seg_mn

    # -- the solve + fence ----------------------------------------------
    def solve(self, snap, pending, cluster, meta=None):
        """Returns (assignment, admitted, wait, fail_codes, stats) —
        host arrays over the snapshot's (padded) pod axis, bit-identical
        to the sequential parity scan. Falls back to `Scheduler.solve`
        (still bit-identical — it IS the parity path) when K == 1 or the
        fence-exact gate rejects the profile/snapshot. `meta` (the
        snapshot's `SnapshotMeta`, optional) lets the cross-cycle
        screen-input cache fingerprint the resource axis exactly."""
        stats = LaneStats(k=self.k, path="laned")
        exact, reason = fence_exact(self.scheduler, snap)
        if self.k == 1 or not exact:
            stats.path = "serial"
            stats.serial_fallback_reason = reason if not exact else "k=1"
            if not exact:
                obs.metrics.inc(obs.LANE_SERIAL_FALLBACKS)
            t0 = time.perf_counter()
            result = self.scheduler.solve(snap, mode="sequential")
            assignment = np.asarray(result.assignment)
            admitted = np.asarray(result.admitted)
            wait = np.asarray(result.wait)
            codes = np.asarray(result.failed_plugin)
            stats.solve_ms = (time.perf_counter() - t0) * 1000.0
            return assignment, admitted, wait, codes, stats

        t0 = time.perf_counter()
        lanes, seg_of_pod, lane_of_seg, seg_keys, fresh = (
            partition_segments(
                pending, cluster, self.k, self.partition, self._key_cache
            )
        )
        seg_mx, seg_mn = self._segment_extremes(
            snap, pending, seg_of_pod, seg_keys, fresh, meta
        )
        stats.partition_ms = (time.perf_counter() - t0) * 1000.0
        stats.sizes = [len(lane) for lane in lanes]
        stats.committed = [0] * self.k
        stats.conflicts = [0] * self.k
        P_live = len(pending)
        P = snap.num_pods
        bucket = _bucket(max(1, max(stats.sizes) if stats.sizes else 1))
        idx2d = np.zeros((self.k, bucket), np.int32)
        live2d = np.zeros((self.k, bucket), bool)
        lane_of_pod = lane_of_seg[seg_of_pod]
        for j, lane in enumerate(lanes):
            idx2d[j, : len(lane)] = lane
            live2d[j, : len(lane)] = True

        state0 = self.scheduler.initial_state(snap)
        auxes = tuple(p.aux() for p in self.scheduler.profile.plugins)
        outs = self._dispatch(snap, state0, auxes, idx2d, live2d, stats)
        stats.solve_ms = (time.perf_counter() - t0) * 1000.0

        # scatter lane outputs back to pod order. Padded snapshot rows
        # (>= P_live) belong to no lane and keep the masked-pod outputs
        # (-1 / False / 0) — exactly what the serial scan emits for them.
        assignment = np.full(P, -1, np.int32)
        admitted = np.zeros(P, bool)
        codes = np.zeros(P, np.int32)
        for j in range(self.k):
            n = len(lanes[j])
            assignment[idx2d[j, :n]] = outs[j][0][:n]
            admitted[idx2d[j, :n]] = outs[j][1][:n]
            codes[idx2d[j, :n]] = outs[j][2][:n]

        # the conflict fence: stage-1 compiled monotone screen (one
        # dispatch), then the exact serial-order refine over its
        # (usually empty) candidate set — docs/SCALING.md carries the
        # proof. The host fence tables are built LAZILY: the wholesale-
        # commit fast path never pulls the snapshot columns to host.
        t0 = time.perf_counter()
        from scheduler_plugins_tpu.plugins import (
            CapacityScheduling, Coscheduling,
        )
        quota_on = snap.quota is not None and any(
            isinstance(p, CapacityScheduling)
            for p in self.scheduler.profile.plugins
        )
        gang_on = snap.gangs is not None and any(
            isinstance(p, Coscheduling)
            for p in self.scheduler.profile.plugins
        )
        lane_full = np.zeros(P, np.int32)
        lane_full[:P_live] = lane_of_pod
        # the segment axis rides its own bucket (set by
        # `_segment_extremes`) so tenant churn retraces at half-octave
        # boundaries, not every cycle
        S_b = seg_mx.shape[0]
        seg_lanes = np.zeros(S_b, np.int32)
        seg_lanes[: lane_of_seg.shape[0]] = lane_of_seg
        conflict_at, committed = -1, None
        gang_col = None
        with obs.tracer.span("Lane/fence", tid="Lane/fence",
                             pods=P_live):
            screen = _cached_screen_fn(
                self.scheduler, self.k, quota_on, gang_on
            )
            assign_dev = jnp.asarray(assignment)
            lane_dev = jnp.asarray(lane_full)
            core = (
                snap.pods.req, snap.pods.mask, snap.pods.gated,
                state0.free, snap.nodes.mask, assign_dev, lane_dev,
                jnp.asarray(seg_mx), jnp.asarray(seg_mn),
                jnp.asarray(seg_lanes),
            )
            quota_args = (
                (snap.pods.ns, snap.quota.has_quota, snap.quota.min,
                 snap.quota.max, state0.eq_used)
                if quota_on else ()
            )
            gang_args = (
                (snap.pods.gang, snap.gangs.cluster_slack,
                 snap.gangs.min_resources,
                 snap.gangs.has_min_resources, state0.gang_inflight)
                if gang_on else ()
            )
            fit_unsafe, flagged = screen(core, quota_args, gang_args)
            flagged = np.asarray(flagged)
            if bool(np.asarray(fit_unsafe)):
                fit_screen = _cached_screen_fit_fn(self.scheduler, self.k)
                flagged = flagged | np.asarray(
                    fit_screen(
                        snap.pods.req, snap.pods.mask, snap.pods.gated,
                        state0.free, snap.nodes.mask, assign_dev,
                        lane_dev,
                    )
                )
            candidates = np.flatnonzero(flagged[:P_live])
            if candidates.size:
                tables = _fence_tables(self.scheduler, snap)
                gang_col = tables.gang
                free0 = np.asarray(state0.free)
                eq0 = (
                    np.asarray(state0.eq_used)
                    if state0.eq_used is not None else None
                )
                infl0 = (
                    np.asarray(state0.gang_inflight)
                    if state0.gang_inflight is not None else None
                )
                conflict_at, committed = _fence_refine(
                    tables, free0, eq0, infl0, assignment, lane_of_pod,
                    candidates, self.k,
                )
        if conflict_at >= 0:
            j = int(lane_of_pod[conflict_at])
            stats.conflicts[j] += 1
            obs.metrics.inc(obs.LANE_CONFLICTS, lane=str(j))
            stats.committed = [
                int(c) for c in
                np.bincount(lane_of_pod[:conflict_at], minlength=self.k)
            ]
            suffix = list(range(conflict_at, P_live))
            stats.re_resolved = len(suffix)
            obs.metrics.inc(obs.LANE_RERESOLVES, len(suffix))
            r_choice, r_ok, r_fail = self._repair(
                snap, auxes, committed, suffix,
                quota_present=snap.quota is not None,
                gangs_present=snap.gangs is not None,
            )
            assignment[suffix] = r_choice
            admitted[suffix] = r_ok
            codes[suffix] = r_fail
        else:
            stats.committed = list(stats.sizes)

        # Permit quorum, post-scan (sequential_solve_body's reduction):
        # recomputed from the FINAL assignment — the per-gang placement
        # counts are exactly the gang_commit tallies the scan would carry
        wait = np.zeros(P, bool)
        if snap.gangs is not None:
            gang = (
                gang_col if gang_col is not None
                else np.asarray(snap.pods.gang)
            )
            placed_in_gang = (assignment >= 0) & (gang >= 0)
            sched = np.bincount(
                gang[placed_in_gang], minlength=self._num_gangs(snap)
            )
            g_assigned = np.asarray(snap.gangs.assigned)
            g_min_member = np.asarray(snap.gangs.min_member)
            quorum = (g_assigned + sched) >= g_min_member
            in_gang = gang >= 0
            pod_quorum = np.where(in_gang, quorum[np.maximum(gang, 0)], True)
            wait = (assignment >= 0) & ~pod_quorum
        stats.fence_ms = (time.perf_counter() - t0) * 1000.0
        for j in range(self.k):
            with obs.tracer.span("Lane/commit", tid=f"Lane/{j}",
                                 committed=stats.committed[j],
                                 conflicts=stats.conflicts[j]):
                pass
        return assignment, admitted, wait, codes, stats
