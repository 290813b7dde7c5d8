"""Sharded batched solve — the multi-chip scheduling step.

One "step" = the full pipeline over a pending wave, batched over pods and
sharded over the mesh:

    PreFilter (gang/quota admission against CARRIED usage, vmapped over pods)
 -> Filter (resource fit + plugin masks, (P, N) sharded pods x nodes)
 -> Score + Normalize (weighted sum)
 -> wave conflict resolution (queue-order admission per node AND per
    namespace quota, exact prefix sums)
 -> Permit (gang quorum as a segment reduction)

Node-axis reductions (argmax, fit all-reduce) and pod-axis prefix sums become
XLA collectives over ICI; side tables (quota, gangs) are replicated and their
segment sums psum naturally. Placements within a wave may differ from the
bit-faithful sequential scan (`Scheduler.solve`) exactly as documented in
`ops.assign.waterfill_assign` — this is the throughput path; the sequential
path remains the parity gate. Hard constraints (fit, quota Max/aggregate-Min,
gang quorum Wait) are enforced in both paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from scheduler_plugins_tpu.ops.allocatable import (
    MODE_LEAST,
    allocatable_scores,
    demote_scores_int32,
)
from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted
from scheduler_plugins_tpu.ops.fit import fits, free_capacity, pod_fit_demand
from scheduler_plugins_tpu.ops.gang import gang_admit
from scheduler_plugins_tpu.ops.quota import quota_admit
from scheduler_plugins_tpu.utils import observability as obs


def nominated_aggregates_batch(quota):
    """(P, R) nominee aggregates from the (M, P) masks x (M, R) requests."""
    in_eq = (
        quota.nom_in_eq_mask.astype(jnp.float64).T
        @ quota.nom_req.astype(jnp.float64)
    ).astype(jnp.int64)
    total = (
        quota.nom_total_mask.astype(jnp.float64).T
        @ quota.nom_req.astype(jnp.float64)
    ).astype(jnp.int64)
    return in_eq, total


def finalize_assignment(assignment, snap):
    """Shared tail: queue-order namespace quota enforcement + gang quorum
    Permit over the final placements (used by both batched solvers)."""
    if snap.quota is not None:
        placed = assignment >= 0
        quota_ok = _namespace_quota_prefix_ok(placed, snap, snap.quota.used)
        assignment = jnp.where(placed & ~quota_ok, -1, assignment)
    wait = jnp.zeros(snap.num_pods, bool)
    if snap.gangs is not None:
        placed = (assignment >= 0).astype(jnp.int32)
        gang = snap.pods.gang
        in_gang = gang >= 0
        G = snap.gangs.min_member.shape[0]
        sched = jnp.zeros(G, jnp.int32).at[jnp.maximum(gang, 0)].add(
            jnp.where(in_gang, placed, 0)
        )
        quorum = snap.gangs.assigned + sched >= snap.gangs.min_member
        pod_quorum = jnp.where(in_gang, quorum[jnp.maximum(gang, 0)], True)
        wait = (assignment >= 0) & ~pod_quorum
    return assignment, wait


def batch_admission(snap, free, eq_used=None):
    """(P,) PreFilter verdicts for the batch against the carried state
    (gang membership/backoff/MinResources + elastic quota)."""
    ok = snap.pods.mask & ~snap.pods.gated
    if snap.gangs is not None:
        gang_ok = jax.vmap(lambda g: gang_admit(snap.gangs, free, g))(
            snap.pods.gang
        )
        ok &= gang_ok
    if snap.quota is not None:
        used = eq_used if eq_used is not None else snap.quota.used
        # (P, R) nominee aggregates from the (M, P) tables — admission runs
        # before any placement here, so the static view is exact. float64
        # matmul avoids an (M, P, R) temporary AND the unsupported s64
        # dot_general on TPU (exact below 2^53).
        nom_in_eq, nom_total = nominated_aggregates_batch(snap.quota)
        quota_ok = jax.vmap(
            lambda ns, req, in_eq, total: quota_admit(
                used,
                snap.quota.min,
                snap.quota.max,
                snap.quota.has_quota,
                ns,
                req,
                in_eq,
                total,
            )
        )(snap.pods.ns, snap.pods.req, nom_in_eq, nom_total)
        ok &= quota_ok
    return ok


def _namespace_quota_prefix_ok_scan(assignment_order_ok, snap, eq_used):
    """(P,) queue-order quota admission, exact: a `lax.scan` threads admitted
    usage through the batch in queue order, so a pod is charged against Max
    (own namespace) and the aggregate-Min pool only if it was itself admitted
    — identical semantics to `quota_commit` threading through the sequential
    scan (no over-approximation from rejected pods' requests).

    Reference implementation: O(P) serial steps, which on TPU costs the
    per-step scan latency P times over. The production path is the
    reject-first-violator fixpoint below (`_namespace_quota_prefix_ok`),
    which is bit-identical (tests/test_parallel.py gates it) with serial
    depth = the number of actually-rejected pods instead of P."""
    quota = snap.quota
    agg_min = jnp.sum(jnp.where(quota.has_quota[:, None], quota.min, 0), axis=0)
    agg_used0 = jnp.sum(jnp.where(quota.has_quota[:, None], eq_used, 0), axis=0)

    def step(carry, x):
        used, agg_used = carry
        ns_p, req_p, active = x
        has_q = quota.has_quota[ns_p]
        own_ok = jnp.all(used[ns_p] + req_p <= quota.max[ns_p])
        agg_ok = jnp.all(agg_used + req_p <= agg_min)
        ok = ~has_q | (own_ok & agg_ok)
        add = jnp.where(active & has_q & ok, req_p, 0)
        return (used.at[ns_p].add(add), agg_used + add), ok

    (_, _), ok = jax.lax.scan(
        step,
        (eq_used, agg_used0),
        (snap.pods.ns, snap.pods.req, assignment_order_ok),
    )
    return ok


def _namespace_quota_prefix_ok(assignment_order_ok, snap, eq_used):
    """(P,) queue-order quota admission as a reject-first-violator fixpoint —
    the parallel reformulation of `_namespace_quota_prefix_ok_scan` with
    identical outputs on every pod.

    Why it is exact: evaluate every pod's Max/aggregate-Min checks against
    prefix sums over the currently-assumed-admitted set. Pods before the
    queue-FIRST violator see only truly-admitted pods in their prefixes (a
    kept pod passing with an over-approximated prefix also passes with the
    true, smaller one), so the first violator's own prefix is exact and its
    rejection is final. Removing it only shrinks later prefixes, so
    violators surface in increasing queue order and each `lax.while_loop`
    trip resolves one true rejection with O(log P)-depth parallel work
    (1-D float64 cumsums — exact below 2^53, the repo-wide quantity bound —
    plus a sorted-segment rebase; no O(P) serial chain). Trip count is the
    number of quota-rejected pods in the batch (typically ~0), worst case
    the candidate count.

    Mirrors /root/reference/pkg/capacityscheduling/capacity_scheduling.go
    PreFilter semantics (208-282) applied in queue order at Reserve time."""
    from scheduler_plugins_tpu.ops.assign import _segment_prefix

    quota = snap.quota
    ns = snap.pods.ns
    P = ns.shape[0]
    has_q = quota.has_quota[ns]
    cand = assignment_order_ok & has_q
    reqf = snap.pods.req.astype(jnp.float64)
    used0_ns = eq_used[ns].astype(jnp.float64)
    max_ns = quota.max[ns].astype(jnp.float64)
    agg_min = jnp.sum(
        jnp.where(quota.has_quota[:, None], quota.min, 0), axis=0
    ).astype(jnp.float64)
    agg_used0 = jnp.sum(
        jnp.where(quota.has_quota[:, None], eq_used, 0), axis=0
    ).astype(jnp.float64)

    # static queue-stable namespace grouping: sort by (ns, queue index) so
    # per-namespace prefixes are 1-D segment cumsums (CLAUDE.md: no 2-D int64
    # cumsums on TPU; float64 is exact here)
    order = jnp.argsort(ns.astype(jnp.int64) * P + jnp.arange(P))
    ns_sorted = ns[order]
    first = jnp.concatenate(
        [jnp.ones(1, bool), ns_sorted[1:] != ns_sorted[:-1]]
    )
    idx = jnp.arange(P)

    def verdicts(admitted):
        """(own_ok & agg_ok) per pod from EXCLUSIVE prefixes over `admitted`
        — the scan's view at each pod's own step."""
        charge = jnp.where(admitted[:, None], reqf, 0.0)
        incl_own_sorted = _segment_prefix(charge[order], first)
        excl_own = jnp.zeros_like(charge).at[order].set(
            incl_own_sorted - charge[order]
        )
        excl_agg = jnp.cumsum(charge, axis=0) - charge
        own_ok = jnp.all(used0_ns + excl_own + reqf <= max_ns, axis=1)
        agg_ok = jnp.all(agg_used0 + excl_agg + reqf <= agg_min, axis=1)
        return own_ok & agg_ok

    def first_violator(admitted):
        viol = admitted & ~verdicts(admitted)
        return jnp.min(jnp.where(viol, idx, P))

    def cond(carry):
        _, v = carry
        return v < P

    def body(carry):
        admitted, v = carry
        admitted = admitted & (idx != v)
        return admitted, first_violator(admitted)

    admitted0 = cand
    admitted, _ = jax.lax.while_loop(
        cond, body, (admitted0, first_violator(admitted0))
    )
    return ~has_q | verdicts(admitted)


def batch_solve(snap, weights, max_waves: int = 8, collect_stats: bool = False):
    """Full batched step: admission -> fit -> allocatable score -> wave
    assignment -> quota prefix enforcement -> gang quorum.
    Returns (assignment (P,), admitted (P,), wait (P,)), plus the per-wave
    occupancy stats dict when `collect_stats` (see
    `ops.assign.waterfill_assign_stateful`).

    Allocatable scores are STATIC per node (the reference scores
    allocatable, not free capacity — resource_allocation.go:49-76), so the
    targeted waterfill applies: per-wave work is O(P·R) target-row gathers,
    not the (P, N) feasibility/score matrix (which at north-star scale is
    ~4B compares per wave). Unschedulable nodes are excluded by zeroing
    their free capacity for the solve (a masked node can then never admit
    any pod — pod demands include a pods-slot of 1)."""
    free0 = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    admitted = batch_admission(snap, free0)

    raw = demote_scores_int32(
        allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
    )
    solve_free0 = jnp.where(snap.nodes.mask[:, None], free0, 0)
    out = waterfill_assign_targeted(
        raw.astype(jnp.int64), snap.pods.req, admitted, solve_free0,
        max_waves=max_waves, collect_stats=collect_stats,
    )
    assignment = out[0]

    assignment, wait = finalize_assignment(assignment, snap)
    if collect_stats:
        return assignment, admitted, wait, out[2]
    return assignment, admitted, wait


def packing_solve(snap, weights, pack_aux, max_waves: int = 8,
                  mover_cap: int = 128, collect_stats: bool = False):
    """`batch_solve`'s flagship semantics with the PACKING refinement
    appended (the third solve mode — ROADMAP item 1, ISSUE 14): the same
    admission -> static allocatable ranking -> targeted waterfill wave
    placement, then `ops.packing.packing_refine` consolidation rounds
    over the wave output, then the shared `finalize_assignment` tail.
    `pack_aux` is the (4,) traced knob vector (`ops.packing
    .pack_aux_vector`: iterations, price_weight, temperature, decay) —
    one compile serves every iteration budget, and budget 0 is
    bit-identical to `batch_solve` by construction (the refinement loop
    never runs).

    Hard constraints hold exactly as on the wave path: refinement moves
    never change WHICH pods are placed (fit holds per move via the
    sorted-segment admission), so the queue-order quota prefix and gang
    quorum families see the identical placed set. Returns
    (assignment, admitted, wait[, stats]) with stats =
    {waterfill stats, "packing": {rounds, moves, emptied}}."""
    from scheduler_plugins_tpu.ops.packing import packing_refine

    free0 = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    admitted = batch_admission(snap, free0)
    raw = demote_scores_int32(
        allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
    ).astype(jnp.int64)
    solve_free0 = jnp.where(snap.nodes.mask[:, None], free0, 0)
    out = waterfill_assign_targeted(
        raw, snap.pods.req, admitted, solve_free0,
        max_waves=max_waves, collect_stats=collect_stats,
    )
    assignment, free_w = out[0], out[1]
    assignment, free_p, pstats = packing_refine(
        raw, snap.pods.req, admitted, snap.nodes.alloc, snap.nodes.mask,
        free_w, assignment, pack_aux, mover_cap=mover_cap,
    )
    assignment, wait = finalize_assignment(assignment, snap)
    if collect_stats:
        return assignment, admitted, wait, {**out[2], "packing": pstats}
    return assignment, admitted, wait


#: the one jitted flagship packing program (bench config 13 + the AOT
#: manifests share this trace cache; knobs ride the traced pack_aux arg,
#: so sweeping budgets never recompiles)
_PACKING_SOLVE_JIT: dict = {}


def packing_solve_fn(max_waves: int = 8, mover_cap: int = 128,
                     collect_stats: bool = True):
    """The memoized jitted `packing_solve` entry:
    fn(snap, weights, pack_aux) — the program bench config 13 runs and
    `tools/tpu_lower.py` AOT-lowers (the same seam discipline as
    `profile_batch_fn`)."""
    key = (max_waves, mover_cap, collect_stats)
    fn = _PACKING_SOLVE_JIT.get(key)
    if fn is None:
        fn = _PACKING_SOLVE_JIT[key] = obs.compile_watch(
            jax.jit(
                lambda snap, weights, pack_aux: packing_solve(
                    snap, weights, pack_aux, max_waves=max_waves,
                    mover_cap=mover_cap, collect_stats=collect_stats,
                )
            ),
            program="packing_solve",
        )
    return fn


class PackingSolveView:
    """The (assignment, admitted, wait) triple a packing-mode solve
    returns to the cycle — deliberately NOT a `SolveResult`: the flight
    recorder keys replay semantics off the result type, and packing
    placements must never be recorded as sequential-parity outputs.
    `stats` carries the refinement counters when collected."""

    __slots__ = ("assignment", "admitted", "wait", "failed_plugin", "stats")

    def __init__(self, assignment, admitted, wait, stats=None):
        self.assignment = assignment
        self.admitted = admitted
        self.wait = wait
        self.failed_plugin = None
        self.stats = stats


def packing_profile_fn(scheduler, snap, mover_cap: int = 128,
                       max_waves: int = 8):
    """(jitted_fn, args) for the packing-mode PROFILE solve — the
    `Scheduler.solve(mode="packing")` body: the targeted fast-path head
    (vmapped PreFilter admission + the single scoring plugin's static
    node ranking, `fast_solve_head`), the wave waterfill, the packing
    refinement, and the shared finalize tail. Packing knobs ride the
    traced `pack_aux` argument built from `profile.packing` per solve —
    the aux-channel discipline, so tuning the budget/price online never
    recompiles.

    Packing mode requires the targeted fast-path profile shape (ONE
    pod-invariant scoring plugin, no per-(pod, node) filters —
    `fast_path_scoring`, the same gate the streamed pipeline uses):
    refinement moves re-place pods on any fitting node, which is only
    sound when resource fit is the sole per-node constraint. Profiles
    outside the gate raise TypeError (load_profile validates the same
    rule at config time)."""
    from scheduler_plugins_tpu.ops.packing import packing_refine
    from scheduler_plugins_tpu.utils import sanitize

    plugins = tuple(scheduler.profile.plugins)
    scoring_p = fast_path_scoring(plugins)
    if scoring_p is None:
        raise TypeError(
            "packing solve mode requires a profile on the targeted "
            "fast path (one pod-invariant scoring plugin, no filters) — "
            f"profile {scheduler.profile.name!r} does not qualify"
        )
    state0 = _donation_safe_state(scheduler.initial_state(snap))
    auxes = tuple(p.aux() for p in plugins)
    pack_aux = scheduler.profile.packing.aux()

    def pack_batch(snap, state0, auxes, pack_aux):
        admitted, raw, free0 = fast_solve_head(
            plugins, scoring_p, snap, state0, auxes
        )
        out = waterfill_assign_targeted(
            raw, snap.pods.req, admitted, free0, max_waves=max_waves,
        )
        assignment, free_p, pstats = packing_refine(
            raw, snap.pods.req, admitted, snap.nodes.alloc,
            snap.nodes.mask, out[1], out[0], pack_aux,
            mover_cap=mover_cap,
        )
        assignment, wait = finalize_assignment(assignment, snap)
        return assignment, admitted, wait, pstats

    key = ("profile_packing", max_waves, mover_cap,
           sanitize.enabled()) + scheduler.weights_key() + tuple(
        p.static_key() for p in plugins
    )
    cache = scheduler._solve_cache
    if key not in cache:
        if sanitize.enabled():
            fn = sanitize.checkified(pack_batch, program="profile_packing")
        else:
            fn = _wrap_donated(jax.jit(pack_batch, donate_argnums=(1,)))
        cache[key] = obs.compile_watch(fn, program="profile_packing")
    return cache[key], (snap, state0, auxes, pack_aux)


def packing_profile_solve(scheduler, snap, mover_cap: int = 128,
                          max_waves: int = 8):
    """Run the packing-mode profile solve; returns a `PackingSolveView`.
    Under `SPT_PACK_CERTIFY=1` the solve is additionally certified by the
    `tuning.gates` numpy replay oracles (fit/mask/quota/gang-quorum) and
    raises on ANY violation — the per-solve certification hook
    (tests/test_packing.py runs the same oracles unconditionally)."""
    import os

    fn, args = packing_profile_fn(
        scheduler, snap, mover_cap=mover_cap, max_waves=max_waves
    )
    assignment, admitted, wait, pstats = fn(*args)
    view = PackingSolveView(
        assignment, admitted, wait,
        stats={k: int(v) for k, v in pstats.items()},
    )
    if os.environ.get("SPT_PACK_CERTIFY") == "1":
        import numpy as np

        from scheduler_plugins_tpu.tuning.gates import hard_violations

        verdict = hard_violations(
            snap, np.asarray(assignment), np.asarray(wait)
        )
        if verdict["total"]:
            raise AssertionError(
                f"packing solve violated hard constraints: {verdict}"
            )
    return view


def profile_batch_solve(scheduler, snap, max_waves: int = 8,
                        collect_stats: bool = False):
    """Run `profile_batch_fn`'s jitted solve — see that docstring for the
    semantics contract vs the sequential parity path."""
    fn, args = profile_batch_fn(
        scheduler, snap, max_waves=max_waves, collect_stats=collect_stats
    )
    return fn(*args)


#: sparse straggler-wave window for the profile solvers: re-filter rows per
#: straggler wave. 128 (vs the generic default 256) halves the dominant
#: (S, N, Z, R) NUMA re-filter cost per wave; wider straggler cohorts just
#: drain over more (cheaper) waves, and a stalled sparse wave still
#: escalates to one dense wave (ops.assign starvation guard).
PROFILE_STRAGGLER_CAP = 128


def fast_path_scoring(plugins):
    """The single scoring plugin of the targeted fast path, or None when
    the profile doesn't qualify — THE one copy of the gate (ISSUE 2
    review): no per-(pod, node) filters, no state-dependent plugins, ONE
    scoring plugin rating nodes pod-invariantly (`static_node_scores`)
    with positive weight (raw order == normalized-weighted order only
    holds for a positive weight — ADVICE r4). Shared by
    `profile_batch_fn`'s fast branch and the streamed pipeline solve
    (`parallel.pipeline.streamed_profile_solve`) so the two paths cannot
    gate differently."""
    from scheduler_plugins_tpu.framework.plugin import Plugin as _PluginBase

    plugins = tuple(plugins)
    scoring = tuple(
        p for p in plugins if type(p).score is not _PluginBase.score
    )
    filtering = tuple(
        p for p in plugins if type(p).filter is not _PluginBase.filter
    )
    ok = (
        not any(p.state_dependent_filter for p in plugins)
        and not filtering
        and len(scoring) == 1
        and type(scoring[0]).static_node_scores
        is not _PluginBase.static_node_scores
        and scoring[0].weight > 0
    )
    return scoring[0] if ok else None


def fast_solve_head(plugins, scoring, snap, state0, auxes):
    """Traced head shared by the targeted fast paths: bind aux/presolve,
    vmapped PreFilter admission, the raw static node ranking, and the
    masked initial free capacity. Returns (admitted (P,), raw (N,) int64,
    free0 (N, R))."""
    for plugin, aux in zip(plugins, auxes):
        plugin.bind_aux(aux)
    for plugin in plugins:
        plugin.bind_presolve(plugin.prepare_solve(snap))

    def admit_one(p):
        ok = snap.pods.mask[p] & ~snap.pods.gated[p]
        for plugin in plugins:
            verdict = plugin.admit(state0, snap, p)
            if verdict is not None:
                ok &= verdict
        return ok

    admitted = jax.vmap(admit_one)(jnp.arange(snap.num_pods))
    raw = scoring.static_node_scores(snap).astype(jnp.int64)
    free0 = jnp.where(snap.nodes.mask[:, None], state0.free, 0)
    return admitted, raw, free0


def _wrap_donated(fn):
    """Silence jax's "Some donated buffers were not usable" lowering
    warning for the profile solves ONLY: the state argument is donated as a
    whole, and the (N, R)/(N, Z, R) carries intentionally have no
    same-shape output to alias — XLA still releases them early (peak-memory
    win); the warning would otherwise fire on every first compile."""
    import functools
    import warnings

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return fn(*args, **kwargs)

    return obs.expose_stages(wrapped, fn)


def _donation_safe_state(state0):
    """SolverState with the leaves that may ALIAS snapshot tensors copied
    (eq_used is snap.quota.used; net_placed is snap.network.placed_node;
    the scheduling carries come from jnp.asarray over snapshot bases):
    the jitted profile solves donate the state argument, and a donated
    buffer that is also reachable through the non-donated snapshot
    argument would be written under the snapshot's feet. The copied
    tensors are side tables — (Q, R)/(W, N) — not the (N, ...) carries."""

    def copy(x):
        return None if x is None else jnp.asarray(x).copy()

    return state0.replace(
        eq_used=copy(state0.eq_used),
        net_placed=copy(state0.net_placed),
        sel_counts=copy(state0.sel_counts),
        sel_dom_counts=copy(state0.sel_dom_counts),
        anti_domains=copy(state0.anti_domains),
        sym_counts=copy(state0.sym_counts),
        sel_dom_view=copy(state0.sel_dom_view),
        anti_view=copy(state0.anti_view),
        sym_view=copy(state0.sym_view),
    )


def profile_batch_fn(scheduler, snap, max_waves: int = 8,
                     collect_stats: bool = False):
    """(jitted_fn, args) for the batched profile solve on `snap`, WITHOUT
    invoking it — the AOT seam: `tools/tpu_lower.py` exports exactly the
    callable the runtime executes (same trace-cache, same fast-path gate),
    so compile-readiness evidence covers the shipped program, not a
    re-derivation of it.

    Throughput mode for an ARBITRARY plugin profile: the same plugin
    tensor methods the sequential scan fuses are vmapped over the pod batch,
    then placed wave-parallel.

    Semantics vs the sequential parity path:

    - **Hard plugin constraints hold.** Filters of plugins whose verdict
      depends on earlier placements (`state_dependent_filter`: NUMA zone
      fitting, network dependency thresholds) are RE-EVALUATED every wave
      against the carried state with the previous waves' placements
      committed (`ops.assign.waterfill_assign_stateful`), and within a wave
      the NUMA plugin's exact zone guard checks each pod against the
      same-node demand of earlier same-wave winners — so a final placement
      never lands on a node whose zones were consumed mid-wave. Resource
      fit, queue-order node admission, quota prefix caps and gang quorum
      were already exact.
    - **Scores stay cycle-initial** (soft orderings): score tensors are
      computed once against the cycle-initial state, so tie-breaking and
      score-driven packing order may differ from the sequential scan —
      the wave trade-off documented in ops.assign.waterfill_assign.

    The jitted solve DONATES the state argument (`donate_argnums`): the
    SolverState carries (free, eq_used, gang_inflight, numa_avail) threaded
    through the wave loops update in place instead of holding a second
    copy of every carry alive across the dispatch. `args` is therefore
    single-shot — `profile_batch_fn` builds a fresh state per call, and a
    caller holding on to `args` must not invoke the returned fn twice with
    the same tuple (tools/graft_lint.py GL006 flags such reuse).

    Under `SPT_SANITIZE=1` (utils.sanitize) the solve is instead built as a
    checkify-instrumented jit — index OOB on the commit scatters, NaN,
    div-by-zero — with donation dropped (debug mode) and errors reported as
    structured JSON; the cache key carries the mode so toggling the env var
    never reuses a differently-instrumented program.
    """
    import jax

    from scheduler_plugins_tpu.utils import sanitize

    plugins = tuple(scheduler.profile.plugins)
    static_plugins = tuple(
        p for p in plugins if not p.state_dependent_filter
    )
    dyn_plugins = tuple(p for p in plugins if p.state_dependent_filter)
    from scheduler_plugins_tpu.framework.plugin import Plugin as _PluginBase

    for p in dyn_plugins:
        # the hard-constraint guarantee relies on wave commits actually
        # updating the carry — fail loudly, not silently, on a plugin that
        # declares a state-dependent filter with neither a batched Reserve
        # nor a sequential validator (framework-carried tracks count via
        # validate_at; see ops.selectors)
        if (
            type(p).commit_batch is _PluginBase.commit_batch
            and p.validate_at is None
        ):
            raise TypeError(
                f"{p.name}: state_dependent_filter requires commit_batch "
                "or validate_at"
            )
    state0 = _donation_safe_state(scheduler.initial_state(snap))
    auxes = tuple(p.aux() for p in plugins)

    # ---- targeted fast path ------------------------------------------
    # When the profile has NO per-(pod, node) filters and its single
    # scoring plugin rates nodes pod-invariantly (static_node_scores),
    # the whole (P, N) pipeline collapses: admission is a (P,) vmap, and
    # placement is the targeted waterfill (O(P·R) waves against the one
    # static node ranking). Gang quorum and the queue-order quota prefix
    # still run exactly in finalize_assignment. This is the shape of the
    # coscheduling/capacity profiles, where the reference spends its time
    # in PreFilter bookkeeping, not Filter fan-out
    # (capacity_scheduling.go:208-282). Ranking uses the plugin's RAW
    # static scores — sound because the gate (`fast_path_scoring`, shared
    # with the streamed pipeline solve) requires a SINGLE scoring plugin
    # and static_node_scores' contract requires its normalize to be
    # monotone with positive weight (framework/plugin.py).
    scoring_p = fast_path_scoring(plugins)
    if scoring_p is not None:

        def fast_batch(snap, state0, auxes):
            admitted, raw, free0 = fast_solve_head(
                plugins, scoring_p, snap, state0, auxes
            )
            out = waterfill_assign_targeted(
                raw, snap.pods.req, admitted, free0,
                max_waves=max_waves, collect_stats=collect_stats,
            )
            assignment, wait = finalize_assignment(out[0], snap)
            if collect_stats:
                return assignment, admitted, wait, out[2]
            return assignment, admitted, wait

        key = ("profile_batch_fast", max_waves, collect_stats,
               sanitize.enabled()) + scheduler.weights_key() + tuple(
            p.static_key() for p in plugins
        )
        cache = scheduler._solve_cache
        if key not in cache:
            if sanitize.enabled():
                fast_fn = sanitize.checkified(
                    fast_batch, program="profile_batch_fast"
                )
            else:
                fast_fn = _wrap_donated(
                    jax.jit(fast_batch, donate_argnums=(1,))
                )
            cache[key] = obs.compile_watch(
                fast_fn, program="profile_batch_fast"
            )
        return cache[key], (snap, state0, auxes)
    # ------------------------------------------------------------------

    def batch(snap, state0, auxes):
        for plugin, aux in zip(plugins, auxes):
            plugin.bind_aux(aux)
        for plugin in plugins:
            plugin.bind_presolve(plugin.prepare_solve(snap))
        P = snap.num_pods

        from scheduler_plugins_tpu.ops.fit import fits_one

        # class-collapsed whole-batch tensors (plugin.filter_batch /
        # score_batch): computed ONCE against state0, outside the per-pod
        # vmap; rows are gathered per pod below. A plugin providing them
        # does O(K·N) class work instead of O(P·N·...) vmapped work.
        def _batch_filter(plugin, state):
            if type(plugin).filter_batch is not _PluginBase.filter_batch:
                return plugin.filter_batch(state, snap)
            return None

        # class-collapsed cycle-initial rows — the shared hook dispatch
        # (`collapsed_batch_rows`) also feeds `batch_explain_rows`, so the
        # explain surface sees exactly the rows this solve ranks by
        filter0_rows, score_rows = collapsed_batch_rows(plugins, state0, snap)

        # plugins with batched score rows AND the base identity normalize
        # contribute a feasibility-independent weighted sum — fold them
        # into ONE whole-matrix total outside the per-pod vmap
        pre_total = None
        pre_ids = {
            i for i in score_rows
            if type(plugins[i]).normalize is _PluginBase.normalize
        }
        for i in pre_ids:
            term = plugins[i].weight * score_rows[i].astype(jnp.int32)
            pre_total = term if pre_total is None else pre_total + term

        def per_pod(p):
            ok = snap.pods.mask[p] & ~snap.pods.gated[p]
            for plugin in plugins:
                verdict = plugin.admit(state0, snap, p)
                if verdict is not None:
                    ok &= verdict
            # state-INDEPENDENT filters are wave-invariant: evaluate once;
            # normalize over the same fit-and-admit-filtered set the
            # sequential step uses (cycle-initial free capacity + the
            # cycle-initial view of the state-dependent filters)
            static_feasible = jnp.ones(snap.num_nodes, bool)
            for i, plugin in enumerate(plugins):
                if plugin not in static_plugins:
                    continue
                if i in filter0_rows:
                    static_feasible &= filter0_rows[i][p]
                    continue
                mask = plugin.filter(state0, snap, p)
                if mask is not None:
                    static_feasible &= mask
            feasible = (
                fits_one(snap.pods.req[p], state0.free, snap.nodes.mask)
                & static_feasible
            )
            for i, plugin in enumerate(plugins):
                if plugin not in dyn_plugins:
                    continue
                if i in filter0_rows:
                    feasible &= filter0_rows[i][p]
                    continue
                mask = plugin.filter(state0, snap, p)
                if mask is not None:
                    feasible &= mask
            feasible &= ok
            total = jnp.zeros(snap.num_nodes, jnp.int64)
            for i, plugin in enumerate(plugins):
                if i in pre_ids:
                    continue  # folded into pre_total outside the vmap
                raw = (
                    score_rows[i][p] if i in score_rows
                    else plugin.score(state0, snap, p)
                )
                if raw is not None:
                    total = total + plugin.weight * plugin.normalize(raw, feasible)
            # int32 demotion: normalized scores are <= 100 * sum(weights),
            # far inside int32 — halves the (P, N) score-matrix traffic in
            # the waterfill's per-wave argmax/mean passes
            total = total.astype(jnp.int32)
            if pre_total is not None:
                total = total + pre_total[p]
            return ok, static_feasible, feasible, total

        admitted, static_feasible, feasible0, scores0 = jax.vmap(per_pod)(
            jnp.arange(P)
        )

        def batch_fn(free, state, active):
            feasible = fits(
                snap.pods.req, free, pod_mask=active, node_mask=snap.nodes.mask
            ) & static_feasible
            for plugin in dyn_plugins:
                # class-collapsed whole-matrix re-filter when offered
                m = _batch_filter(plugin, state)
                if m is not None:
                    feasible &= m
                    continue
                def one(p, _pl=plugin):
                    return _pl.filter(state, snap, p)
                # a filter can opt out (None) on Python-level layout checks;
                # the probe's dead ops are DCE'd by XLA
                if one(jnp.int32(0)) is None:
                    continue
                feasible &= jax.vmap(one)(jnp.arange(P))
            return feasible, scores0

        def sub_batch_fn(free, state, idx, act_sub):
            """Sparse straggler re-filter: (S, N) rows for the `idx` pods
            only — a straggler wave re-runs the dyn filters on a small
            window instead of the whole batch."""
            feasible = fits(
                snap.pods.req[idx], free,
                pod_mask=act_sub, node_mask=snap.nodes.mask,
            ) & static_feasible[idx]
            for plugin in dyn_plugins:
                # row-sliced re-filter when offered (NUMA): S rows at S/P
                # of the whole-matrix cost — the whole-matrix form would
                # recompute (P, N, Z, R) per straggler wave
                if type(plugin).filter_rows is not _PluginBase.filter_rows:
                    r = plugin.filter_rows(state, snap, idx)
                    if r is not None:
                        feasible &= r
                        continue
                m = _batch_filter(plugin, state)
                if m is not None:
                    # class-collapsed rows: XLA folds the row gather into
                    # the (W, N) -> (P, N) class gather
                    feasible &= m[idx]
                    continue
                def one(p, _pl=plugin):
                    return _pl.filter(state, snap, p)
                if one(jnp.int32(0)) is None:
                    continue
                feasible &= jax.vmap(one)(idx)
            return feasible, scores0[idx]

        # hard DOMAIN constraints (topology spread, inter-pod anti-affinity)
        # span nodes, so neither the per-wave re-filter nor the same-node
        # wave guard can see a same-wave cross-node conflict. Validators
        # re-check each wave's winners sequentially in queue order against
        # the live carry inside the waterfill (O(1) gathers per pod on the
        # common fast path; a (CT,N)->(CT,D) scatter per pod only when a
        # spread node-inclusion policy excludes a keyed node); their
        # carries commit per pod there, every other dyn carry batch-commits
        # on the kept winners.
        validators = tuple(
            pl for pl in dyn_plugins if pl.validate_at is not None
        )
        batch_committers = tuple(
            pl for pl in dyn_plugins if pl.validate_at is None
        )

        def commit_fn(state, placed, choice):
            for plugin in batch_committers:
                state = plugin.commit_batch(state, snap, placed, choice)
            return state

        validate_fn = validate_commit_fn = None
        if validators:
            from scheduler_plugins_tpu.ops.selectors import commit_tracks

            def validate_fn(state, q, choice):
                ok = jnp.bool_(True)
                for pl in validators:
                    ok &= pl.validate_at(state, snap, q, choice)
                return ok

            def validate_commit_fn(state, q, choice):
                if snap.scheduling is not None:
                    state = commit_tracks(state, snap.scheduling, q, choice)
                for pl in validators:
                    state = pl.commit(state, snap, q, choice)
                return state

        guards, guard_demands = [], []
        for plugin in dyn_plugins:
            gdem = plugin.wave_guard_demand(snap)
            if gdem is not None:
                guards.append(
                    lambda state, p, n, pre, _pl=plugin: _pl.wave_guard(
                        state, snap, p, n, pre
                    )
                )
                guard_demands.append(gdem)
        capacity_fns = tuple(
            (lambda state, active, _pl=plugin: _pl.wave_capacity(
                state, snap, active
            ))
            for plugin in dyn_plugins
            if type(plugin).wave_capacity
            is not _PluginBase.wave_capacity
        )

        from scheduler_plugins_tpu.ops.assign import waterfill_assign_stateful

        out = waterfill_assign_stateful(
            batch_fn,
            commit_fn,
            tuple(guards),
            tuple(guard_demands),
            snap.pods.req,
            admitted,
            state0.free,
            state0,
            max_waves=max_waves,
            validate_fn=validate_fn,
            validate_commit_fn=validate_commit_fn,
            capacity_fns=capacity_fns,
            # wave 0 reuses the cycle-initial filter pass per_pod already
            # paid for (state is unchanged until the first commit)
            initial_batch=(feasible0, scores0),
            sub_batch_fn=sub_batch_fn,
            straggler_cap=PROFILE_STRAGGLER_CAP,
            collect_stats=collect_stats,
        )
        assignment, wait = finalize_assignment(out[0], snap)
        if collect_stats:
            return assignment, admitted, wait, out[3]
        return assignment, admitted, wait

    key = ("profile_batch", max_waves, collect_stats,
           sanitize.enabled()) + scheduler.weights_key() + tuple(
        p.static_key() for p in plugins
    )
    cache = scheduler._solve_cache
    if key not in cache:
        if sanitize.enabled():
            batch_fn_j = sanitize.checkified(batch, program="profile_batch")
        else:
            batch_fn_j = _wrap_donated(jax.jit(batch, donate_argnums=(1,)))
        cache[key] = obs.compile_watch(batch_fn_j, program="profile_batch")
    return cache[key], (snap, state0, auxes)


def sweep_solve_fn(scheduler):
    """The vmapped-over-weights counterfactual solve entry (the tuning
    observatory's hot program): a single jitted function

        fn(snap, state0, auxes, W (K, L) int64) ->
            (assignment (K, P), admitted (K, P), wait (K, P))

    that runs the bit-faithful sequential parity body
    (`framework.runtime.sequential_solve_body`) once per candidate weight
    vector, vmapped over the K axis — the per-candidate weight scalars are
    traced arguments bound through `Plugin.bind_weight` (the aux-channel
    discipline of CLAUDE.md applied to the one config knob the profile
    format keeps host-side), so K candidates share ONE compile and zero
    per-candidate retraces (`tools/tune.py` asserts this via the PR 5
    compile-watch counters, program "sweep_solve"). Lane k is
    bit-identical to a standalone `Scheduler.solve(auxes=)` on a profile
    whose static weights equal W[k] (tests/test_tuning.py gates it).

    Callers pad K to a power-of-two bucket (`tuning.sweep.pad_candidates`)
    so candidate-count churn stays within bounded retraces, exactly like
    `run_explain_rows`' index buckets."""
    from scheduler_plugins_tpu.framework.runtime import sequential_solve_body

    plugins = tuple(scheduler.profile.plugins)
    key = ("sweep_solve",) + tuple(p.static_key() for p in plugins)
    cache = scheduler._solve_cache
    if key not in cache:

        def sweep(snap, state0, auxes, W):
            def lane(w):
                r = sequential_solve_body(
                    plugins, snap, state0, auxes, unroll=1, weights=w
                )
                return r.assignment, r.admitted, r.wait

            return jax.vmap(lane)(W)

        cache[key] = obs.compile_watch(jax.jit(sweep), program="sweep_solve")
    return cache[key]


def collapsed_batch_rows(plugins, state0, snap):
    """(filter_rows, score_rows): plugin position -> class-collapsed whole-
    batch (P, N) rows from the `batch_rows` / `filter_batch` / `score_batch`
    hooks against the cycle-initial state — THE one copy of the hook
    dispatch, shared by the batched profile solve's cycle-initial pass and
    `batch_explain_rows`, so the explain surface consumes exactly the rows
    the batched solver ranks by."""
    from scheduler_plugins_tpu.framework.plugin import Plugin as _PluginBase

    filter_rows, score_rows = {}, {}
    for i, plugin in enumerate(plugins):
        # fused filter+score rows when offered: one shared-intermediate
        # pass instead of two (networkaware tallies)
        if type(plugin).batch_rows is not _PluginBase.batch_rows:
            fused = plugin.batch_rows(state0, snap)
            if fused is not None:
                f_row, s_row = fused
                if f_row is not None:
                    filter_rows[i] = f_row
                if s_row is not None:
                    score_rows[i] = s_row
                continue
        if type(plugin).filter_batch is not _PluginBase.filter_batch:
            m = plugin.filter_batch(state0, snap)
            if m is not None:
                filter_rows[i] = m
        if type(plugin).score_batch is not _PluginBase.score_batch:
            s = plugin.score_batch(state0, snap)
            if s is not None:
                score_rows[i] = s
    return filter_rows, score_rows


def batch_explain_rows(scheduler, snap, indices, auxes=None):
    """The BATCHED twin of `Scheduler.explain_rows`: identical output
    schema (admitted / fail_code / feasible / fit_margin / columns /
    total, sliced to len(indices)), but the per-plugin filter verdicts and
    raw scores come through the batched solver's class-collapsed row hooks
    (`collapsed_batch_rows`) — the rows `profile_batch_fn`'s cycle-initial
    pass actually ranks by — fed into the SAME shared explain body
    (`framework.runtime._explain_one`). The two entries differ only in
    where rows come from, so sequential and batched explains cannot
    drift; tests/test_explain.py asserts exact agreement."""
    from scheduler_plugins_tpu.framework.runtime import (
        _explain_one,
        run_explain_rows,
    )

    plugins = tuple(scheduler.profile.plugins)

    def explain(snap, state0, auxes, idx):
        for plugin, aux in zip(plugins, auxes):
            plugin.bind_aux(aux)
        for plugin in plugins:
            plugin.bind_presolve(plugin.prepare_solve(snap))
        filter_rows, score_rows = collapsed_batch_rows(plugins, state0, snap)
        return jax.vmap(
            lambda p: _explain_one(
                plugins, state0, snap, p,
                filter_rows=filter_rows, score_rows=score_rows,
            )
        )(idx)

    return run_explain_rows(
        scheduler, snap, indices, auxes, "batch_explain", explain
    )


def profile_initial_scores(scheduler, snap, auxes=None):
    """(P, N) weighted normalized plugin score matrix and (P, N) feasibility
    against the CYCLE-INITIAL state — the objective both solve modes rank
    nodes by before placements start. Used to quantify the batched path's
    placement-quality drift vs the sequential scan (VERDICT r2 item 8):
    score_sum(assignment) = Σ_p scores[p, assignment[p]] is comparable
    across modes because both optimize this same cycle-initial surface
    (the sequential path then re-evaluates state-dependent filters as it
    commits; scores stay cycle-initial in both, runtime.py step()).
    `auxes` force-binds recorded config arrays on the flight-recorder
    replay path (the tuner's drift anchor must score with exactly the
    recorded inputs), like `Scheduler.solve(auxes=)`."""
    import jax

    plugins = tuple(scheduler.profile.plugins)
    state0 = scheduler.initial_state(snap)
    if auxes is None:
        auxes = tuple(p.aux() for p in plugins)
    key = ("profile_scores",) + scheduler.weights_key() + tuple(
        p.static_key() for p in plugins
    )
    cache = scheduler._solve_cache
    if key not in cache:

        def scores_fn(snap, state0, auxes):
            for plugin, aux in zip(plugins, auxes):
                plugin.bind_aux(aux)
            for plugin in plugins:
                plugin.bind_presolve(plugin.prepare_solve(snap))

            from scheduler_plugins_tpu.ops.fit import fits_one

            def per_pod(p):
                feasible = fits_one(
                    snap.pods.req[p], state0.free, snap.nodes.mask
                )
                for plugin in plugins:
                    mask = plugin.filter(state0, snap, p)
                    if mask is not None:
                        feasible &= mask
                total = jnp.zeros(snap.num_nodes, jnp.int64)
                for plugin in plugins:
                    raw = plugin.score(state0, snap, p)
                    if raw is not None:
                        total = total + plugin.weight * plugin.normalize(
                            raw, feasible
                        )
                return total, feasible

            return jax.vmap(per_pod)(jnp.arange(snap.num_pods))

        cache[key] = obs.compile_watch(
            jax.jit(scores_fn), program="profile_scores"
        )
    return cache[key](snap, state0, auxes)


def score_drift_vs_sequential(scheduler, snap, seq_assignment,
                              bat_assignment):
    """Relative score-sum drift of the batched placements vs the sequential
    parity path on the shared cycle-initial objective
    (`profile_initial_scores`) — the single definition both the bench
    metric and the drift-bound test report, so they always measure the
    same quantity. Padded/unplaced slots carry assignment -1 and are
    excluded. Returns (drift, placed_seq, placed_bat)."""
    import numpy as np

    scores = np.asarray(profile_initial_scores(scheduler, snap)[0])
    seq = np.asarray(seq_assignment)
    bat = np.asarray(bat_assignment)

    def score_sum(a):
        placed = a >= 0
        return int(scores[np.nonzero(placed)[0], a[placed]].sum())

    s_seq, s_bat = score_sum(seq), score_sum(bat)
    drift = (s_bat - s_seq) / max(abs(s_seq), 1)
    return drift, int((seq >= 0).sum()), int((bat >= 0).sum())


def sharded_batch_solve(snap, mesh, weights, max_waves: int = 8):
    """Jit `batch_solve` with the snapshot sharded over `mesh`; XLA inserts
    the cross-shard collectives."""
    from scheduler_plugins_tpu.parallel.mesh import shard_snapshot

    snap = shard_snapshot(snap, mesh)
    with jax.set_mesh(mesh):
        fn = obs.compile_watch(
            jax.jit(lambda s, w: batch_solve(s, w, max_waves)),
            program="sharded_batch_solve",
        )
        return fn(snap, weights)


def sharded_profile_batch_solve(scheduler, snap, mesh, max_waves: int = 8):
    """`profile_batch_solve` (the FULL plugin roster: NUMA wave guards,
    network thresholds, spread/affinity validators, trimaran scores — not
    just the flagship allocatable solve) with the snapshot sharded over
    `mesh`. Node-major tensors (free capacity, NUMA zone tables, score rows)
    split over the "nodes" axis, pod-major tensors over "pods"; side tables
    replicate, and XLA's sharding propagation inserts the cross-shard
    collectives for the argmax/segment reductions — the multi-chip analog of
    the reference runtime's 16-worker Filter/Score fan-out (SURVEY.md §2.9;
    /root/reference/pkg/noderesourcetopology/filter.go:90-160 is the hot
    loop that lands on the node-sharded axis).

    Placement semantics are those of `profile_batch_solve` (sharding never
    changes the math, only its partitioning); `tests/test_parallel.py`
    asserts sharded == unsharded placements on an 8-device CPU mesh."""
    from scheduler_plugins_tpu.parallel.mesh import shard_snapshot

    snap = shard_snapshot(snap, mesh)
    with jax.set_mesh(mesh):
        return profile_batch_solve(scheduler, snap, max_waves=max_waves)


# ---------------------------------------------------------------------------
# Sharded wave solver: shard_map ring-election waterfill (node axis sharded)
# ---------------------------------------------------------------------------


def rank_order_inputs(raw_scores, free0, node_mask, n_shards: int):
    """(node_ids, rank_free) for the sharded wave solver: the node axis
    permuted into GLOBAL SCORE-RANK ORDER (stable argsort — the lowest-
    index tie-break of the single-device ranking is baked into the
    permutation) and padded to a multiple of `n_shards` with zero-capacity
    rows (node id -1), so each shard owns a contiguous global rank block
    and the shard-local wave kernels never need the (N,) score vector
    again. Masked nodes are zeroed like `batch_solve`'s solve_free0 — a
    masked node can then never admit any pod (pod demands include a
    pods-slot of 1). One O(N log N) sort + one gather per SOLVE (scores
    are static across waves and chunks), not per wave."""
    from scheduler_plugins_tpu.parallel.mesh import pad_to_shards

    N, R = free0.shape
    order_n = jnp.argsort(-raw_scores, stable=True)
    rank_free = jnp.where(node_mask[:, None], free0, 0)[order_n]
    node_ids = order_n.astype(jnp.int32)
    pad = pad_to_shards(N, n_shards) - N
    if pad:
        rank_free = jnp.concatenate(
            [rank_free, jnp.zeros((pad, R), rank_free.dtype)]
        )
        node_ids = jnp.concatenate(
            [node_ids, jnp.full((pad,), -1, jnp.int32)]
        )
    return node_ids, rank_free


def sharded_wave_chunk_solver(mesh, n_nodes: int, max_waves: int = 8,
                              rescue_window: int = 512,
                              lite_window: int = 1024,
                              collect_stats: bool = True,
                              use_pallas: bool | None = None,
                              pallas_interpret: bool | None = None):
    """The sharded wave chunk program: `ops.assign.waterfill_targeted_sharded`
    wrapped in a `shard_map` over `mesh`'s "nodes" axis and jitted with the
    resident rank-ordered free carry DONATED — the pipeline calling
    convention (`parallel.pipeline.run_chunk_pipeline`):

        fn(node_ids, req_chunk, mask_chunk, rank_free)
            -> ((assignment[, stats]), rank_free)

    `node_ids`/`rank_free` come from `rank_order_inputs` (node axis in
    global score-rank order, padded to the shard count); `n_nodes` is the
    PRE-PADDING node count those inputs were built from (the probe-clamp
    anchor — see the body's docstring); req/mask chunks are replicated. The carry stays device-resident and SHARDED across
    chunks — chunk boundaries never reassemble the node axis, and per-wave
    cross-shard traffic is O(shards) ring/psum collectives (see the body's
    docstring). Placements are bit-identical to the single-device
    `waterfill_assign_targeted` chunk program at any shard count (below
    the documented 2^53 cumulative-capacity bound).

    `use_pallas`/`pallas_interpret` (None = resolve from `SPT_PALLAS` /
    the backend via `parallel.kernels`) swap the per-wave framework
    collectives for the Pallas ring kernels — bit-identical placements,
    gated by tests/test_differential.py and `make pallas-smoke`."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from scheduler_plugins_tpu.ops.assign import waterfill_targeted_sharded
    from scheduler_plugins_tpu.parallel import kernels as pk
    from scheduler_plugins_tpu.parallel.mesh import NODES_AXIS
    from scheduler_plugins_tpu.parallel.pipeline import donated_chunk_solver
    from scheduler_plugins_tpu.utils import sanitize

    if use_pallas is None:
        # checkify cannot instrument pallas_call bodies — the sanitizer
        # gate keeps certifying the lax formulation, which is placement-
        # identical by the differential gates
        use_pallas = pk.pallas_enabled() and not sanitize.enabled()
    if pallas_interpret is None:
        pallas_interpret = pk.pallas_interpret()
    n_shards = mesh.shape[NODES_AXIS]
    body = partial(
        waterfill_targeted_sharded,
        axis_name=NODES_AXIS, n_shards=n_shards, n_real=n_nodes,
        max_waves=max_waves,
        rescue_window=rescue_window, lite_window=lite_window,
        collect_stats=collect_stats,
        use_pallas=use_pallas, pallas_interpret=pallas_interpret,
    )
    stats_spec = (
        ({"occupancy": P(), "waves": P(), "pallas_sites": P()},)
        if collect_stats else ()
    )
    sharded_body = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(NODES_AXIS, None), P(NODES_AXIS), P(), P()),
        out_specs=(P(), P(NODES_AXIS, None)) + stats_spec,
        check_vma=False,  # ppermute ring + replicated outputs via psum
    )

    def sharded_wave_chunk(node_ids, req_chunk, mask_chunk, rank_free):
        out = sharded_body(rank_free, node_ids, req_chunk, mask_chunk)
        if collect_stats:
            assignment, rank_free, stats = out
            return (assignment, stats), rank_free
        assignment, rank_free = out
        return (assignment,), rank_free

    return donated_chunk_solver(sharded_wave_chunk, carry_argnum=3)


#: built sharded-wave chunk solvers by (mesh, n_nodes, chunk, knobs) — the
#: trace-cache seam `sharded_wave_solve` reuses across calls (jit caches
#: per wrapper object, so rebuilding the wrapper would recompile)
_WAVE_SOLVER_CACHE: dict = {}

#: static collective census per solver identity, computed lazily for
#: tracer-enabled solves only (the merged trace's shard_wave/census row)
_WAVE_CENSUS_CACHE: dict = {}


def sharded_wave_solve(snap, mesh, weights, chunk: int | None = None,
                       max_waves: int = 8, rescue_window: int = 512,
                       collect_stats: bool = False):
    """`batch_solve`'s flagship semantics with the WAVE HOT LOOP sharded:
    admission (gang/quota PreFilter), the static allocatable ranking and
    the finalize tail (queue-order namespace quota prefix + gang quorum
    Permit) are unchanged; placement runs through the shard_map ring-
    election waterfill with the node axis sharded over `mesh` and the free
    carry resident per shard. Pods stream in queue-order chunks (`chunk`
    None = one chunk) with the carry threading device-side, donated.

    Hard constraints (fit, queue-order node admission, quota caps, gang
    quorum) hold exactly at every shard count; placements are bit-
    identical to the single-device wave path below the 2^53 cumulative-
    capacity bound (tests/test_shard_wave.py + tests/test_differential.py
    gate both). Returns (assignment, admitted, wait[, stats]).

    Under `SPT_PALLAS=1` the wave elections run as the `parallel.kernels`
    Pallas ring programs (interpret twins off-TPU) — resolved HERE so the
    solver cache key carries the mode and an env toggle never reuses a
    differently-built program."""
    from scheduler_plugins_tpu.parallel import kernels as pk
    from scheduler_plugins_tpu.parallel.mesh import NODES_AXIS
    from scheduler_plugins_tpu.utils import sanitize

    use_pallas = pk.pallas_enabled() and not sanitize.enabled()
    pallas_interpret = pk.pallas_interpret()
    free0 = free_capacity(snap.nodes.alloc, snap.nodes.requested)
    admitted = batch_admission(snap, free0)
    raw = demote_scores_int32(
        allocatable_scores(snap.nodes.alloc, weights, MODE_LEAST)
    ).astype(jnp.int64)
    n_shards = mesh.shape[NODES_AXIS]
    node_ids, rank_free = rank_order_inputs(
        raw, free0, snap.nodes.mask, n_shards
    )
    P = snap.num_pods
    chunk = P if chunk is None else min(chunk, P)
    if P % chunk != 0:
        raise ValueError(f"pod count {P} not a multiple of chunk {chunk}")
    # memoize the built solver per program identity: a fresh jit wrapper
    # per call would recompile the whole multi-device program on every
    # solve of the same shapes
    key = (mesh, free0.shape[0], chunk, max_waves, rescue_window,
           collect_stats, use_pallas, pallas_interpret)
    solve_chunk = _WAVE_SOLVER_CACHE.get(key)
    if solve_chunk is None:
        solve_chunk = _WAVE_SOLVER_CACHE[key] = sharded_wave_chunk_solver(
            mesh, free0.shape[0], max_waves=max_waves,
            rescue_window=rescue_window, collect_stats=collect_stats,
            use_pallas=use_pallas, pallas_interpret=pallas_interpret,
        )
    tracing = obs.tracer.enabled
    if tracing:
        # one-time static collective census for the merged trace (a
        # make_jaxpr trace per solver identity — cached; tracer-enabled
        # runs only, the hot path never pays it)
        census = _WAVE_CENSUS_CACHE.get(key)
        if census is None:
            with jax.set_mesh(mesh):
                census = _WAVE_CENSUS_CACHE[key] = collective_census(
                    solve_chunk, node_ids, snap.pods.req[:chunk],
                    admitted[:chunk], rank_free,
                )
        obs.tracer.complete(
            "census", obs.tracer.now_ns(), 0, tid="shard_wave",
            args={"shards": n_shards, **census},
        )
    parts, stats_parts = [], []
    with jax.set_mesh(mesh):
        for i, lo in enumerate(range(0, P, chunk)):
            start_ns = obs.tracer.now_ns() if tracing else 0
            out, rank_free = solve_chunk(
                node_ids, snap.pods.req[lo:lo + chunk],
                admitted[lo:lo + chunk], rank_free,
            )
            parts.append(out[0])
            if collect_stats:
                stats_parts.append(out[1])
            if tracing:
                # per-chunk row: host-sync envelope of dispatch through
                # stats transfer, stamped with the chunk's wave counters
                # (device numbers strictly via host transfer — GL008)
                args = {"chunk": i}
                if collect_stats:
                    import numpy as np

                    args["waves"] = int(np.asarray(out[1]["waves"]))
                    occ = [int(x) for x in np.asarray(out[1]["occupancy"])]
                    while len(occ) > 1 and occ[-1] == 0:
                        occ.pop()
                    args["wave_occupancy"] = occ
                obs.tracer.complete(
                    f"chunk[{i}]", start_ns,
                    obs.tracer.now_ns() - start_ns,
                    tid="shard_wave", args=args,
                )
    assignment = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    assignment, wait = finalize_assignment(assignment, snap)
    if collect_stats:
        stats = {
            "occupancy": sum(jnp.asarray(s["occupancy"]) for s in stats_parts),
            "waves": sum(jnp.asarray(s["waves"]) for s in stats_parts),
            "pallas_sites": stats_parts[-1]["pallas_sites"],
        }
        return assignment, admitted, wait, stats
    return assignment, admitted, wait


#: cross-shard collective primitives the census tracks; `all_gather` /
#: `all_to_all` should NEVER appear in the sharded wave program (the ring
#: election's silent degradation mode — graft_lint GL009 is the source-level
#: twin of this jaxpr-level check). `pallas_call` marks one fused ring
#: kernel program (the SPT_PALLAS path); `dma_start` equations inside its
#: body are the neighbor transfers — S-1 per ring, so the census stays the
#: per-wave O(shards) traffic bound in both formulations
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "all_gather", "all_gather_invariant",
    "all_to_all", "pallas_call", "dma_start",
})


def collective_census(fn, *args):
    """{collective primitive: equation count} over the traced `fn(*args)`
    jaxpr, recursing through every sub-jaxpr (jit/shard_map/while/scan/
    cond — and `pallas_call` kernel bodies, whose `dma_start` equations
    are the ring's neighbor transfers). Because the wave loops are
    `lax.while_loop`s, each wave BODY appears exactly once in the jaxpr —
    so the static census directly bounds the PER-WAVE collective count,
    independent of how many waves a solve actually runs:
    tests/test_shard_wave.py asserts it stays O(shards) and that no
    full-axis gather ever appears."""
    from jax import core

    closed = jax.make_jaxpr(fn)(*args)
    counts: dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                counts[name] = counts.get(name, 0) + 1
            for sub in core.jaxprs_in_params(eqn.params):
                walk(getattr(sub, "jaxpr", sub))

    walk(closed.jaxpr)
    return counts
