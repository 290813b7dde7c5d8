"""Plugin trait layer — the tensor equivalent of the `fwk.*Plugin` interfaces.

The reference's extension points receive (pod, nodeInfo) pairs one at a time
(/root/reference/pkg/coscheduling/coscheduling.go:49-55 asserts the interface
set per plugin). Here each extension point is a masked tensor transformation
evaluated inside the jitted solve:

- `admit`       PreFilter verdict for one pod: scalar bool (reject before the
                node sweep).
- `filter`      (N,) node feasibility for one pod.
- `score`       (N,) raw int64 node scores for one pod.
- `normalize`   per-pod transform of the raw scores over feasible nodes.
- `commit`      Reserve: fold the chosen placement into the SolverState carried
                through the scan (quota usage, gang counts, NUMA deductions).
- `queue_key`   host-side QueueSort key for a Pod object (lower sorts first).

All tensor methods run under jit and must be pure; `prepare(meta)` is called
once per snapshot layout so plugins can bake resource-axis-aligned constants
(e.g. the allocatable weight vector).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from flax import struct

from scheduler_plugins_tpu.api import events as ev
from scheduler_plugins_tpu.state.snapshot import ClusterSnapshot, SnapshotMeta


@struct.dataclass
class SolverState:
    """Mutable-across-pods solver state, carried through the assignment scan.

    `free` mirrors NodeInfo leftover capacity; `eq_used` mirrors the
    ElasticQuotaInfos usage map; `gang_scheduled` counts members placed in
    this cycle (assumed, pre-bind) per gang.
    """

    free: jnp.ndarray  # (N, R) int64
    eq_used: Optional[jnp.ndarray] = None  # (Q, R) int64
    gang_scheduled: Optional[jnp.ndarray] = None  # (G,) int32
    #: (G, R) demand placed by each gang earlier in this scan — added back in
    #: the MinResources cluster check (the gang's own pods don't count
    #: against it, core.go:433-467)
    gang_inflight: Optional[jnp.ndarray] = None
    #: (W, N) live placed-pod counts per AppGroup workload — in-cycle
    #: placements must be visible to later pods' network tallies
    net_placed: Optional[jnp.ndarray] = None
    #: (N, Z, R) live NUMA zone availability with in-cycle placements
    #: pessimistically deducted from every reported zone of the chosen node
    #: (cache/store.go:129-160). Carried as FLOAT64 — exact for integer
    #: quantities below 2^53 — so the scan body's feasibility compares and
    #: score divisions run entirely in f64 with no per-step int64
    #: temporaries or conversions (integer division is the slow path on
    #: both backends)
    numa_avail: Optional[jnp.ndarray] = None
    #: (P,) which batch pods have placed so far in this scan — nominee
    #: aggregates drop a nominee the moment it places (upstream removes
    #: assumed pods from the nominated set)
    placed_mask: Optional[jnp.ndarray] = None
    #: (TR, N) live per-(track, NODE) matching-pod counts (track = unique
    #: (selector, topology key) pair): base = assigned matches, in-cycle
    #: placements added by the BUILT-IN commit
    #: (`ops.selectors.commit_tracks`). Node-level so PodTopologySpread's
    #: node-inclusion policies can mask ineligible nodes per (pod,
    #: constraint) at aggregation time.
    sel_counts: Optional[jnp.ndarray] = None
    #: (TR, D) the same counts pre-aggregated per topology DOMAIN —
    #: InterPodAffinity (no node-inclusion policy) reads this directly so
    #: its per-pod checks stay O(1) row gathers; kept in lockstep by the
    #: same built-in commit
    sel_dom_counts: Optional[jnp.ndarray] = None
    #: (E, D) live anti-affinity domain presence: True when a pod carrying
    #: existing-anti term e occupies a node in domain d; built-in commit
    anti_domains: Optional[jnp.ndarray] = None
    #: (E2, D) live symmetric-score carrier counts (existing pods'
    #: preferred/required affinity terms per domain); built-in commit
    sym_counts: Optional[jnp.ndarray] = None
    #: NODE-SPACE VIEWS of the three domain carries above, one row per
    #: table row: (TR, N) / (E, N) / (E2, N) in the tables' own dtypes.
    #: Invariant, after every step of the scan (`tests/
    #: test_domain_node_views.py` holds it): `view[t, n] == table[t,
    #: code[topo[t], n]]` where node n has row t's topology key, 0 / False
    #: where it has not — what InterPodAffinity and PodTopologySpread need
    #: per node, kept current by the built-in commit BY COMPARE instead of
    #: gathered out of the (., D) table at every pod. Derived state with
    #: no static snapshot counterpart: `ops.selectors.attach_node_views`
    #: gathers them once, inside the solve and before its scan, and the
    #: solve drops them from its result; None anywhere else (the readers
    #: then gather, `ops.selectors.domain_at`).
    sel_dom_view: Optional[jnp.ndarray] = None
    anti_view: Optional[jnp.ndarray] = None
    sym_view: Optional[jnp.ndarray] = None
    #: (G2, M) live rank -> node assignment of the rank-aware gang phase
    #: (`gangs.topology.gang_solve_body`): initialized from the resident
    #: assignment (`RankGangState.prev_assigned`, its static snapshot
    #: counterpart per `state.snapshot.CARRY_COUNTERPARTS`) and updated as
    #: gangs place during the gang scan — elastic growth anchors on the
    #: carried rows, never on a re-read of the static tensor. None outside
    #: the gang phase (the per-pod solves do not thread it).
    rank_nodes: Optional[jnp.ndarray] = None


#: cluster events that can free capacity for the framework's built-in
#: resource-fit Filter (upstream NodeResourcesFit EventsToRegister) —
#: kinds from the shared `api.events` table
BUILTIN_EVENTS = (ev.NODE_ADD, ev.NODE_UPDATE, ev.POD_DELETE)


class Plugin:
    """Base plugin: every method is optional; `None` means "not implemented
    at this extension point" and costs nothing in the fused solve."""

    name: str = "Plugin"
    #: score weight, the framework multiplies normalized scores by this
    #: (upstream plugin weights in the profile config).
    weight: int = 1
    #: True when `filter` reads the SolverState carry (its verdict depends
    #: on earlier in-cycle placements). The batched throughput path
    #: (`parallel.solver.profile_batch_solve`) re-evaluates such filters
    #: every wave against the committed carry — a plugin that sets this MUST
    #: implement `commit_batch`, and should implement the `wave_guard` pair
    #: when its filter is a hard resource constraint that same-wave
    #: placements can violate.
    state_dependent_filter: bool = False

    def prepare(self, meta: SnapshotMeta) -> None:
        """Bake per-snapshot-layout constants (resource weights, arg vectors)."""

    def aux(self):
        """Per-cycle array inputs (weight vectors, cost matrices) that must be
        TRACED into the solve rather than closure-captured — jit caches the
        traced program by shape, so closure-captured arrays would be
        constant-folded and silently go stale when config or name<->code
        layouts change between cycles. Return a pytree of arrays or None."""
        return None

    def bind_aux(self, aux) -> None:
        """Called inside the traced solve with this plugin's aux pytree (as
        tracers); tensor methods read `self._aux`. Also clears any traced
        weight override left by a sweep trace (`bind_weight`) so every
        solve body that binds aux starts from the static profile weight —
        a leaked weight tracer from an earlier sweep trace would otherwise
        poison the next program traced against this plugin object."""
        self._aux = aux
        self._weight_t = None

    def bind_weight(self, w) -> None:
        """Traced per-candidate weight override — the tuning sweep's aux
        channel for the ONE config knob the profile format keeps outside
        `aux()` (the score weight, a host int baked at trace time).
        `tuning.sweep` binds each vmapped lane's weight scalar here so K
        candidate weight vectors share one compiled program; None falls
        back to the static `weight`."""
        self._weight_t = w

    @property
    def eff_weight(self):
        """The weight the traced score fold multiplies by: the traced
        override when a sweep bound one, else the static profile int.
        Identical arithmetic either way (int64 scalar times the int64
        normalized column), so a swept lane is bit-identical to a solve
        whose static weight equals that lane's vector."""
        w = getattr(self, "_weight_t", None)
        return self.weight if w is None else w

    def prepare_solve(self, snap: ClusterSnapshot):
        """Called once inside the traced solve, BEFORE the per-pod scan:
        derive loop-invariant tensors from the snapshot (dtype conversions,
        static masks) so they are computed once instead of per scan step.
        Return a pytree (read back via `self._presolve`) or None."""
        return None

    def host_state(self):
        """Cluster-derived host state that `prepare_cluster` bakes into the
        trace and that a flight-recorder bundle cannot rebuild (bundles
        carry the snapshot tensors, not the Cluster object). The recorder
        packs this per plugin at capture time; replay restores it via
        `restore_host_state` after `prepare(meta, None)` so the rebuilt
        plugin traces the SAME specialization the recorded solve did.
        Return a pytree of arrays/scalars or None (nothing to restore)."""
        return None

    def restore_host_state(self, state) -> None:
        """Inverse of `host_state`: re-bake a recorded specialization into
        a rebuilt plugin (utils.flightrec replay/explain paths)."""

    def bind_presolve(self, ctx) -> None:
        """Called inside the traced solve with this plugin's prepare_solve
        result; tensor methods read `self._presolve`."""
        self._presolve = ctx

    def events_to_register(self) -> tuple:
        """EnqueueExtensions: cluster-event kinds ("Resource/Action") that
        may make a pod THIS plugin failed schedulable again — the host loop
        keeps failed pods out of the batch until a registered event (or the
        periodic flush) occurs. Score-only plugins never fail a pod and
        register nothing (upstream EventsToRegister)."""
        return ()

    def static_key(self):
        """Hashable fingerprint of any PYTHON-LEVEL specialization this
        plugin bakes into the trace (static branch selections that cannot be
        traced aux arrays). The runtime keys its jit caches on the tuple of
        these, so changing a specialization retraces instead of silently
        reusing a stale program."""
        return None

    # --- host-side -------------------------------------------------------
    def configure_cluster(self, cluster) -> None:
        """Called by the cycle driver BEFORE the snapshot is taken: plugins
        whose args configure host-side machinery (NRT cache selection, pod
        request-prediction defaults) install it here — the analog of the
        wiring the reference does in each plugin's New()."""

    def queue_key(self, pod, cluster):  # pragma: no cover - trivial default
        """QueueSort key component for `pod`; tuples compare lexicographically."""
        return None

    # --- jitted ----------------------------------------------------------
    def admit(self, state: SolverState, snap: ClusterSnapshot, p):
        """PreFilter: scalar bool verdict for pod index `p` (tracer)."""
        return None

    def filter(self, state: SolverState, snap: ClusterSnapshot, p):
        """Filter: (N,) bool feasibility for pod `p` against current state."""
        return None

    def score(self, state: SolverState, snap: ClusterSnapshot, p):
        """Score: (N,) int64 raw scores for pod `p`."""
        return None

    def static_node_scores(self, snap: ClusterSnapshot):
        """(N,) raw scores when this plugin's `score` is POD-INVARIANT
        against the cycle-initial state — i.e. `score(state0, snap, p)`
        returns the same vector for every p (the reference's allocatable
        scorer rates allocatable capacity, not the pod,
        resource_allocation.go:49-76). Implementing this lets the batched
        solver take the targeted-waterfill fast path (O(P·R) waves, no
        (P, N) score matrix). Must be called after `bind_aux`. Return None
        (default) when scores depend on the pod.

        CONTRACT: the fast path ranks nodes by this RAW vector and never
        calls `normalize` or applies `weight` — only implement it when
        your `normalize` is monotone non-decreasing in the raw score (e.g.
        minmax_normalize) and your configured weight is positive, so the
        raw ordering equals the normalized-weighted ordering."""
        return None

    def normalize(self, scores, feasible):
        """NormalizeScore: transform (N,) raw scores over the feasible mask."""
        return scores

    def commit(self, state: SolverState, snap: ClusterSnapshot, p, choice):
        """Reserve: fold `choice` (node index or -1) into the carried state."""
        return state

    # --- batched whole-matrix variants (parallel.solver) -----------------
    def filter_batch(self, state: SolverState, snap: ClusterSnapshot):
        """(P, N) Filter verdicts for the WHOLE batch against `state`, or
        None to fall back to vmapping `filter` over pods. Implement when
        per-pod verdicts collapse onto equivalence classes (e.g. every pod
        of an AppGroup workload shares one dependency row) so the batched
        solver does O(K·N) work + a gather instead of O(P·N·...). Must be
        bit-identical to the vmapped `filter`."""
        return None

    def score_batch(self, state: SolverState, snap: ClusterSnapshot):
        """(P, N) raw scores for the whole batch, or None to vmap `score`.
        Same class-collapse rationale and bit-identity contract as
        `filter_batch`; `normalize` still runs per pod row."""
        return None

    def filter_rows(self, state: SolverState, snap: ClusterSnapshot, idx):
        """(S, N) Filter verdicts for the `idx` pod rows only against
        `state`, or None to fall back to `filter_batch`/vmapped `filter`.
        Implement when the whole-matrix `filter_batch` is NOT class-
        collapsed (its cost scales with P): a sparse straggler wave then
        re-filters S rows at S/P of the dense cost instead of recomputing
        the full matrix and gathering. Same bit-identity contract as
        `filter` on the selected rows."""
        return None

    def batch_rows(self, state: SolverState, snap: ClusterSnapshot):
        """(filter (P, N) bool | None, scores (P, N) | None) computed in ONE
        pass, or None to fall back to `filter_batch`/`score_batch`.
        Implement when both derive from one shared intermediate (e.g. the
        network dependency tallies) so the batched solver's cycle-initial
        pass pays for it once instead of twice. Each element carries the
        same bit-identity contract as the split hooks."""
        return None

    # --- batched throughput path (parallel.solver) -----------------------
    def commit_batch(self, state: SolverState, snap: ClusterSnapshot,
                     placed, choice):
        """Batched Reserve: fold a whole wave's placements (`placed` (P,)
        bool, `choice` (P,) int32) into the carry in one shot. Must be
        order-independent — the carries this framework uses (zone
        deductions, placement tallies) are sums, so batch == any sequential
        order of per-pod `commit`s. Required iff `state_dependent_filter`."""
        return state

    def wave_guard_demand(self, snap: ClusterSnapshot):
        """(P, R') non-negative per-pod demand in this plugin's admission
        domain, or None when the plugin needs no within-wave guard."""
        return None

    def wave_guard(self, state: SolverState, snap: ClusterSnapshot, p, node,
                   prefix):
        """Exact within-wave admission: True iff pod `p` still passes this
        plugin's filter on `node` after `prefix` (R',) of earlier same-wave
        winners' demand landed there (evaluated against the wave-start
        carry). See `ops.assign.waterfill_assign_stateful`."""
        return jnp.bool_(True)

    def wave_capacity(self, state: SolverState, snap: ClusterSnapshot,
                      active):
        """(N,) per-node capacity ESTIMATE (in pods) under this plugin's
        constraints for the current wave's active set, or None. Only steers
        the waterfill's bucketing (how many queue-ranked pods are SENT to
        each node) — admission stays exact via guards/validators — but a
        tight estimate is what keeps a constrained wave from funneling pods
        onto nodes that can only accept one."""
        return None

    #: overridden (not None) when the plugin's hard filter must be
    #: re-validated pod-by-pod after the batched waterfill: the wave guard
    #: only sees same-NODE conflicts, but domain-counting constraints
    #: (topology spread, inter-pod anti-affinity) span nodes. The batched
    #: solver then runs a sequential demotion scan in queue order calling
    #: this with each placed pod's chosen node — the check is O(1) per pod
    #: (a few gathers), unlike re-running the (N,)-wide filter.
    validate_at = None

    # subclasses override as:
    # def validate_at(self, state, snap, p, node) -> bool:
    #     '''True iff pod `p` still passes this plugin's hard filter on
    #     `node` against the live carry; the scan commits the pod (via
    #     `commit`) only when every validator agrees, else demotes it.'''
