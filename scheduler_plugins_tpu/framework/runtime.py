"""The scheduling cycle: fuse enabled plugins into one jitted batched solve.

Reference dataflow per pending pod (SURVEY.md §1): QueueSort -> PreFilter ->
Filter(xnodes) -> PreScore -> Score(xnodes) -> Normalize -> Reserve -> Permit.
Here the whole pending batch runs as a single `lax.scan` whose body evaluates
every enabled plugin's tensor contribution for one pod against the carried
SolverState (free capacity, quota usage, gang counts), then commits the chosen
node before the next pod — preserving the reference's one-pod-at-a-time
semantics while keeping each step fully vectorized over nodes.

Permit is evaluated after the scan as a segment reduction over gangs
(quorum = assigned-before + scheduled-this-cycle >= MinMember), mirroring
/root/reference/pkg/coscheduling/core/core.go:308-345; the host shell
(`Scheduler.schedule`) then binds, parks, or rejects.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import struct

from scheduler_plugins_tpu.framework.plugin import Plugin, SolverState
from scheduler_plugins_tpu.ops import selectors
from scheduler_plugins_tpu.ops.fit import fits_one, free_capacity, pod_fit_demand
from scheduler_plugins_tpu.state.snapshot import ClusterSnapshot, SnapshotMeta
from scheduler_plugins_tpu.utils import observability as obs

#: attribution name for failures owned by the FRAMEWORK, not a profile
#: plugin: scheduling gates, resource-fit exhaustion, wave-capacity
#: exhaustion in the batched path (the upstream built-in fit plugin name)
BUILTIN_FIT = "NodeResourcesFit"


@struct.dataclass
class SolveResult:
    assignment: jnp.ndarray  # (P,) int32 node index, -1 unschedulable
    admitted: jnp.ndarray  # (P,) bool PreFilter verdict
    wait: jnp.ndarray  # (P,) bool Permit said Wait (gang quorum unmet)
    state: SolverState  # final carried state
    #: (P,) int32 unschedulability attribution, the upstream
    #: `UnschedulablePlugins` signal per pod: -1 = placed; 0 = built-in
    #: (gated, or resource fit exhausted against the carried free
    #: capacity); 1+i = profile plugin i (its PreFilter rejected the pod,
    #: or its Filter first emptied the remaining feasible node set in
    #: profile order). Decoded via `Scheduler.fail_plugin_names`.
    failed_plugin: Optional[jnp.ndarray] = None


def solve_output_anomaly(assignment, admitted, wait, n_nodes: int):
    """Reason string when solve outputs violate the framework contract,
    else None — integer (P,) assignment in [-1, n_nodes), matching-shape
    admitted/wait, no NaNs. THE one statement of the output contract:
    the resilience watchdog (`resilience.watchdog`) runs it after every
    device solve's completion fence to classify garbage output (a
    desynced backend answers with plausible-length junk) as a backend
    fault rather than committing it."""
    import numpy as np

    a = np.asarray(assignment)
    if a.ndim != 1 or not np.issubdtype(a.dtype, np.integer):
        return f"assignment dtype/rank {a.dtype}/{a.ndim}"
    if a.size and (int(a.min()) < -1 or int(a.max()) >= n_nodes):
        return (
            f"assignment out of range [{int(a.min())}, {int(a.max())}] "
            f"vs {n_nodes} nodes"
        )
    for name, arr in (("admitted", admitted), ("wait", wait)):
        x = np.asarray(arr)
        if x.shape != a.shape:
            return f"{name} shape {x.shape} != assignment {a.shape}"
        if np.issubdtype(x.dtype, np.floating) and np.isnan(x).any():
            return f"NaN in {name}"
    return None


def _admit_with_attribution(plugins, state, snap, p, ok0):
    """PreFilter sweep with attribution: (ok, admit_code) where
    `admit_code` is the FIRST plugin (profile order) whose verdict flipped
    the pod inadmissible, -1 when none did — the upstream
    UnschedulablePlugins attribution at PreFilter. THE one copy of the
    attribution ordering, shared by the sequential scan and the batched
    reduction (`Scheduler.attribution_codes`) so the two cannot drift."""
    ok = ok0
    admit_code = jnp.int32(-1)
    for i, plugin in enumerate(plugins):
        verdict = plugin.admit(state, snap, p)
        if verdict is not None:
            admit_code = jnp.where(
                (admit_code < 0) & ok & ~verdict, jnp.int32(i), admit_code
            )
            ok &= verdict
    return ok, admit_code


def _filter_with_attribution(plugins, state, snap, p, fit0, rows=None):
    """Filter chain with attribution: (feasible, filter_code) where
    `filter_code` is the first plugin whose Filter emptied the
    still-feasible node set, -1 when none did. Shared like
    `_admit_with_attribution`. `rows` (plugin position -> precomputed
    (P, N) verdict rows, the batched solver's class-collapsed
    `filter_batch`/`batch_rows` outputs) substitutes `rows[i][p]` for the
    per-pod `filter` call — how `parallel.solver.batch_explain_rows`
    derives the batched explain through THIS same chain, so the two
    explain paths cannot drift."""
    feasible = fit0
    alive = fit0.any()
    filter_code = jnp.int32(-1)
    for i, plugin in enumerate(plugins):
        if rows is not None and i in rows:
            mask = rows[i][p]
        else:
            mask = plugin.filter(state, snap, p)
        if mask is not None:
            feasible &= mask
            now_alive = feasible.any()
            filter_code = jnp.where(
                (filter_code < 0) & alive & ~now_alive,
                jnp.int32(i), filter_code,
            )
            alive = now_alive
    return feasible, filter_code


def _free_with_nominee_holds(state, snap, p):
    """Effective free capacity pod `p`'s built-in fit sees: nominated
    pods' demand holds capacity against lower-or-equal-priority pods
    (upstream AddNominatedPods; the pod's own batch row excluded, and a
    batch nominee stops holding once placed). Shared by the sequential
    solve step and the explain body (`_explain_one`) so the explain
    surface reproduces exactly the fit the parity path enforced."""
    if snap.nominees is None:
        return state.free
    nm = snap.nominees
    live = (
        nm.mask
        & (nm.priority >= snap.pods.priority[p])
        & (nm.batch_idx != p)
    )
    if state.placed_mask is not None:
        placed_in_batch = (nm.batch_idx >= 0) & state.placed_mask[
            jnp.maximum(nm.batch_idx, 0)
        ]
        live &= ~placed_in_batch
    hold = jnp.zeros_like(state.free).at[
        jnp.maximum(nm.node, 0)
    ].add(jnp.where(live[:, None], nm.demand, 0))
    return state.free - hold


def _score_columns(plugins, state, snap, p, feasible, rows=None):
    """((L, N) int64 per-plugin weighted normalized score columns,
    (N,) int64 total) for pod `p` — THE one copy of the explain score
    decomposition. Each column is exactly the `weight * normalize(raw,
    feasible)` term the solve step folds into its total, so the columns
    sum to the solver's node score by construction; plugins without a
    Score contribute a zero column (the upstream score dump lists every
    scoring plugin). `rows` substitutes the batched solver's
    class-collapsed `score_batch`/`batch_rows` rows for the per-pod
    `score` call (same drift guarantee as `_filter_with_attribution`)."""
    N = state.free.shape[0]
    cols = []
    total = jnp.zeros(N, jnp.int64)
    for i, plugin in enumerate(plugins):
        if rows is not None and i in rows:
            raw = rows[i][p]
        else:
            raw = plugin.score(state, snap, p)
        if raw is None:
            cols.append(jnp.zeros(N, jnp.int64))
            continue
        col = (plugin.eff_weight * plugin.normalize(raw, feasible)).astype(
            jnp.int64
        )
        cols.append(col)
        total = total + col
    return jnp.stack(cols), total


def _explain_one(plugins, state0, snap, p, filter_rows=None, score_rows=None):
    """Explain body for one pod against the cycle-initial state: admit +
    attribution, built-in fit + margin, the filter chain, and the
    per-plugin score columns — shared (via the `*_rows` overrides) by the
    sequential and batched explain entries."""
    ok0 = snap.pods.mask[p] & ~snap.pods.gated[p]
    ok, admit_code = _admit_with_attribution(plugins, state0, snap, p, ok0)
    demand = pod_fit_demand(snap.pods.req[p])
    # built-in fit margin: the binding resource's headroom (min over the
    # axis of effective free - demand, nominee holds included — the same
    # capacity the solve step fits against); masked nodes get the sentinel
    free_eff = _free_with_nominee_holds(state0, snap, p)
    margin = jnp.min(free_eff - demand[None, :], axis=1)
    margin = jnp.where(snap.nodes.mask, margin, jnp.int64(-(2 ** 62)))
    fit0 = fits_one(snap.pods.req[p], free_eff, snap.nodes.mask)
    feasible, filter_code = _filter_with_attribution(
        plugins, state0, snap, p, fit0, rows=filter_rows
    )
    feasible &= ok
    columns, total = _score_columns(
        plugins, state0, snap, p, feasible, rows=score_rows
    )
    fail_code = _encode_fail(
        ok0, admit_code, fit0.any(), filter_code, jnp.int32(-1)
    )
    return ok, fail_code, feasible, margin, columns, total


def run_explain_rows(scheduler, snap, indices, auxes, program, explain_fn):
    """Shared plumbing for the two explain entries (`Scheduler
    .explain_rows` sequential, `parallel.solver.batch_explain_rows`
    batched): power-of-two index-bucket padding (bounded retraces, like
    `attribution_codes`), the per-static_key jit cache with compile
    attribution, aux binding defaults, and the host transfer + slice-to-S
    packaging of `_explain_one`'s outputs. The entries define ONLY
    `explain_fn(snap, state0, auxes, idx)` — where the per-plugin rows
    come from — so their output contract cannot drift."""
    import numpy as np

    plugins = tuple(scheduler.profile.plugins)
    idx = np.asarray(indices, np.int32)
    if idx.size == 0:
        N = snap.num_nodes
        L = max(len(plugins), 1)
        return {
            "admitted": np.zeros(0, bool),
            "fail_code": np.zeros(0, np.int32),
            "feasible": np.zeros((0, N), bool),
            "fit_margin": np.zeros((0, N), np.int64),
            "columns": np.zeros((0, L, N), np.int64),
            "total": np.zeros((0, N), np.int64),
        }
    bucket = 1 << int(idx.size - 1).bit_length()
    idx_padded = np.full(bucket, idx[0], np.int32)
    idx_padded[: idx.size] = idx
    # weight tuple in the key: explain bakes `eff_weight` host ints into
    # its trace — a live-weight swap (Scheduler.set_live_weights) must
    # retrace this cold path, not serve stale-weight score columns
    key = (program,) + scheduler.weights_key() + tuple(
        p.static_key() for p in plugins
    )
    cache = scheduler._solve_cache
    if key not in cache:
        cache[key] = obs.compile_watch(jax.jit(explain_fn), program=program)
    if auxes is None:
        auxes = tuple(p.aux() for p in plugins)
    out = cache[key](
        snap, scheduler.initial_state(snap), auxes, jnp.asarray(idx_padded)
    )
    ok, fail, feasible, margin, columns, total = (
        np.asarray(x)[: idx.size] for x in out
    )
    return {
        "admitted": ok,
        "fail_code": fail,
        "feasible": feasible,
        "fit_margin": margin,
        "columns": columns,
        "total": total,
    }


def _encode_fail(ok0, admit_code, fit0_any, filter_code, fallback):
    """Merge the stage attributions into one code (see
    `SolveResult.failed_plugin`): PreFilter rejections name their plugin
    first (upstream runs PreFilter before the node sweep), then built-in
    fit, then the first Filter plugin that emptied the feasible set, then
    `fallback` (0 = built-in for the sequential scan, where reaching it
    means in-cycle capacity exhaustion; -1 = "feasible cycle-initially"
    for the batched reduction)."""
    return jnp.where(
        ~ok0,
        jnp.int32(0),
        jnp.where(
            admit_code >= 0,
            admit_code + 1,
            jnp.where(
                ~fit0_any,
                jnp.int32(0),
                jnp.where(filter_code >= 0, filter_code + 1, fallback),
            ),
        ),
    )


def _solve_step(plugins, carry, p, snap: ClusterSnapshot, view_codes=None):
    """One pod of the bit-faithful sequential scan: PreFilter -> built-in
    fit (nominee holds) -> Filter chain -> Score/Normalize weighted sum ->
    argmax select -> Reserve commits — THE parity-path step body, shared by
    `Scheduler.solve`, the vmapped counterfactual sweep
    (`parallel.solver.sweep_solve_fn`) and the K-lane speculative solve
    (`parallel.lanes.lane_solve_fn`, which feeds it a one-pod snapshot
    view per step), so no fast path can drift from the parity program.
    `view_codes`: the code rows of the carry's node-space views
    (`ops.selectors.attach_node_views`), from a scan that carries views."""
    state = carry
    # PreFilter, with per-plugin attribution (shared helper)
    ok0 = snap.pods.mask[p] & ~snap.pods.gated[p]
    ok, admit_code = _admit_with_attribution(
        plugins, state, snap, p, ok0
    )
    # Filter: built-in resource fit (nominee capacity holds
    # included — see _free_with_nominee_holds) + plugin filters
    free_eff = _free_with_nominee_holds(state, snap, p)
    fit0 = fits_one(snap.pods.req[p], free_eff, snap.nodes.mask)
    # Filter chain with attribution (shared helper) — exact
    # against the CARRIED state: the parity path's ground truth
    feasible, filter_code = _filter_with_attribution(
        plugins, state, snap, p, fit0
    )
    feasible &= ok
    # Score + Normalize, weighted sum (eff_weight: the static profile int,
    # or the traced per-candidate scalar a sweep lane bound)
    total = jnp.zeros(state.free.shape[0], jnp.int64)
    for plugin in plugins:
        raw = plugin.score(state, snap, p)
        if raw is not None:
            total = total + plugin.eff_weight * plugin.normalize(raw, feasible)
    # select: argmax score among feasible, lowest index tie-break
    masked = jnp.where(feasible, total, jnp.int64(-(2**62)))
    choice = jnp.where(
        feasible.any(), jnp.argmax(masked).astype(jnp.int32), jnp.int32(-1)
    )
    # built-in Reserve: commit capacity
    demand = pod_fit_demand(snap.pods.req[p])
    onehot = (jnp.arange(state.free.shape[0]) == choice)[:, None]
    state = state.replace(
        free=state.free - jnp.where(choice >= 0, onehot * demand[None, :], 0)
    )
    if state.placed_mask is not None:
        state = state.replace(
            placed_mask=state.placed_mask.at[p].set(choice >= 0)
        )
    if snap.scheduling is not None:
        # built-in: selector/domain carries are shared by multiple
        # plugins (spread, inter-pod affinity) — commit once
        state = selectors.commit_tracks(
            state, snap.scheduling, p, choice, view_codes
        )
    for plugin in plugins:
        state = plugin.commit(state, snap, p, choice)
    # attribution code (SolveResult.failed_plugin); fallback 0:
    # a failed pod that no stage rejected lost to in-cycle
    # capacity consumption -> built-in fit
    fail_code = jnp.where(
        choice >= 0,
        jnp.int32(-1),
        _encode_fail(ok0, admit_code, fit0.any(), filter_code,
                     jnp.int32(0)),
    )
    return state, (choice, ok, fail_code)


def sequential_solve_body(plugins, snap: ClusterSnapshot,
                          state0: SolverState, auxes, unroll: int = 1,
                          weights=None) -> SolveResult:
    """The traced sequential parity solve over one snapshot: bind aux (and
    optionally a traced (L,) per-plugin `weights` vector — the tuning
    sweep's counterfactual channel), hoist presolves, scan `_solve_step`,
    reduce gang quorum. `Scheduler._make_solve` jits this with
    weights=None; `parallel.solver.sweep_solve_fn` vmaps it over K weight
    vectors so every candidate shares one compile."""
    # bind per-plugin traced aux inputs (weight vectors, cost
    # matrices) so they are solve ARGUMENTS, not baked constants
    for plugin, aux in zip(plugins, auxes):
        plugin.bind_aux(aux)  # also clears any stale weight override
    if weights is not None:
        for i, plugin in enumerate(plugins):
            plugin.bind_weight(weights[i])
    # loop-invariant per-solve precomputes (hoisted out of the scan)
    for plugin in plugins:
        plugin.bind_presolve(plugin.prepare_solve(snap))
    # the domain tables' node-space views: gathered here, once a solve,
    # kept by compare in the scan's built-in commit, dropped from the result
    state0, codes = selectors.attach_node_views(state0, snap.scheduling)
    P = snap.num_pods
    state, (assignment, admitted, failed_plugin) = jax.lax.scan(
        lambda c, p: _solve_step(plugins, c, p, snap, codes), state0,
        jnp.arange(P), unroll=unroll,
    )
    state = selectors.drop_node_views(state)
    wait = jnp.zeros(P, bool)
    if snap.gangs is not None and state.gang_scheduled is not None:
        # Permit quorum: previously-assigned + this cycle's placements
        total_per_gang = snap.gangs.assigned + state.gang_scheduled
        quorum = total_per_gang >= snap.gangs.min_member
        gang = snap.pods.gang
        in_gang = gang >= 0
        pod_quorum = jnp.where(
            in_gang, quorum[jnp.maximum(gang, 0)], True
        )
        wait = (assignment >= 0) & ~pod_quorum
    return SolveResult(
        assignment=assignment, admitted=admitted, wait=wait,
        state=state, failed_plugin=failed_plugin,
    )


#: the solve modes a profile may select (`Profile.solve_mode`): the
#: bit-faithful sequential parity scan (default), or the packing
#: optimizer — wave placement + iterative consolidation refinement
#: (`parallel.solver.packing_profile_solve`; docs/PACKING.md). The wave
#: throughput path stays caller-selected (stream_chunk / the batched
#: entries), not a profile mode — it has no per-profile knobs.
SOLVE_MODES = ("sequential", "packing")


@dataclass
class PackingConfig:
    """Knobs of the packing solve mode (docs/PACKING.md). All of
    `iterations` / `price_weight` / `temperature` / `decay` ride the
    traced `aux()` vector (CLAUDE.md aux-channel discipline — one
    compile, tunable online); `mover_cap` is a static shape knob."""

    #: refinement-round budget (0 = the wave placement bit-identically)
    iterations: int = 32
    #: weight of the fragmentation price vs the score term in each bid
    price_weight: float = 4.0
    #: initial minimum fill edge a target must have over the donor
    temperature: float = 0.0
    #: per-round multiplicative temperature decay, in (0, 1]
    decay: float = 0.5
    #: static per-round mover-window width
    mover_cap: int = 128

    def __post_init__(self):
        if self.iterations < 0:
            raise ValueError("packing iterations must be >= 0")
        if int(self.iterations) != self.iterations:
            # the jax build floors the traced budget to match the numpy
            # twin — reject fractional config values instead of silently
            # rounding a tuner's proposal
            raise ValueError(
                f"packing iterations must be integral, got "
                f"{self.iterations!r}"
            )
        if self.price_weight < 0:
            raise ValueError("packing priceWeight must be >= 0")
        if self.temperature < 0:
            raise ValueError("packing temperature must be >= 0")
        if not 0 < self.decay <= 1:
            raise ValueError("packing decay must be in (0, 1]")
        if self.mover_cap < 1:
            raise ValueError("packing moverCap must be >= 1")

    def aux(self):
        """The (4,) traced float64 knob vector (`ops.packing`)."""
        from scheduler_plugins_tpu.ops.packing import pack_aux_vector

        return pack_aux_vector(
            self.iterations, self.price_weight, self.temperature,
            self.decay,
        )


@dataclass
class Profile:
    """An enabled-plugin set, the equivalent of one KubeSchedulerConfiguration
    profile (SURVEY.md §5 config system)."""

    plugins: Sequence[Plugin] = field(default_factory=list)
    #: queue-sort plugin; None selects the first enabled plugin that overrides
    #: `queue_key` (a profile enables exactly one QueueSort upstream), falling
    #: back to upstream PrioritySort semantics
    queue_sort: Optional[Plugin] = None
    #: PostFilter preemption engine; None auto-selects from the enabled
    #: plugins (CapacityScheduling -> quota-aware preemption,
    #: PreemptionToleration -> default preemption with toleration)
    preemption: Optional[object] = None
    name: str = "tpu-scheduler"
    #: which solve serves this profile's cycles (`SOLVE_MODES`);
    #: "sequential" is the bit-faithful parity path every differential
    #: gate anchors on, "packing" opts into the consolidation optimizer
    solve_mode: str = "sequential"
    #: packing-mode knobs (ignored under other modes)
    packing: PackingConfig = field(default_factory=PackingConfig)

    def __post_init__(self):
        if self.solve_mode not in SOLVE_MODES:
            raise ValueError(
                f"unknown solve mode {self.solve_mode!r}; "
                f"expected one of {SOLVE_MODES}"
            )
        if self.queue_sort is None:
            for plugin in self.plugins:
                if type(plugin).queue_key is not Plugin.queue_key or hasattr(
                    plugin, "queue_compare"
                ):
                    self.queue_sort = plugin
                    break
        if self.preemption is None:
            for plugin in self.plugins:
                if hasattr(plugin, "preemption_engine"):
                    self.preemption = plugin.preemption_engine()
                    break


class Scheduler:
    """Host shell around the jitted solve.

    Owns nothing but the profile; cluster state comes in as a snapshot and
    decisions go back to the caller (the `state.cluster.Cluster` store drives
    bind/park/reject)."""

    def __init__(self, profile: Profile):
        self.profile = profile
        self._solve_cache = {}
        #: (L,) int64 live per-plugin weight vector, or None (static
        #: profile weights). Set via `set_live_weights` — the online
        #: tuner's rollout seam (ISSUE 15).
        self._live_weights = None

    # -- queue ----------------------------------------------------------
    def sort_pending(self, pods, cluster=None):
        """QueueSort: order the pending list with the profile's comparator
        (default: upstream PrioritySort — priority desc, then queue time).
        Plugins exposing a pairwise `queue_compare` (TopologicalSort) are
        used via cmp_to_key, preserving exact Less() semantics."""
        qs = self.profile.queue_sort
        qs_name = qs.name if qs is not None else "PrioritySort"
        with obs.extension_span("QueueSort", qs_name, pods=len(pods)):
            if qs is not None and hasattr(qs, "queue_compare"):
                import functools

                return sorted(
                    pods,
                    key=functools.cmp_to_key(
                        lambda a, b: qs.queue_compare(a, b, cluster)
                    ),
                )

            def key(pod):
                if qs is not None:
                    k = qs.queue_key(pod, cluster)
                    if k is not None:
                        return k
                return (
                    -pod.priority, pod.creation_ms,
                    f"{pod.namespace}/{pod.name}",
                )

            return sorted(pods, key=key)

    # -- solve ----------------------------------------------------------
    def prepare(self, meta: SnapshotMeta, cluster=None):
        for plugin in self.profile.plugins:
            with obs.extension_span("Prepare", plugin.name):
                plugin.prepare(meta)
                if hasattr(plugin, "prepare_cluster"):
                    plugin.prepare_cluster(meta, cluster)

    # -- live weights (the online tuner's rollout seam) -----------------
    @property
    def live_weights(self):
        """The (L,) int64 live weight vector, or None when the static
        profile weights rule."""
        return self._live_weights

    def set_live_weights(self, weights) -> None:
        """Swap the profile's per-plugin score weights LIVE, with zero
        recompiles on the hot path (ISSUE 15 / ROADMAP item 2): while a
        live vector is set, `solve` routes through the "solve_live"
        program, whose weights are a TRACED (L,) argument bound per
        plugin via `Plugin.bind_weight` — the aux-channel discipline
        applied to the one profile knob the config format keeps
        host-side, exactly like the counterfactual sweep's lanes
        (`parallel.solver.sweep_solve_fn`), so every subsequent swap or
        rollback is an argument change, never a retrace. The plugins'
        host `weight` ints are updated in lockstep so every host-side
        consumer (the degraded-mode `resilience.hostsolve` parity solve,
        the flight recorder's capture, the explain tables — whose cold
        jit caches key on the weight tuple) sees the same vector the
        traced solve multiplies by. `None` reverts to the static profile
        weights (the original profile ints are NOT restored — pass the
        incumbent vector explicitly to roll back)."""
        import numpy as np

        if weights is None:
            self._live_weights = None
            return
        w = np.asarray(weights, np.int64)
        if w.shape != (len(self.profile.plugins),):
            raise ValueError(
                f"live weights shape {w.shape} != "
                f"({len(self.profile.plugins)},)"
            )
        if (w < 1).any():
            raise ValueError("live weights must be positive (the solve "
                             "contracts require positive weights)")
        self._live_weights = w
        for plugin, wi in zip(self.profile.plugins, w):
            plugin.weight = int(wi)
        self._evict_stale_weight_programs()

    def weights_key(self) -> tuple:
        """The marked host weight tuple — folded into the jit-cache keys
        of every program that BAKES `plugin.weight` as a trace constant
        (explain, profile scores, the batched/packing solvers), so a
        live-weight swap retraces those cold paths instead of silently
        serving scores computed under stale weights. The hot sequential
        path never pays this: its live variant traces weights as an
        argument. The "weights" marker makes the segment locatable in
        the flat cache-key tuples so `set_live_weights` can EVICT
        stale-weight entries — without eviction a long-tuning daemon
        would accumulate one permanent compiled program per historical
        weight vector per cold path."""
        return ("weights",) + tuple(
            int(p.weight) for p in self.profile.plugins
        )

    def _evict_stale_weight_programs(self) -> None:
        """Drop cached programs keyed on a weight tuple other than the
        current one (see `weights_key`) — bounds the cold-path cache at
        one entry per program under live tuning."""
        current = self.weights_key()
        span = len(self.profile.plugins) + 1
        for key in list(self._solve_cache):
            if not isinstance(key, tuple) or "weights" not in key:
                continue
            i = key.index("weights")
            if key[i:i + span] != current:
                del self._solve_cache[key]

    def _make_solve(self, unroll: int, live: bool = False):
        plugins = tuple(self.profile.plugins)

        if live:
            def solve_live(
                snap: ClusterSnapshot, state0: SolverState, auxes, weights
            ) -> SolveResult:
                return sequential_solve_body(
                    plugins, snap, state0, auxes, unroll, weights=weights
                )

            return jax.jit(solve_live)

        def solve(
            snap: ClusterSnapshot, state0: SolverState, auxes
        ) -> SolveResult:
            return sequential_solve_body(plugins, snap, state0, auxes, unroll)

        return jax.jit(solve)

    def _scan_unroll(self) -> int:
        """Scan unroll factor: 8 on a TPU, to amortize per-step loop
        overhead (not measured on a chip yet — ROADMAP A6); the body stays
        strictly one-pod-at-a-time (bit-faithful). CPU (tests) keeps 1 —
        extra compile time buys nothing there. SPT_SCAN_UNROLL overrides
        for tuning — read host-side per solve and folded into the
        trace-cache key, so changing it retraces instead of being silently
        baked."""
        import os

        raw = os.environ.get("SPT_SCAN_UNROLL")
        if raw is None:
            return 8 if jax.default_backend() == "tpu" else 1
        try:
            unroll = int(raw)
        except ValueError:
            raise ValueError(f"SPT_SCAN_UNROLL={raw!r} is not an integer")
        if unroll < 1:
            raise ValueError(f"SPT_SCAN_UNROLL must be >= 1, got {unroll}")
        return unroll

    def solve(self, snap: ClusterSnapshot, state0: Optional[SolverState] = None,
              auxes=None, mode: Optional[str] = None):
        """Run the fused plugin pipeline over the snapshot's pending batch.
        `auxes` overrides the per-plugin traced aux pytrees (normally
        recomputed from the prepared plugins) — the flight-recorder replay
        path (`tools/replay.py`) force-binds the RECORDED arrays so the
        solve consumes exactly what the recorded cycle saw.

        `mode` selects the solve (None = the profile's `solve_mode`):
        "sequential" is the bit-faithful parity scan below; "packing"
        dispatches to `parallel.solver.packing_profile_solve` (wave
        placement + consolidation refinement, docs/PACKING.md) and
        returns its `PackingSolveView` (assignment/admitted/wait, no
        SolverState carry). Replay/differential callers that NEED the
        parity semantics pass mode="sequential" explicitly so a packing
        profile can never change what they certify."""
        if mode is None:
            mode = self.profile.solve_mode
        if mode == "packing":
            from scheduler_plugins_tpu.parallel.solver import (
                packing_profile_solve,
            )

            if self._live_weights is not None:
                # the packing waves rank on a single scoring plugin's
                # static scores (weight-invariant argmax), but its bid
                # arithmetic has no traced-weight channel — refuse
                # rather than silently ignore a live vector
                raise ValueError(
                    "live weights require the sequential parity path "
                    "(profile solve_mode 'packing' has no traced-weight "
                    "channel)"
                )
            if auxes is not None:
                raise ValueError(
                    "auxes= replay override requires the sequential "
                    "parity path (pass mode='sequential')"
                )
            if state0 is not None:
                # same rule as auxes: the packing solve builds its own
                # donation-safe initial state — silently dropping a
                # caller-prepared carry would solve against different
                # state than the caller intended
                raise ValueError(
                    "state0= requires the sequential parity path "
                    "(pass mode='sequential')"
                )
            return packing_profile_solve(
                self, snap, mover_cap=self.profile.packing.mover_cap
            )
        if mode != "sequential":
            raise ValueError(f"unknown solve mode {mode!r}")
        if state0 is None:
            state0 = self.initial_state(snap)
        if auxes is None:
            auxes = tuple(plugin.aux() for plugin in self.profile.plugins)
        if selectors.has_domain_tables(snap.scheduling):
            # static per compiled shape: this program reads node-space
            # views of its domain tables (`ops.selectors`)
            obs.metrics.inc(obs.SOLVE_NODE_VIEWS)
        unroll = self._scan_unroll()
        live = self._live_weights
        if live is not None:
            # the live-weights variant: ONE compile per (unroll,
            # static_key) like the static program, with the weight
            # vector a traced argument — promotions and rollbacks are
            # argument changes, zero recompiles (the aux discipline)
            key = ("solve_live", unroll) + tuple(
                plugin.static_key() for plugin in self.profile.plugins
            )
            if key not in self._solve_cache:
                self._solve_cache[key] = obs.compile_watch(
                    self._make_solve(unroll, live=True), program="solve_live"
                )
            return self._solve_cache[key](
                snap, state0, auxes, jnp.asarray(live)
            )
        key = ("solve", unroll) + tuple(
            plugin.static_key() for plugin in self.profile.plugins
        )
        if key not in self._solve_cache:
            self._solve_cache[key] = obs.compile_watch(
                self._make_solve(unroll), program="solve"
            )
        return self._solve_cache[key](snap, state0, auxes)

    def filter_verdicts(self, snap: ClusterSnapshot, pod_index: int):
        """(N,) AND of the enabled plugins' Filter verdicts for one pod
        against the cycle-initial state (resource fit excluded — callers
        handle capacity themselves). Used by the preemption dry run, which
        mirrors RunFilterPluginsWithNominatedPods: plugin filters see the
        CURRENT cache state, exactly as the reference's re-filter does
        (removing victims from the NodeInfo does not change e.g. the NRT
        cache view the TopologyMatch filter reads)."""
        plugins = tuple(self.profile.plugins)
        key = ("filter_verdicts",) + tuple(p.static_key() for p in plugins)
        if key not in self._solve_cache:

            def verdicts(snap, state0, auxes, p):
                for plugin, aux in zip(plugins, auxes):
                    plugin.bind_aux(aux)
                # presolve deliberately NOT bound: it precomputes whole-batch
                # tensors to amortize a P-step scan, but this entry evaluates
                # ONE pod — the plugins' per-row fallbacks are cheaper here
                for plugin in plugins:
                    plugin.bind_presolve(None)
                feasible = jnp.ones(snap.num_nodes, bool)
                for plugin in plugins:
                    mask = plugin.filter(state0, snap, p)
                    if mask is not None:
                        feasible &= mask
                return feasible

            self._solve_cache[key] = obs.compile_watch(
                jax.jit(verdicts), program="filter_verdicts"
            )
        auxes = tuple(plugin.aux() for plugin in plugins)
        return self._solve_cache[key](
            snap, self.initial_state(snap), auxes, pod_index
        )

    # -- attribution / explain ------------------------------------------
    def fail_plugin_names(self) -> list:
        """Decoder for attribution codes (`SolveResult.failed_plugin` /
        `attribution_codes`): code 0 (and any negative code on a failed
        pod) -> the built-in fit, code 1+i -> profile plugin i."""
        return [BUILTIN_FIT] + [p.name for p in self.profile.plugins]

    def attribution_codes(self, snap: ClusterSnapshot, indices):
        """(len(indices),) int32 unschedulability attribution for the
        `indices` pod rows against the CYCLE-INITIAL state — the batched
        paths' reduction of the per-plugin PreFilter verdicts and Filter
        masks they already evaluate (profile_batch_fn's per_pod pass
        computes exactly these masks; this entry re-derives them through
        the SAME shared helpers as the sequential scan so the two cannot
        drift). Only the failed rows are evaluated — the working set is
        (S, N) for S failures, never the (P, N) batch the streamed
        pipeline exists to avoid — and the row index vector is padded to
        a power-of-two bucket so jit retraces stay bounded.

        Encoding matches `SolveResult.failed_plugin`, except -1 here means
        "feasible cycle-initially": a failed pod with code -1 lost to
        in-cycle capacity consumption and decodes to the built-in fit
        (cycle.py maps code <= 0 -> built-in). For the sequential parity
        path the in-solve codes (exact against the carried state) take
        precedence; this entry is the fallback for solve paths without
        one."""
        import numpy as np

        plugins = tuple(self.profile.plugins)
        idx = np.asarray(indices, np.int32)
        if idx.size == 0:
            return np.zeros(0, np.int32)
        bucket = 1 << int(idx.size - 1).bit_length()
        idx_padded = np.full(bucket, idx[0], np.int32)
        idx_padded[: idx.size] = idx
        key = ("attribution",) + tuple(p.static_key() for p in plugins)
        if key not in self._solve_cache:

            def codes(snap, state0, auxes, idx):
                for plugin, aux in zip(plugins, auxes):
                    plugin.bind_aux(aux)
                for plugin in plugins:
                    plugin.bind_presolve(plugin.prepare_solve(snap))

                def one(p):
                    ok0 = snap.pods.mask[p] & ~snap.pods.gated[p]
                    ok, admit_code = _admit_with_attribution(
                        plugins, state0, snap, p, ok0
                    )
                    fit0 = fits_one(
                        snap.pods.req[p], state0.free, snap.nodes.mask
                    )
                    feasible, filter_code = _filter_with_attribution(
                        plugins, state0, snap, p, fit0
                    )
                    return _encode_fail(
                        ok0, admit_code, fit0.any(), filter_code,
                        jnp.int32(-1),
                    )

                return jax.vmap(one)(idx)

            self._solve_cache[key] = obs.compile_watch(
                jax.jit(codes), program="attribution"
            )
        auxes = tuple(plugin.aux() for plugin in plugins)
        out = self._solve_cache[key](
            snap, self.initial_state(snap), auxes, jnp.asarray(idx_padded)
        )
        return np.asarray(out)[: idx.size]

    def explain_rows(self, snap: ClusterSnapshot, indices, auxes=None):
        """Per-plugin score decomposition for the `indices` pod rows
        against the CYCLE-INITIAL state — the "why this node" surface
        behind `CycleReport.explain`, the daemon's `/explain?uid=` and
        `tools/replay.py explain` (the upstream `--v=10` per-plugin score
        dump). Row work is (S, N) for S requested rows, padded to a
        power-of-two bucket like `attribution_codes` so retraces stay
        bounded; `auxes` force-binds recorded config arrays on replay.

        Returns host numpy arrays (each sliced to len(indices)):
        `admitted` (S,), `fail_code` (S,) int32 (`_encode_fail` encoding,
        -1 = feasible cycle-initially), `feasible` (S, N), `fit_margin`
        (S, N) int64 (min over resources of effective free - demand,
        nominee capacity holds included — `_free_with_nominee_holds`, the
        same fit the solve step enforces; -2^62 on masked nodes),
        `columns` (S, L, N) int64 weighted normalized
        per-plugin scores in profile order, `total` (S, N) int64 = the
        column sum, which reproduces the solve step's weighted node score
        (`_score_columns` is the same code path).

        Scores are cycle-initial — the objective both solve modes rank by
        (`parallel.solver.profile_initial_scores`); in-cycle carry effects
        on later pods' scores are a sequential-scan refinement this
        surface deliberately does not chase (the batched/streamed solves
        never see them either). `parallel.solver.batch_explain_rows`
        computes these same outputs through the batched solver's
        class-collapsed row hooks; tests/test_explain.py gates the two
        for agreement."""
        plugins = tuple(self.profile.plugins)

        def explain(snap, state0, auxes, idx):
            for plugin, aux in zip(plugins, auxes):
                plugin.bind_aux(aux)
            for plugin in plugins:
                plugin.bind_presolve(plugin.prepare_solve(snap))
            return jax.vmap(
                lambda p: _explain_one(plugins, state0, snap, p)
            )(idx)

        return run_explain_rows(self, snap, indices, auxes, "explain", explain)

    def initial_state(self, snap: ClusterSnapshot) -> SolverState:
        free = free_capacity(snap.nodes.alloc, snap.nodes.requested)
        eq_used = snap.quota.used if snap.quota is not None else None
        gang_sched = None
        gang_inflight = None
        if snap.gangs is not None:
            G = snap.gangs.min_member.shape[0]
            gang_sched = jnp.zeros(G, jnp.int32)
            gang_inflight = jnp.zeros((G, snap.num_resources), jnp.int64)
        net_placed = (
            snap.network.placed_node if snap.network is not None else None
        )
        if snap.numa is not None:
            from scheduler_plugins_tpu.ops.numa import live_avail_init

            numa_avail = live_avail_init(snap.numa)
        else:
            numa_avail = None
        placed_mask = (
            jnp.zeros(snap.num_pods, bool)
            if snap.quota is not None or snap.nominees is not None
            else None
        )
        sel_counts = None
        sel_dom_counts = None
        anti_domains = None
        sym_counts = None
        if snap.scheduling is not None:
            if (
                snap.scheduling.track_node_base is not None
                and snap.scheduling.spread_needs_node_counts
            ):
                # the node-level carry is only materialized when a spread
                # eligibility row actually excludes a keyed node
                sel_counts = jnp.asarray(snap.scheduling.track_node_base)
            if snap.scheduling.track_base is not None:
                sel_dom_counts = jnp.asarray(snap.scheduling.track_base)
            if snap.scheduling.exist_anti_base is not None:
                anti_domains = jnp.asarray(snap.scheduling.exist_anti_base)
            if snap.scheduling.sym_base is not None:
                sym_counts = jnp.asarray(snap.scheduling.sym_base)
        return SolverState(
            free=free,
            eq_used=eq_used,
            gang_scheduled=gang_sched,
            gang_inflight=gang_inflight,
            net_placed=net_placed,
            numa_avail=numa_avail,
            placed_mask=placed_mask,
            sel_counts=sel_counts,
            sel_dom_counts=sel_dom_counts,
            anti_domains=anti_domains,
            sym_counts=sym_counts,
        )


def now_ms() -> int:
    return int(time.time() * 1000)
