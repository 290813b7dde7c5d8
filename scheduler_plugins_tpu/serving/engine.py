"""ServeEngine: device-resident node state across cycles, O(changed) ingest.

`framework.cycle.run_cycle(serve=engine)` swaps the per-cycle full
re-snapshot (`Cluster.snapshot`: an O(nodes + assigned pods) Python
rebuild plus a full host->device ship) for this engine's `refresh`: the
`NodeState` columns live on device across cycles and each refresh applies
only the deltas the store's mutation hooks captured since the last one
(`serving.deltas.DeltaSink`), via one donated scatter program. The solve
itself is untouched — the assembled snapshot feeds the SAME bit-faithful
sequential parity path, so serve-mode placements are bit-identical to a
fresh-snapshot solve (gated by tests/test_serving.py's delta-equivalence
differential).

Capacity policy (docs/SERVING.md):

- **grow**: node adds past the padded capacity pad the resident columns
  to the next `bucket_size` bucket device-side (cheap `jnp.pad`, usage
  history preserved; one retrace for the new shape).
- **re-base** (the compact path): Node/Delete, an existing node's
  region/zone label change, a resource name the axis does not hold yet,
  or a pod event against a node the engine has never seen (cross-watch
  ordering) all invalidate either the row order or the packed axis — the
  engine rebuilds from a fresh `Cluster.snapshot` at the canonical
  bucket for the new node count, exactly like the C++ columnar mirror's
  `_native_rebuild`. Rare control-plane events pay O(cluster); steady
  churn pays O(changed).
- **the resource axis** (`ServeEngine.index`) is the canonical four plus
  every extended resource the store names when the resident base is
  built (nodes, assigned and pending pods, PodGroups, quotas). A node,
  pod, PodGroup or quota that names another one later triggers ONE
  rebase, which widens the axis (the names it had keep their columns);
  the axis never narrows. `scheduler_serve_axis_rebases_total` counts
  the rebases that changed it.

Compatibility gate: the engine owns the snapshot while every side table
is either None or one the resident state fully describes. Gang
(PodGroup) and quota (ElasticQuota) rosters are OWNED since ISSUE 12 —
their aggregate tensors assemble O(G + Q) from resident side tables
(`serving.deltas.SideTables`) maintained O(changed) from the same
drained delta stream, docs/SERVING.md "Resident gang/quota side
tables". The load watcher's report (`Cluster.node_metrics`) is OWNED
since ISSUE 29 — its nine columns are lowered once per report and the
unreported-CPU column is kept O(changed), docs/SERVING.md "Resident
node metrics". PodTopologySpread's selector and topology-domain counts
are OWNED since ISSUE 32 (`serving.selectors.ResidentSelectors`;
docs/SERVING.md "Resident selector counts"), InterPodAffinity's terms and
carrier counts since ISSUE 34 (the same class; "Resident affinity
terms"), NodeAffinity's (spec, node) verdict and score rows since ISSUE 38
(`serving.node_terms.ResidentNodeTerms`; "Resident node-term rows"). What
still gates is listed,
clause by clause, in `ServeEngine.fallback_reason` (the same shape of
condition as the native-store fast path in `Cluster.snapshot`). While
incompatible, `refresh` returns None (the cycle falls back to the full
snapshot) but KEEPS absorbing deltas, so the resident columns stay in sync
and serving resumes without a rebase once the side objects go away.
"""

from __future__ import annotations

import heapq
import time
from typing import Optional

import numpy as np

from scheduler_plugins_tpu.serving import deltas as D
from scheduler_plugins_tpu.serving.node_terms import ResidentNodeTerms
from scheduler_plugins_tpu.serving.selectors import ResidentSelectors
from scheduler_plugins_tpu.state.snapshot import (
    ClusterSnapshot,
    GangState,
    MetricsState,
    PodRecord,
    QuotaState,
    SnapshotMeta,
    _Interner,
    build_pod_state,
    empty_quota_nominees,
    gang_object_tables,
    node_metric_columns,
    quota_object_tables,
)
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size


class ServeEngine:
    """Long-lived serving engine for one `Cluster` store."""

    def __init__(self):
        self._sink = D.DeltaSink()
        self._cluster = None
        self._nodes = None  # resident NodeState (device arrays) or None
        self._npad = 0
        self._names: list[str] = []  # slot order == cluster.nodes order
        self._slots: dict[str, int] = {}
        # first-seen label interning over the shared tables (the snapshot
        # path's own _Interner — one convention, O(1) lookups)
        self._regions: list[str] = []
        self._zones: list[str] = []
        self._regions_in = _Interner(self._regions)
        self._zones_in = _Interner(self._zones)
        self._node_labels: dict[str, tuple] = {}  # name -> (region, zone)
        self._tainted: set[str] = set()
        self._apply = D.delta_apply_program()
        #: the resident columns' resource axis: canonical until a rebase
        #: finds extended resources in the store, then widened by them
        self._index = D.CANON_INDEX
        self._generation = 0
        self._rebases = 0
        self._staleness = 0  # delta events applied since last rebase
        self._base_digest: Optional[str] = None
        #: last refresh's packed batch + mode, for the flight recorder
        self._last: Optional[dict] = None
        # -- anti-entropy (docs/ROBUSTNESS.md) --------------------------
        #: digest the resident columns against a freshly built snapshot
        #: every N serving refreshes (0 = periodic checks off); any
        #: divergence forces a rebase, so a corrupted/dropped delta can
        #: poison at most one verification window. SPT_SERVE_VERIFY_EVERY
        #: overrides.
        self.verify_every = self._verify_every_default()
        self._refreshes = 0
        #: force a verify at the next refresh (set by `note_fault` — any
        #: watchdog/backend fault is treated as potential corruption)
        self._verify_pending = False
        self.antientropy_divergences = 0
        self.last_fault: Optional[str] = None
        # -- rank-gang awareness (docs/GANGS.md) ------------------------
        #: gang full_name -> {pod uid: node name}: the per-gang resident
        #: rank-assignment mirror, maintained O(changed) from the SAME
        #: drained delta stream that feeds the node columns — elastic
        #: grow/shrink consumers read the current rank roster without a
        #: cluster re-scan. Gang-carrying rosters still DEGRADE the
        #: snapshot path to fallback (`compatible` returns False while
        #: PodGroups exist): the resident node columns cannot express
        #: gang/quota side tables, and serving them anyway would
        #: silently mis-serve — the mirror keeps absorbing so serving
        #: resumes the moment the gangs drain away.
        self.resident_ranks: dict[str, dict] = {}
        #: refreshes that fell back while the cluster carried PodGroups.
        #: Since ISSUE 12 a gang/quota roster is served RESIDENT (the
        #: side tables below) — this counts only fallbacks forced by some
        #: OTHER incompatibility while gangs were present, so a compatible
        #: gang roster keeps it at 0 (tests/test_gangs.py gates that).
        #: Exported as `scheduler_serve_gang_fallbacks_total`.
        self.gang_fallbacks = 0
        # -- resident gang/quota side tables (ISSUE 12; docs/SERVING.md)
        #: device-resident `serving.deltas.SideTables` aggregates in
        #: engine-stable row order, maintained O(changed) by the donated
        #: `side_apply_program` from the SAME drained delta stream as the
        #: node columns; None until first built
        self._side = None
        self._gang_rows: dict[str, int] = {}  # gang full_name -> row
        self._ns_rows: dict[str, int] = {}  # namespace -> row
        self._side_apply = D.side_apply_program()
        self._side_gpad = 0
        self._side_qpad = 0
        #: gang slack depends on node EXISTENCE (a fresh snapshot drops
        #: contributions of pods bound to since-deleted nodes) — the rare
        #: invalidating events (node delete under streaming compaction, a
        #: previously-unknown node arriving, checkpoint restore) mark the
        #: side tables dirty; the next assembly rebuilds them with ONE
        #: O(pods) store scan instead of corrupting incrementally
        self._side_dirty = True
        #: per-namespace quota aggregates are maintained only once an
        #: ElasticQuota has been sighted — without this gate every bind in
        #: a quota-less cluster would pay a side-delta row (and a second
        #: apply dispatch) for tables nobody reads
        self._quota_tracking = False
        # -- resident node metrics (ISSUE 29; docs/SERVING.md) ----------
        #: the report (`cluster.node_metrics`) the columns below were
        #: lowered from, by identity: its writers (feed, collector) replace
        #: the dict wholesale. None while the store holds none, and then
        #: there is no column, no span and no staging
        self._report = None
        #: host `MetricsState` columns by field, rows in `_names` order,
        #: padded to `_npad`: the nine a report gives, and under
        #: `missing_cpu_millis` what the report itself says of it
        self._metric_cols: Optional[dict] = None
        #: (npad,) int64, the recent bindings' predicted CPU by node row:
        #: what `Cluster._metrics_with_missing` adds to the report
        self._unreported: Optional[np.ndarray] = None
        #: uid -> (bind ms, row | None, millis) of every binding counted
        #: in `_unreported` (row None: its node has no row, it counts 0),
        #: and a heap of (bind ms, uid) to expire them by
        self._recent: dict = {}
        self._recent_heap: list = []
        #: uids whose `BINDING_TOUCHED` events the last drain held
        self._touched: set = set()
        #: the `MetricsState` `_assemble` hands the solve, staged; its
        #: arrays are replaced, never written, when a column changes
        self._metrics_state = None
        #: nothing is lowered yet, the rows moved (node added or compacted
        #: away, bucket grown), or events were absorbed on a fallback
        #: cycle: lower everything from the store
        self._metrics_stale = True
        #: the clock and the prediction `_unreported` was last brought to
        self._metrics_now = 0
        self._tlp: Optional[tuple] = None
        # -- resident selector counts (ISSUE 32; docs/SERVING.md) -------
        #: PodTopologySpread's O(assigned) tables, kept O(changed) from the
        #: same drained events; inert while no pod of the store declares a
        #: spread constraint
        self._selectors = ResidentSelectors(self)
        # -- resident node-term rows (ISSUE 38; docs/SERVING.md) ----------
        #: NodeAffinity's `node_term_ok` / `pref_score` rows, one a spec,
        #: evaluated once and kept O(held specs) a node event; holds
        #: nothing while no pending pod names a nodeSelector or a node
        #: affinity term
        self._node_terms = ResidentNodeTerms(self)
        # -- per-pod records (ISSUE 37; docs/SERVING.md) ------------------
        #: uid -> `PodRecord`: a pod object's spec lowered once, read by
        #: the batch's axis test and its assembly, by the classification of
        #: its assign and unassign events and by the cadenced anti-entropy
        #: check. An entry follows the store's object: dropped with the
        #: pod's last event (`POD_UNASSIGN` or `POD_FORGET` of an object the
        #: store no longer holds), wholesale when the axis changes, and
        #: rebuilt for the assigned population by every rebase
        self._records: dict = {}
        #: lookups since the last flush to
        #: `scheduler_serve_pod_lowerings_total`, by (reader, result)
        self._lookups: dict = {}

    @property
    def _vec_cache(self) -> dict:
        """The record table under the name it had while it held the usage
        vectors alone (tests/test_pipeline_cycle.py reads it)."""
        return self._records

    @staticmethod
    def _verify_every_default() -> int:
        import os

        try:
            return int(os.environ.get("SPT_SERVE_VERIFY_EVERY", "32"))
        except ValueError:
            return 32

    # -- wiring ---------------------------------------------------------
    def attach(self, cluster) -> "ServeEngine":
        """Install the delta sink on `cluster`. The resident base is built
        lazily at the first `refresh` (which sees the full store)."""
        cluster.delta_sink = self._sink
        self._cluster = cluster
        self._nodes = None
        return self

    def detach(self) -> None:
        """Uninstall the sink and drop the resident base. Call when serve
        mode is retired for a still-live cluster — otherwise every mutator
        keeps appending events nobody drains (bounded by
        `DeltaSink.MAX_EVENTS`, but pinning Pod references until then)."""
        if (
            self._cluster is not None
            and self._cluster.delta_sink is self._sink
        ):
            self._cluster.delta_sink = None
        self._cluster = None
        self._nodes = None
        self._sink.events.clear()
        self._sink.overflowed = False
        self._sink.nominated_unbound.clear()
        self._side = None
        self._side_dirty = True
        self._gang_rows.clear()
        self._ns_rows.clear()
        self._drop_metrics()
        self._selectors.reset()
        self._node_terms.reset()
        self._records.clear()

    @property
    def generation(self) -> int:
        return self._generation

    @property
    def index(self):
        """The `ResourceIndex` of the resident columns and side tables."""
        return self._index

    def _extended(self) -> tuple:
        """The axis's names after the canonical four: what a fresh snapshot
        is told to hold (`extra_resources`), so that the names keep their
        columns and the axis never narrows."""
        return self._index.names[len(D.CANON_INDEX):]

    @property
    def rebases(self) -> int:
        """Full re-snapshots THIS engine performed (the process-global
        `scheduler_serve_rebases_total` sums across engines/runs)."""
        return self._rebases

    @property
    def resident_nodes(self):
        """The live resident `NodeState` (None before the first refresh).
        Treat as consumed after the next `refresh` — the apply program
        donates it."""
        return self._nodes

    @property
    def npad(self) -> int:
        return self._npad

    # -- compatibility gate ---------------------------------------------
    def compatible(self, cluster, pending) -> bool:
        """True when the engine can own this cycle's snapshot
        (`fallback_reason` names no clause)."""
        return self.fallback_reason(cluster, pending) is None

    def fallback_reason(self, cluster, pending) -> Optional[str]:
        """The clause that hands this cycle back to the O(cluster)
        `Cluster.snapshot`, or None when every side table is either absent
        or one the resident state fully describes. Gang (PodGroup) and
        quota (ElasticQuota) rosters are OWNED since ISSUE 12, the load
        watcher's report since ISSUE 29 (`_sync_metrics`), topology-spread
        constraints since ISSUE 32 (`ResidentSelectors`: any key, the
        hostname key included), pod (anti-)affinity terms since ISSUE 34
        (required and preferred, the incoming pod's own and the assigned
        carriers': `aff_*`, `anti_*`, `waff_*` are built O(batch),
        `exist_anti_base` and `sym_base` are resident carrier counts), a
        nodeSelector and node-affinity terms, required and preferred, since
        ISSUE 38 (`ResidentNodeTerms`: the rows of `node_term_ok` and
        `pref_score` are kept, a pod's two indices sit in its record); a
        resource name is no reason to fall back (`_outside_axis`: the axis
        widens by a rebase). What still falls back, each counted under its
        reason in `scheduler_serve_fallback_total`:

        - `nrt`: the store holds NodeResourceTopology objects;
        - `app-group`: it holds AppGroups (network-aware tables);
        - `seccomp`: it holds seccomp profiles (SySched tables);
        - `taints`: some node carries a taint (`tol_ok` / `tol_prefer`);
        - `affinity-namespace-selector`: some pod, pending OR bound,
          carries a pod (anti-)affinity term with a non-empty
          `namespaceSelector`: its scope moves when a Namespace's labels
          do, so no row keeps it across cycles
          (`Cluster.selectors.unscoped`);
        - `nomination`: a nominated node anywhere (a gated or reserved
          nominee, or one in the batch);
        - `spread-node-affinity`: a pod of the batch has a nodeSelector or
          a required node-affinity term AND a topology-spread constraint
          whose `nodeAffinityPolicy` is Honor (the default): the
          constraint's eligibility row (`spread_elig`) reads the pod's
          verdict row, and the resident selector tables keep the all-true
          one;
        - `spread-node-counts`: a pod of the batch names, in one class,
          several topology keys that some node carries only in part, so
          its domains are counted by node (`spread_needs_node_counts`,
          the (TR, N) `track_node_base`)."""
        if cluster.nrts:
            return "nrt"
        if cluster.app_groups:
            return "app-group"
        if cluster.seccomp_profiles:
            return "seccomp"
        if self._tainted:
            return "taints"
        if cluster.selectors.unscoped:
            return "affinity-namespace-selector"
        # nominations OUTSIDE the pending batch still count into the full
        # snapshot's nominated column / nominee holds: scheduling-gated
        # nominees (sink-tracked at upsert) and reserved nominees
        # (O(reserved), in practice unreachable without gangs)
        if self._sink.nominated_unbound:
            return "nomination"
        for uid in cluster.reserved:
            p = cluster.pods.get(uid)
            if p is not None and p.nominated_node_name is not None:
                return "nomination"
        # batch-local specs (O(batch), not O(cluster)): nominations feed
        # the nominee holds; a spread constraint that honours the pod's own
        # node affinity needs an eligibility row nobody keeps
        for pod in pending:
            if pod.nominated_node_name is not None:
                return "nomination"
            if pod.topology_spread and (
                pod.node_selector or pod.node_affinity_required
            ) and any(
                c.node_affinity_policy != "Ignore"
                for c in pod.topology_spread
            ):
                return "spread-node-affinity"
        if cluster.selectors.tracks and self._selectors.needs_node_counts(
            cluster, pending
        ):
            return "spread-node-counts"
        return None

    def _outside_axis(self, cluster, pending) -> bool:
        """True when a PodGroup, a quota or a pending pod names a resource
        the resident axis does not hold: the fresh snapshot's axis is the
        union of all of them (`build_snapshot`), so the next refresh
        rebases and widens. Nodes and assigned pods are caught where their
        events are classified. A pending pod answers with its record: one
        that exists was lowered on this axis; a miss lowers the pod here,
        once, and `_assemble` reads the record a moment later.
        O(G + Q + batch), objects only."""
        index = self._index
        for pg in cluster.pod_groups.values():
            if pg.min_resources and any(
                r not in index for r in pg.min_resources
            ):
                return True
        for eq in cluster.quotas.values():
            if any(r not in index for r in eq.min) or any(
                r not in index for r in eq.max
            ):
                return True
        try:
            for pod in pending:
                self._record(pod, "batch")
        except D.UnsupportedResource:
            return True
        return False

    # -- the per-cycle entry --------------------------------------------
    def refresh(self, cluster, pending, now_ms: int = 0):
        """(snapshot, meta) for this cycle, or None when the engine cannot
        own the state (caller falls back to `Cluster.snapshot`). Drains
        the sink either way — deltas are absorbed even while falling
        back, so the resident columns never go stale."""
        try:
            return self._refresh(cluster, pending, now_ms)
        finally:
            self._flush_lookups()

    def _refresh(self, cluster, pending, now_ms: int):
        with obs.tracer.span("ServeRefresh/drain", tid="serve"):
            events = self._sink.drain()
        obs.metrics.set_gauge(obs.SERVE_PENDING_DELTAS, len(events))
        if cluster.quotas and not self._quota_tracking:
            # first ElasticQuota sighting: start maintaining the quota
            # aggregates; the activation rebuild picks up every already-
            # assigned pod (classification below only carries deltas)
            self._quota_tracking = True
            self._side_dirty = True
        with obs.tracer.span(
            "ServeRefresh/classify", tid="serve", events=len(events)
        ):
            upserts, usage, side, rebase = self._ingest(events)
        if self._sink.consume_overflow():
            # the queue collapsed while nobody drained: the surviving
            # events are a partial window — the resident base is
            # unrecoverable from deltas alone
            rebase = "sink-overflow"
            self._side_dirty = True
        n_nodes = len(cluster.nodes)
        grow = self._nodes is not None and n_nodes > self._npad

        reason = self.fallback_reason(cluster, pending)
        if reason is not None:
            obs.metrics.inc(obs.SERVE_FALLBACKS, reason=reason)
            if cluster.pod_groups:
                self.gang_fallbacks += 1
                obs.metrics.inc(obs.SERVE_GANG_FALLBACKS)
            # keep the columns in sync while incompatible; a rebase-class
            # event just drops the base (rebuilt at the next compatible
            # refresh)
            if rebase:
                self._nodes = None
                self._side_dirty = True
                self._selectors.invalidate()
                self._node_terms.invalidate()
            elif self._nodes is not None:
                if grow:
                    self._grow(bucket_size(n_nodes))
                self._apply_batch(upserts, usage, side)
            # the metrics columns are not kept up on a cycle they do not
            # serve: the next one that does lowers them from the store
            self._metrics_stale = True
            self._touched.clear()
            self._last = None
            return None

        if (
            rebase or self._nodes is None
            or self._outside_axis(cluster, pending)
        ):
            return self._rebase(cluster, pending, now_ms)
        if grow:
            self._grow(bucket_size(n_nodes))
        self._apply_batch(upserts, usage, side)
        self._sync_metrics(cluster, now_ms)
        self._selectors.ensure(cluster, self._names, self._npad)
        self._refreshes += 1
        divergence = None
        if self._verify_pending:
            # after a fault or a restore nothing of the delta path is
            # trusted, the records included: the fresh-snapshot digest
            divergence = self.verify(cluster, now_ms)
        elif self.verify_every and self._refreshes % self.verify_every == 0:
            divergence = self.verify_assigned(cluster, now_ms)
        if divergence is not None:
            return self._rebase(cluster, pending, now_ms)
        if (cluster.pod_groups or cluster.quotas) and not self._ensure_side(
            cluster
        ):
            # defensive: the side tables could not be rebuilt (an assigned
            # pod names a resource the classification did not see): the
            # rebase widens the axis and rebuilds them
            return self._rebase(cluster, pending, now_ms)
        return self._assemble(cluster, pending, now_ms)

    # -- event classification -------------------------------------------
    def _ingest(self, events):
        """Classification seam: the streaming subclass splits the event
        stream at node-delete boundaries (compacting rows in place); the
        base engine classifies the whole batch, with a node delete
        forcing a rebase."""
        return self._classify(events)

    # -- per-pod records ---------------------------------------------------
    def _lower(self, pod) -> PodRecord:
        """`pod`'s spec lowered on the engine's axis. Raises
        `UnsupportedResource` where it names a resource outside it (no
        record is made: the rebase that follows widens the axis)."""
        try:
            return PodRecord(pod, self._index, self._cluster.tlp_prediction)
        except KeyError as exc:
            raise D.UnsupportedResource(str(exc)) from exc

    def _record(self, pod, reader: str, final: bool = False) -> PodRecord:
        """`pod`'s record: the table's where it is valid for this pod
        object, axis and TLP parameters (a hit), else lowered now and kept
        (a miss). `final` marks the last event of a pod object the store
        no longer holds: its entry is released, and a miss keeps none."""
        rec = self._records.get(pod.uid)
        if rec is not None and rec.valid(
            pod, self._index, self._cluster.tlp_prediction
        ):
            self._looked(reader, "hit")
            if final:
                del self._records[pod.uid]
            return rec
        self._looked(reader, "miss")
        if not final:
            rec = self._records[pod.uid] = self._lower(pod)
            return rec
        if rec is not None and rec.pod is pod:
            del self._records[pod.uid]  # its own, lowered on another axis
        return self._lower(pod)

    def _pod_vectors(self, pod, final=False, reader="classify"):
        """One pod's (requested, nonzero, limits, quota) contribution
        vectors — the node usage columns' per-pod arithmetic plus the
        ElasticQuota `used` row's raw request encode — read off its record
        (`_record`)."""
        return self._record(pod, reader, final).vectors

    def _looked(self, reader: str, result: str, n: int = 1) -> None:
        key = (reader, result)
        self._lookups[key] = self._lookups.get(key, 0) + n

    def _flush_lookups(self) -> None:
        """One registry write per (reader, result) a refresh or a check,
        not one a lookup."""
        if self._lookups:
            for (reader, result), n in self._lookups.items():
                obs.metrics.inc(
                    obs.SERVE_POD_LOWERINGS, n, reader=reader, result=result
                )
            self._lookups.clear()

    def _prime_records(self, cluster) -> None:
        """Records for the assigned population, on the store's REAL pod
        objects (never `_assigned_pods`'s per-reserved copies: a copy-keyed
        entry can never hit the identity check), and none for an object the
        store no longer holds. A rebase is already O(cluster): paying the
        lowerings here keeps the first cadenced check of a run from owning
        them on a timed cycle."""
        pods = cluster.pods
        self._records = {
            uid: rec for uid, rec in self._records.items()
            if pods.get(uid) is rec.pod
        }
        try:
            for pod in pods.values():
                if pod.node_name is not None or pod.uid in cluster.reserved:
                    self._record(pod, "rebase")
        except D.UnsupportedResource:
            pass  # outside the axis: the cadenced check falls through

    def _stage_args(self, args):
        """Host->device staging of one packed delta batch. The base
        engine ships explicit device copies; the streaming engine hands
        pjit the numpy arrays directly (one C++ shard_args pass instead
        of a Python conversion per array — same bytes either way)."""
        import jax.numpy as jnp

        return tuple(jnp.asarray(a) for a in args)

    def _stage_pods(self, pod_state):
        """Host->device staging of the assembled pod tensors (same
        split as `_stage_args`)."""
        import jax
        import jax.numpy as jnp

        return jax.tree.map(jnp.asarray, pod_state)

    def _gang_row(self, name: str) -> int:
        row = self._gang_rows.get(name)
        if row is None:
            row = self._gang_rows[name] = len(self._gang_rows)
        return row

    def _ns_row(self, name: str) -> int:
        row = self._ns_rows.get(name)
        if row is None:
            row = self._ns_rows[name] = len(self._ns_rows)
        return row

    def _classify(self, events):
        """Coalesce drained events into packed-row lists. Returns
        (upsert_rows, usage_rows, side_rows, rebase_reason|None) where
        `side_rows` is the (gang_rows, ns_rows) pair feeding the resident
        gang/quota side tables (`serving.deltas.SideDeltas.pack`)."""
        upserts: dict[int, tuple] = {}  # slot -> row (last write wins)
        usage: list[tuple] = []
        # side aggregates coalesce per engine-stable row (sums)
        gang_acc: dict[int, list] = {}
        ns_acc: dict[int, list] = {}
        index = self._index
        R = len(index)
        # events without a resource payload (terminating flips)
        zero = np.zeros((3, R), np.int64)
        rebase = None
        store = self._cluster.pods

        def fail(reason):
            nonlocal rebase
            if rebase is None:
                rebase = reason

        def gang_add(name, d_assigned, d_gated, d_slack):
            row = self._gang_row(name)
            acc = gang_acc.get(row)
            if acc is None:
                acc = gang_acc[row] = [0, 0, np.zeros(R, np.int64)]
            acc[0] += d_assigned
            acc[1] += d_gated
            if d_slack is not None:
                acc[2] = acc[2] + d_slack

        def ns_add(name, d_used, d_count):
            row = self._ns_row(name)
            acc = ns_acc.get(row)
            if acc is None:
                acc = ns_acc[row] = [np.zeros(R, np.int64), 0]
            acc[0] = acc[0] + d_used
            acc[1] += d_count

        for ev in events:
            kind = ev[0]
            if kind == D.GANG_GATED:
                # unbound gated gang-membership transition (event-time
                # delta; see Cluster._gang_gated_key)
                gang_add(ev[1], 0, ev[2], None)
                continue
            if kind == D.BINDING_TOUCHED:
                self._touched.add(ev[1])
                continue
            if kind == D.POD_FORGET:
                rec = self._records.get(ev[1])
                if rec is not None and store.get(ev[1]) is not rec.pod:
                    del self._records[ev[1]]
                continue
            if kind == D.NODE_DELETE:
                # the row order dies with the node — but so do its label/
                # taint entries: a deleted node must not pin `compatible`
                # False forever (the rebase that follows rebuilds these
                # tables only on the COMPATIBLE path)
                name = ev[1]
                self._tainted.discard(name)
                self._node_labels.pop(name, None)
                self._selectors.invalidate()
                fail("node-delete")
            elif kind == D.NODE_UPSERT:
                node = ev[1]
                if node.taints:
                    self._tainted.add(node.name)
                else:
                    self._tainted.discard(node.name)
                labels = (node.region or "", node.zone or "")
                prev = self._node_labels.get(node.name)
                if prev is not None and prev != labels:
                    # region/zone re-interning cannot be expressed as a
                    # row overwrite (codes are first-seen in slot order)
                    fail("label-change")
                self._node_labels[node.name] = labels
                slot = self._slots.get(node.name)
                new_node = slot is None
                if new_node:
                    slot = len(self._names)
                    self._slots[node.name] = slot
                    self._names.append(node.name)
                    # the report, or a recent binding, may name it
                    self._metrics_stale = True
                    if self._gang_rows:
                        # a NEW node name can resurrect gang slack for
                        # pods already bound to it (cross-watch arrival:
                        # fresh snapshots include slack only for nodes
                        # that exist) — rebuild rather than drift
                        self._side_dirty = True
                self._selectors.node_row(node, slot, new_node)
                self._node_terms.node_column(node, slot, new_node)
                try:
                    alloc = D._encode(node.allocatable, index)
                    cap = D._encode(node.capacity, index)
                except D.UnsupportedResource:
                    fail("extended-resource")
                    continue
                upserts[slot] = (
                    slot, alloc, cap, not node.unschedulable,
                    self._regions_in.code(node.region) if node.region
                    else -1,
                    self._zones_in.code(node.zone) if node.zone else -1,
                )
            else:  # pod usage transitions
                pod, node_name = ev[1], ev[2]
                gang = pod.pod_group()
                if gang:
                    # O(changed) per-gang resident rank mirror: assigns
                    # record the rank's node, unassigns drop it (the
                    # terminating transition keeps the slot — the rank
                    # still occupies its node until the delete lands)
                    roster = self.resident_ranks.setdefault(
                        f"{pod.namespace}/{gang}", {}
                    )
                    if kind == D.POD_ASSIGN:
                        roster[pod.uid] = node_name
                    elif kind != D.POD_TERMINATING:
                        roster.pop(pod.uid, None)
                        if not roster:
                            self.resident_ranks.pop(
                                f"{pod.namespace}/{gang}", None
                            )
                slot = self._slots.get(node_name)
                if kind == D.POD_TERMINATING:
                    if slot is None:
                        fail("unknown-node")
                        continue
                    usage.append((slot, zero, 0, 1))
                    continue
                sign = 1 if kind == D.POD_ASSIGN else -1
                try:
                    # the entry goes with the last event of an object
                    # the store no longer holds (a released reservation
                    # leaves the pod, and its record, where they are)
                    rec = self._record(
                        pod, "classify",
                        final=sign < 0 and store.get(pod.uid) is not pod,
                    )
                except D.UnsupportedResource:
                    fail("extended-resource")
                    continue
                # side-table contributions FIRST: the quota used row and
                # the gang assigned count follow the pod regardless of
                # node existence (build_snapshot's rule); gang slack only
                # when the node is known (fresh drops unknown-node slack)
                if self._quota_tracking:
                    ns_add(pod.namespace, sign * rec.req, sign)
                if gang:
                    gang_add(
                        f"{pod.namespace}/{gang}", sign, 0,
                        sign * rec.vectors[0] if slot is not None else None,
                    )
                if slot is None:
                    # pod referenced a node the engine never saw (cross-
                    # watch ordering): the fresh snapshot skips such pods
                    # until the node arrives, at which point row contents
                    # change wholesale — re-base to stay exact
                    fail("unknown-node")
                    continue
                # event-time flag, NOT pod.terminating: a mark_terminating
                # between event and drain mutates the pod in place and
                # queues its own +1 — a drain-time read would double-count
                term = 1 if ev[3] else 0
                self._selectors.pod_event(pod, slot, sign)
                usage.append((slot, rec.usage, sign, sign * term))
        side = (
            [(row, a, g, s) for row, (a, g, s) in gang_acc.items()],
            [(row, u, c) for row, (u, c) in ns_acc.items()],
        )
        return list(upserts.values()), usage, side, rebase

    # -- state transitions ----------------------------------------------
    def _apply_batch(self, upsert_rows, usage_rows, side=None) -> None:
        with obs.tracer.span(
            "ServeRefresh/apply", tid="serve",
            upserts=len(upsert_rows), usage=len(usage_rows),
        ):
            self._apply_batch_inner(upsert_rows, usage_rows, side)

    def _apply_batch_inner(self, upsert_rows, usage_rows, side=None) -> None:
        import warnings

        import jax
        import jax.numpy as jnp

        R = len(self._index)
        ups = D.NodeUpserts.pack(upsert_rows, R)
        use = D.UsageDeltas.pack(usage_rows, R)
        # slot indices are host-validated (< npad); the jit scatter relies
        # on that, and SPT_SANITIZE=1 re-checks it with checkify
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            self._nodes = self._apply(
                self._nodes,
                *self._stage_args(ups.as_args()),
                *self._stage_args(use.as_args()),
            )
        for w in caught:
            msg = str(w.message)
            if "donated buffers were not usable" not in msg:
                warnings.warn_explicit(
                    w.message, w.category, w.filename, w.lineno
                )
            elif msg.count("[") > 1 and jax.default_backend() != "cpu":
                # ONE undonated buffer is expected — the intentionally
                # unused `nominated` column (rewritten as zeros). More
                # than one on a donating backend means the resident
                # columns silently stopped aliasing, i.e. every apply
                # pays the O(cluster) copy this subsystem exists to
                # remove — keep that visible. (CPU never donates and
                # lists everything, like the profile solves of PR 2.)
                warnings.warn_explicit(
                    w.message, w.category, w.filename, w.lineno
                )
        side_dict = self._apply_side(side)
        self._selectors.apply()
        self._generation += 1
        n_events = len(upsert_rows) + len(usage_rows)
        self._staleness += n_events
        self._last = {
            "mode": "delta", "events": n_events,
            "upserts": ups.as_dict(), "usage": use.as_dict(),
        }
        if side_dict is not None:
            self._last["side"] = side_dict
        if self._selectors.last_packed is not None:
            self._last["selectors"] = self._selectors.last_packed
        self._observe()

    def _apply_side(self, side):
        """Fold this window's packed side-table deltas into the resident
        gang/quota aggregates (donated jit scatter). Skipped entirely for
        windows without gang/quota rows (the common quota-less churn
        case pays nothing) and while the tables are dirty — the pending
        O(pods) rebuild supersedes any incremental application."""
        if side is None:
            return None
        gang_rows, ns_rows = side
        if (not gang_rows and not ns_rows) or self._side_dirty:
            return None
        if self._side is None:
            self._side_dirty = True
            return None
        import warnings

        need_g = max((row for row, *_ in gang_rows), default=-1) + 1
        need_q = max((row for row, *_ in ns_rows), default=-1) + 1
        self._grow_side(need_g, need_q)
        packed = D.SideDeltas.pack(
            gang_rows, ns_rows, len(self._index),
            self._side_gpad, self._side_qpad,
        )
        with warnings.catch_warnings():
            # CPU backends never donate and list every buffer
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            self._side = self._side_apply(
                self._side, *self._stage_args(packed.as_args())
            )
        return packed.as_dict()

    @staticmethod
    def _side_floor(cluster) -> tuple:
        """(gang rows, namespace rows) the side tables hold at the least:
        as many as the store has PodGroups, and namespaces or quotas. A
        gang or a namespace takes its row when its first member is
        assigned, so tables sized to the rows taken so far grew bucket by
        bucket through a run's first minute, a `serve_side_apply` program
        each; sized to the objects they have their shape from the start."""
        return (
            max(len(cluster.pod_groups), 1),
            max(len(cluster.namespaces), len(cluster.quotas), 1),
        )

    def _grow_side(self, need_g: int, need_q: int) -> None:
        """Pad the resident side tables to cover rows `need_g`/`need_q`
        (bucketed, zero-padded — new gangs/namespaces appear mid-run)."""
        import jax.numpy as jnp

        floor_g, floor_q = self._side_floor(self._cluster)
        new_g = bucket_size(max(need_g, self._side_gpad, floor_g))
        new_q = bucket_size(max(need_q, self._side_qpad, floor_q))
        if new_g == self._side_gpad and new_q == self._side_qpad:
            return

        def pad1(arr, n):
            widths = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
            return jnp.pad(arr, widths)

        self._side = self._side.replace(
            gang_assigned=pad1(self._side.gang_assigned, new_g),
            gang_gated=pad1(self._side.gang_gated, new_g),
            gang_slack=pad1(self._side.gang_slack, new_g),
            quota_used=pad1(self._side.quota_used, new_q),
            ns_assigned=pad1(self._side.ns_assigned, new_q),
        )
        self._side_gpad = new_g
        self._side_qpad = new_q

    def _grow(self, new_npad: int) -> None:
        """Pad the resident columns to a larger bucket device-side —
        usage history is preserved, only the shape changes (one retrace
        of the apply/solve programs for the new bucket)."""
        import jax.numpy as jnp

        pad = new_npad - self._npad
        if pad <= 0:
            return
        nodes = self._nodes

        def pad1(arr, value=0):
            widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
            return jnp.pad(arr, widths, constant_values=value)

        self._nodes = nodes.replace(
            alloc=pad1(nodes.alloc),
            capacity=pad1(nodes.capacity),
            requested=pad1(nodes.requested),
            nonzero_requested=pad1(nodes.nonzero_requested),
            limits=pad1(nodes.limits),
            mask=pad1(nodes.mask, False),
            region=pad1(nodes.region, -1),
            zone=pad1(nodes.zone, -1),
            pod_count=pad1(nodes.pod_count),
            terminating=pad1(nodes.terminating),
            nominated=pad1(nodes.nominated),
        )
        self._npad = new_npad
        self._metrics_stale = True
        self._selectors.grow(new_npad)
        self._node_terms.invalidate()

    def _rebase(self, cluster, pending, now_ms: int):
        """Full re-snapshot: rebuild the resident base from the store (the
        compact path — the new bucket fits the CURRENT node count) and
        reset slot/interning tables to the store's own order."""
        with obs.tracer.span(
            "ServeRefresh/rebase", tid="serve", nodes=len(cluster.nodes)
        ):
            return self._rebase_inner(cluster, pending, now_ms)

    def _rebase_inner(self, cluster, pending, now_ms: int):
        npad = bucket_size(max(len(cluster.nodes), 1))
        # whatever else the store names now is appended to the axis
        snap, meta = cluster.snapshot(
            pending, now_ms=now_ms, pad_nodes=npad,
            extra_resources=self._extended(),
        )
        if meta.index.names != self._index.names:
            self._index = meta.index
            obs.metrics.inc(obs.SERVE_AXIS_REBASES)
            self._axis_changed()
        self._nodes = snap.nodes
        self._npad = npad
        self._names = list(meta.node_names)
        self._slots = {n: i for i, n in enumerate(self._names)}
        self._regions = meta.regions  # share: _assemble copies per cycle
        self._zones = meta.zones
        self._regions_in = _Interner(self._regions)
        self._zones_in = _Interner(self._zones)
        self._node_labels = {
            n.name: (n.region or "", n.zone or "")
            for n in cluster.nodes.values()
        }
        self._tainted = {n.name for n in cluster.nodes.values() if n.taints}
        # a rebase is already O(cluster): rebuild the gang/quota side
        # tables in the same breath (their aggregates must match the
        # fresh snapshot this rebase just served from)
        self._rebuild_side_tables(cluster)
        # and the metrics columns, through the code the snapshot just used
        self._metrics_stale = True
        self._sync_metrics(cluster, now_ms)
        # and the selector tables; the cycle is handed them in the resident
        # layout (padded axes, the registry's row order), so that the solve
        # of the cold build is the program of every cycle after it
        self._selectors.rebuild(cluster, self._names, npad)
        # the node-term rows are evaluated again on first use, in the new
        # row order: by this cycle where its batch names a spec
        self._node_terms.invalidate()
        self._prime_records(cluster)
        if snap.scheduling is not None:
            snap = snap.replace(scheduling=self._assemble_scheduling(
                cluster, pending, snap.num_pods
            ))
        self._generation += 1
        self._staleness = 0
        self._rebases += 1
        obs.metrics.inc(obs.SERVE_REBASES)
        self._base_digest = None
        from scheduler_plugins_tpu.utils import flightrec

        if flightrec.recorder.enabled:
            self._base_digest = flightrec._pack_digest(
                {k: np.asarray(v) for k, v in self._node_columns().items()}
            )
        self._last = {"mode": "rebase", "events": 0}
        self._observe()
        return snap, meta

    def _axis_changed(self) -> None:
        """The axis widened: every record lowered on the old one is the
        wrong length."""
        self._records.clear()

    # -- resident gang/quota side tables --------------------------------
    def _ensure_side(self, cluster) -> bool:
        """Side tables ready for assembly: rebuild them from one O(pods)
        store scan when dirty or absent (activation, node-set change,
        restore, divergence)."""
        if self._side is not None and not self._side_dirty:
            return True
        return self._rebuild_side_tables(cluster)

    def _scan_side_aggregates(self, cluster, vectors):
        """ONE store scan producing the gang/quota aggregate dicts a
        fresh `build_snapshot` would accumulate: {gang full_name:
        [assigned, gated, slack_vec]} + {namespace: [used_vec, count]}.
        Shared by the rebuild (packs them resident; `vectors` reads the
        records) and the fresh-snapshot verify (compares them against the
        resident copies; `vectors` lowers every pod cold). Raises
        `UnsupportedResource` when an assigned pod names a resource
        outside the axis (the next rebase widens it)."""
        R = len(self._index)
        gangs: dict[str, list] = {}
        namespaces: dict[str, list] = {}

        def gang_acc(name):
            acc = gangs.get(name)
            if acc is None:
                acc = gangs[name] = [0, 0, np.zeros(R, np.int64)]
            return acc

        for pod in cluster.pods.values():
            held = pod.node_name or cluster.reserved.get(pod.uid)
            gang = pod.pod_group()
            if held is not None:
                req, _nz, _lim, qreq = vectors(pod)
                if self._quota_tracking:
                    acc = namespaces.get(pod.namespace)
                    if acc is None:
                        acc = namespaces[pod.namespace] = [
                            np.zeros(R, np.int64), 0,
                        ]
                    acc[0] = acc[0] + qreq
                    acc[1] += 1
                if gang:
                    acc = gang_acc(f"{pod.namespace}/{gang}")
                    acc[0] += 1
                    if held in cluster.nodes:
                        # fresh snapshots count slack only for nodes that
                        # exist (node_pos membership)
                        acc[2] = acc[2] + req
            # gated runs on `gated_pods()`'s own predicate (node_name is
            # None), INDEPENDENT of a permit reservation: a reserved
            # gated pod counts BOTH ways in a fresh snapshot (assigned
            # via its materialized reserved copy, gated via the real
            # unbound object) and the delta stream mirrors that
            # (POD_ASSIGN at reserve + GANG_GATED at upsert)
            if (
                gang
                and pod.node_name is None
                and pod.scheduling_gated
                and not pod.terminating
            ):
                gang_acc(f"{pod.namespace}/{gang}")[1] += 1
        return gangs, namespaces

    def _rebuild_side_tables(self, cluster) -> bool:
        """Rebuild the resident side tables from the store (O(pods), the
        rare path — steady state is the O(changed) `_apply_side`).
        Returns False (tables stay dirty) when an assigned pod names a
        resource outside the axis: the caller rebases, which widens it."""
        import jax.numpy as jnp

        with obs.tracer.span(
            "ServeRefresh/side_rebuild", tid="serve",
            pods=len(cluster.pods),
        ):
            try:
                gangs, namespaces = self._scan_side_aggregates(
                    cluster, lambda pod: self._pod_vectors(pod, reader="side")
                )
            except D.UnsupportedResource:
                self._side_dirty = True
                return False
            R = len(self._index)
            self._gang_rows = {name: i for i, name in enumerate(gangs)}
            self._ns_rows = {name: i for i, name in enumerate(namespaces)}
            floor_g, floor_q = self._side_floor(cluster)
            self._side_gpad = bucket_size(max(len(gangs), floor_g))
            self._side_qpad = bucket_size(max(len(namespaces), floor_q))
            ga = np.zeros(self._side_gpad, np.int32)
            gg = np.zeros(self._side_gpad, np.int32)
            gs = np.zeros((self._side_gpad, R), np.int64)
            qu = np.zeros((self._side_qpad, R), np.int64)
            qc = np.zeros(self._side_qpad, np.int32)
            for name, (assigned, gated, slack) in gangs.items():
                row = self._gang_rows[name]
                ga[row] = assigned
                gg[row] = gated
                gs[row] = slack
            for name, (used, count) in namespaces.items():
                row = self._ns_rows[name]
                qu[row] = used
                qc[row] = count
            self._side = D.SideTables(
                gang_assigned=jnp.asarray(ga),
                gang_gated=jnp.asarray(gg),
                gang_slack=jnp.asarray(gs),
                quota_used=jnp.asarray(qu),
                ns_assigned=jnp.asarray(qc),
            )
            self._side_dirty = False
            return True

    def _side_host(self) -> dict:
        """Host copies of the resident side tables (small: (G,)/(Q, R))."""
        return {
            "gang_assigned": np.asarray(self._side.gang_assigned),
            "gang_gated": np.asarray(self._side.gang_gated),
            "gang_slack": np.asarray(self._side.gang_slack),
            "quota_used": np.asarray(self._side.quota_used),
            "ns_assigned": np.asarray(self._side.ns_assigned),
        }

    def _side_verify_live(self, cluster) -> bool:
        """True when the side tables have state worth verifying (skipped
        — costing nothing — in plain churn)."""
        return (
            self._side is not None
            and not self._side_dirty
            and bool(
                cluster.pod_groups or cluster.quotas
                or self._quota_tracking
            )
        )

    def _side_divergence(self, gangs: dict, namespaces: dict
                         ) -> Optional[str]:
        """Compare expected aggregate dicts (a `_scan_side_aggregates`
        result) against the resident side tables. Consumes the dicts."""
        host = self._side_host()
        for name, row in self._gang_rows.items():
            exp = gangs.pop(name, None)
            if exp is None:
                exp = [0, 0, np.zeros(len(self._index), np.int64)]
            if (
                int(host["gang_assigned"][row]) != exp[0]
                or int(host["gang_gated"][row]) != exp[1]
                or not (host["gang_slack"][row] == exp[2]).all()
            ):
                return "side-gang"
        if gangs:
            return "side-gang"  # expected rows the resident table lacks
        for name, row in self._ns_rows.items():
            exp = namespaces.pop(name, None)
            if exp is None:
                exp = [np.zeros(len(self._index), np.int64), 0]
            if (
                int(host["ns_assigned"][row]) != exp[1]
                or not (host["quota_used"][row] == exp[0]).all()
            ):
                return "side-quota"
        if namespaces:
            return "side-quota"
        return None

    def _verify_side(self, cluster) -> Optional[str]:
        """Anti-entropy over the gang/quota side tables for the
        fresh-snapshot verify: recompute the expected aggregates from the
        store, every pod lowered cold (independent of the delta path and of
        the records), and compare to the resident copies. Skipped — costing
        nothing — while no gang/quota state is live. (The cadenced check
        folds the expectation into its single `_expected_columns` pass.)"""
        if not self._side_verify_live(cluster):
            return None
        index = self._index

        def cold(pod):
            return D.pod_usage_vectors(pod, index) + (
                D.pod_quota_vector(pod, index),
            )

        try:
            gangs, namespaces = self._scan_side_aggregates(cluster, cold)
        except D.UnsupportedResource:
            return "axis-width"
        return self._side_divergence(gangs, namespaces)

    # -- resident node metrics -------------------------------------------
    def _drop_metrics(self) -> None:
        self._report = None
        self._metric_cols = None
        self._unreported = None
        self._metrics_state = None
        self._metrics_stale = True
        self._recent.clear()
        self._recent_heap.clear()
        self._touched.clear()
        self._tlp = None

    def _sync_metrics(self, cluster, now_ms: int) -> None:
        """Bring the resident `MetricsState` to the store's report and to
        `now_ms`, after the drained events were classified and applied
        (`_slots` is the store's node order). Where the store holds no
        report this is one test. A report not seen before is lowered once,
        O(nodes), through `node_metric_columns`, the snapshot path's own
        code; the unreported-CPU column follows the drained
        `BINDING_TOUCHED` events and the clock, O(changed). Equal, leaf
        for leaf, to `cluster.snapshot(..., now_ms=now_ms)[0].metrics`
        (tests/test_serving.py::TestResidentMetrics)."""
        report = cluster.node_metrics
        if report is None:
            if self._report is not None or self._touched:
                self._drop_metrics()
            return
        with obs.tracer.span("ServeRefresh/metrics", tid="serve"):
            lower = self._metrics_stale or report is not self._report
            if lower:
                self._metric_cols = node_metric_columns(
                    report, self._slots, self._npad
                )
                self._report = report
                obs.metrics.inc(obs.SERVE_METRICS_RELOWERS)
            if (
                self._metrics_stale
                # a clock set back revives bindings already expired here,
                # and another prediction re-prices every one of them
                or now_ms < self._metrics_now
                or cluster.tlp_prediction != self._tlp
            ):
                self._rebuild_unreported(cluster, now_ms)
                moved = True
            else:
                moved = self._expire_bindings(cluster, now_ms)
                for uid in self._touched:
                    moved = self._recount_binding(cluster, uid, now_ms) or moved
            self._touched.clear()
            self._metrics_stale = False
            self._metrics_now = now_ms
            if not (lower or moved):
                return
            missing = (
                self._metric_cols["missing_cpu_millis"] + self._unreported
            )
            if lower:
                self._metrics_state = self._stage_pods(MetricsState(**{
                    **self._metric_cols, "missing_cpu_millis": missing,
                }))
            else:
                self._metrics_state = self._metrics_state.replace(
                    missing_cpu_millis=self._stage_pods(missing)
                )

    def _rebuild_unreported(self, cluster, now_ms: int) -> None:
        """The recent bindings' column from the store, O(recent bindings):
        the first build, and whenever rows, clock or prediction moved
        under the entries held."""
        self._unreported = np.zeros(self._npad, np.int64)
        self._recent.clear()
        self._recent_heap.clear()
        self._tlp = cluster.tlp_prediction
        for uid in cluster.recent_bindings:
            self._recount_binding(cluster, uid, now_ms)

    def _recount_binding(self, cluster, uid: str, now_ms: int) -> bool:
        """Set what `uid` adds to `_unreported` to what the store says
        now: `Cluster._metrics_with_missing`'s rule for one binding (its
        pod exists, it is younger than the report interval). True when the
        column changed."""
        moved = False
        old = self._recent.pop(uid, None)
        if old is not None and old[1] is not None:
            self._unreported[old[1]] -= old[2]
            moved = True
        entry = cluster.recent_bindings.get(uid)
        pod = cluster.pods.get(uid)
        if (
            entry is None or pod is None
            or now_ms - entry[0] >= cluster.METRICS_REPORT_INTERVAL_MS
        ):
            return moved
        ts, node = entry
        row = self._slots.get(node)
        millis = pod.tlp_predicted_cpu_millis(*cluster.tlp_prediction)
        self._recent[uid] = (ts, row, millis)
        heapq.heappush(self._recent_heap, (ts, uid))
        if row is not None:
            self._unreported[row] += millis
            moved = True
        return moved

    def _expire_bindings(self, cluster, now_ms: int) -> bool:
        """Drop the bindings that have aged past the report interval at
        `now_ms` (the snapshot path's inequality). O(expired); a heap
        entry whose binding was since replaced or dropped is skipped."""
        heap = self._recent_heap
        interval = cluster.METRICS_REPORT_INTERVAL_MS
        moved = False
        while heap and now_ms - heap[0][0] >= interval:
            ts, uid = heapq.heappop(heap)
            held = self._recent.get(uid)
            if held is None or held[0] != ts:
                continue
            del self._recent[uid]
            if held[1] is not None:
                self._unreported[held[1]] -= held[2]
                moved = True
        return moved

    def _metrics_divergence(self, expected) -> Optional[str]:
        """The staged metrics columns against `expected` (a `MetricsState`
        of the store at the clock they were brought to, or None)."""
        from scheduler_plugins_tpu.utils import flightrec

        mine = self._metrics_state
        if mine is None or expected is None:
            return None if mine is expected else "metrics-presence"
        fields = tuple(self._metric_cols)
        if flightrec._pack_digest(
            {k: np.asarray(getattr(mine, k)) for k in fields}
        ) != flightrec._pack_digest(
            {k: np.asarray(getattr(expected, k)) for k in fields}
        ):
            return "metrics-digest"
        return None

    # -- anti-entropy ----------------------------------------------------
    def note_fault(self, reason: Optional[str] = None) -> None:
        """Treat any runtime fault (watchdog timeout/device error/garbage
        output, crash restore) as potential resident-state corruption:
        the NEXT refresh digests the resident columns against a freshly
        built snapshot before serving from them."""
        self._verify_pending = True
        self.last_fault = reason

    def verify(self, cluster, now_ms: Optional[int] = None
               ) -> Optional[str]:
        """Anti-entropy digest against a freshly built snapshot: blake2b
        over the canonical tensor bytes of the resident node columns (the
        flight-recorder content-address scheme) vs the same columns of
        `cluster.snapshot`, the resident metrics columns vs that
        snapshot's, the side tables vs a cold store scan, the selector
        tables vs the store. Returns a divergence reason (caller re-bases)
        or None (resident state is byte-exact). O(cluster) host work and
        nothing of the delta path in its expectation, the pod records
        included: the check `note_fault` and a checkpoint restore force,
        and the one an audit asks for by name
        (tests/test_resilience.py::TestAntiEntropy). The snapshot is taken
        at `now_ms`, by default the clock of the last refresh: what the
        unreported-CPU column holds depends on it."""
        return self._checked(cluster, now_ms, fast=False)

    def verify_assigned(self, cluster, now_ms: Optional[int] = None
                        ) -> Optional[str]:
        """The cadenced anti-entropy check (every `verify_every` refreshes):
        the same digests as `verify` and the same verdicts, with the
        expected node columns accumulated O(nodes + assigned) straight
        from the store's objects (`_expected_columns`) instead of through
        an O(cluster) snapshot rebuild — byte-identical expectations by
        construction (tests/test_resilience.py holds the two kinds to one
        verdict on clean and on corrupted state). Independence: the
        resident columns were built through the sink and the device, the
        expectation comes from the store; the one thing both read is the
        `PodRecord` of a pod object that was not replaced since it was
        lowered. A corrupted or dropped delta can therefore poison at most
        one verification window. A store this path cannot describe (a
        resource outside the axis) gets the fresh-snapshot check, which
        names it, inside the same span and count."""
        return self._checked(cluster, now_ms, fast=True)

    def _checked(self, cluster, now_ms: Optional[int], fast: bool
                 ) -> Optional[str]:
        """One anti-entropy check of either kind: one count, one
        `ServeRefresh/verify` span whose `fast` says which kind ran, one
        `scheduler_serve_verify_ms{kind}` observation."""
        if now_ms is None:
            now_ms = self._metrics_now
        start = time.perf_counter_ns()
        with obs.tracer.span(
            "ServeRefresh/verify", tid="serve", staleness=self._staleness,
            fast=fast,
        ) as said:
            self._verify_pending = False
            obs.metrics.inc(obs.ANTIENTROPY_CHECKS)
            reason = None
            if self._nodes is not None:
                if fast:
                    try:
                        reason = self._divergence_assigned(cluster, now_ms)
                    except D.UnsupportedResource:
                        fast = said["fast"] = False
                if not fast:
                    reason = self._divergence_snapshot(cluster, now_ms)
            if reason is not None:
                self.antientropy_divergences += 1
                obs.metrics.inc(obs.ANTIENTROPY_DIVERGENCE)
                obs.logger.warning(
                    "serve anti-entropy divergence (%s) after %d delta "
                    "events%s: re-basing", reason, self._staleness,
                    f" (last fault: {self.last_fault})"
                    if self.last_fault else "",
                )
        obs.metrics.observe_ms(
            obs.SERVE_VERIFY_MS, (time.perf_counter_ns() - start) / 1e6,
            kind="assigned" if fast else "snapshot",
        )
        self._flush_lookups()
        return reason

    def _columns_digest(self) -> str:
        from scheduler_plugins_tpu.utils import flightrec

        return flightrec._pack_digest(
            {k: np.asarray(v) for k, v in self._node_columns().items()}
        )

    def _divergence_snapshot(self, cluster, now_ms: int) -> Optional[str]:
        """`verify`'s comparison: everything against a fresh snapshot."""
        from scheduler_plugins_tpu.utils import flightrec

        fresh, meta = cluster.snapshot(
            [], now_ms=now_ms, pad_nodes=self._npad,
            extra_resources=self._extended(),
        )
        if meta.index.names != self._index.names:
            # the store names a resource the axis does not hold
            return "axis-width"
        if list(meta.node_names) != self._names:
            return "row-order"
        if self._columns_digest() != flightrec._pack_digest(
            {k: np.asarray(getattr(fresh.nodes, k))
             for k in self._node_columns()}
        ):
            return "column-digest"
        return (
            self._metrics_divergence(fresh.metrics)
            or self._verify_side(cluster)
            or self._selectors.divergence(cluster, self._names)
            or self._node_terms.divergence(cluster, self._names)
        )

    def _divergence_assigned(self, cluster, now_ms: int) -> Optional[str]:
        """`verify_assigned`'s comparison: the same things in the same
        order, against the store's objects. Raises `UnsupportedResource`
        where an assigned pod names a resource outside the axis."""
        from scheduler_plugins_tpu.utils import flightrec

        names = list(cluster.nodes)
        if names != self._names:
            return "row-order"
        expected, side_exp = self._expected_columns(
            cluster, names, want_side=self._side_verify_live(cluster)
        )
        if self._columns_digest() != flightrec._pack_digest(expected):
            return "column-digest"
        reason = self._metrics_divergence(
            self._expected_metrics(cluster, now_ms)
        )
        if reason is None and side_exp is not None:
            reason = self._side_divergence(*side_exp)
        return (
            reason
            or self._selectors.divergence(cluster, self._names)
            or self._node_terms.divergence(cluster, self._names)
        )

    def _expected_metrics(self, cluster, now_ms: int):
        """The `MetricsState` a fresh `build_snapshot` at this padding and
        clock would produce, from the store's own merge
        (`Cluster._metrics_with_missing`) and the shared lowering: nothing
        of the delta path is read. Staged as the resident columns and a
        fresh snapshot's are: the digest compares what the device holds,
        and a float64 column read back from a TPU is not, bit for bit, the
        host array that was put there (`PERF.md` finding 40). None where
        the store holds no report."""
        merged = cluster._metrics_with_missing(now_ms)
        if merged is None:
            return None
        node_pos = {name: i for i, name in enumerate(cluster.nodes)}
        return self._stage_pods(MetricsState(
            **node_metric_columns(merged, node_pos, self._npad)
        ))

    def _expected_columns(self, cluster, names, want_side=False):
        """The node columns a fresh `build_snapshot` at this padding
        would produce, accumulated O(nodes + assigned): every assigned pod
        adds its record's `usage` block at its node's row (requested/
        nonzero carry the pods-count slot per pod, so their sums equal the
        snapshot's pod_count overwrite), stacked and added in one call.
        With `want_side`, the SAME pass also accumulates the expected
        gang/quota side aggregates (`_scan_side_aggregates` semantics —
        one store walk covers both verifications); returns
        (columns, (gangs, namespaces) | None)."""
        index = self._index
        R = len(index)
        side_gangs: dict = {}
        side_ns: dict = {}

        def side_gang_acc(name):
            acc = side_gangs.get(name)
            if acc is None:
                acc = side_gangs[name] = [0, 0, np.zeros(R, np.int64)]
            return acc

        def side_assigned(pod, held, rec):
            if self._quota_tracking:
                acc = side_ns.get(pod.namespace)
                if acc is None:
                    acc = side_ns[pod.namespace] = [
                        np.zeros(R, np.int64), 0,
                    ]
                acc[0] = acc[0] + rec.req
                acc[1] += 1
            gang = pod.pod_group()
            if gang:
                acc = side_gang_acc(f"{pod.namespace}/{gang}")
                acc[0] += 1
                if held in cluster.nodes:
                    acc[2] = acc[2] + rec.usage[0]
        npad = self._npad
        alloc = np.zeros((npad, R), np.int64)
        capacity = np.zeros((npad, R), np.int64)
        mask = np.zeros(npad, bool)
        region = np.full(npad, -1, np.int32)
        zone = np.full(npad, -1, np.int32)
        # fresh first-seen label interning in store order (NOT the
        # engine's surviving tables): this keeps the label-drift check
        # the fresh-snapshot verify performs — deleting the first-seen
        # carrier of a code diverges here and rebases
        regions: dict = {}
        zones: dict = {}
        node_pos = {}
        for i, node in enumerate(cluster.nodes.values()):
            node_pos[node.name] = i
            alloc[i] = D._encode(node.allocatable, index)
            capacity[i] = D._encode(node.capacity, index)
            mask[i] = not node.unschedulable
            if node.region:
                region[i] = regions.setdefault(node.region, len(regions))
            if node.zone:
                zone[i] = zones.setdefault(node.zone, len(zones))
        # the assigned view, on the REAL pod objects: bound pods at their
        # node plus reserved (permit-waiting) pods at their held node —
        # the same definition `Cluster._assigned_pods` materializes, but
        # without its per-reserved-pod copies (a copy would miss the
        # record's identity check and evict the real pod's entry on every
        # check)
        records = self._records
        tlp = cluster.tlp_prediction
        rows: list = []  # the node row of each assigned pod ...
        blocks: list = []  # ... and its (3, R) usage block
        terminating_rows: list = []
        hits = 0

        def record_of(pod):
            # `_record`, with its hit spelled out: this runs once an
            # assigned pod and a method call each would be a tenth of it
            nonlocal hits
            rec = records.get(pod.uid)
            if (
                rec is None or rec.pod is not pod
                or rec.index is not index or rec.tlp != tlp
            ):
                return self._record(pod, "check")
            hits += 1
            return rec

        def held_at(pod, i, rec):
            rows.append(i)
            blocks.append(rec.usage)
            if pod.deletion_ms is not None:
                terminating_rows.append(i)

        for pod in cluster.pods.values():
            node_name = pod.node_name
            if node_name is None:
                if want_side:
                    # the `gated_pods()` predicate, INDEPENDENT of a
                    # permit reservation: a reserved gated pod counts
                    # both gated (here) and assigned (the reserved
                    # loop), exactly like the fresh snapshot and the
                    # delta stream (`_scan_side_aggregates`)
                    gang = pod.pod_group()
                    if (
                        gang and pod.scheduling_gated
                        and not pod.terminating
                    ):
                        side_gang_acc(f"{pod.namespace}/{gang}")[1] += 1
                continue
            i = node_pos.get(node_name)
            if i is None:
                if want_side:
                    # bound to a node the store no longer has: still
                    # counts into quota used + gang assigned (never
                    # slack) — build_snapshot's rule
                    side_assigned(pod, node_name, record_of(pod))
                continue
            rec = record_of(pod)
            held_at(pod, i, rec)
            if want_side:
                side_assigned(pod, node_name, rec)
        for uid, node in cluster.reserved.items():
            pod = cluster.pods.get(uid)
            if pod is None or pod.node_name is not None:
                continue
            rec = record_of(pod)
            if want_side:
                side_assigned(pod, node, rec)
            i = node_pos.get(node)
            if i is not None:
                held_at(pod, i, rec)
        self._looked("check", "hit", hits)
        usage = np.zeros((npad, 3, R), np.int64)
        at = np.array(rows, np.intp)
        if rows:
            np.add.at(usage, at, np.concatenate(blocks).reshape(-1, 3, R))
        requested, nonzero, limits = usage.transpose(1, 0, 2)
        # same key order as _node_columns so the digests align
        return {
            "alloc": alloc, "capacity": capacity, "requested": requested,
            "nonzero_requested": nonzero, "limits": limits,
            "mask": mask, "region": region, "zone": zone,
            "pod_count": np.bincount(at, minlength=npad).astype(np.int32),
            "terminating": np.bincount(
                np.array(terminating_rows, np.intp), minlength=npad
            ).astype(np.int32),
        }, ((side_gangs, side_ns) if want_side else None)

    # -- checkpoint / restore -------------------------------------------
    #: checkpoint format version (bump on layout change; restore refuses
    #: versions it does not understand)
    CHECKPOINT_VERSION = 1

    def checkpoint_bytes(self) -> Optional[bytes]:
        """Self-contained npz of the resident columns + slot/interning
        tables, or None before the first refresh. Written crash-safe by
        `save_checkpoint`; a process killed after writing one resumes
        serving via `restore_checkpoint` without rebuilding the resident
        base from the store."""
        import io
        import json as _json

        if self._nodes is None:
            return None
        cols = {k: np.asarray(v) for k, v in self._node_columns().items()}
        cols["nominated"] = np.asarray(self._nodes.nominated)
        header = {
            "version": self.CHECKPOINT_VERSION,
            "npad": self._npad,
            "generation": self._generation,
            "staleness": self._staleness,
            "names": self._names,
            "resources": list(self._index.names),
            "regions": self._regions,
            "zones": self._zones,
            "node_labels": {k: list(v) for k, v in
                            self._node_labels.items()},
            "tainted": sorted(self._tainted),
        }
        buf = io.BytesIO()
        np.savez(
            buf,
            header=np.frombuffer(
                _json.dumps(header, sort_keys=True).encode(), np.uint8
            ),
            **cols,
        )
        return buf.getvalue()

    def save_checkpoint(self, path: str) -> bool:
        """Crash-safe checkpoint write (`obs.atomic_write` temp+rename).
        Returns False when there is no resident base to checkpoint."""
        data = self.checkpoint_bytes()
        if data is None:
            return False
        obs.atomic_write(path, data)
        return True

    def restore_checkpoint(self, source) -> bool:
        """Rebuild the resident base from a checkpoint (`bytes` or a file
        path) — call AFTER `attach`. The restored state is NOT trusted
        blindly: `note_fault` marks it for an anti-entropy verify at the
        next refresh, so a checkpoint stale against the live store (the
        usual case after a crash — the dying sink's undrained deltas are
        gone) re-bases within one window, while an exact one resumes
        serving with generation continuity and no rebase
        (tests/test_resilience.py::TestCheckpointRestore)."""
        import io
        import json as _json

        import jax.numpy as jnp

        if isinstance(source, (str, bytes, bytearray)):
            data = source
            if isinstance(source, str):
                with open(source, "rb") as f:
                    data = f.read()
        else:
            raise TypeError(f"checkpoint source {type(source).__name__}")
        with np.load(io.BytesIO(data), allow_pickle=False) as z:
            header = _json.loads(bytes(z["header"].tobytes()).decode())
            if header.get("version") != self.CHECKPOINT_VERSION:
                raise ValueError(
                    f"checkpoint version {header.get('version')} != "
                    f"{self.CHECKPOINT_VERSION}"
                )
            from scheduler_plugins_tpu.state.snapshot import NodeState

            self._nodes = NodeState(
                **{k: jnp.asarray(z[k]) for k in (
                    "alloc", "capacity", "requested", "nonzero_requested",
                    "limits", "mask", "region", "zone", "pod_count",
                    "terminating", "nominated",
                )}
            )
        self._npad = int(header["npad"])
        # a checkpoint written before the axis could widen holds the
        # canonical four and no names
        from scheduler_plugins_tpu.api.resources import ResourceIndex

        self._index = ResourceIndex(header.get("resources", ()))
        self._axis_changed()
        self._generation = int(header["generation"])
        self._staleness = int(header["staleness"])
        self._names = list(header["names"])
        self._slots = {n: i for i, n in enumerate(self._names)}
        self._regions = list(header["regions"])
        self._zones = list(header["zones"])
        self._regions_in = _Interner(self._regions)
        self._zones_in = _Interner(self._zones)
        self._node_labels = {
            k: tuple(v) for k, v in header["node_labels"].items()
        }
        self._tainted = set(header["tainted"])
        # side tables are cheap to re-derive (one store scan) relative to
        # checkpointing them: rebuilt lazily at the next gang/quota use
        self._side = None
        self._side_dirty = True
        self._gang_rows = {}
        self._ns_rows = {}
        self._quota_tracking = False
        # the metrics columns likewise: lowered from the store's report
        # at the first refresh (O(nodes + recent bindings), no rebase)
        self._drop_metrics()
        # and the selector tables: built from the store at the first refresh
        self._selectors.reset()
        # and the node-term rows: each evaluated again on first use
        self._node_terms.reset()
        self._base_digest = None
        self._last = None
        self.note_fault("checkpoint-restore")
        self._observe()
        return True

    def _node_columns(self) -> dict:
        n = self._nodes
        return {
            "alloc": n.alloc, "capacity": n.capacity,
            "requested": n.requested,
            "nonzero_requested": n.nonzero_requested, "limits": n.limits,
            "mask": n.mask, "region": n.region, "zone": n.zone,
            "pod_count": n.pod_count, "terminating": n.terminating,
        }

    def _assemble(self, cluster, pending, now_ms: int = 0):
        """Snapshot view over the resident node columns + this cycle's
        pending batch (built through the same `build_pod_state` the full
        snapshot path uses, so the pod tensors are bit-identical). Gang
        and quota rosters assemble their `GangState`/`QuotaState` from
        the resident side tables: the per-PodGroup/per-quota OBJECT
        columns re-lower O(G + Q) through the SAME
        `gang_object_tables`/`quota_object_tables` the fresh path uses,
        the per-pod AGGREGATES come from the O(changed)-maintained side
        tables — never an O(cluster) pod loop. The load watcher's report
        rides along as the resident `MetricsState` (`_sync_metrics` brought
        it to this cycle's clock). Two spans side by side:
        `ServeRefresh/assemble` (one a served cycle: the pod tensors) and,
        where the store holds PodGroups or quotas, `ServeRefresh/gangs`
        (their tensors)."""
        index = self._index
        meta = SnapshotMeta(index=index)
        # gang interning in pod_groups-dict order — build_snapshot's own
        # first-seen rule, so codes match the fresh path's exactly
        pod_groups = list(cluster.pod_groups.values())
        gangs_in = _Interner(meta.gang_names)
        gang_pos = {
            pg.full_name: gangs_in.code(pg.full_name) for pg in pod_groups
        }
        ns_in = _Interner(meta.namespaces)
        batch_counts: dict[int, int] = {}
        with obs.tracer.span(
            "ServeRefresh/assemble", tid="serve", pending=len(pending)
        ):
            P = bucket_size(max(len(pending), 1))
            meta.node_names = list(self._names)
            meta.pod_names = [p.uid for p in pending]
            meta.regions = list(self._regions)
            meta.zones = list(self._zones)

            def gang_of(pod):
                name = pod.pod_group()
                if not name:
                    return -1
                return gang_pos.get(f"{pod.namespace}/{name}", -1)

            if pod_groups:
                def gang_of_counted(pod, _inner=gang_of):
                    g = _inner(pod)
                    if g >= 0:
                        batch_counts[g] = batch_counts.get(g, 0) + 1
                    return g
                gang_code = gang_of_counted
            else:
                gang_code = gang_of
            pods = self._stage_pods(build_pod_state(
                pending, P, index, ns_in, gang_code,
                cluster.tlp_prediction, row_cache=self._records,
            ))
        gang_state = quota_state = None
        if pod_groups or cluster.quotas:
            with obs.tracer.span(
                "ServeRefresh/gangs", tid="serve",
                gangs=len(pod_groups), quotas=len(cluster.quotas),
            ):
                gang_state, quota_state = self._assemble_side(
                    cluster, pod_groups, gang_pos, batch_counts, ns_in,
                    meta, P, now_ms,
                )
        snap = ClusterSnapshot(
            nodes=self._nodes, pods=pods, gangs=gang_state,
            quota=quota_state, metrics=self._metrics_state,
            scheduling=self._assemble_scheduling(cluster, pending, P),
        )
        return snap, meta

    def _assemble_scheduling(self, cluster, pending, P: int):
        """This cycle's `SchedulingState`: the selector tables'
        (`_assemble_selectors`), with the resident node-term rows and the
        batch's two index columns laid over it where a pod of the batch
        names a nodeSelector or a node-affinity term (span
        `ServeRefresh/node_terms`, opened for such a batch only). None
        where the batch carries neither, as a fresh build has it."""
        return self._node_terms.scheduling_state(
            pending, P, self._npad,
            self._assemble_selectors(cluster, pending, P),
        )

    def _assemble_selectors(self, cluster, pending, P: int):
        """This cycle's `SchedulingState` over the resident selector
        tables: the O(batch) rows (`pend_match`, `spread_*`, and under
        `ServeRefresh/affinity` the `aff_*` / `anti_*` / `waff_*` /
        `exist_anti_*` / `sym_*` ones) under the span
        `ServeRefresh/selectors`, opened only where the store's pods
        declare a spread constraint or a pod (anti-)affinity term. None
        where the batch carries no constraint and the store no term, as a
        fresh build has it."""
        if not cluster.selectors.tracks:
            return None
        with obs.tracer.span(
            "ServeRefresh/selectors", tid="serve", pending=len(pending)
        ):
            return self._selectors.scheduling_state(pending, P, self._npad)

    def _assemble_side(self, cluster, pod_groups, gang_pos, batch_counts,
                       ns_in, meta, P: int, now_ms: int):
        """(GangState | None, QuotaState | None), staged, on `bucket_size`
        buckets of the PodGroups and namespaces there are: a row none of
        them holds is inert, and objects that come and go give the solve
        no shape each (`build_snapshot` does the same)."""
        index = self._index
        R = len(index)
        side = self._side_host()
        gang_state = quota_state = None
        if pod_groups:
            G = bucket_size(len(gang_pos))
            backed_off = [
                name
                for name, until in cluster.gang_backoff_until_ms.items()
                if until > now_ms
            ]
            obj = gang_object_tables(
                pod_groups, gang_pos, index, G, backed_off
            )
            assigned = np.zeros(G, np.int32)
            gated = np.zeros(G, np.int32)
            slack = np.zeros((G, R), np.int64)
            for pg in pod_groups:
                row = self._gang_rows.get(pg.full_name)
                if row is None:
                    continue
                g = gang_pos[pg.full_name]
                assigned[g] = side["gang_assigned"][row]
                gated[g] = side["gang_gated"][row]
                slack[g] = side["gang_slack"][row]
            # total = this cycle's batch members + assigned + gated: the
            # same three populations build_snapshot's pod loop walks
            total = (assigned + gated).astype(np.int32)
            for g, count in batch_counts.items():
                total[g] += count
            gang_state = self._stage_pods(GangState(
                total_members=total,
                assigned=assigned,
                gated=gated,
                cluster_slack=slack,
                **obj,
            ))
        if cluster.quotas:
            quotas = list(cluster.quotas.values())
            # fresh interning order: batch namespaces (above), then quota
            # namespaces, then assigned-pod namespaces. The assigned tail
            # rows are all-default (used accumulates only under a quota),
            # so only the SET matters — the resident count tracks it.
            for q in quotas:
                ns_in.code(q.namespace)
            for name, row in self._ns_rows.items():
                if side["ns_assigned"][row] > 0:
                    ns_in.code(name)
            Q = bucket_size(len(meta.namespaces))
            qmin, qmax, qhas = quota_object_tables(quotas, index, ns_in, Q)
            qused = np.zeros((Q, R), np.int64)
            for q in quotas:
                row = self._ns_rows.get(q.namespace)
                if row is not None:
                    qused[ns_in.get(q.namespace)] = side["quota_used"][row]
            nom_req, nom_in_eq, nom_total, nom_batch = empty_quota_nominees(
                R, P
            )
            quota_state = self._stage_pods(QuotaState(
                min=qmin, max=qmax, used=qused, has_quota=qhas,
                nom_req=nom_req, nom_in_eq_mask=nom_in_eq,
                nom_total_mask=nom_total, nom_batch_idx=nom_batch,
            ))
        return gang_state, quota_state

    def _observe(self) -> None:
        obs.metrics.set_gauge(obs.SERVE_GENERATION, self._generation)
        obs.metrics.set_gauge(obs.SERVE_STALENESS, self._staleness)

    # -- observability hookups ------------------------------------------
    def annotate_record(self, rec) -> None:
        """Attach the serve-cycle provenance to a flight-recorder record:
        resident generation, events-since-base staleness, the base
        snapshot digest, and the packed delta stream itself (as plain
        dict-of-array specs, so generic `unpack_pytree` reads them back).
        The record stays replayable through the standard path — the
        assembled snapshot is captured in full — and this block is the
        evidence tying it to the delta stream that produced it."""
        from scheduler_plugins_tpu.utils.flightrec import pack_pytree

        if self._last is None:
            return
        serve = {
            "generation": self._generation,
            "staleness_events": self._staleness,
            "base_digest": self._base_digest,
            "mode": self._last["mode"],
            "events": self._last["events"],
        }
        if self._last["mode"] == "delta":
            packed = {
                "upserts": self._last["upserts"],
                "usage": self._last["usage"],
            }
            if "side" in self._last:
                packed["side"] = self._last["side"]
            if "selectors" in self._last:
                packed["selectors"] = self._last["selectors"]
            serve["deltas"] = pack_pytree(packed, rec.blobs)
        rec.manifest["serve"] = serve


def _shift_gather_args(npad: int, slot: int, survivors: int):
    """(gather_idx, valid) for `compact_node_rows`: rows above `slot`
    shift down one, the freed tail re-pads; `survivors` real rows remain.
    ONE constructor shared by the live compaction path and the AOT
    compile-readiness gate, so the certified argument layout IS the
    shipped one."""
    idx = np.empty(npad, np.int32)
    idx[:slot] = np.arange(slot, dtype=np.int32)
    idx[slot:npad - 1] = np.arange(slot + 1, npad, dtype=np.int32)
    idx[npad - 1] = npad - 1
    valid = np.zeros(npad, bool)
    valid[:survivors] = True
    return idx, valid


class StreamingServeEngine(ServeEngine):
    """The serving engine of the pipelined cycle engine
    (`framework.pipeline_cycle.PipelinedCycle`; docs/SCALING.md measured
    breakdown). Same exactness contract as the base engine — the
    differential gates hold it bit-identical to fresh snapshots — and what
    is its own:

    - **Node-delete compaction**: a Node/Delete no longer forces the
      O(cluster) rebase. The resident rows are shift-compacted in place
      by one donated gather program (`serving.deltas.compact_node_rows`),
      preserving row order (= the store's dict order after the pop) and
      re-padding the freed tail byte-identically to a fresh snapshot's
      pad rows. The event stream is segmented at each delete so slot
      numbering stays exact within every applied batch. Remaining
      rebase-class events (label re-interning, extended resources,
      unknown-node pods, sink overflow) rebase exactly as before. One
      self-healing caveat: the region/zone interning tables survive a
      compaction, so deleting the first-seen carrier of a label code can
      make the next anti-entropy digest diverge from a fresh re-intern —
      the divergence rebases (exact, just slower), never mis-serves.
    - **Staging**: packed deltas and pod tensors go to pjit as numpy
      (`_stage_args`, `_stage_pods`).
    - **`verify` is the O(nodes + assigned) check** whoever asks: the
      engine compacts rows in place, and every check of it reads the
      store's objects (`ServeEngine.verify_assigned`).

    The per-pod records and the O(assigned) expectation it used to
    override are the base engine's since ISSUE 37.
    """

    def __init__(self):
        super().__init__()
        self._compact_fn = D.node_compact_program()
        self._compact_warm: set = set()
        #: node-delete row compactions performed (each replaces what the
        #: base engine counts as a rebase)
        self.compactions = 0

    def verify(self, cluster, now_ms: Optional[int] = None
               ) -> Optional[str]:
        return self.verify_assigned(cluster, now_ms)

    def _stage_args(self, args):
        # pjit stages numpy args itself in one C++ pass; the explicit
        # per-array device conversion is pure Python overhead here
        return args

    def _stage_pods(self, pod_state):
        # the solve jit stages the pod tensors with the call; keeping
        # them numpy also spares the recorder a device round-trip
        return pod_state

    def _rebase_inner(self, cluster, pending, now_ms: int):
        out = super()._rebase_inner(cluster, pending, now_ms)
        if self._nodes is not None and self._npad not in self._compact_warm:
            # compile the compaction program for this resident shape NOW,
            # on a throwaway zero-state (NEVER the live carry — the
            # program donates its input, and the rebase just handed the
            # live tensors to the current cycle's snapshot), so the first
            # real node delete never pays a mid-run retrace
            self._compact_warm.add(self._npad)
            import warnings

            import jax
            import jax.numpy as jnp

            dummy = jax.tree.map(
                lambda a: jnp.zeros_like(a), self._nodes
            )
            idx = np.arange(self._npad, dtype=np.int32)
            valid = np.zeros(self._npad, bool)
            valid[:len(self._names)] = True
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers were not usable.*"
                )
                self._compact_fn(dummy, idx, valid)
        return out

    # -- segmented ingest -----------------------------------------------
    def _ingest(self, events):
        """Split the drained stream at compactable node-delete
        boundaries: classify+apply each preceding segment (slot numbering
        is exact within a segment — deletes renumber slots), compact the
        deleted row, continue. Returns the final delete-free tail for the
        base refresh flow. Falls back to the base whole-batch classify
        (rebase on delete) whenever there is no resident base to
        compact."""
        if self._nodes is None or not any(
            ev[0] == D.NODE_DELETE for ev in events
        ):
            return self._classify(events)
        segment: list = []
        rebase = None
        side_gang: list = []
        side_ns: list = []
        for ev in events:
            if ev[0] == D.NODE_DELETE and rebase is None:
                name = ev[1]
                # classify+apply the preceding segment FIRST: a node
                # added (or otherwise touched) in THIS drain window gets
                # its slot from the segment's upserts — looking the slot
                # up before applying would discard the delete and leave
                # a ghost resident row for a node the store no longer
                # has (an add+remove flap within one window)
                ups, use, side, rebase = self._classify(segment)
                segment = []
                if rebase is not None:
                    continue  # the resident base is dying anyway
                side_gang.extend(side[0])
                side_ns.extend(side[1])
                if ups or use:
                    self._grow(bucket_size(max(len(self._names), 1)))
                    self._apply_batch(ups, use)
                slot = self._slots.get(name)
                if slot is None:
                    # node the engine truly never saw: nothing resident
                    # to remove — keep the base bookkeeping only
                    self._tainted.discard(name)
                    self._node_labels.pop(name, None)
                    continue
                self._compact_row(name, slot)
                continue
            segment.append(ev)
        ups, use, side, seg_rebase = self._classify(segment)
        side = (side_gang + side[0], side_ns + side[1])
        return ups, use, side, rebase if rebase is not None else seg_rebase

    def _compact_row(self, name: str, slot: int) -> None:
        import warnings

        import jax.numpy as jnp

        with obs.tracer.span(
            "ServeRefresh/compact", tid="serve", slot=slot
        ):
            self._tainted.discard(name)
            self._node_labels.pop(name, None)
            idx, valid = _shift_gather_args(
                self._npad, slot, len(self._names) - 1
            )
            with warnings.catch_warnings():
                # CPU backends never donate and list every buffer (the
                # delta-apply program's known shape, PR 2/6)
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers were not usable.*"
                )
                self._nodes = self._compact_fn(
                    self._nodes, jnp.asarray(idx), jnp.asarray(valid)
                )
            self._names.pop(slot)
            self._slots = {n: i for i, n in enumerate(self._names)}
            self._metrics_stale = True
            self._selectors.invalidate()
            self._node_terms.invalidate()
            if self._gang_rows:
                # fresh snapshots drop gang slack of pods bound to a
                # deleted node — rebuild rather than drift (the base
                # engine's rebase path rebuilds side tables implicitly)
                self._side_dirty = True
            self.compactions += 1
            self._generation += 1
            self._staleness += 1
            self._last = {"mode": "compact", "events": 1}
            self._observe()


def compact_lower_args(n_nodes: int = 256, delete_slot: int = 3):
    """(jitted fn, sample args) for the AOT compile-readiness gate — the
    exact donated row-compaction program `StreamingServeEngine` runs on a
    node delete (`tools/tpu_lower.py` serving_node_compact), at the same
    reduced resident shape as `lower_program_args`. One constructor so
    the certified program and the shipped program cannot drift."""
    from scheduler_plugins_tpu.models import allocatable_scenario

    cluster = allocatable_scenario(n_nodes=n_nodes, n_pods=1)
    npad = bucket_size(n_nodes)
    snap, _meta = cluster.snapshot([], now_ms=0, pad_nodes=npad)
    idx, valid = _shift_gather_args(npad, delete_slot, n_nodes - 1)
    return D.node_compact_program(), (snap.nodes, idx, valid)


def side_lower_args(n_gangs: int = 8, n_ns: int = 4, n_rows: int = 16):
    """(jitted fn, sample args) for the AOT compile-readiness gate — the
    exact donated side-table apply program `ServeEngine` folds gang/quota
    aggregate deltas with (`tools/tpu_lower.py` serving_side_apply), at a
    reduced resident shape. One constructor so the certified program and
    the shipped program cannot drift."""
    import jax.numpy as jnp

    R = len(D.CANON_INDEX)
    G = bucket_size(n_gangs)
    Q = bucket_size(n_ns)
    tables = D.zero_side_tables(G, Q, R)
    gang_rows = [
        (j % n_gangs, 1, 0, np.ones(R, np.int64)) for j in range(n_rows)
    ]
    ns_rows = [
        (j % n_ns, np.ones(R, np.int64), 1) for j in range(n_rows)
    ]
    packed = D.SideDeltas.pack(gang_rows, ns_rows, R, G, Q)
    args = (tables, *(jnp.asarray(a) for a in packed.as_args()))
    return D.side_apply_program(), args


def selector_lower_args(n_tracks: int = 3, n_terms: int = 2,
                        n_domains: int = 48, n_rows: int = 40):
    """(jitted fn, sample args) for the AOT compile-readiness gate — the
    exact donated selector apply program `ResidentSelectors.apply` folds a
    window's +-1 rows with (`tools/tpu_lower.py` serving_selector_apply),
    at a reduced shape with all three tables: the matching-pod counts, the
    carriers of required anti terms and those of the score's terms."""
    import jax.numpy as jnp

    TR, Dp = bucket_size(n_tracks), bucket_size(n_domains)
    E = bucket_size(n_terms, minimum=1)
    tables = tuple(
        jnp.zeros((rows, Dp), jnp.int64) for rows in (TR, E, E)
    )
    packed = D.SelectorDeltas.pack({
        (j % (TR + 2 * E), j % n_domains): 1 - 2 * (j % 2)
        for j in range(n_rows)
    })
    args = (tables, *(jnp.asarray(a) for a in packed.as_args()))
    return D.selector_apply_program(), args


def lower_program_args(n_nodes: int = 256, n_upserts: int = 8,
                       n_deltas: int = 64):
    """(jitted fn, sample args) for the AOT compile-readiness gate — the
    exact donated apply program `ServeEngine` runs, at a reduced resident
    shape (`tools/tpu_lower.py` serving_delta_apply). One constructor so
    the certified program and the shipped program cannot drift."""
    import jax
    import jax.numpy as jnp

    from scheduler_plugins_tpu.models import allocatable_scenario

    cluster = allocatable_scenario(n_nodes=n_nodes, n_pods=1)
    npad = bucket_size(n_nodes)
    snap, _meta = cluster.snapshot([], now_ms=0, pad_nodes=npad)
    R = len(D.CANON_INDEX)
    ups = D.NodeUpserts.pack(
        [(j, np.zeros(R, np.int64), np.zeros(R, np.int64), True, -1, -1)
         for j in range(n_upserts)],
        R,
    )
    use = D.UsageDeltas.pack(
        [(j % n_nodes, np.zeros((3, R), np.int64), 0, 0)
         for j in range(n_deltas)],
        R,
    )
    args = (
        snap.nodes,
        *(jnp.asarray(a) for a in ups.as_args()),
        *(jnp.asarray(a) for a in use.as_args()),
    )
    return D.delta_apply_program(), args
