"""Mutable host-side cluster store.

The event-driven shell the reference builds out of client-go informers +
plugin-local caches (SURVEY.md §1 dataflow): object upserts/deletes come in,
snapshots go out. Also owns the scheduling-runtime bookkeeping that must not
live on-device: Permit reservations (waiting pods), gang deadlines, backoff and
failure times (/root/reference/pkg/coscheduling/core/core.go:134-192).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from scheduler_plugins_tpu.api import events as ev
from scheduler_plugins_tpu.api.objects import (
    AppGroup,
    ElasticQuota,
    NetworkTopology,
    Node,
    NodeResourceTopology,
    Pod,
    PodDisruptionBudget,
    PodGroup,
    PodPhase,
    PriorityClass,
    SeccompProfile,
)
from scheduler_plugins_tpu.obs import ledger as podledger
from scheduler_plugins_tpu.state.scheduling import (
    SelectorRegistry,
    has_affinity_terms,
)
from scheduler_plugins_tpu.state.snapshot import build_snapshot


@dataclass
class Cluster:
    nodes: dict[str, Node] = field(default_factory=dict)
    pods: dict[str, Pod] = field(default_factory=dict)  # keyed by uid
    pod_groups: dict[str, PodGroup] = field(default_factory=dict)  # ns/name
    quotas: dict[str, ElasticQuota] = field(default_factory=dict)  # namespace
    nrts: dict[str, NodeResourceTopology] = field(default_factory=dict)
    app_groups: dict[str, AppGroup] = field(default_factory=dict)
    network_topologies: dict[str, NetworkTopology] = field(default_factory=dict)
    seccomp_profiles: dict[str, SeccompProfile] = field(default_factory=dict)
    priority_classes: dict[str, PriorityClass] = field(default_factory=dict)
    pdbs: dict[str, PodDisruptionBudget] = field(default_factory=dict)
    #: Namespace objects (labels) — PodAffinityTerm.namespaceSelector targets
    namespaces: dict[str, "Namespace"] = field(default_factory=dict)
    node_metrics: Optional[dict] = None
    #: TargetLoadPacking pod CPU-prediction parameters
    #: (multiplier, default-request millis) — installed by the plugin's
    #: configure_cluster from DefaultRequests/DefaultRequestsMultiplier
    #: (apis/config/v1/defaults.go:76-90)
    tlp_prediction: tuple = (1.5, 1000)
    #: optional NRT cache policy (state.nrt_cache); when set, snapshots read
    #: the cache's adjusted zone view instead of the raw NRT objects
    nrt_cache: Optional[object] = None
    #: profile names THIS scheduler owns: only pods whose
    #: spec.schedulerName matches enter the queue (the upstream scheduler
    #: dequeues per-profile; a second-scheduler deployment must never
    #: steal default-scheduler pods). Other pods still count for capacity,
    #: gang membership and NRT foreign-pod tracking.
    scheduler_names: set = field(
        default_factory=lambda: {"tpu-scheduler"}
    )

    # scheduling-runtime bookkeeping (host-only)
    reserved: dict[str, str] = field(default_factory=dict)  # uid -> node
    #: per-POD permit deadlines (the upstream waitingPods timers,
    #: coscheduling.go:227-235): uid -> wall-clock ms at which this waiting
    #: pod's Permit times out; each sibling gets its own timer at ITS
    #: reservation time, and the earliest firing rejects the whole gang
    pod_deadline_ms: dict[str, int] = field(default_factory=dict)
    gang_backoff_until_ms: dict[str, int] = field(default_factory=dict)
    gang_last_failure_ms: dict[str, int] = field(default_factory=dict)
    #: recently-bound pods whose load the metrics provider has not reported
    #: yet (the trimaran PodAssignEventHandler ScheduledPodsCache,
    #: /root/reference/pkg/trimaran/handler.go:47-171): uid -> (bind ms, node)
    recent_bindings: dict[str, tuple[int, str]] = field(default_factory=dict)
    #: binds this store has made (`bind`), counted where the pod's
    #: `node_name` is set: whoever has seen a pod bound reads a count that
    #: holds it. The daemon's `bound_total` is this number
    binds_total: int = 0
    #: uids of LIVE pods, pending or bound, carrying pod (anti-)affinity
    #: terms: a fresh build of the scheduling tables then needs the
    #: assigned pod objects, which the native snapshot fast path skips
    _affinity_spec_pods: set = field(default_factory=set)
    #: the spread tracks and the pod (anti-)affinity terms the store's pods
    #: declare, interned where a pod is added and released where it is
    #: removed (pod specs are immutable): the resident engine keeps its
    #: selector and carrier counts by them (docs/SERVING.md "Resident
    #: selector counts", "Resident affinity terms")
    selectors: SelectorRegistry = field(default_factory=SelectorRegistry)
    # EnqueueExtensions bookkeeping (upstream scheduling queue): a monotonic
    # event counter, the last counter value per event kind, and per-pod
    # unschedulable records (event counter at failure, flush deadline).
    #: upstream podMaxInUnschedulablePodsDuration: failed pods re-enter the
    #: batch unconditionally after this long even with no event
    requeue_flush_ms: int = 5 * 60 * 1000
    event_seq: int = field(default=0)
    event_last: dict[str, int] = field(default_factory=dict)
    unschedulable_since: dict[str, tuple[int, int]] = field(
        default_factory=dict
    )
    # requeue backoff (upstream backoffQ: k8s.io/kubernetes
    # pkg/scheduler/internal/queue/scheduling_queue.go
    # calculateBackoffDuration — podInitialBackoffDuration 1s doubling to
    # podMaxBackoffDuration 10s per scheduling attempt): per-pod attempt
    # counts and the wall-clock ms before which `_requeue_eligible` must
    # not re-admit the pod. The jitter multiplier is DETERMINISTIC
    # (blake2b of seed/uid/attempt, in [0.5, 1.0]) so colliding retries
    # spread out while a seeded run replays exactly.
    backoff_initial_ms: int = 1000
    backoff_max_ms: int = 10_000
    backoff_seed: int = 0
    pod_attempts: dict[str, int] = field(default_factory=dict)
    pod_backoff_until_ms: dict[str, int] = field(default_factory=dict)
    #: last failure stamp per pod — one cycle can mark the same pod twice
    #: (bind-loop failure + whole-gang rejection); only the first marks
    #: an ATTEMPT
    _pod_last_failure_ms: dict[str, int] = field(default_factory=dict)
    #: optional `serving.deltas.DeltaSink`: when set (ServeEngine.attach),
    #: the mutators below push typed node-column delta events alongside
    #: their `note_event` calls — the O(changed) feed the resident-state
    #: serving engine ingests instead of re-snapshotting (docs/SERVING.md)
    delta_sink: Optional[object] = None
    #: opt-in O(changed) pending index (`enable_pending_index`: the
    #: daemon switches it on for every engine, the pipelined and laned
    #: engines themselves): uid -> Pod for every currently-schedulable
    #: pod, maintained by the same mutators that notify the delta sink.
    #: None (the default) keeps `pending_pods` as the exact O(pods) scan.
    _pending_idx: Optional[dict] = None
    #: admission serial per uid, reproducing the pods-dict iteration
    #: order the scan yields: assigned at FIRST add (dict updates keep
    #: their position), re-assigned when a removed uid is re-added
    #: (Python dicts move it to the end) — so the indexed queue order is
    #: bit-identical to the scan's, ties and all
    _pod_order: dict = field(default_factory=dict)
    _order_next: int = 0
    #: optional hook, called with no argument whenever the pending index
    #: gains a uid it did not hold (first sighting, gate lifted,
    #: reservation released): the daemon's loop waits on the doorbell it
    #: rings. It runs under whatever lock guards the store, once per pod
    #: on the ingest path, so it must be cheap. A pod a cycle leaves
    #: pending never left the index, so it does not fire it. Needs the
    #: index (`enable_pending_index`).
    on_pending_gain: Optional[Callable[[], None]] = None

    def note_event(self, kind: str) -> None:
        """Record a cluster event ("Resource/Action", `api.events`) for
        requeue gating."""
        self.event_seq += 1
        self.event_last[kind] = self.event_seq

    def mark_unschedulable(self, uid: str, now_ms: int) -> None:
        """Park a pod and charge one backoff attempt: duration =
        min(initial * 2^(attempts-1), max) scaled by the deterministic
        jitter in [0.5, 1.0] (upstream calculateBackoffDuration shape —
        see the field comment above for the citation). A successful bind
        or a pod delete clears the attempt count."""
        if self._pod_last_failure_ms.get(uid) != now_ms:
            self._pod_last_failure_ms[uid] = now_ms
            attempts = self.pod_attempts.get(uid, 0) + 1
            self.pod_attempts[uid] = attempts
            base = min(
                self.backoff_initial_ms * (1 << min(attempts - 1, 30)),
                self.backoff_max_ms,
            )
            self.pod_backoff_until_ms[uid] = now_ms + int(
                base * (0.5 + 0.5 * self._backoff_jitter(uid, attempts))
            )
            led = podledger.LEDGER
            if led.enabled:
                # the charged branch only: a same-now re-mark (bind-loop
                # failure + whole-gang rejection in one cycle) is one
                # attempt and one ledger transition
                pod = self.pods.get(uid)
                led.on_unschedulable(
                    uid, attempts,
                    self.pod_backoff_until_ms[uid] - now_ms,
                    bool(pod is not None and pod.pod_group()),
                )
        self.unschedulable_since[uid] = (
            self.event_seq,
            now_ms + self.requeue_flush_ms,
        )

    def _backoff_jitter(self, uid: str, attempt: int) -> float:
        """[0, 1) from blake2b(seed:uid:attempt) — stable across runs
        and processes (Python's hash() is salted; an rng stream would
        depend on failure ORDER, which serve/baseline arms must not)."""
        import hashlib

        h = hashlib.blake2b(
            f"{self.backoff_seed}:{uid}:{attempt}".encode(), digest_size=8
        ).digest()
        return int.from_bytes(h, "big") / 2.0 ** 64

    def _clear_backoff(self, uid: str) -> None:
        self.pod_attempts.pop(uid, None)
        self.pod_backoff_until_ms.pop(uid, None)
        self._pod_last_failure_ms.pop(uid, None)

    # -- native mirror ----------------------------------------------------
    def attach_native_store(self):
        """Mirror the hot node columns into the C++ columnar store
        (bridge/snapshot_store.cc) so snapshots read them via memcpy exports
        instead of an O(assigned pods) Python accumulate per cycle (the
        informer-cache -> NodeInfo lowering the reference keeps in Go).
        Replays current state; subsequent upserts/binds/deletes maintain it
        incrementally. The fast path engages only when the snapshot's
        resource axis is exactly the canonical four (the store layout,
        CLAUDE.md invariant) and no side-table subsystems need the assigned
        pod objects."""
        from scheduler_plugins_tpu.bridge import NativeStore
        from scheduler_plugins_tpu.api.resources import CANONICAL

        self._native = NativeStore(len(CANONICAL))
        self._native_node_ids: dict[str, int] = {}
        self._native_pod_ids: dict[str, int] = {}
        #: monotonic — deletions must never free an id for reuse, or a new
        #: pod would silently replace a live one's store contribution
        self._native_next_pod_id = 0
        #: object keys carrying extended resources the 4-slot store cannot
        #: represent; the fast path disengages while any are LIVE (deleting
        #: the object re-enables it)
        self._native_incompat: set[str] = set()
        self._native_replaying = True
        try:
            for node in self.nodes.values():
                self._native_upsert_node(node)
            for pod in self.pods.values():
                self._native_upsert_pod(pod)  # re-binds live reservations
        finally:
            self._native_replaying = False
        return self._native

    @property
    def native(self):
        return getattr(self, "_native", None)

    def _canon_vec(self, key, *quantity_maps):
        from scheduler_plugins_tpu.api.resources import CANONICAL

        import numpy as np

        vecs = []
        incompat = False
        for quantities in quantity_maps:
            vec = np.zeros(len(CANONICAL), np.int64)
            for r, v in quantities.items():
                try:
                    vec[CANONICAL.index(r)] = v
                except ValueError:
                    # extended resource: the 4-slot store can't carry it
                    incompat = True
            vecs.append(vec)
        if incompat:
            self._native_incompat.add(key)
        else:
            self._native_incompat.discard(key)
        return vecs

    def _native_upsert_node(self, node: Node):
        is_new = node.name not in self._native_node_ids
        if is_new:
            self._native_node_ids[node.name] = len(self._native_node_ids)
        alloc, cap = self._canon_vec(
            f"node/{node.name}", node.allocatable, node.capacity
        )
        self._native.upsert_node(self._native_node_ids[node.name], alloc, cap)
        if not is_new or getattr(self, "_native_replaying", False):
            # known node (routine status update), or the attach replay will
            # upsert every pod afterwards anyway: nothing to re-link
            return
        # pods mirrored before their node arrived (cross-watch event
        # ordering) were stored unbound: re-upsert them now
        for pod in self.pods.values():
            if pod.node_name == node.name:
                self._native_upsert_pod(pod)
        for uid, rnode in self.reserved.items():
            if rnode == node.name and uid in self._native_pod_ids:
                self._native.bind(
                    self._native_pod_ids[uid],
                    self._native_node_ids[node.name],
                )

    def _native_upsert_pod(self, pod: Pod):
        if pod.uid not in self._native_pod_ids:
            # ids are never reused: a delete+re-add is a new incarnation
            self._native_pod_ids[pod.uid] = self._native_next_pod_id
            self._native_next_pod_id += 1
        req, lim = self._canon_vec(
            f"pod/{pod.uid}", pod.effective_request(), pod.effective_limits()
        )
        self._native.upsert_pod(
            self._native_pod_ids[pod.uid],
            req,
            limits=lim,
            priority=pod.priority,
            creation_ms=pod.creation_ms,
            node_id=self._native_node_ids.get(pod.node_name, -1),
            terminating=pod.terminating,
        )
        # a re-upsert of a permit-reserved pod must not drop its hold
        rnode = self.reserved.get(pod.uid)
        if rnode is not None and rnode in self._native_node_ids:
            self._native.bind(
                self._native_pod_ids[pod.uid], self._native_node_ids[rnode]
            )

    def _native_rebuild(self):
        """Node deletion invalidates store row order: replay from scratch
        (rare control-plane event; everything else is incremental)."""
        self._native.close()
        self.attach_native_store()

    # -- upserts ---------------------------------------------------------
    def add_node(self, node: Node):
        self.note_event(
            ev.NODE_UPDATE if node.name in self.nodes else ev.NODE_ADD
        )
        self.nodes[node.name] = node
        if self.native is not None:
            self._native_upsert_node(node)
        if self.delta_sink is not None:
            self.delta_sink.node_upsert(node)

    def remove_node(self, name: str):
        if self.nodes.pop(name, None) is not None:
            self.note_event(ev.NODE_DELETE)
            if self.delta_sink is not None:
                self.delta_sink.node_delete(name)
        if self.native is not None:
            self._native_rebuild()

    _has_affinity_terms = staticmethod(has_affinity_terms)

    def _held_node(self, pod: Optional[Pod]) -> Optional[str]:
        """The node whose usage columns `pod` currently contributes to:
        its binding, else its permit reservation (reserved pods hold
        capacity exactly like bound ones in the snapshot's assigned
        view). None for plain pending pods."""
        if pod is None:
            return None
        return pod.node_name or self.reserved.get(pod.uid)

    def _gang_gated_key(self, pod: Optional[Pod]) -> Optional[str]:
        """The gang this pod counts into as an UNBOUND, scheduling-gated
        member (the `gated_pods()` contribution to the snapshot's gang
        gated/total counters), or None — the serving engine's resident
        gang side table tracks transitions of this predicate
        (serving.deltas.GANG_GATED)."""
        if pod is None or pod.node_name is not None:
            return None
        if not pod.scheduling_gated or pod.terminating:
            return None
        name = pod.pod_group()
        if not name:
            return None
        return f"{pod.namespace}/{name}"

    def add_pod(self, pod: Pod):
        old = self.pods.get(pod.uid)
        self.note_event(ev.POD_UPDATE if old is not None else ev.POD_ADD)
        if old is None and pod.node_name is None:
            led = podledger.LEDGER
            if led.enabled:
                led.on_first_seen(pod)
        if self.delta_sink is not None:
            # an upsert swaps the pod's assigned contribution wholesale
            # (requests may have changed; a stale echo may drop the node)
            old_hold = self._held_node(old)
            if old_hold is not None:
                self.delta_sink.pod_unassigned(old, old_hold)
            elif old is not None:
                self.delta_sink.pod_forgotten(pod.uid)
            # gated-gang-membership transition, captured at event time
            # (the upsert replaces the object wholesale)
            old_gated = self._gang_gated_key(old)
            new_gated = self._gang_gated_key(pod)
            if old_gated != new_gated:
                if old_gated is not None:
                    self.delta_sink.gang_gated(old_gated, -1)
                if new_gated is not None:
                    self.delta_sink.gang_gated(new_gated, +1)
        self.pods[pod.uid] = pod
        if self.delta_sink is not None:
            new_hold = self._held_node(pod)
            if new_hold is not None:
                self.delta_sink.pod_assigned(pod, new_hold)
            self.delta_sink.note_nomination(pod)
        self._binding_touched(pod.uid)
        affine = self._has_affinity_terms(pod)
        if affine:
            # affinity tables need ASSIGNED pod objects at snapshot build,
            # which the native fast path skips (pod specs are immutable,
            # so count on add/remove)
            self._affinity_spec_pods.add(pod.uid)
        elif old is not None:
            self._affinity_spec_pods.discard(pod.uid)
        if affine or pod.topology_spread or old is not None:
            self.selectors.add(pod)
        if self.nrt_cache is not None and hasattr(self.nrt_cache, "track_pod"):
            # foreign-pod detection (cache/foreign_pods.go:42-99)
            self.nrt_cache.track_pod(pod)
        if self.native is not None:
            self._native_upsert_pod(pod)
        self._index_add_pod(pod, was_present=old is not None)

    def remove_pod(self, uid: str):
        self.release_reservation(uid)  # notifies the NRT cache too
        self._affinity_spec_pods.discard(uid)
        self.selectors.remove(uid)
        self.unschedulable_since.pop(uid, None)
        self._clear_backoff(uid)
        pod = self.pods.pop(uid, None)
        self._binding_touched(uid)
        # after the pop: release_reservation may have re-indexed the
        # still-present pod above; a removed uid must leave both tables
        # (a later re-add lands at the end, like the pods dict)
        self._index_drop_pod(uid, forget_order=True)
        if pod is not None:
            self.note_event(ev.POD_DELETE)
            if pod.node_name is None:
                led = podledger.LEDGER
                if led.enabled:
                    led.on_delete(uid)
            if self.delta_sink is not None:
                if pod.node_name is not None:
                    # bound pod's usage leaves with it (a reserved pod's
                    # hold was already released above)
                    self.delta_sink.pod_unassigned(pod, pod.node_name)
                else:
                    self.delta_sink.pod_forgotten(uid)
                gated = self._gang_gated_key(pod)
                if gated is not None:
                    self.delta_sink.gang_gated(gated, -1)
                self.delta_sink.forget_nomination(uid)
        if (
            pod is not None
            and pod.node_name is not None
            and self.nrt_cache is not None
        ):
            # a bound pod's assumed deduction must not outlive the pod
            self.nrt_cache.unreserve(pod.node_name, pod)
        if pod is not None and self.native is not None:
            pod_id = self._native_pod_ids.pop(uid, None)
            if pod_id is not None:
                self._native.delete_pod(pod_id)
            self._native_incompat.discard(f"pod/{uid}")

    def mark_terminating(self, uid: str, now_ms: int):
        """DELETE issued (preemption victim): flips the terminating flag in
        both the object model and the native mirror."""
        pod = self.pods.get(uid)
        if pod is None:
            return
        was_terminating = pod.terminating
        # gated-gang contribution captured BEFORE the in-place flip (a
        # terminating gated member leaves `gated_pods()`)
        gated = (
            self._gang_gated_key(pod)
            if self.delta_sink is not None and not was_terminating else None
        )
        pod.deletion_ms = now_ms
        if not was_terminating:
            led = podledger.LEDGER
            if led.enabled:
                led.on_terminating(uid)
        if gated is not None:
            self.delta_sink.gang_gated(gated, -1)
        self._index_drop_pod(uid)
        self.note_event(ev.POD_UPDATE)
        if self.native is not None:
            self._native_upsert_pod(pod)
        if self.delta_sink is not None and not was_terminating:
            # the held-capacity node, binding OR reservation: a reserved
            # victim's terminating flag counts at its reserved node in the
            # snapshot's assigned view, and the eventual release subtracts
            # the event-time flag — skipping the +1 here would leave the
            # resident terminating column permanently negative
            held = self._held_node(pod)
            if held is not None:
                self.delta_sink.pod_terminating(pod, held)

    def add_pod_group(self, pg: PodGroup):
        self.note_event(
            ev.POD_GROUP_UPDATE if pg.full_name in self.pod_groups
            else ev.POD_GROUP_ADD
        )
        self.pod_groups[pg.full_name] = pg

    def add_quota(self, eq: ElasticQuota):
        self.note_event(
            ev.ELASTIC_QUOTA_UPDATE if eq.namespace in self.quotas
            else ev.ELASTIC_QUOTA_ADD
        )
        self.quotas[eq.namespace] = eq

    def add_nrt(self, nrt: NodeResourceTopology):
        self.note_event(
            ev.NRT_UPDATE if nrt.node_name in self.nrts
            else ev.NRT_ADD
        )
        self.nrts[nrt.node_name] = nrt
        if self.nrt_cache is not None:
            self.nrt_cache.update_nrt(nrt)

    def remove_nrt(self, node_name: str):
        """NRT CR deleted: evict from the cache tier too, or the snapshot
        keeps building NUMA tables from the stale copy forever."""
        if node_name in self.nrts:
            self.note_event(ev.NRT_DELETE)
        self.nrts.pop(node_name, None)
        if self.nrt_cache is not None:
            self.nrt_cache.delete_nrt(node_name)

    def add_app_group(self, ag: AppGroup):
        self.note_event(
            ev.APP_GROUP_UPDATE
            if f"{ag.namespace}/{ag.name}" in self.app_groups
            else ev.APP_GROUP_ADD
        )
        self.app_groups[f"{ag.namespace}/{ag.name}"] = ag

    def add_network_topology(self, nt: NetworkTopology):
        self.note_event(
            ev.NETWORK_TOPOLOGY_UPDATE
            if f"{nt.namespace}/{nt.name}" in self.network_topologies
            else ev.NETWORK_TOPOLOGY_ADD
        )
        self.network_topologies[f"{nt.namespace}/{nt.name}"] = nt

    def add_seccomp_profile(self, sp: SeccompProfile):
        self.note_event(
            ev.SECCOMP_PROFILE_UPDATE
            if sp.full_name in self.seccomp_profiles
            else ev.SECCOMP_PROFILE_ADD
        )
        self.seccomp_profiles[sp.full_name] = sp

    def add_priority_class(self, pc: PriorityClass):
        self.note_event(
            ev.PRIORITY_CLASS_UPDATE if pc.name in self.priority_classes
            else ev.PRIORITY_CLASS_ADD
        )
        self.priority_classes[pc.name] = pc

    def add_namespace(self, ns):
        self.note_event(
            ev.NAMESPACE_UPDATE if ns.name in self.namespaces
            else ev.NAMESPACE_ADD
        )
        self.namespaces[ns.name] = ns

    def add_pdb(self, pdb: PodDisruptionBudget):
        self.note_event(
            ev.PDB_UPDATE
            if f"{pdb.namespace}/{pdb.name}" in self.pdbs
            else ev.PDB_ADD
        )
        self.pdbs[f"{pdb.namespace}/{pdb.name}"] = pdb

    # -- derived ---------------------------------------------------------
    def pod_group_of(self, pod: Pod) -> Optional[PodGroup]:
        name = pod.pod_group()
        if not name:
            return None
        return self.pod_groups.get(f"{pod.namespace}/{name}")

    def gang_sort_time(self, pg: PodGroup) -> int:
        """Queue-sort timestamp for a gang: last schedule-failure time when
        set (defeats head-of-line blocking, core.go:365-384), else creation."""
        return self.gang_last_failure_ms.get(pg.full_name, pg.creation_ms)

    def gang_members(self, pg: PodGroup) -> list[Pod]:
        return [
            p
            for p in self.pods.values()
            if p.namespace == pg.namespace
            and p.pod_group() == pg.name
        ]

    def _pending_eligible(self, pod: Pod) -> bool:
        """THE schedulable-queue predicate — one copy shared by the scan
        and the maintained index, so the two views cannot drift."""
        return (
            pod.node_name is None
            and pod.uid not in self.reserved
            and pod.phase == PodPhase.PENDING
            and not pod.terminating
            and not pod.scheduling_gated
            and pod.scheduler_name in self.scheduler_names
        )

    def enable_pending_index(self) -> None:
        """Switch `pending_pods` from the O(pods) scan to a maintained
        O(changed) index (the pipelined engine's ingest path,
        docs/SCALING.md). Call AFTER `scheduler_names` and the initial
        population are configured; mutators keep it exact from here on.
        Code that flips a pod's eligibility IN PLACE (outside the store
        mutators — the same blind spot the delta sink has) must call
        `reindex_pod`."""
        self._pod_order = {uid: i for i, uid in enumerate(self.pods)}
        self._order_next = len(self._pod_order)
        self._pending_idx = {
            p.uid: p for p in self.pods.values() if self._pending_eligible(p)
        }

    def disable_pending_index(self) -> None:
        self._pending_idx = None
        self._pod_order = {}
        self._order_next = 0

    def reindex_pod(self, uid: str) -> None:
        """Re-evaluate one pod's pending-index membership after an
        in-place eligibility flip (phase / scheduling gate)."""
        pod = self.pods.get(uid)
        if pod is not None and pod.node_name is None:
            led = podledger.LEDGER
            if led.enabled:
                led.on_gate_flip(uid, bool(pod.scheduling_gated))
        if self._pending_idx is None:
            return
        if pod is not None and self._pending_eligible(pod):
            self._index_hold(pod)
        else:
            self._pending_idx.pop(uid, None)

    def _index_hold(self, pod: Pod) -> None:
        gained = self.on_pending_gain
        if gained is not None and pod.uid not in self._pending_idx:
            gained()
        self._pending_idx[pod.uid] = pod

    def _index_add_pod(self, pod: Pod, was_present: bool) -> None:
        if self._pending_idx is None:
            return
        if not was_present or pod.uid not in self._pod_order:
            # first sighting (or re-add after a remove): dicts append
            self._pod_order[pod.uid] = self._order_next
            self._order_next += 1
        if self._pending_eligible(pod):
            self._index_hold(pod)
        else:
            self._pending_idx.pop(pod.uid, None)

    def _index_drop_pod(self, uid: str, forget_order: bool = False) -> None:
        if self._pending_idx is None:
            return
        self._pending_idx.pop(uid, None)
        if forget_order:
            self._pod_order.pop(uid, None)

    def pending_pods(self) -> list[Pod]:
        """Schedulable queue: gated pods stay out (upstream keeps them off
        activeQ entirely — they are neither attempted nor reported failed),
        and only pods addressed to one of `scheduler_names` enter (the
        upstream per-profile dequeue). With the opt-in index enabled the
        list is assembled O(pending log pending) in the identical order
        (admission serials mirror the dict iteration the scan performs)."""
        if self._pending_idx is not None:
            order = self._pod_order
            return sorted(
                self._pending_idx.values(), key=lambda p: order[p.uid]
            )
        return [
            p
            for p in self.pods.values()
            if self._pending_eligible(p)
        ]

    def pending_count(self) -> int:
        """`len(pending_pods())`: the index's size when it is on, the scan
        otherwise."""
        if self._pending_idx is not None:
            return len(self._pending_idx)
        return len(self.pending_pods())

    def admission_serial(self, uid: str) -> int:
        """The pod's position in admission order — the reproducible
        partition key of the K-lane engine's "hash" mode
        (`parallel.lanes.lane_key`). With the pending index enabled this
        is the maintained `_pod_order` serial (survives removes of other
        pods); without it, the dict-iteration position (the same order
        the index would have assigned). -1 for an unknown uid."""
        if self._pod_order:
            return self._pod_order.get(uid, -1)
        for i, known in enumerate(self.pods):
            if known == uid:
                return i
        return -1

    def gated_pods(self) -> list[Pod]:
        return [
            p
            for p in self.pods.values()
            if p.node_name is None and p.scheduling_gated and not p.terminating
        ]

    # -- binding / reservations -----------------------------------------
    def bind(self, uid: str, node_name: str, now_ms: int = 0):
        held = self.reserved.pop(uid, None)
        self.pod_deadline_ms.pop(uid, None)
        self.unschedulable_since.pop(uid, None)
        self._clear_backoff(uid)
        self.note_event(ev.POD_UPDATE)  # assigned: spec.nodeName set
        if self.delta_sink is not None:
            if held != node_name:
                # a reservation-to-bind on the SAME node is already
                # counted; anything else transfers the contribution
                if held is not None:
                    self.delta_sink.pod_unassigned(self.pods[uid], held)
                self.delta_sink.pod_assigned(self.pods[uid], node_name)
            # a (defensively possible) gated pod leaves `gated_pods()`
            # the moment nodeName lands — its gang gated count drops
            gated = self._gang_gated_key(self.pods[uid])
            if gated is not None:
                self.delta_sink.gang_gated(gated, -1)
            # bound pods never count toward the nominated column
            self.delta_sink.forget_nomination(uid)
        self.pods[uid].node_name = node_name
        self.binds_total += 1
        self._index_drop_pod(uid)
        led = podledger.LEDGER
        if led.enabled:
            led.on_bind(uid, node_name)
        self.recent_bindings[uid] = (now_ms, node_name)
        if self.binds_total % self.BINDING_CACHE_PRUNE_EVERY == 0:
            self._prune_recent_bindings(now_ms)
        self._binding_touched(uid)
        if self.nrt_cache is not None:
            # Reserve -> bind -> PostBind lifecycle for the NRT cache
            self.nrt_cache.reserve(node_name, self.pods[uid])
            self.nrt_cache.post_bind(node_name, self.pods[uid])
        if self.native is not None:
            # no-op if the reservation already bound it to this node
            self._native.bind(
                self._native_pod_ids[uid], self._native_node_ids[node_name]
            )

    def reserve(self, uid: str, node_name: str):
        """Permit said Wait: hold the placement without binding."""
        self.reserved[uid] = node_name
        self._index_drop_pod(uid)
        led = podledger.LEDGER
        if led.enabled:
            led.on_reserve(uid, node_name)
        if self.delta_sink is not None:
            # a reservation holds capacity exactly like a binding
            self.delta_sink.pod_assigned(self.pods[uid], node_name)
        if self.nrt_cache is not None:
            self.nrt_cache.reserve(node_name, self.pods[uid])
        if self.native is not None:
            # a reservation holds capacity exactly like a binding
            self._native.bind(
                self._native_pod_ids[uid], self._native_node_ids[node_name]
            )

    def release_reservation(self, uid: str):
        self.pod_deadline_ms.pop(uid, None)
        node = self.reserved.pop(uid, None)
        if node is not None and self.delta_sink is not None:
            self.delta_sink.pod_unassigned(self.pods[uid], node)
        if node is not None and self.nrt_cache is not None:
            self.nrt_cache.unreserve(node, self.pods[uid])
        if node is not None and self.native is not None:
            # re-upsert as unbound (removes the hold's contribution)
            self._native_upsert_pod(self.pods[uid])
        if node is not None:
            self.reindex_pod(uid)

    def gang_reservations(self, pg: PodGroup) -> list[str]:
        return [
            uid
            for uid, _ in self.reserved.items()
            if (p := self.pods.get(uid)) is not None
            and p.namespace == pg.namespace
            and p.pod_group() == pg.name
        ]

    #: metrics-agent reporting interval: recently-bound pods within this
    #: window are presumed unreported and their predicted CPU is added
    #: (handler.go comment; BASELINE.md metrics freshness envelope)
    METRICS_REPORT_INTERVAL_MS = 60_000
    #: ScheduledPodsCache GC horizon (handler.go: 5 minutes)
    BINDING_CACHE_GC_MS = 300_000
    #: `bind` prunes the cache once in this many binds, so that it is
    #: bounded on every path: the walk in `_metrics_with_missing` runs only
    #: where a fresh snapshot is built, which the resident path never does
    BINDING_CACHE_PRUNE_EVERY = 1024

    def _prune_recent_bindings(self, now_ms: int) -> None:
        """Drop the entries at the front of `recent_bindings` (a dict keeps
        bind order) that are past the GC horizon: O(dropped), and it stops
        at the first entry that is not — a uid bound again keeps its old
        place with its new stamp and holds the ones behind it that much
        longer, no more."""
        stale = []
        for uid, (ts, _) in self.recent_bindings.items():
            if now_ms - ts <= self.BINDING_CACHE_GC_MS:
                break
            stale.append(uid)
        for uid in stale:
            del self.recent_bindings[uid]

    def _binding_touched(self, uid: str) -> None:
        """Tell the delta sink that what `uid` adds to its node's
        unreported CPU (`_metrics_with_missing`) may have changed: it was
        bound, or it is a recent binding whose pod object was replaced or
        deleted. Nothing is sent while there is no report to add it to."""
        if (
            self.node_metrics is not None
            and self.delta_sink is not None
            and uid in self.recent_bindings
        ):
            self.delta_sink.binding_touched(uid)

    def _metrics_with_missing(self, now_ms: int):
        """Augment node metrics with the missing-utilization compensation
        (targetloadpacking.go:148-168): predicted CPU of pods bound within
        the metrics reporting interval, per node."""
        # GC the binding cache regardless of metrics config, or it grows
        # unboundedly on clusters without trimaran metrics
        for uid, (ts, _) in list(self.recent_bindings.items()):
            if now_ms - ts > self.BINDING_CACHE_GC_MS:
                del self.recent_bindings[uid]
        if self.node_metrics is None:
            return None
        missing: dict[str, int] = {}
        for uid, (ts, node) in self.recent_bindings.items():
            pod = self.pods.get(uid)
            if pod is None or now_ms - ts >= self.METRICS_REPORT_INTERVAL_MS:
                continue
            missing[node] = missing.get(node, 0) + pod.tlp_predicted_cpu_millis(
                *self.tlp_prediction
            )
        if not missing:
            return self.node_metrics
        merged = {name: dict(m) for name, m in self.node_metrics.items()}
        for node, millis in missing.items():
            merged.setdefault(node, {})["missing_cpu_millis"] = (
                merged.get(node, {}).get("missing_cpu_millis", 0) + millis
            )
        return merged

    # -- snapshot --------------------------------------------------------
    def _assigned_pods(self, exclude=frozenset()):
        """Bound pods plus reserved (permit-waiting) pods materialized with
        their held node — THE definition of 'assigned' for snapshot
        lowering and the preemption dry-run's hypothetical rebuild (one
        source so the two views cannot desynchronize)."""
        import copy

        assigned = [
            p for p in self.pods.values()
            if p.node_name is not None and p.uid not in exclude
        ]
        for uid, node in self.reserved.items():
            pod = self.pods.get(uid)
            if pod is not None and pod.node_name is None and uid not in exclude:
                held = copy.copy(pod)
                held.node_name = node
                assigned.append(held)
        return assigned

    def post_eviction_tables(self, snap, meta, exclude_uids):
        """Pod-derived side tables with `exclude_uids` treated as already
        evicted: the preemption dry run's post-eviction filter view
        (capacity_scheduling.go SelectVictimsOnNode removes victims from
        the NodeInfo before RunFilterPluginsWithNominatedPods). Rebuilds
        the scheduling track bases (affinity/anti-affinity/spread existing-
        pod counts) and decrements the network placed-workload counts; the
        NRT cache view is deliberately NOT touched — upstream's
        TopologyMatch filter reads its own overreserve cache, which victim
        removal does not update either. Returns a snapshot sharing every
        other table with `snap`."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from scheduler_plugins_tpu.state import scheduling as _sched

        excl = set(exclude_uids)
        new_sched = snap.scheduling
        if snap.scheduling is not None:
            nodes = [self.nodes[n] for n in meta.node_names if n in self.nodes]
            pending = [
                self.pods[uid] for uid in meta.pod_names if uid in self.pods
            ]
            assigned = self._assigned_pods(exclude=excl)
            new_sched = _sched.build_scheduling(
                nodes, pending, snap.num_nodes, snap.num_pods,
                assigned=assigned, namespaces=list(self.namespaces.values()),
            )
            if new_sched is not None:
                new_sched = jax.tree.map(jnp.asarray, new_sched)
        new_network = snap.network
        if snap.network is not None and getattr(meta, "workloads", None):
            placed = np.asarray(snap.network.placed_node).copy()
            node_pos = {name: i for i, name in enumerate(meta.node_names)}
            wl_pos = {name: i for i, name in enumerate(meta.workloads)}
            for uid in excl:
                pod = self.pods.get(uid)
                if pod is None or pod.node_name not in node_pos:
                    continue
                sel = pod.workload_selector()
                wc = wl_pos.get(f"{pod.namespace}/{sel}") if sel else None
                if wc is not None:
                    ni = node_pos[pod.node_name]
                    placed[wc, ni] = max(placed[wc, ni] - 1, 0)
            new_network = snap.network.replace(
                placed_node=jnp.asarray(placed)
            )
        return snap.replace(scheduling=new_sched, network=new_network)

    def snapshot(self, pending: list[Pod], now_ms: int = 0, **kwargs):
        """Lower current state for the solver. Reserved (permit-waiting) pods
        count as assigned to their reserved node — they hold capacity and
        quorum exactly like the reference's waiting pods in assignedPodsByPG."""
        # native fast path: node usage columns come from the C++ store,
        # which accounts every bound AND reserved pod incrementally — the
        # O(assigned) Python accumulate is skipped. Assigned pod objects are
        # still needed whenever a side-table subsystem reads them.
        native_exports = None
        if (
            self.native is not None
            and not self._native_incompat
            and not self.pod_groups
            and not self.quotas
            and not self.app_groups
            and not self.seccomp_profiles
            and not self._affinity_spec_pods
            # assigned pods' spread constraints are nobody's input: only a
            # batch that carries one needs the assigned objects' labels
            and not (
                self.selectors.tracks
                and any(p.topology_spread for p in pending)
            )
        ):
            exports = self._native.export_nodes()
            if len(exports["ids"]) == len(self.nodes) and all(
                self._native_node_ids.get(n) == i
                for i, n in enumerate(self.nodes)
            ):
                native_exports = exports
        if native_exports is not None:
            assigned = []
        else:
            assigned = self._assigned_pods()
        backed_off = [
            name
            for name, until in self.gang_backoff_until_ms.items()
            if until > now_ms
        ]
        metrics = self._metrics_with_missing(now_ms)
        nrt_list = list(self.nrts.values())
        stale_nodes: list[str] = []
        if self.nrt_cache is not None:
            nrt_list, stale = self.nrt_cache.view()
            stale_nodes = list(stale)
        return build_snapshot(
            list(self.nodes.values()),
            pending,
            assigned_pods=assigned,
            pod_groups=list(self.pod_groups.values()),
            quotas=list(self.quotas.values()),
            nrts=nrt_list,
            stale_nrt_nodes=stale_nodes,
            app_groups=list(self.app_groups.values()),
            node_metrics=metrics,
            backed_off_gangs=backed_off,
            extra_pods=self.gated_pods(),
            seccomp_profiles=list(self.seccomp_profiles.values()),
            native_nodes=native_exports,
            tlp_prediction=self.tlp_prediction,
            sysched_default_profile=getattr(
                self, "sysched_default_profile", None
            ),
            namespaces=list(self.namespaces.values()),
            **kwargs,
        )
