"""Dense-tensor cluster snapshot.

The reference walks an object graph per pod x per node (NodeInfo lists,
informer caches). Here the whole scheduling problem is lowered once per cycle
into a pytree of dense int64/float64 arrays with static (bucketed) shapes:

- nodes:   (N, R) allocatable / requested / non-zero-requested, region/zone
           codes, per-node pod-state counters.
- pods:    (P, R) effective requests for the *pending batch*, priority, QoS,
           namespace / gang / app-group codes, queue-sort keys.
- gangs:   (G,) PodGroup min-member / membership counts, (G, R) MinResources.
- quota:   (Q, R) ElasticQuota min/max/used indexed by namespace code.
- metrics: (N,) load-watcher utilisation mu/sigma percentages.
- numa:    (N, Z, R) per-zone availability + topology-manager config codes.

Name<->code mappings and resource-axis metadata live in `SnapshotMeta`, which
is host-only and deliberately NOT part of the pytree, so jit sees only arrays
(changing names never retriggers compilation; changing bucket sizes does).

Quantities are int64 in reference units (SURVEY.md §7) — bit-identical
placement needs integer compares, e.g.
/root/reference/pkg/capacityscheduling/elasticquota.go:189-221.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np
from flax import struct

from scheduler_plugins_tpu.api.objects import (
    AppGroup,
    ElasticQuota,
    Node,
    NodeResourceTopology,
    Pod,
    PodGroup,
)
from scheduler_plugins_tpu.api.resources import (
    CANONICAL,
    CPU,
    DEFAULT_MEMORY_REQUEST,
    DEFAULT_MILLI_CPU_REQUEST,
    MEMORY,
    PODS,
    ResourceIndex,
)
from scheduler_plugins_tpu.state import scheduling as _sched
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size

I64 = np.int64
I32 = np.int32
F64 = np.float64

#: Static snapshot tensors that have a LIVE SolverState carry counterpart
#: (keyed by pytree path relative to the snapshot root -> carry field name,
#: `framework.plugin.SolverState`). The CLAUDE.md invariant — in-cycle
#: mutations flow through carries, never through re-reads of the static
#: snapshot — is machine-checked on the COMPILED programs by
#: `tools/jaxpr_audit.py` (rule JA001): a traced solve whose outputs depend
#: on one of these tensors while the carry counterpart is dead in the jaxpr
#: has bypassed the carry. The scheduling-table counterparts live in
#: `state.scheduling.TRACK_CARRY_COUNTERPARTS`.
CARRY_COUNTERPARTS = {
    ".nodes.requested": "free",
    ".quota.used": "eq_used",
    ".gangs.assigned": "gang_scheduled",
    ".network.placed_node": "net_placed",
    ".numa.available": "numa_avail",
    # the gang phase's resident rank assignment (gangs.topology
    # RankGangState.prev_assigned -> the SolverState.rank_nodes carry):
    # the rank-gang solve must thread in-cycle placements through the
    # carry, never re-read the static resident tensor
    ".ranks.prev_assigned": "rank_nodes",
}


@struct.dataclass
class NodeState:
    alloc: np.ndarray  # (N, R) int64 allocatable
    capacity: np.ndarray  # (N, R) int64 node capacity (TLP/Peaks read this)
    requested: np.ndarray  # (N, R) int64 sum of assigned pods' requests
    nonzero_requested: np.ndarray  # (N, R) int64 with upstream non-zero defaults
    #: (N, R) sum of assigned pods' effective limits clamped to >= requests
    #: per pod (trimaran SetMaxLimits, resourcestats.go:225-231)
    limits: np.ndarray
    mask: np.ndarray  # (N,) bool — real, schedulable node
    region: np.ndarray  # (N,) int32 region code (-1 unset)
    zone: np.ndarray  # (N,) int32 zone code (-1 unset)
    pod_count: np.ndarray  # (N,) int32 assigned pods
    terminating: np.ndarray  # (N,) int32 terminating pods (PodState score)
    nominated: np.ndarray  # (N,) int32 nominated pods (PodState score)


@struct.dataclass
class PodState:
    req: np.ndarray  # (P, R) int64 effective request (pods slot = 0)
    limits: np.ndarray  # (P, R) int64 trimaran effective limits (unclamped)
    #: (P,) TargetLoadPacking per-pod CPU prediction with default args
    predicted_cpu_millis: np.ndarray
    #: (P, C, R) raw per-container requests, init containers first — the NUMA
    #: container-scope Filter/Score iterate containers individually
    #: (filter.go:39-78, score.go:152-165)
    container_req: np.ndarray
    container_is_init: np.ndarray  # (P, C) bool
    container_mask: np.ndarray  # (P, C) bool
    priority: np.ndarray  # (P,) int64
    ns: np.ndarray  # (P,) int32 namespace code
    gang: np.ndarray  # (P,) int32 gang code (-1 = not in a PodGroup)
    qos: np.ndarray  # (P,) int32 QOSClass
    mask: np.ndarray  # (P,) bool
    creation_ms: np.ndarray  # (P,) int64 queue-sort timestamp
    gated: np.ndarray  # (P,) bool SchedulingGated


@struct.dataclass
class GangState:
    """PodGroup bookkeeping (/root/reference/pkg/coscheduling/core/core.go)."""

    min_member: np.ndarray  # (G,) int32
    total_members: np.ndarray  # (G,) int32 siblings known cluster-wide
    assigned: np.ndarray  # (G,) int32 already bound/running members
    gated: np.ndarray  # (G,) int32 SchedulingGated siblings
    min_resources: np.ndarray  # (G, R) int64 whole-gang demand
    has_min_resources: np.ndarray  # (G,) bool
    creation_ms: np.ndarray  # (G,) int64 (failure-time override applied)
    backed_off: np.ndarray  # (G,) bool recently rejected
    #: (G, R) extra whole-cluster capacity visible to this gang's
    #: CheckClusterResource because its own assigned pods are added back
    #: (core.go:433-467 getNodeResource removes the gang's pods)
    cluster_slack: np.ndarray  # (G, R) int64
    mask: np.ndarray  # (G,) bool


@struct.dataclass
class QuotaState:
    """ElasticQuota arrays indexed by namespace code
    (/root/reference/pkg/capacityscheduling/elasticquota.go:34-87)."""

    min: np.ndarray  # (Q, R) int64
    max: np.ndarray  # (Q, R) int64
    used: np.ndarray  # (Q, R) int64
    has_quota: np.ndarray  # (Q,) bool namespace has an EQ
    #: nominated-pod tables (capacity_scheduling.go:226-263). M nominees:
    #: their requests, per-(nominee, pending-pod) contribution masks for the
    #: own-Max ("in EQ": same namespace, priority >= pod) and aggregate-Min
    #: checks, and each nominee's index in the pending batch (-1 if outside)
    #: so in-scan placements drop them from the aggregates (upstream removes
    #: a pod from the nominated set the moment it is assumed).
    nom_req: np.ndarray  # (M, R) int64
    nom_in_eq_mask: np.ndarray  # (M, P) bool
    nom_total_mask: np.ndarray  # (M, P) bool
    nom_batch_idx: np.ndarray  # (M,) int32


@struct.dataclass
class MetricsState:
    """Load-watcher node metrics in percent of capacity
    (/root/reference/pkg/trimaran/collector.go, resourcestats.go:33-107)."""

    cpu_avg: np.ndarray  # (N,) float64 %
    #: (N,) the CPU value TargetLoadPacking reads — its selection loop lets a
    #: later Latest override Average (targetloadpacking.go:130-139); defaults
    #: to cpu_avg
    cpu_tlp: np.ndarray
    #: (N,) the CPU value Peaks reads — the FIRST Average-or-Latest sample in
    #: report order (peaks.go:118-131); defaults to cpu_tlp/cpu_avg
    cpu_peaks: np.ndarray
    cpu_std: np.ndarray  # (N,) float64 %
    mem_avg: np.ndarray  # (N,) float64 %
    mem_std: np.ndarray  # (N,) float64 %
    cpu_valid: np.ndarray  # (N,) bool
    #: (N,) whether an Average/Latest CPU metric was actually seen — TLP
    #: requires one (targetloadpacking.go:130-146) and must not score a
    #: std-only node from a defaulted 0/avg value
    cpu_tlp_valid: np.ndarray
    mem_valid: np.ndarray  # (N,) bool
    #: predicted-but-unreported CPU millis per node (ScheduledPodsCache
    #: compensation, /root/reference/pkg/trimaran/handler.go:47-171)
    missing_cpu_millis: np.ndarray  # (N,) int64


@struct.dataclass
class NumaState:
    """Per-node NUMA zones from NodeResourceTopology CRs
    (/root/reference/pkg/noderesourcetopology/numaresources.go:32-103)."""

    available: np.ndarray  # (N, Z, R) int64
    allocatable: np.ndarray  # (N, Z, R) int64
    zone_mask: np.ndarray  # (N, Z) bool
    #: per-resource "zone reports this resource" mask — NUMA affinity only
    #: applies to reported resources (numaresources.go:105-135)
    reported: np.ndarray  # (N, Z, R) bool
    policy: np.ndarray  # (N,) int32 TopologyManagerPolicy
    scope: np.ndarray  # (N,) int32 TopologyManagerScope
    distances: np.ndarray  # (N, Z, Z) int32 SLIT costs (default 10)
    has_nrt: np.ndarray  # (N,) bool
    #: (N,) cache freshness: not-fresh nodes are Unschedulable for any
    #: non-best-effort pod (filter.go:194-197) and score 0
    fresh: np.ndarray
    #: (N,) per-node topology-manager MaxNUMANodes (LeastNUMA normalization,
    #: least_numa.go:88-102; default 8)
    max_numa: np.ndarray
    #: STATIC per-resource power-of-2 rescale enabling the f32 NUMA fast
    #: path: every zone quantity and pending request is exactly divisible by
    #: its scale and the rescaled values keep `value * 100 < 2^24` (exact in
    #: float32, scale-invariant trunc-division scores). None when any
    #: resource fails the guard — solvers then carry float64. Part of the
    #: pytree STRUCTURE, so jit retraces when packability changes.
    pack_scales: Optional[tuple] = struct.field(pytree_node=False, default=None)


@struct.dataclass
class SyscallState:
    """SySched syscall-set tensors (/root/reference/pkg/sysched/sysched.go).

    The per-existing-pod difference sum decomposes per syscall:
        sum_p |newHost - p| = pod_count * |newHost| - sum_s newHost[s] * counts[n, s]
    so only per-node unions and per-syscall pod counts are needed.
    """

    pod_sets: np.ndarray  # (P, S) bool — pending pods' syscall sets
    has_profile: np.ndarray  # (P,) bool
    host_sets: np.ndarray  # (N, S) bool — union over assigned pods
    #: (N, S) number of assigned pods on the node whose set contains syscall s
    counts: np.ndarray
    host_pod_count: np.ndarray  # (N,) int32 assigned pods (HostToPods length)


@struct.dataclass
class NomineeState:
    """Unbound pods nominated to a node after preemption: their demand HOLDS
    node capacity against lower-or-equal-priority pods during the solve — the
    upstream nominator's AddNominatedPods semantics
    (RunFilterPluginsWithNominatedPods adds nominated pods with priority >=
    the evaluated pod). A nominee inside the pending batch stops holding the
    moment it places (tracked via `SolverState.placed_mask`)."""

    node: np.ndarray  # (M,) int32 nominated node index
    demand: np.ndarray  # (M, R) int64 fit demand (pods slot = 1)
    priority: np.ndarray  # (M,) int64
    batch_idx: np.ndarray  # (M,) int32 index in the pending batch, -1 outside
    mask: np.ndarray  # (M,) bool


@struct.dataclass
class ClusterSnapshot:
    nodes: NodeState
    pods: PodState
    gangs: Optional[GangState] = None
    quota: Optional[QuotaState] = None
    metrics: Optional[MetricsState] = None
    numa: Optional[NumaState] = None
    network: Optional["NetworkState"] = None
    syscalls: Optional[SyscallState] = None
    nominees: Optional[NomineeState] = None
    #: in-tree companion-plugin tables (taints, node affinity) — see
    #: state.scheduling
    scheduling: Optional["_sched.SchedulingState"] = None

    @property
    def num_nodes(self) -> int:
        return self.nodes.alloc.shape[0]

    @property
    def num_pods(self) -> int:
        return self.pods.req.shape[0]

    @property
    def num_resources(self) -> int:
        return self.nodes.alloc.shape[1]


@struct.dataclass
class NetworkState:
    """AppGroup dependency + topology cost tensors
    (/root/reference/pkg/networkaware/networkoverhead/networkoverhead.go:448-638).

    Costs between a candidate node and an already-placed dependency pod depend
    only on (region, zone) codes, so placed pods aggregate into per-zone /
    per-region counts and cost lookup is a small dense gather instead of a
    per-pod map search.
    """

    dep_workload: np.ndarray  # (P, D) int32 workload code (-1 pad)
    dep_max_cost: np.ndarray  # (P, D) int64
    dep_mask: np.ndarray  # (P, D) bool
    pod_workload: np.ndarray  # (P,) int32 pending pod's own workload (-1 none)
    #: (W, N) placed pods per workload per node; the live copy is carried
    #: through the scan (SolverState.net_placed) so in-cycle placements are
    #: visible to later pods
    placed_node: np.ndarray
    zone_region: np.ndarray  # (ZC,) int32 region code of each zone (-1 unknown)
    #: class-level dependency rows, one per WORKLOAD code: every pod of a
    #: workload shares its dependency list, so batched filter/score tallies
    #: run once per class ((W, N) work) and gather by `pod_workload`
    #: instead of vmapping the (D, N) tallies over every pod
    cls_dep_workload: np.ndarray = None  # (W, D) int32
    cls_dep_max_cost: np.ndarray = None  # (W, D) int64
    cls_dep_mask: np.ndarray = None  # (W, D) bool


@dataclass
class SnapshotMeta:
    """Host-only name<->code mappings for one snapshot."""

    index: ResourceIndex
    node_names: list[str] = field(default_factory=list)
    pod_names: list[str] = field(default_factory=list)  # pending batch, queue order
    namespaces: list[str] = field(default_factory=list)
    gang_names: list[str] = field(default_factory=list)
    regions: list[str] = field(default_factory=list)
    zones: list[str] = field(default_factory=list)
    workloads: list[str] = field(default_factory=list)

    def node_id(self, name: str) -> int:
        return self.node_names.index(name)

    def ns_id(self, name: str) -> int:
        return self.namespaces.index(name)


class _Interner:
    """O(1) name -> stable-code interning over a shared list."""

    def __init__(self, table: list[str]):
        self.table = table
        self.pos = {name: i for i, name in enumerate(table)}

    def code(self, name: str) -> int:
        i = self.pos.get(name)
        if i is None:
            i = len(self.table)
            self.table.append(name)
            self.pos[name] = i
        return i

    def get(self, name: str) -> int:
        """Code for `name`, or -1 if never interned."""
        return self.pos.get(name, -1)


def nonzero_request(req, index: ResourceIndex):
    """Apply the upstream non-zero defaults used for scoring accounting:
    pods without cpu/memory requests are charged 100m / 200Mi. `req` is an
    encoded vector or its plain list (`ResourceIndex.slots`); a copy of
    the same kind comes back."""
    out = req.copy()
    cpu_i = index.position(CPU)
    mem_i = index.position(MEMORY)
    if out[cpu_i] == 0:
        out[cpu_i] = DEFAULT_MILLI_CPU_REQUEST
    if out[mem_i] == 0:
        out[mem_i] = DEFAULT_MEMORY_REQUEST
    return out


def usage_rows(req: list, limits: list, index: ResourceIndex) -> list:
    """[requested, nonzero_requested, limits]: the three rows that ONE
    assigned pod adds to its node's usage columns, from its raw request and
    limit slots (`ResourceIndex.slots`: plain lists in, plain lists out) —
    the per-pod arithmetic of `build_snapshot`'s assigned loop: nonzero
    defaults applied, limits clamped to >= requests (SetMaxLimits), and the
    pods slot carrying the count contribution (1) on the requested/nonzero
    rows (the snapshot overwrites those slots with pod_count)."""
    requested = req.copy()
    nonzero = nonzero_request(req, index)
    requested[index.position(PODS)] = nonzero[index.position(PODS)] = 1
    return [requested, nonzero, [max(l, r) for l, r in zip(limits, req)]]


class PodRecord:
    """One pod object lowered once: everything derivable from the pod SPEC
    alone — the request and limit encodes, the container rows, QoS, the TLP
    prediction (what `build_pod_state` reads) and the usage vectors (what
    the serving engine's classification and its cadenced anti-entropy
    check read; `req` is also the raw ElasticQuota vector). Keyed by uid in
    its table, valid while `record.pod is pod`, the axis is the same object
    and the TLP parameters are equal (`valid`): a feed upsert replaces the
    pod object wholesale and so invalidates by itself. Meta-dependent codes
    (namespace interning, gang code) and flags that mutate in place
    (scheduling gate, terminating, node_name) are never recorded. Every
    row is lowered as a plain list and the record makes ONE array of them
    (a pod costs one numpy call, not one a vector); `req`, `limits`,
    `usage` and `creq` are views of it, read-only: every reader copies or
    adds out of them. Raises KeyError where the spec names a resource
    outside `index`."""

    __slots__ = ("pod", "index", "tlp", "req", "limits", "predicted",
                 "creq", "cinit", "qos", "usage", "vectors", "node_keys",
                 "node_rows")

    def __init__(self, pod, index, tlp_prediction):
        self.pod = pod
        self.index = index
        self.tlp = tlp_prediction
        req = index.slots(pod.effective_request())
        limits = index.slots(pod.effective_limits())
        n_init = len(pod.init_containers)
        conts = [*pod.init_containers, *pod.containers]
        block = np.array(
            [req, limits, *usage_rows(req, limits, index),
             *(index.slots(c.requests) for c in conts)],
            dtype=I64,
        )
        block.flags.writeable = False
        self.req = block[0]
        self.limits = block[1]
        #: `usage_rows` as one (3, R) piece, so that the cadenced check
        #: stacks the assigned population's in one call
        self.usage = block[2:5]
        self.creq = block[5:]
        self.cinit = np.arange(len(conts)) < n_init
        self.predicted = pod.tlp_predicted_cpu_millis(*tlp_prediction)
        self.qos = int(pod.qos_class())
        #: (requested, nonzero, limits, quota): the usage rows and the raw
        #: request encode, the tuple `ServeEngine._pod_vectors` hands out
        self.vectors = (block[2], block[3], block[4], self.req)
        #: the keys of the pod's nodeSelector / node-affinity specs
        #: (`state.scheduling.node_spec_keys`; None for a pod without any),
        #: and where the serving engine's resident rows hold them: (the
        #: tables' epoch, row of `node_term_ok`, row of `pref_score`), set
        #: by `serving.node_terms` the first time a batch holds the pod
        self.node_keys = _sched.node_spec_keys(pod)
        self.node_rows = None

    def valid(self, pod, index, tlp_prediction) -> bool:
        return (
            self.pod is pod and self.index is index
            and self.tlp == tlp_prediction
        )


def build_pod_state(
    pending_pods: Sequence[Pod],
    P: int,
    index: ResourceIndex,
    ns_in: "_Interner",
    gang_of,
    tlp_prediction: tuple = (1.5, 1000),
    row_cache: dict | None = None,
) -> PodState:
    """Lower the pending batch into `PodState` (host numpy) — THE one copy
    of the pod-tensor lowering, shared by `build_snapshot` and the serving
    engine's per-cycle assembly (`serving.engine.ServeEngine._assemble`),
    so the two paths produce bit-identical pod tensors by construction.
    `ns_in` interns namespace codes into the caller's meta table;
    `gang_of(pod) -> int` maps a pod to its gang code (-1 outside).
    `row_cache` (uid -> `PodRecord`: the serving engine's record table)
    memoizes the spec-derived pieces across cycles — entries re-derive
    whenever the pod object, resource axis or TLP parameters differ, so a
    hit is bit-identical by construction."""
    R = len(index)
    preq = np.zeros((P, R), I64)
    plimits = np.zeros((P, R), I64)
    ppredicted = np.zeros(P, I64)
    C = max(
        max(
            (len(p.init_containers) + len(p.containers) for p in pending_pods),
            default=1,
        ),
        1,
    )
    pcreq = np.zeros((P, C, R), I64)
    pcinit = np.zeros((P, C), bool)
    pcmask = np.zeros((P, C), bool)
    ppriority = np.zeros(P, I64)
    pns = np.zeros(P, I32)
    pgang = np.full(P, -1, I32)
    pqos = np.zeros(P, I32)
    pmask = np.zeros(P, bool)
    pcreated = np.zeros(P, I64)
    pgated = np.zeros(P, bool)
    for i, pod in enumerate(pending_pods):
        row = None
        if row_cache is not None:
            row = row_cache.get(pod.uid)
            if row is None or not row.valid(pod, index, tlp_prediction):
                row = row_cache[pod.uid] = PodRecord(
                    pod, index, tlp_prediction
                )
        if row is not None:
            preq[i] = row.req
            plimits[i] = row.limits
            ppredicted[i] = row.predicted
            nC = row.creq.shape[0]
            pcreq[i, :nC] = row.creq
            pcinit[i, :nC] = row.cinit
            pcmask[i, :nC] = True
            pqos[i] = row.qos
        else:
            preq[i] = index.encode(pod.effective_request())
            plimits[i] = index.encode(pod.effective_limits())
            ppredicted[i] = pod.tlp_predicted_cpu_millis(*tlp_prediction)
            for c, cont in enumerate(
                list(pod.init_containers) + list(pod.containers)
            ):
                pcreq[i, c] = index.encode(cont.requests)
                pcinit[i, c] = c < len(pod.init_containers)
                pcmask[i, c] = True
            pqos[i] = int(pod.qos_class())
        ppriority[i] = pod.priority
        pns[i] = ns_in.code(pod.namespace)
        pgang[i] = gang_of(pod)
        pmask[i] = True
        pcreated[i] = pod.creation_ms
        pgated[i] = pod.scheduling_gated
    return PodState(
        req=preq,
        limits=plimits,
        predicted_cpu_millis=ppredicted,
        container_req=pcreq,
        container_is_init=pcinit,
        container_mask=pcmask,
        priority=ppriority,
        ns=pns,
        gang=pgang,
        qos=pqos,
        mask=pmask,
        creation_ms=pcreated,
        gated=pgated,
    )


def gang_object_tables(pod_groups, gang_pos, index, G: int,
                       backed_off_gangs) -> dict:
    """The PodGroup-OBJECT-derived `GangState` columns (min_member,
    creation, backoff, MinResources incl. the pods-slot MinMember
    injection, mask) — THE one copy of this lowering, shared by
    `build_snapshot` and the serving engine's resident side-table
    assembly (`serving.engine.ServeEngine._assemble`), so the two paths
    produce bit-identical object columns by construction. The per-pod
    AGGREGATE columns (total/assigned/gated/cluster_slack) are the
    caller's: the fresh path accumulates them from the pod population,
    the serving engine from its O(changed) resident side tables."""
    R = len(index)
    pods_i = index.position(PODS)
    backed_off = set(backed_off_gangs)
    # a row no PodGroup holds (G is a bucket) stays inert: no member asks
    # for it, `min_member` 0, mask False
    gang_min = np.zeros(G, I32)
    gang_minres = np.zeros((G, R), I64)
    gang_has_minres = np.zeros(G, bool)
    gang_created = np.zeros(G, I64)
    gang_backoff = np.zeros(G, bool)
    gang_mask = np.zeros(G, bool)
    for pg in pod_groups:
        g = gang_pos[pg.full_name]
        gang_mask[g] = True
        gang_min[g] = pg.min_member
        gang_created[g] = pg.creation_ms
        gang_backoff[g] = pg.full_name in backed_off
        if pg.min_resources:
            gang_minres[g] = index.encode(pg.min_resources)
            gang_has_minres[g] = True
            # MinResources demand includes a pods slot of MinMember
            # (core.go:295-297 injects minResources[pods] = MinMember)
            gang_minres[g, pods_i] = pg.min_member
    return {
        "min_member": gang_min,
        "min_resources": gang_minres,
        "has_min_resources": gang_has_minres,
        "creation_ms": gang_created,
        "backed_off": gang_backoff,
        "mask": gang_mask,
    }


def quota_object_tables(quotas, index, ns_in: "_Interner", Q: int):
    """The ElasticQuota-OBJECT-derived `QuotaState` columns (min, max,
    has_quota) — one copy shared by `build_snapshot` and the serving
    engine (same rationale as `gang_object_tables`). Callers must have
    interned every quota namespace into `ns_in` already (the fresh
    path's interning order: batch first, then quotas, then assigned)."""
    R = len(index)
    qmin = np.zeros((Q, R), I64)
    qmax = np.full((Q, R), np.iinfo(I64).max, I64)
    qhas = np.zeros(Q, bool)
    for q in quotas:
        nsi = ns_in.get(q.namespace)
        qhas[nsi] = True
        qmin[nsi] = index.encode(q.min)
        # absent resources in Max are unbounded (UpperBound semantics,
        # /root/reference/pkg/capacityscheduling/elasticquota.go:96-120)
        qmax[nsi] = index.encode(q.max, default=np.iinfo(I64).max)
    return qmin, qmax, qhas


def empty_quota_nominees(R: int, P: int):
    """The nominee-table defaults an empty nominated set produces
    (M = 1 all-zero rows, batch_idx -1) — the serving engine's case by
    construction: its compatibility gate excludes every nomination."""
    return (
        np.zeros((1, R), I64),
        np.zeros((1, P), bool),
        np.zeros((1, P), bool),
        np.full(1, -1, I32),
    )


def _build_quota(quotas, pending_pods, assigned_pods, extra_pods, index,
                 ns_in: "_Interner", meta, P: int) -> QuotaState:
    """`build_snapshot`'s ElasticQuota section: the object tables, the
    per-namespace usage of the assigned pods and the nominee tables."""
    R = len(index)
    for q in quotas:
        ns_in.code(q.namespace)
    for pod in assigned_pods:
        ns_in.code(pod.namespace)
    # a bucket, like G: namespaces that come and go must not give the
    # solve a shape each; a row no namespace holds has no quota
    Q = bucket_size(len(meta.namespaces))
    qused = np.zeros((Q, R), I64)
    qmin, qmax, qhas = quota_object_tables(quotas, index, ns_in, Q)
    for pod in assigned_pods:
        if pod.node_name is None:
            continue
        nsi = ns_in.get(pod.namespace)
        if qhas[nsi]:
            qused[nsi] += index.encode(pod.effective_request())
    # nominated-pod tables
    nominated = [
        p
        for p in list(pending_pods) + list(extra_pods)
        if p.nominated_node_name is not None and p.node_name is None
    ]
    batch_pos = {p.uid: i for i, p in enumerate(pending_pods)}
    M = max(len(nominated), 1)
    nom_req = np.zeros((M, R), I64)
    nom_in_eq_mask = np.zeros((M, P), bool)
    nom_total_mask = np.zeros((M, P), bool)
    nom_batch_idx = np.full(M, -1, I32)
    if nominated:
        from scheduler_plugins_tpu.ops.quota import nominee_contribution

        over_min = np.any(qused > qmin, axis=1)  # (Q,) usedOverMin
        for j, m in enumerate(nominated):
            m_ns = ns_in.get(m.namespace)
            if m_ns < 0 or not qhas[m_ns]:
                continue
            nom_req[j] = index.encode(m.effective_request())
            nom_batch_idx[j] = batch_pos.get(m.uid, -1)
            for i, pod in enumerate(pending_pods):
                if m.uid == pod.uid:
                    continue
                in_eq, total = nominee_contribution(
                    m.namespace == pod.namespace, m.priority,
                    pod.priority, bool(over_min[m_ns]),
                )
                nom_in_eq_mask[j, i] = in_eq
                nom_total_mask[j, i] = total
    return QuotaState(
        min=qmin, max=qmax, used=qused, has_quota=qhas,
        nom_req=nom_req, nom_in_eq_mask=nom_in_eq_mask,
        nom_total_mask=nom_total_mask, nom_batch_idx=nom_batch_idx,
    )


def node_metric_columns(node_metrics: dict, node_pos: dict, N: int) -> dict:
    """The load watcher's report as `MetricsState`'s (N,) columns, by field
    name: `node_pos` maps a node's name to its row, a node the report does
    not name keeps zeros / not valid, and a name outside `node_pos` is
    skipped. Shared by `build_snapshot` and the serving engine's resident
    metrics (`serving.engine.ServeEngine._relower_metrics`) so the two
    lowerings cannot drift."""
    cpu_avg = np.zeros(N, F64)
    cpu_tlp = np.zeros(N, F64)
    cpu_peaks = np.zeros(N, F64)
    cpu_std = np.zeros(N, F64)
    mem_avg = np.zeros(N, F64)
    mem_std = np.zeros(N, F64)
    cpu_valid = np.zeros(N, bool)
    cpu_tlp_valid = np.zeros(N, bool)
    mem_valid = np.zeros(N, bool)
    missing = np.zeros(N, I64)
    for name, m in node_metrics.items():
        if name not in node_pos:
            continue
        i = node_pos[name]
        if "cpu_avg" in m:
            cpu_avg[i] = m["cpu_avg"]
        cpu_tlp[i] = m.get("cpu_tlp", m.get("cpu_avg", 0.0))
        cpu_peaks[i] = m.get(
            "cpu_peaks", m.get("cpu_tlp", m.get("cpu_avg", 0.0))
        )
        cpu_std[i] = m.get("cpu_std", 0.0)
        # a node with ANY cpu sample (avg/latest or std-only) is valid:
        # GetResourceData returns isValid=true, avg=0 for std-only
        # (resourcestats.go:88-106)
        cpu_valid[i] = "cpu_avg" in m or "cpu_std" in m
        cpu_tlp_valid[i] = "cpu_tlp" in m or "cpu_avg" in m
        if "mem_avg" in m:
            mem_avg[i] = m["mem_avg"]
        mem_valid[i] = "mem_avg" in m or "mem_std" in m
        mem_std[i] = m.get("mem_std", 0.0)
        missing[i] = m.get("missing_cpu_millis", 0)
    return dict(
        cpu_avg=cpu_avg,
        cpu_tlp=cpu_tlp,
        cpu_peaks=cpu_peaks,
        cpu_std=cpu_std,
        mem_avg=mem_avg,
        mem_std=mem_std,
        cpu_valid=cpu_valid,
        cpu_tlp_valid=cpu_tlp_valid,
        mem_valid=mem_valid,
        missing_cpu_millis=missing,
    )


def build_snapshot(
    nodes: Sequence[Node],
    pending_pods: Sequence[Pod],
    assigned_pods: Sequence[Pod] = (),
    pod_groups: Sequence[PodGroup] = (),
    quotas: Sequence[ElasticQuota] = (),
    nrts: Sequence[NodeResourceTopology] = (),
    app_groups: Sequence[AppGroup] = (),
    node_metrics: Optional[dict] = None,
    extra_resources: Sequence[str] = (),
    pad_nodes: Optional[int] = None,
    pad_pods: Optional[int] = None,
    backed_off_gangs: Sequence[str] = (),
    extra_pods: Sequence[Pod] = (),
    stale_nrt_nodes: Sequence[str] = (),
    seccomp_profiles: Sequence = (),
    native_nodes: Optional[dict] = None,
    tlp_prediction: tuple = (1.5, 1000),
    sysched_default_profile: Optional[str] = None,
    namespaces: Sequence = (),
) -> tuple[ClusterSnapshot, SnapshotMeta]:
    """Lower host objects into a `ClusterSnapshot`.

    `pending_pods` become the pod batch (in the given order — queue order is
    decided by the framework before calling this). `assigned_pods` only
    contribute to node usage / gang+quota accounting. `extra_pods` are pods
    that are neither schedulable nor assigned (e.g. SchedulingGated) but still
    count toward gang membership and gated-quorum accounting.

    `native_nodes`, when given, is a `bridge.NativeStore.export_nodes()` dict
    whose rows are in the SAME order as `nodes`; the hot node columns (alloc,
    capacity, requested, nonzero, limits, pod_count, terminating) are taken
    from it verbatim — the caller guarantees the store already accounts every
    assigned/reserved pod, so `assigned_pods` should be empty. Engaged only
    when the resource axis is exactly the canonical four (the store layout).
    """
    index = ResourceIndex.union(
        {r: 0 for r in extra_resources},
        *[n.allocatable for n in nodes],
        *[pg.min_resources for pg in pod_groups],
        *[q.min for q in quotas],
        *[q.max for q in quotas],
        *[p.effective_request() for p in list(pending_pods) + list(assigned_pods)],
        *[z.available for t in nrts for z in t.zones],
        *[z.allocatable for t in nrts for z in t.zones],
    )
    R = len(index)
    N = pad_nodes or bucket_size(max(len(nodes), 1))
    P = pad_pods or bucket_size(max(len(pending_pods), 1))

    meta = SnapshotMeta(index=index)
    meta.node_names = [n.name for n in nodes]
    meta.pod_names = [p.uid for p in pending_pods]
    regions_in = _Interner(meta.regions)
    zones_in = _Interner(meta.zones)
    ns_in = _Interner(meta.namespaces)
    gangs_in = _Interner(meta.gang_names)

    # --- nodes ---------------------------------------------------------
    alloc = np.zeros((N, R), I64)
    capacity = np.zeros((N, R), I64)
    requested = np.zeros((N, R), I64)
    nonzero_req = np.zeros((N, R), I64)
    node_limits = np.zeros((N, R), I64)
    node_mask = np.zeros(N, bool)
    region = np.full(N, -1, I32)
    zone = np.full(N, -1, I32)
    pod_count = np.zeros(N, I32)
    terminating = np.zeros(N, I32)
    nominated = np.zeros(N, I32)

    use_native = native_nodes is not None and tuple(index.names) == CANONICAL
    node_pos = {}
    for i, node in enumerate(nodes):
        node_pos[node.name] = i
        if not use_native:
            alloc[i] = index.encode(node.allocatable)
            capacity[i] = index.encode(node.capacity)
        node_mask[i] = not node.unschedulable
        if node.region:
            region[i] = regions_in.code(node.region)
        if node.zone:
            zone[i] = zones_in.code(node.zone)

    # nominated counter (PodState score, pod_state.go:56): every unbound pod
    # with a nomination counts, wherever it lives — upstream's nominator keeps
    # a popped pod's own nomination until assume, so the batch is included
    seen_nominated: set = set()
    nominee_pods: list[Pod] = []
    for pod in list(pending_pods) + list(assigned_pods) + list(extra_pods):
        if (
            pod.node_name is None
            and pod.nominated_node_name in node_pos
            and pod.uid not in seen_nominated
        ):
            seen_nominated.add(pod.uid)
            nominated[node_pos[pod.nominated_node_name]] += 1
            nominee_pods.append(pod)

    pods_i = index.position(PODS)
    if use_native:
        # hot columns straight from the C++ store exports (the store
        # already accounts every assigned/reserved pod, pods slot included)
        n_act = len(nodes)
        alloc[:n_act] = native_nodes["alloc"]
        capacity[:n_act] = native_nodes["capacity"]
        requested[:n_act] = native_nodes["requested"]
        nonzero_req[:n_act] = native_nodes["nonzero_requested"]
        node_limits[:n_act] = native_nodes["limits"]
        pod_count[:n_act] = native_nodes["pod_count"]
        terminating[:n_act] = native_nodes["terminating"]
    else:
        for pod in assigned_pods:
            if pod.node_name is None or pod.node_name not in node_pos:
                continue
            i = node_pos[pod.node_name]
            req = index.encode(pod.effective_request())
            requested[i] += req
            nonzero_req[i] += nonzero_request(req, index)
            # limits clamped to >= requests per pod (SetMaxLimits)
            node_limits[i] += np.maximum(
                index.encode(pod.effective_limits()), req
            )
            pod_count[i] += 1
            if pod.terminating:
                terminating[i] += 1

        # the "pods" resource is accounted as a count, not a request sum
        requested[:, pods_i] = pod_count
        nonzero_req[:, pods_i] = pod_count

    node_state = NodeState(
        alloc=alloc,
        capacity=capacity,
        requested=requested,
        nonzero_requested=nonzero_req,
        limits=node_limits,
        mask=node_mask,
        region=region,
        zone=zone,
        pod_count=pod_count,
        terminating=terminating,
        nominated=nominated,
    )

    # --- gangs ---------------------------------------------------------
    gang_pos = {}
    for pg in pod_groups:
        gang_pos[pg.full_name] = gangs_in.code(pg.full_name)

    def _gang_of(pod: Pod) -> int:
        name = pod.pod_group()
        if not name:
            return -1
        return gang_pos.get(f"{pod.namespace}/{name}", -1)

    gang_state = None
    if pod_groups:
        with obs.tracer.span("Snapshot/gangs", tid="snapshot",
                             gangs=len(gang_pos)):
            # a bucket, as nodes and pods land on: PodGroups that come and
            # go must not give the solve a shape each
            G = bucket_size(len(gang_pos))
            obj = gang_object_tables(pod_groups, gang_pos, index, G,
                                     backed_off_gangs)
            gang_total = np.zeros(G, I32)
            gang_assigned = np.zeros(G, I32)
            gang_gated = np.zeros(G, I32)
            # cluster_slack[g] = total demand of already-assigned members,
            # added back in the cluster sweep (getNodeResource removes the
            # gang's own pods, core.go:433-467; raw sums make the
            # correction a plain total)
            gang_slack = np.zeros((G, R), I64)
            for pod in (
                list(pending_pods) + list(assigned_pods) + list(extra_pods)
            ):
                g = _gang_of(pod)
                if g >= 0:
                    gang_total[g] += 1
                    if pod.node_name is not None:
                        gang_assigned[g] += 1
                        if pod.node_name in node_pos:
                            vec = index.encode(pod.effective_request())
                            vec[pods_i] = 1
                            gang_slack[g] += vec
                    elif pod.scheduling_gated:
                        gang_gated[g] += 1
            gang_state = GangState(
                total_members=gang_total,
                assigned=gang_assigned,
                gated=gang_gated,
                cluster_slack=gang_slack,
                **obj,
            )

    # --- pods (pending batch) -----------------------------------------
    pod_state = build_pod_state(
        pending_pods, P, index, ns_in, _gang_of, tlp_prediction
    )

    # --- quota ---------------------------------------------------------
    quota_state = None
    if quotas:
        with obs.tracer.span("Snapshot/quota", tid="snapshot",
                             quotas=len(quotas)):
            quota_state = _build_quota(
                quotas, pending_pods, assigned_pods, extra_pods, index,
                ns_in, meta, P,
            )

    # --- metrics --------------------------------------------------------
    metrics_state = None
    if node_metrics is not None:
        metrics_state = MetricsState(
            **node_metric_columns(node_metrics, node_pos, N)
        )

    # --- numa -----------------------------------------------------------
    numa_state = None
    if nrts:
        # zone axis is indexed by NUMA id (zones lists may arrive unordered;
        # costs are keyed by numa_id, so both axes must share the id space)
        Z = max(
            max((z.numa_id + 1 for t in nrts for z in t.zones), default=1), 1
        )
        z_avail = np.zeros((N, Z, R), I64)
        z_alloc = np.zeros((N, Z, R), I64)
        z_mask = np.zeros((N, Z), bool)
        z_reported = np.zeros((N, Z, R), bool)
        policy = np.zeros(N, I32)
        scope = np.zeros(N, I32)
        distances = np.full((N, Z, Z), 10, I32)
        has_nrt = np.zeros(N, bool)
        nrt_fresh = np.ones(N, bool)
        max_numa = np.full(N, 8, I32)
        for name in stale_nrt_nodes:
            if name in node_pos:
                nrt_fresh[node_pos[name]] = False
        for t in nrts:
            if t.node_name not in node_pos:
                continue
            i = node_pos[t.node_name]
            has_nrt[i] = True
            policy[i] = int(t.policy)
            scope[i] = int(t.scope)
            max_numa[i] = t.max_numa_nodes
            for zinfo in t.zones:
                z = zinfo.numa_id
                z_mask[i, z] = True
                z_avail[i, z] = index.encode(zinfo.available)
                z_alloc[i, z] = index.encode(zinfo.allocatable)
                for rname in zinfo.available:
                    z_reported[i, z, index.position(rname)] = True
                for other, cost in zinfo.costs.items():
                    if other < Z:
                        distances[i, z, other] = cost
        numa_state = NumaState(
            available=z_avail,
            allocatable=z_alloc,
            zone_mask=z_mask,
            reported=z_reported,
            policy=policy,
            scope=scope,
            distances=distances,
            has_nrt=has_nrt,
            fresh=nrt_fresh,
            max_numa=max_numa,
            pack_scales=_numa_pack_scales(
                z_avail, z_alloc, pod_state.req, pod_state.container_req, R
            ),
        )

    # nominee capacity holds (upstream AddNominatedPods semantics)
    nominee_state = None
    if nominee_pods:
        M = len(nominee_pods)
        batch_pos_nom = {p.uid: i for i, p in enumerate(pending_pods)}
        nom_node = np.zeros(M, I32)
        nom_demand = np.zeros((M, R), I64)
        nom_pri = np.zeros(M, I64)
        nom_batch = np.full(M, -1, I32)
        for j, p in enumerate(nominee_pods):
            nom_node[j] = node_pos[p.nominated_node_name]
            nom_demand[j] = index.encode(p.effective_request())
            nom_demand[j, pods_i] = 1
            nom_pri[j] = p.priority
            nom_batch[j] = batch_pos_nom.get(p.uid, -1)
        nominee_state = NomineeState(
            node=nom_node, demand=nom_demand, priority=nom_pri,
            batch_idx=nom_batch, mask=np.ones(M, bool),
        )

    snapshot = ClusterSnapshot(
        nominees=nominee_state,
        nodes=node_state,
        pods=pod_state,
        gangs=gang_state,
        quota=quota_state,
        metrics=metrics_state,
        numa=numa_state,
        network=_build_network(
            app_groups, pending_pods, assigned_pods, node_pos, region, zone, meta, P
        )
        if app_groups
        else None,
        syscalls=_build_syscalls(
            seccomp_profiles, pending_pods, assigned_pods, node_pos, N, P,
            default_profile=sysched_default_profile,
        )
        if seccomp_profiles
        else None,
        scheduling=_sched.build_scheduling(
            nodes, pending_pods, N, P, assigned=assigned_pods,
            namespaces=namespaces,
        ),
    )
    # hand jit-ready device arrays to callers (numpy is build-time only;
    # tracer indexing inside lax.scan requires jax arrays)
    import jax
    import jax.numpy as jnp

    snapshot = jax.tree.map(jnp.asarray, snapshot)
    return snapshot, meta


#: rescaled quantities must keep value * MAX_NODE_SCORE (100) exactly
#: representable in float32
_F32_PACK_LIMIT = (1 << 24) // 128


def _numa_pack_scales(z_avail, z_alloc, preq, pcreq, R):
    """Per-resource power-of-2 scales for the f32 NUMA fast path, or None.

    A resource packs when every zone quantity and every pending (container)
    request is divisible by 2^k and the rescaled maximum stays below
    2^24/128 (so `value * 100` is exact in float32). Scale-invariance of the
    trunc-division strategy scores (floor of an unchanged rational) keeps
    packed placements bit-identical to the int64 semantics.
    """
    scales = []
    for r in range(R):
        vals = np.concatenate(
            [z_avail[:, :, r].ravel(), z_alloc[:, :, r].ravel(),
             preq[:, r].ravel(), pcreq[:, :, r].ravel()]
        )
        vals = vals[vals > 0]
        if vals.size == 0:
            scales.append(1)
            continue
        # largest power of two dividing every value: min of lowest set bits
        scale = int(np.min(vals & -vals))
        if int(vals.max()) // scale >= _F32_PACK_LIMIT:
            return None
        scales.append(scale)
    return tuple(scales)


def _build_network(app_groups, pending_pods, assigned_pods, node_pos, region, zone, meta, P):
    """Lower AppGroup dependencies + placed-pod locations into NetworkState.
    Cost matrices are attached later by the NetworkOverhead plugin config
    (they come from the NetworkTopology CR, not the AppGroup)."""
    # intern workload selectors
    workloads_in = _Interner(meta.workloads)
    dep_lists = {}  # workload code -> [(dep workload code, max cost)]
    for ag in app_groups:
        for w in ag.workloads:
            wc = workloads_in.code(f"{ag.namespace}/{w.selector}")
            dep_lists[wc] = [
                (workloads_in.code(f"{ag.namespace}/{d.workload_selector}"), d.max_network_cost)
                for d in w.dependencies
            ]
    W = max(len(meta.workloads), 1)
    D = max(max((len(v) for v in dep_lists.values()), default=1), 1)
    ZC = max(len(meta.zones), 1)
    RC = max(len(meta.regions), 1)
    N = region.shape[0]

    dep_workload = np.full((P, D), -1, I32)
    dep_max_cost = np.zeros((P, D), I64)
    dep_mask = np.zeros((P, D), bool)
    pod_workload = np.full(P, -1, I32)
    for i, pod in enumerate(pending_pods):
        sel = pod.workload_selector()
        key = f"{pod.namespace}/{sel}"
        wc = workloads_in.get(key) if sel else -1
        if wc < 0:
            continue
        pod_workload[i] = wc
        deps = dep_lists.get(wc, [])
        for d, (dw, mc) in enumerate(deps):
            dep_workload[i, d] = dw
            dep_max_cost[i, d] = mc
            dep_mask[i, d] = True

    placed_node = np.zeros((W, N), I32)
    zone_region = np.full(ZC, -1, I32)
    for ni in range(N):
        if zone[ni] >= 0 and region[ni] >= 0:
            zone_region[zone[ni]] = region[ni]
    for pod in assigned_pods:
        sel = pod.workload_selector()
        if not sel or pod.node_name not in node_pos:
            continue
        key = f"{pod.namespace}/{sel}"
        wc = workloads_in.get(key)
        if wc < 0:
            continue
        placed_node[wc, node_pos[pod.node_name]] += 1

    cls_dep_workload = np.full((W, D), -1, I32)
    cls_dep_max_cost = np.zeros((W, D), I64)
    cls_dep_mask = np.zeros((W, D), bool)
    for wc, deps in dep_lists.items():
        for d, (dw, mc) in enumerate(deps):
            cls_dep_workload[wc, d] = dw
            cls_dep_max_cost[wc, d] = mc
            cls_dep_mask[wc, d] = True

    return NetworkState(
        dep_workload=dep_workload,
        dep_max_cost=dep_max_cost,
        dep_mask=dep_mask,
        pod_workload=pod_workload,
        placed_node=placed_node,
        zone_region=zone_region,
        cls_dep_workload=cls_dep_workload,
        cls_dep_max_cost=cls_dep_max_cost,
        cls_dep_mask=cls_dep_mask,
    )


#: pod annotations whose key contains this mark carry an SPO profile path
#: (sysched.go SPO_ANNOTATION)
SPO_ANNOTATION = "seccomp.security.alpha.kubernetes.io"


def parse_profile_path(path: str):
    """parseNameNS (sysched.go:67-83): namespace = second-to-last path
    segment, name = last segment minus extension; <2 segments = invalid."""
    if not path:
        return None
    parts = path.split("/")
    if len(parts) < 2:
        return None
    name = parts[-1]
    if "." in name:
        name = name[: name.rindex(".")]
    return f"{parts[-2]}/{name}"


def _build_syscalls(
    profiles, pending_pods, assigned_pods, node_pos, N, P,
    default_profile=None,
):
    """Lower seccomp profiles + pod references into SyscallState
    (/root/reference/pkg/sysched/sysched.go:124-210): pod syscall set =
    union of (container SeccompProfile references) + (the first SPO
    annotation's profile); pods resolving NO syscalls fall back to the
    configured default profile (the all-syscalls CR), and only when that
    too is missing does the plugin score them MaxInt64-equivalent."""
    by_name = {}
    universe: list[str] = []
    pos: dict[str, int] = {}
    for prof in profiles:
        by_name[prof.full_name] = prof
        for sc in sorted(prof.syscalls):
            if sc not in pos:
                pos[sc] = len(universe)
                universe.append(sc)
    S = max(len(universe), 1)

    def resolve(ref, namespace):
        if not ref:
            return None
        if ref.count("/") >= 2 or ref.endswith(".json"):
            # localhost profile path (operator/<ns>/<name>.json)
            ref = parse_profile_path(ref)
        elif "/" not in ref:
            # bare names resolve in the pod's own namespace
            ref = f"{namespace}/{ref}"
        return by_name.get(ref) if ref else None

    def pod_set(pod):
        vec = np.zeros(S, bool)
        found = False
        for cont in list(pod.containers) + list(pod.init_containers):
            prof = resolve(cont.seccomp_profile, pod.namespace)
            if prof is not None:
                found = True
                for sc in prof.syscalls:
                    vec[pos[sc]] = True
        # SPO auto-annotation: the reference merges the FIRST seccomp
        # annotation then breaks (sysched.go:171-196); Go map order is
        # random — we pin sorted key order for determinism
        for key in sorted(pod.annotations):
            if SPO_ANNOTATION in key:
                prof = resolve(pod.annotations[key], pod.namespace)
                if prof is not None:
                    found = True
                    for sc in prof.syscalls:
                        vec[pos[sc]] = True
                break
        if not found and default_profile is not None:
            prof = by_name.get(default_profile)
            if prof is not None and prof.syscalls:
                found = True
                for sc in prof.syscalls:
                    vec[pos[sc]] = True
        return vec, found

    pod_sets = np.zeros((P, S), bool)
    has_profile = np.zeros(P, bool)
    for i, pod in enumerate(pending_pods):
        pod_sets[i], has_profile[i] = pod_set(pod)

    host_sets = np.zeros((N, S), bool)
    counts = np.zeros((N, S), I32)
    host_pods = np.zeros(N, I32)
    for pod in assigned_pods:
        if pod.node_name not in node_pos:
            continue
        ni = node_pos[pod.node_name]
        vec, _ = pod_set(pod)
        host_sets[ni] |= vec
        counts[ni] += vec
        host_pods[ni] += 1
    return SyscallState(
        pod_sets=pod_sets,
        has_profile=has_profile,
        host_sets=host_sets,
        counts=counts,
        host_pod_count=host_pods,
    )
