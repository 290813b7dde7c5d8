"""Cluster-event kind table — THE one copy of the "Resource/Action" strings.

The reference registers cluster events per plugin via EventsToRegister
(framework.ClusterEventWithHint, e.g. coscheduling.go:113-122) and the
scheduling queue gates requeues on them. Here the same kinds flow through
three seams that previously each spelled the strings by hand:

- `state.cluster.Cluster.note_event` (the store's mutation hooks),
- `bridge.feed` (delete acks for CR kinds the store has no remover for),
- plugin `events_to_register()` registrations and the framework's
  `BUILTIN_EVENTS`.

A typo in any one of them silently broke requeue gating (the event would
never match a registration); with this table the spelling exists once.
`KIND_<RESOURCE>_<ACTION>` constants are plain strings so every existing
comparison, dict key and JSON serialization keeps working unchanged.

This module is also the delta classification the serving engine consumes
(`serving.deltas`): `NODE_COLUMN_EVENTS` names exactly the kinds that can
change the resident node tensors, and `SERVE_REBASE_EVENTS` the kinds
whose effects the O(changed) scatter programs cannot express (row-order
or side-table changes) — see docs/SERVING.md for the mapping.
"""

from __future__ import annotations

# -- core objects -----------------------------------------------------------
NODE_ADD = "Node/Add"
NODE_UPDATE = "Node/Update"
NODE_DELETE = "Node/Delete"
POD_ADD = "Pod/Add"
POD_UPDATE = "Pod/Update"
POD_DELETE = "Pod/Delete"

# -- scheduler-plugins CRs --------------------------------------------------
POD_GROUP_ADD = "PodGroup/Add"
POD_GROUP_UPDATE = "PodGroup/Update"
POD_GROUP_DELETE = "PodGroup/Delete"
ELASTIC_QUOTA_ADD = "ElasticQuota/Add"
ELASTIC_QUOTA_UPDATE = "ElasticQuota/Update"
ELASTIC_QUOTA_DELETE = "ElasticQuota/Delete"
NRT_ADD = "NodeResourceTopology/Add"
NRT_UPDATE = "NodeResourceTopology/Update"
NRT_DELETE = "NodeResourceTopology/Delete"
APP_GROUP_ADD = "AppGroup/Add"
APP_GROUP_UPDATE = "AppGroup/Update"
APP_GROUP_DELETE = "AppGroup/Delete"
NETWORK_TOPOLOGY_ADD = "NetworkTopology/Add"
NETWORK_TOPOLOGY_UPDATE = "NetworkTopology/Update"
NETWORK_TOPOLOGY_DELETE = "NetworkTopology/Delete"
SECCOMP_PROFILE_ADD = "SeccompProfile/Add"
SECCOMP_PROFILE_UPDATE = "SeccompProfile/Update"
SECCOMP_PROFILE_DELETE = "SeccompProfile/Delete"

# -- companion objects ------------------------------------------------------
PRIORITY_CLASS_ADD = "PriorityClass/Add"
PRIORITY_CLASS_UPDATE = "PriorityClass/Update"
PRIORITY_CLASS_DELETE = "PriorityClass/Delete"
NAMESPACE_ADD = "Namespace/Add"
NAMESPACE_UPDATE = "Namespace/Update"
NAMESPACE_DELETE = "Namespace/Delete"
PDB_ADD = "PodDisruptionBudget/Add"
PDB_UPDATE = "PodDisruptionBudget/Update"
PDB_DELETE = "PodDisruptionBudget/Delete"

#: every kind the store can emit, grouped by resource — the registry a
#: requeue registration is validated against (an unknown kind can never
#: fire, so registering one is a bug, not a no-op)
EVENT_KINDS = frozenset({
    NODE_ADD, NODE_UPDATE, NODE_DELETE,
    POD_ADD, POD_UPDATE, POD_DELETE,
    POD_GROUP_ADD, POD_GROUP_UPDATE, POD_GROUP_DELETE,
    ELASTIC_QUOTA_ADD, ELASTIC_QUOTA_UPDATE, ELASTIC_QUOTA_DELETE,
    NRT_ADD, NRT_UPDATE, NRT_DELETE,
    APP_GROUP_ADD, APP_GROUP_UPDATE, APP_GROUP_DELETE,
    NETWORK_TOPOLOGY_ADD, NETWORK_TOPOLOGY_UPDATE, NETWORK_TOPOLOGY_DELETE,
    SECCOMP_PROFILE_ADD, SECCOMP_PROFILE_UPDATE, SECCOMP_PROFILE_DELETE,
    PRIORITY_CLASS_ADD, PRIORITY_CLASS_UPDATE, PRIORITY_CLASS_DELETE,
    NAMESPACE_ADD, NAMESPACE_UPDATE, NAMESPACE_DELETE,
    PDB_ADD, PDB_UPDATE, PDB_DELETE,
})

#: kinds whose effects land entirely in the resident NODE tensors (alloc,
#: capacity, mask, usage columns) — the serving engine expresses these as
#: O(changed) scatter deltas (serving.deltas)
NODE_COLUMN_EVENTS = frozenset({
    NODE_ADD, NODE_UPDATE, POD_ADD, POD_UPDATE, POD_DELETE,
})

#: kinds that invalidate the resident row order or an excluded side table:
#: the serving engine re-bases (full re-snapshot) when one fires — the
#: same rule `Cluster._native_rebuild` applies to the C++ columnar mirror
SERVE_REBASE_EVENTS = frozenset({NODE_DELETE})

# -- pod-lifecycle ledger transitions (observability plane) ----------------
#: the `obs.ledger` transition vocabulary — NOT store mutation kinds (they
#: never enter `EVENT_KINDS` or requeue gating) but registered here so the
#: ledger, the store hooks that feed it, and the timeline renderers spell
#: one set of strings, exactly like the mutation kinds above
LIFECYCLE_FIRST_SEEN = "PodLifecycle/FirstSeen"
LIFECYCLE_WAIT = "PodLifecycle/Wait"
LIFECYCLE_UNSCHEDULABLE = "PodLifecycle/Unschedulable"
LIFECYCLE_NOMINATED = "PodLifecycle/Nominated"
LIFECYCLE_NOMINATION_CLEARED = "PodLifecycle/NominationCleared"
LIFECYCLE_RESERVED = "PodLifecycle/Reserved"
LIFECYCLE_BOUND = "PodLifecycle/Bound"
LIFECYCLE_TERMINATING = "PodLifecycle/Terminating"
LIFECYCLE_DELETED = "PodLifecycle/Deleted"
LIFECYCLE_GATE = "PodLifecycle/Gate"

#: every transition the ledger can record — appends are validated against
#: this set (an unregistered kind is a bug in the feeding seam, not a new
#: feature)
LIFECYCLE_KINDS = frozenset({
    LIFECYCLE_FIRST_SEEN, LIFECYCLE_WAIT, LIFECYCLE_UNSCHEDULABLE,
    LIFECYCLE_NOMINATED, LIFECYCLE_NOMINATION_CLEARED, LIFECYCLE_RESERVED,
    LIFECYCLE_BOUND, LIFECYCLE_TERMINATING, LIFECYCLE_DELETED,
    LIFECYCLE_GATE,
})
assert not (LIFECYCLE_KINDS & EVENT_KINDS)

#: every kind the rank-aware gang phase can emit or gate on
#: (`gangs.phase.GangPhase`): elastic growth arrives as Pod/Add, binds as
#: Pod/Update, shrink as Pod/Delete, spec changes as PodGroup/Update —
#: all spelled HERE, so the phase introduces no literal kind strings and
#: a parked gang member requeues on exactly the kinds Coscheduling
#: already registers (plus Pod/Delete: freed capacity can complete a
#: previously capacity-rejected gang)
GANG_EVENTS = frozenset({
    POD_ADD, POD_UPDATE, POD_DELETE,
    POD_GROUP_ADD, POD_GROUP_UPDATE, POD_GROUP_DELETE,
    NODE_ADD, NODE_UPDATE,
    NETWORK_TOPOLOGY_ADD, NETWORK_TOPOLOGY_UPDATE,
})
assert GANG_EVENTS <= EVENT_KINDS
