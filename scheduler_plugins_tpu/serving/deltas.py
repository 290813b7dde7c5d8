"""Delta classification + the jittable O(changed) scatter-apply program.

The reference's watch-driven design never rebuilds state: informer events
mutate NodeInfo incrementally and each cycle reads the live cache. This
module is the tensor equivalent for the serving engine
(`serving.engine.ServeEngine`): host mutations of the `Cluster` store are
captured as typed delta events by a `DeltaSink` (installed as
`Cluster.delta_sink`), coalesced and packed into two fixed-bucket array
groups, and applied to the device-resident `NodeState` columns by ONE
jitted scatter program whose resident carry is DONATED — the node tensors
thread cycle to cycle in place, and the per-cycle work is O(changed), not
O(cluster).

Delta classification (the `api.events` kinds each group expresses):

- `NodeUpserts` — Node/Add, Node/Update: row overwrites of the static node
  columns (alloc, capacity, mask, region, zone). Expressed as
  scatter-ADD of `new - current` (gathered in-jit), so padded rows are
  exact no-ops and duplicate indices cannot race: the host coalesces to at
  most one upsert per slot per batch, making the add exact.
- `UsageDeltas` — Pod/Add (assigned), Pod/Update (bind / terminating
  flip), Pod/Delete: signed contributions to the usage columns
  (requested, nonzero_requested, limits, pod_count, terminating),
  mirroring exactly the per-assigned-pod accumulation
  `state.snapshot.build_snapshot` performs — scatter-add, where duplicate
  indices are well-defined (sum) and padded rows are zero.
- Node/Delete (and anything the scatter programs cannot express — row
  reordering, label re-interning, a resource the engine's axis does not
  hold yet) re-bases instead: `api.events.SERVE_REBASE_EVENTS`, the same
  rule the C++ columnar mirror applies (`Cluster._native_rebuild`).

Both groups are padded to `utils.intmath.bucket_size` buckets so the jit
cache stays warm across cycles (distinct (U, K) bucket pairs retrace once
each, like every other padded shape in this repo). All inputs are
ARGUMENTS — no config closure captures (CLAUDE.md / GL001) and no wall
clocks inside jit (GL008).
"""

from __future__ import annotations

import numpy as np
from flax import struct

from scheduler_plugins_tpu.api.resources import ResourceIndex
from scheduler_plugins_tpu.resilience import faults as _faults
from scheduler_plugins_tpu.state.snapshot import NodeState, usage_rows
from scheduler_plugins_tpu.utils.intmath import bucket_size

#: the axis an engine starts from: the canonical four (what the C++
#: columnar store's 4-slot layout holds). An engine's own axis
#: (`ServeEngine.index`) is this plus the extended resources its store
#: names, taken at a rebase; a cluster without any never leaves this one
CANON_INDEX = ResourceIndex(())

I64 = np.int64
I32 = np.int32


class UnsupportedResource(ValueError):
    """An object names a resource the given axis does not hold: the packed
    delta vectors cannot carry it until a rebase has widened the axis."""


def _encode(quantities: dict, index: ResourceIndex) -> np.ndarray:
    try:
        return index.encode(quantities)
    except KeyError as exc:
        raise UnsupportedResource(str(exc)) from exc


def pod_quota_vector(pod, index: ResourceIndex) -> np.ndarray:
    """One assigned pod's contribution to its namespace's ElasticQuota
    `used` row — the RAW effective-request encode (no pods-slot override:
    `build_snapshot`'s quota accumulation sums `index.encode(
    pod.effective_request())` verbatim). Raises `UnsupportedResource` on
    a resource outside `index`, like the usage vectors."""
    return _encode(pod.effective_request(), index)


def pod_usage_vectors(
    pod, index: ResourceIndex
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(requested, nonzero_requested, limits) contribution of ONE assigned
    pod to its node's usage columns, lowered cold from the pod's dicts
    (`state.snapshot.usage_rows` holds the arithmetic; the serving
    engine reads the same vectors off the pod's `PodRecord`). Raises
    `UnsupportedResource` on a resource outside `index`."""
    try:
        rows = usage_rows(
            index.slots(pod.effective_request()),
            index.slots(pod.effective_limits()), index,
        )
    except KeyError as exc:
        raise UnsupportedResource(str(exc)) from exc
    return tuple(np.array(rows, dtype=I64))


# ---------------------------------------------------------------------------
# delta sink: the Cluster's mutation hooks push typed events here
# ---------------------------------------------------------------------------

# event tuples: (kind, payload...) — kept as raw object references; the
# engine derives the RESOURCE vectors at drain time (upserts replace pod
# objects wholesale, so event-time references are stable for requests/
# limits), but the terminating FLAG is captured at event time:
# `mark_terminating` mutates the live pod in place AND queues its own
# POD_TERMINATING delta, so a drain-time read of the flag would double-
# count a flip that lands in the same drain window as the pod's assign
NODE_UPSERT = "node_upsert"
NODE_DELETE = "node_delete"
POD_ASSIGN = "pod_assign"
POD_UNASSIGN = "pod_unassign"
POD_TERMINATING = "pod_terminating"
#: gang GATED-count transition (resident gang side tables): an UNBOUND,
#: scheduling-gated gang member appeared (+1) or left that state (-1).
#: The full snapshot counts such pods into `GangState.gated`/`total`
#: (via `Cluster.gated_pods`), and no node-column event fires for them —
#: the mutators push this kind with the delta captured at EVENT time
#: (the gate/terminating flags mutate in place)
GANG_GATED = "gang_gated"
#: what a pod adds to its node's unreported CPU may have changed (the
#: resident `missing_cpu_millis` column): it was bound, or it is a recent
#: binding whose pod object was replaced or deleted. Carries the uid alone:
#: the engine reads the store at drain time, as the full snapshot would,
#: so a dropped, doubled or reordered event cannot add a pod twice. Sent
#: only while the store holds a load watcher's report
BINDING_TOUCHED = "binding_touched"
#: an UNHELD pod object left the store (a pending pod deleted, or replaced
#: by an upsert): no column moves, but the engine's `PodRecord` of it has no
#: reader left. Carries the uid alone; the engine drops the entry only where
#: the store no longer holds the recorded object. A held pod needs none: its
#: `POD_UNASSIGN` says the same
POD_FORGET = "pod_forget"


class DeltaSink:
    """Typed event queue installed as `Cluster.delta_sink`. The store's
    mutators (`add_node`, `bind`, `remove_pod`, ...) push exactly the
    state transitions that change node columns; `drain()` hands the
    accumulated batch to the engine once per cycle. Host-side and
    allocation-light: one list append per mutation."""

    #: backstop for a sink nobody drains (engine dropped, serve mode
    #: toggled off while still attached): past this many undrained events
    #: a full re-snapshot is cheaper than replaying them anyway, so the
    #: queue collapses to an `overflowed` marker (the next refresh
    #: re-bases) instead of pinning Pod references without bound
    MAX_EVENTS = 1 << 18

    def __init__(self):
        self.events: list[tuple] = []
        self.overflowed = False
        #: drain generation: bumped by every `drain()` — the pipelined
        #: engine's conflict-fence accounting compares it around a bind
        #: flush to tell whether the flush crossed an ingest boundary
        self.drains = 0
        #: unbound pods carrying a NominatedNodeName that the per-cycle
        #: pending gate cannot see (scheduling-gated pods arrive through
        #: `add_pod`, never through the pending batch) — any entry keeps
        #: `ServeEngine.compatible` False: the full snapshot counts such
        #: nominations into the `nominated` node column and nominee-hold
        #: tables, which the resident columns do not carry
        self.nominated_unbound: set[str] = set()

    def _push(self, ev: tuple) -> None:
        if _faults.ACTIVE is not None:
            # chaos harness only (zero overhead when no plan is
            # installed): drop/duplicate/corrupt THIS sink event — the
            # Cluster store never sees the mutation, so the poisoning is
            # invisible to everything except the serving engine's
            # anti-entropy digest (docs/ROBUSTNESS.md)
            for mutated in _faults.mutate_delta(ev):
                self._push_one(mutated)
            return
        self._push_one(ev)

    def _push_one(self, ev: tuple) -> None:
        if len(self.events) >= self.MAX_EVENTS:
            self.events.clear()
            self.overflowed = True
        self.events.append(ev)

    # -- node lifecycle --------------------------------------------------
    def node_upsert(self, node) -> None:
        self._push((NODE_UPSERT, node))

    def node_delete(self, name: str) -> None:
        self._push((NODE_DELETE, name))

    # -- pod usage transitions ------------------------------------------
    def pod_assigned(self, pod, node_name: str) -> None:
        """Pod now holds capacity on `node_name` (bound OR permit-
        reserved — reservations count exactly like bindings in the
        snapshot's assigned view). The terminating flag rides in the
        event (a later `mark_terminating` queues its OWN +1 delta)."""
        self._push(
            (POD_ASSIGN, pod, node_name, bool(pod.terminating))
        )

    def pod_unassigned(self, pod, node_name: str) -> None:
        self._push(
            (POD_UNASSIGN, pod, node_name, bool(pod.terminating))
        )

    def pod_terminating(self, pod, node_name: str) -> None:
        """Terminating flag flipped False -> True on a held (bound or
        reserved) pod."""
        self._push((POD_TERMINATING, pod, node_name))

    # -- gang side-table transitions ------------------------------------
    def gang_gated(self, gang_full_name: str, delta: int) -> None:
        """Unbound+gated membership transition of gang `gang_full_name`
        (+1 appeared / -1 left). Delta captured at event time — the
        scheduling-gate and terminating flags mutate pods in place, so a
        drain-time re-read could double- or under-count a flip landing in
        the same drain window (the POD_ASSIGN terminating-flag rule)."""
        self._push((GANG_GATED, gang_full_name, delta))

    # -- resident node metrics -----------------------------------------
    def binding_touched(self, uid: str) -> None:
        self._push((BINDING_TOUCHED, uid))

    # -- per-pod records -------------------------------------------------
    def pod_forgotten(self, uid: str) -> None:
        self._push((POD_FORGET, uid))

    # -- sticky compatibility flags -------------------------------------
    def note_nomination(self, pod) -> None:
        """Track/untrack an upserted pod's nomination (reads the SAME pod
        object the next full snapshot would, so the two views agree)."""
        if pod.node_name is None and pod.nominated_node_name is not None:
            self.nominated_unbound.add(pod.uid)
        else:
            self.nominated_unbound.discard(pod.uid)

    def forget_nomination(self, uid: str) -> None:
        self.nominated_unbound.discard(uid)

    def drain(self) -> list[tuple]:
        events, self.events = self.events, []
        self.drains += 1
        return events

    def consume_overflow(self) -> bool:
        """True once if the queue overflowed since the last drain — the
        surviving events are partial, so the caller must re-base."""
        overflowed, self.overflowed = self.overflowed, False
        return overflowed


# ---------------------------------------------------------------------------
# packed delta batches (fixed-bucket shapes; numpy on the host side)
# ---------------------------------------------------------------------------


class NodeUpserts:
    """Packed node-row overwrites: at most one row per slot (host-
    coalesced), padded to a bucket with valid=False rows."""

    __slots__ = ("idx", "valid", "alloc", "capacity", "mask", "region",
                 "zone")

    def __init__(self, idx, valid, alloc, capacity, mask, region, zone):
        self.idx = idx
        self.valid = valid
        self.alloc = alloc
        self.capacity = capacity
        self.mask = mask
        self.region = region
        self.zone = zone

    @classmethod
    def pack(cls, rows: list[tuple], R: int) -> "NodeUpserts":
        """`rows`: [(slot, alloc_vec, cap_vec, schedulable, region_code,
        zone_code)] with unique slots."""
        U = bucket_size(max(len(rows), 1))
        idx = np.zeros(U, I32)
        valid = np.zeros(U, bool)
        alloc = np.zeros((U, R), I64)
        capacity = np.zeros((U, R), I64)
        mask = np.zeros(U, I32)
        region = np.full(U, -1, I32)
        zone = np.full(U, -1, I32)
        for j, (slot, a, c, sched, r, z) in enumerate(rows):
            idx[j] = slot
            valid[j] = True
            alloc[j] = a
            capacity[j] = c
            mask[j] = 1 if sched else 0
            region[j] = r
            zone[j] = z
        return cls(idx, valid, alloc, capacity, mask, region, zone)

    def as_args(self) -> tuple:
        return (self.idx, self.valid, self.alloc, self.capacity, self.mask,
                self.region, self.zone)

    def as_dict(self) -> dict:
        """Plain-dict view for flight-recorder packing (generic unpack —
        no struct registry entry needed)."""
        return {
            "idx": self.idx, "valid": self.valid, "alloc": self.alloc,
            "capacity": self.capacity, "mask": self.mask,
            "region": self.region, "zone": self.zone,
        }


class UsageDeltas:
    """Packed signed usage contributions; duplicate slots sum (scatter-add
    semantics), padded rows are zero."""

    __slots__ = ("idx", "requested", "nonzero", "limits", "pod_count",
                 "terminating")

    def __init__(self, idx, requested, nonzero, limits, pod_count,
                 terminating):
        self.idx = idx
        self.requested = requested
        self.nonzero = nonzero
        self.limits = limits
        self.pod_count = pod_count
        self.terminating = terminating

    #: bucket floor. A cycle's rows are the binds of the cycle before and
    #: the departures since, about twice its batch, and a program is
    #: compiled per bucket of them (~0.5 s on a v5e, PERF.md finding 11).
    #: A served daemon's batches run from a few pods (the tick after a
    #: long one) to a thousand and more, so a 64-row floor met eight
    #: buckets, 64 to 4,096, the small ones rarely and so inside a
    #: measured window (PR 27's runs: nine `serve_delta_apply` shapes).
    #: 1,024 rows is twice a batch of 512, the smallest batch a closed
    #: backlog of 2,048 outstanding makes all the time: every rarer,
    #: smaller batch shares its program, and what is left above it
    #: (2,048, 3,072, 4,096) a start-up can warm by name. The scatter
    #: costs ~0.25 us a row on the device (0.65-0.77 ms for 2,048-3,072
    #: rows, my chip runs, PR 27's traces), so the floor is 0.25 ms a
    #: cycle at most, and packing 1,024 zero rows ~10 us on the host.
    MIN_BUCKET = 1024

    @classmethod
    def pack(cls, rows: list[tuple], R: int) -> "UsageDeltas":
        """`rows`: [(slot, usage, sign, d_term)]: `usage` the pod's (3, R)
        (requested, nonzero, limits) block as its `PodRecord` holds it,
        `sign` +1 for an assign, -1 for an unassign and 0 for an event
        without a resource payload, `d_term` what the event adds to the
        terminating count. The blocks are stacked and signed in one call,
        not a row at a time."""
        n = len(rows)
        K = bucket_size(max(n, 1), minimum=cls.MIN_BUCKET)
        idx = np.zeros(K, I32)
        usage = np.zeros((K, 3, R), I64)
        pod_count = np.zeros(K, I32)
        terminating = np.zeros(K, I32)
        if n:
            slots, blocks, signs, terms = zip(*rows)
            idx[:n] = slots
            pod_count[:n] = signs
            terminating[:n] = terms
            np.multiply(
                np.concatenate(blocks).reshape(n, 3, R),
                pod_count[:n, None, None], out=usage[:n],
            )
        requested, nonzero, limits = (
            np.ascontiguousarray(part) for part in usage.transpose(1, 0, 2)
        )
        return cls(idx, requested, nonzero, limits, pod_count, terminating)

    def as_args(self) -> tuple:
        return (self.idx, self.requested, self.nonzero, self.limits,
                self.pod_count, self.terminating)

    def as_dict(self) -> dict:
        return {
            "idx": self.idx, "requested": self.requested,
            "nonzero": self.nonzero, "limits": self.limits,
            "pod_count": self.pod_count, "terminating": self.terminating,
        }


# ---------------------------------------------------------------------------
# the jittable apply program
# ---------------------------------------------------------------------------


def apply_node_deltas(nodes: NodeState,
                      up_idx, up_valid, up_alloc, up_capacity, up_mask,
                      up_region, up_zone,
                      d_idx, d_requested, d_nonzero, d_limits, d_pod_count,
                      d_terminating) -> NodeState:
    """Fold one packed delta batch into the resident `NodeState` columns.

    Upserts use the gather-diff form — `add(new - current)` under the
    valid mask — so padded rows are exact no-ops without needing current
    values host-side, and the only write primitive anywhere is a
    well-defined scatter-add (no unordered scatter-set). Bool/int32
    columns round-trip through int32 arithmetic (exact). Usage deltas are
    plain scatter-adds of signed contributions. The `nodes` argument is
    donated at the jit boundary (`delta_apply_program`): callers treat the
    resident carry as consumed and rebind it from the result."""
    import jax.numpy as jnp

    gi = up_idx

    def overwrite2(cur, new):
        # (N, R) row overwrite as add(new - current); pads contribute 0
        delta = jnp.where(up_valid[:, None], new - cur[gi], 0)
        return cur.at[gi].add(delta)

    def overwrite1(cur, new):
        # (N,) int32-or-bool overwrite through exact int32 arithmetic
        cur_i = cur.astype(jnp.int32)
        delta = jnp.where(up_valid, new - cur_i[gi], 0)
        return cur_i.at[gi].add(delta).astype(cur.dtype)

    nodes = nodes.replace(
        alloc=overwrite2(nodes.alloc, up_alloc),
        capacity=overwrite2(nodes.capacity, up_capacity),
        mask=overwrite1(nodes.mask, up_mask),
        region=overwrite1(nodes.region, up_region),
        zone=overwrite1(nodes.zone, up_zone),
        # serve mode owns the snapshot only while NO nomination exists
        # anywhere (ServeEngine.compatible) — the resident nominated
        # column is invariantly zero. Written fresh (not passed through)
        # so no donated buffer aliases an output (JA002).
        nominated=jnp.zeros_like(nodes.nominated),
    )
    di = d_idx
    return nodes.replace(
        requested=nodes.requested.at[di].add(d_requested),
        nonzero_requested=nodes.nonzero_requested.at[di].add(d_nonzero),
        limits=nodes.limits.at[di].add(d_limits),
        pod_count=nodes.pod_count.at[di].add(d_pod_count),
        terminating=nodes.terminating.at[di].add(d_terminating),
    )


def compact_node_rows(nodes: NodeState, gather_idx, valid) -> NodeState:
    """Delete node rows in place: gather the surviving rows into their
    shifted slots (`gather_idx`, host-computed) and re-pad the freed tail
    (`valid` False) with the exact values a fresh `build_snapshot` pad
    row carries (zeros; mask False; region/zone -1) — so the compacted
    resident columns stay byte-identical to a rebase's, and the
    anti-entropy digest cannot tell them apart. Row ORDER is preserved
    (a shift, never a swap-with-last): the store's dict pop preserves the
    order of the remaining nodes, and score tie-breaking is
    lowest-index. This turns the Node/Delete rebase — the one O(cluster)
    event in steady churn — into an O(changed)-host, O(N)-device
    gather (`StreamingServeEngine`). The `nodes` argument is donated at
    the jit boundary (`node_compact_program`)."""
    import jax.numpy as jnp

    def take2(cur):
        return jnp.where(valid[:, None], cur[gather_idx], 0)

    def take1(cur, pad=0):
        out = cur[gather_idx]
        return jnp.where(valid, out, jnp.asarray(pad).astype(out.dtype))

    return nodes.replace(
        alloc=take2(nodes.alloc),
        capacity=take2(nodes.capacity),
        requested=take2(nodes.requested),
        nonzero_requested=take2(nodes.nonzero_requested),
        limits=take2(nodes.limits),
        mask=take1(nodes.mask, False),
        region=take1(nodes.region, -1),
        zone=take1(nodes.zone, -1),
        pod_count=take1(nodes.pod_count),
        terminating=take1(nodes.terminating),
        # invariantly zero while serve mode owns the snapshot (the
        # compatibility gate excludes nominations); written fresh so no
        # donated buffer aliases an output (JA002)
        nominated=jnp.zeros_like(nodes.nominated),
    )


# ---------------------------------------------------------------------------
# resident gang/quota side tables (ISSUE 12; docs/SERVING.md)
# ---------------------------------------------------------------------------

@struct.dataclass
class SideTables:
    """Device-resident gang/quota aggregate side tables, in ENGINE-stable
    row order (first-seen gang / namespace; the per-cycle assembly
    permutes host copies into that cycle's snapshot interning order).
    These are the per-POD aggregates a fresh `build_snapshot` pays
    O(cluster) pod loops for — maintained O(changed) from the drained
    delta stream by `apply_side_deltas`, exactly like the node columns:

    - gang_assigned (G,) i32 / gang_slack (G, R) i64: bound+reserved
      members and their request sums (pods slot 1) per gang — the
      `GangState.assigned` / `cluster_slack` aggregates.
    - gang_gated (G,) i32: unbound scheduling-gated members (the
      `gated_pods()` contribution to `GangState.gated`/`total_members`).
    - quota_used (Q, R) i64: per-namespace assigned request sums (the
      `QuotaState.used` accumulation, raw encodes).
    - ns_assigned (Q,) i32: assigned-pod count per namespace — only used
      host-side to reproduce the fresh snapshot's namespace-interning
      tail (namespaces with assigned pods intern after batch + quotas;
      their rows are all-default, so only the SET matters).
    """

    gang_assigned: np.ndarray
    gang_gated: np.ndarray
    gang_slack: np.ndarray
    quota_used: np.ndarray
    ns_assigned: np.ndarray


def zero_side_tables(G: int, Q: int, R: int) -> SideTables:
    import jax.numpy as jnp

    return SideTables(
        gang_assigned=jnp.zeros(G, jnp.int32),
        gang_gated=jnp.zeros(G, jnp.int32),
        gang_slack=jnp.zeros((G, R), jnp.int64),
        quota_used=jnp.zeros((Q, R), jnp.int64),
        ns_assigned=jnp.zeros(Q, jnp.int32),
    )


class SideDeltas:
    """Packed side-table delta batch: gang rows (engine-stable gang row,
    d_assigned, d_gated, d_slack) + namespace rows (engine-stable ns row,
    d_used, d_count), bucket-padded with zero-delta rows (scatter-add
    no-ops) to the tables' own sizes."""

    __slots__ = ("g_idx", "g_assigned", "g_gated", "g_slack",
                 "q_idx", "q_used", "q_count")

    def __init__(self, g_idx, g_assigned, g_gated, g_slack, q_idx, q_used,
                 q_count):
        self.g_idx = g_idx
        self.g_assigned = g_assigned
        self.g_gated = g_gated
        self.g_slack = g_slack
        self.q_idx = q_idx
        self.q_used = q_used
        self.q_count = q_count

    @classmethod
    def pack(cls, gang_rows: list[tuple], ns_rows: list[tuple],
             R: int, Ug: int, Uq: int) -> "SideDeltas":
        """`gang_rows`: [(row, d_assigned, d_gated, d_slack_vec)];
        `ns_rows`: [(row, d_used_vec, d_count)], at most one entry a row
        (the engine coalesces). `Ug`, `Uq`: the batch's lengths, the
        tables' own (bucketed) sizes, so the apply program has one shape
        per pair of table sizes and none per count of rows a cycle
        touched: gangs of 1 to 64 binding made that count wander over
        five buckets, and the tables are a few thousand rows at most."""
        # entry j carries row j's delta, zero where the cycle left the row
        # alone: a scatter-add of zero is a no-op
        g_idx = np.arange(Ug, dtype=I32)
        g_assigned = np.zeros(Ug, I32)
        g_gated = np.zeros(Ug, I32)
        g_slack = np.zeros((Ug, R), I64)
        for row, da, dg, ds in gang_rows:
            g_assigned[row] += da
            g_gated[row] += dg
            g_slack[row] += ds
        q_idx = np.arange(Uq, dtype=I32)
        q_used = np.zeros((Uq, R), I64)
        q_count = np.zeros(Uq, I32)
        for row, du, dc in ns_rows:
            q_used[row] += du
            q_count[row] += dc
        return cls(g_idx, g_assigned, g_gated, g_slack, q_idx, q_used,
                   q_count)

    def as_args(self) -> tuple:
        return (self.g_idx, self.g_assigned, self.g_gated, self.g_slack,
                self.q_idx, self.q_used, self.q_count)

    def as_dict(self) -> dict:
        return {
            "g_idx": self.g_idx, "g_assigned": self.g_assigned,
            "g_gated": self.g_gated, "g_slack": self.g_slack,
            "q_idx": self.q_idx, "q_used": self.q_used,
            "q_count": self.q_count,
        }


def apply_side_deltas(tables: SideTables, g_idx, g_assigned, g_gated,
                      g_slack, q_idx, q_used, q_count) -> SideTables:
    """Fold one packed side-table delta batch into the resident gang/
    quota aggregates. Pure scatter-adds (a row the cycle left alone adds
    zero), mirroring `apply_node_deltas`'s
    discipline; the `tables` argument is donated at the jit boundary
    (`side_apply_program`) — callers rebind the resident carry from the
    result."""
    return tables.replace(
        gang_assigned=tables.gang_assigned.at[g_idx].add(g_assigned),
        gang_gated=tables.gang_gated.at[g_idx].add(g_gated),
        gang_slack=tables.gang_slack.at[g_idx].add(g_slack),
        quota_used=tables.quota_used.at[q_idx].add(q_used),
        ns_assigned=tables.ns_assigned.at[q_idx].add(q_count),
    )


# ---------------------------------------------------------------------------
# resident selector counts (ISSUE 32; docs/SERVING.md)
# ---------------------------------------------------------------------------


class SelectorDeltas:
    """Packed +-1 contributions to the resident selector tables: one row
    per (table row, domain) a window's binds and deletes touched, the host
    having summed what falls on the same cell. The rows of the three tables
    share one axis, in their order: the (TR, D) matching-pod counts first,
    then the (E, D) carriers of required anti-affinity terms, then the
    (E2, D) carriers of the score's symmetric terms, so a carrier's bind is
    one more row of the batch its labels' tracks are in. Padded with zero
    rows (a scatter-add of zero is a no-op) to `UsageDeltas.MIN_BUCKET`
    and its doublings: a zone-keyed track touches a handful of cells
    whatever the batch, a hostname-keyed one as many as the batch has pod
    events, and twice that where every pod carries a term, so a window's
    cells run from one to four thousand and the doublings keep that to
    three shapes (the 1,024-steps of `bucket_size` would make a shape of
    every thousand, some of them rare enough to compile in a window)."""

    __slots__ = ("track", "domain", "delta")

    def __init__(self, track, domain, delta):
        self.track = track
        self.domain = domain
        self.delta = delta

    @classmethod
    def pack(cls, cells: dict) -> "SelectorDeltas":
        """`cells`: {(row, domain code): signed count}."""
        K = UsageDeltas.MIN_BUCKET
        while K < len(cells):
            K *= 2
        track = np.zeros(K, I32)
        domain = np.zeros(K, I32)
        delta = np.zeros(K, I64)
        for j, ((t, d), count) in enumerate(cells.items()):
            track[j] = t
            domain[j] = d
            delta[j] = count
        return cls(track, domain, delta)

    def as_args(self) -> tuple:
        return (self.track, self.domain, self.delta)

    def as_dict(self) -> dict:
        return {"track": self.track, "domain": self.domain,
                "delta": self.delta}


def apply_selector_deltas(tables, track, domain, delta):
    """Fold one packed batch into the resident selector tables: `tables` is
    (track_base (TR, D), anti_count (E, D) | None, sym_base (E2, D) | None),
    a row of the batch falls on the table whose span of the shared row axis
    it is in, and each table takes a plain scatter-add, like the usage
    columns'. Returns the tables and `anti_count > 0`, the (E, D) presence
    the scan reads as `exist_anti_base` (None without the table): a delete
    lifts a block only when the last carrier of the domain leaves. `tables`
    is donated at the jit boundary (`selector_apply_program`)."""
    import jax.numpy as jnp

    out, first = [], 0
    for table in tables:
        if table is None:
            out.append(None)
            continue
        local = track - first
        mine = (local >= 0) & (local < table.shape[0])
        out.append(table.at[jnp.where(mine, local, 0), domain].add(
            jnp.where(mine, delta, 0)
        ))
        first += table.shape[0]
    return tuple(out), None if out[1] is None else out[1] > 0


#: process-wide memo keyed by sanitize mode: every `ServeEngine` (and a
#: chaos-harness crash restart, which builds a fresh one mid-run) shares
#: ONE jitted apply program per mode, so engine reconstruction never pays
#: a recompile for an already-warm shape
_APPLY_PROGRAMS: dict = {}
_COMPACT_PROGRAMS: dict = {}
_SIDE_PROGRAMS: dict = {}
_SELECTOR_PROGRAMS: dict = {}


def selector_apply_program():
    """The jitted selector-count apply program with the resident table
    DONATED: same constructor and memo discipline as
    `delta_apply_program`."""
    import jax

    from scheduler_plugins_tpu.utils import observability as obs
    from scheduler_plugins_tpu.utils import sanitize

    key = sanitize.enabled()
    if key in _SELECTOR_PROGRAMS:
        return _SELECTOR_PROGRAMS[key]
    if key:
        jitted = sanitize.checkified(
            apply_selector_deltas, program="serve_selector_apply"
        )
    else:
        jitted = jax.jit(apply_selector_deltas, donate_argnums=(0,))
    _SELECTOR_PROGRAMS[key] = obs.compile_watch(
        jitted, program="serve_selector_apply"
    )
    return _SELECTOR_PROGRAMS[key]


def side_apply_program():
    """The jitted side-table apply program with the resident carry
    DONATED — same constructor/memo discipline as `delta_apply_program`,
    registered with the AOT compile-readiness gate as
    `serving_side_apply`."""
    import jax

    from scheduler_plugins_tpu.utils import observability as obs
    from scheduler_plugins_tpu.utils import sanitize

    key = sanitize.enabled()
    if key in _SIDE_PROGRAMS:
        return _SIDE_PROGRAMS[key]
    if key:
        jitted = sanitize.checkified(
            apply_side_deltas, program="serve_side_apply"
        )
    else:
        jitted = jax.jit(apply_side_deltas, donate_argnums=(0,))
    _SIDE_PROGRAMS[key] = obs.compile_watch(
        jitted, program="serve_side_apply"
    )
    return _SIDE_PROGRAMS[key]


def node_compact_program():
    """The jitted row-compaction program with the resident carry DONATED
    (`StreamingServeEngine` node-delete path) — same constructor/memo
    discipline as `delta_apply_program`, registered with the AOT
    compile-readiness gate as `serving_node_compact`."""
    import jax

    from scheduler_plugins_tpu.utils import observability as obs
    from scheduler_plugins_tpu.utils import sanitize

    key = sanitize.enabled()
    if key in _COMPACT_PROGRAMS:
        return _COMPACT_PROGRAMS[key]
    if key:
        jitted = sanitize.checkified(
            compact_node_rows, program="serve_node_compact"
        )
    else:
        jitted = jax.jit(compact_node_rows, donate_argnums=(0,))
    _COMPACT_PROGRAMS[key] = obs.compile_watch(
        jitted, program="serve_node_compact"
    )
    return _COMPACT_PROGRAMS[key]


def delta_apply_program():
    """The jitted apply program with the resident carry DONATED — the
    serving engine's calling convention (rebind the carry from the
    result; GL006). One constructor shared by `ServeEngine` and the AOT
    compile-readiness gate (`tools/tpu_lower.py` serving_delta_apply) so
    the certified program is the shipped program, memoized process-wide
    per sanitize mode. Under `SPT_SANITIZE=1` the program is built
    checkify-instrumented with donation dropped, like every other
    donated jit in the repo."""
    import jax

    from scheduler_plugins_tpu.utils import observability as obs
    from scheduler_plugins_tpu.utils import sanitize

    key = sanitize.enabled()
    if key in _APPLY_PROGRAMS:
        return _APPLY_PROGRAMS[key]
    if key:
        jitted = sanitize.checkified(
            apply_node_deltas, program="serve_delta_apply"
        )
    else:
        jitted = jax.jit(apply_node_deltas, donate_argnums=(0,))
    _APPLY_PROGRAMS[key] = obs.compile_watch(
        jitted, program="serve_delta_apply"
    )
    return _APPLY_PROGRAMS[key]
