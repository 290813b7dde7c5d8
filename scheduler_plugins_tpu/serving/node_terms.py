"""Resident node-term rows: NodeAffinity's tables across cycles.

A fresh `state.scheduling.build_scheduling` evaluates every unique
nodeSelector / node-affinity spec of the batch against every node, in
Python, every cycle, and throws the rows away: O(specs x nodes) on top of
the O(cluster) snapshot that a batch with such a spec used to force. Here
the rows live across cycles (docs/SERVING.md "Resident node-term rows"):

- `node_term_ok` (Tb, N) bool and `pref_score` (Ub, N) int64, host tables
  with a staged copy: one row a spec (`state.scheduling.node_spec_keys`:
  nodeSelector AND the OR of the required terms; the preferred terms with
  their weights), evaluated ONCE over the nodes, O(N) label tests through
  the fresh build's own functions, the first time a pending pod names it.
  Row 0 is the all-true / all-zero row of a pod without a spec, so a
  batch's padded slots and its plain pods index it; the spec axes sit on
  `bucket_size` buckets, so specs that come and go give the solve no shape
  each (an unused row is the trivial one again).
- a row OUTLIVES the pods that named it: a workload's replicas share a spec
  and arrive over many cycles, and under open arrivals the batch empties
  between ticks. Rows go only when their axis would otherwise pass its
  bucket: then every row that no batch has gathered for `IDLE_CYCLES`
  cycles is released, the tables are laid out again on the bucket that
  holds what is left, and `epoch` moves (`scheduler_serve_node_term_
  rebases_total`). A hot set of any size is therefore held; a cold row
  costs one O(N) evaluation when it comes back.
- a pod's two row indices sit in its record (`PodRecord.node_rows`, beside
  its keys `node_keys`), valid for the tables' `epoch`: a cycle reads two
  integers a pod.
- a node that arrives writes its column in every held row, a known node
  whose labels changed re-evaluates its column: O(held specs), staged
  again only when a cell moved. A node delete, a rebase and a grown node
  bucket drop the rows (`invalidate`); they are built again on first use.

What a cycle builds, O(batch), is the two index columns `pod_node_term` and
`pod_pref`; `scheduling_state` lays them and the staged tables over the
selector tables' `SchedulingState`, or over the trivial toleration tables
where the batch has no selector table.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from scheduler_plugins_tpu.state import scheduling as S
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size

I32 = np.int32
I64 = np.int64

#: cycles (of those whose batch held a node term) a row may go ungathered
#: before it is released, and then only when its axis is full
IDLE_CYCLES = 256


class _Rows:
    """One table of spec rows over the nodes; row 0 is the trivial row."""

    def __init__(self, field: str, dtype, trivial, row_of, cell_of):
        self.field = field  # the table's name in `SchedulingState`
        self.dtype, self.trivial = dtype, trivial
        self.row_of, self.cell_of = row_of, cell_of
        self.clear()

    def clear(self) -> None:
        self.rows: dict = {}  # spec key -> row, from 1
        self.specs: list = [None]  # by row: the spec (`S.NodeSpec`)
        self.table: Optional[np.ndarray] = None  # (bucket, N), host
        self.used: Optional[np.ndarray] = None  # (bucket,) last cycle read

    def lay_out(self, N: int, keep: list, room: int) -> None:
        """The table anew on the bucket that holds the trivial row, the
        rows of `keep` (their cells carried over) and `room` more."""
        bucket = bucket_size(1 + len(keep) + room)
        table = np.full((bucket, N), self.trivial, self.dtype)
        used = np.zeros(bucket, I64)
        specs: list = [None]
        for new, key in enumerate(keep, start=1):
            old = self.rows[key]
            table[new] = self.table[old]
            used[new] = self.used[old]
            specs.append(self.specs[old])
        self.rows = {key: row for row, key in enumerate(keep, start=1)}
        self.specs, self.table, self.used = specs, table, used

    def add(self, key, spec, nodes, clock: int) -> None:
        row = len(self.specs)
        self.rows[key] = row
        self.specs.append(spec)
        self.table[row] = self.row_of(spec, nodes, self.table.shape[1])
        self.used[row] = clock

    def write_column(self, node, slot: int) -> bool:
        """The node's cell of every held row; True where one moved."""
        moved = False
        for row in range(1, len(self.specs)):
            cell = self.cell_of(self.specs[row], node)
            if self.table[row, slot] != cell:
                self.table[row, slot] = cell
                moved = True
        return moved

    def expected(self, nodes) -> np.ndarray:
        """The table from a fresh evaluation of every held spec."""
        want = np.full(self.table.shape, self.trivial, self.dtype)
        for row in range(1, len(self.specs)):
            want[row] = self.row_of(self.specs[row], nodes, want.shape[1])
        return want


class ResidentNodeTerms:
    """The node-term rows of one `ServeEngine`. `engine` lends its staging
    seam (`_stage_pods`), its records and its node rows (`_names`)."""

    def __init__(self, engine):
        self._engine = engine
        #: moves whenever a row index stops meaning what it meant; a
        #: record's `node_rows` are valid for the epoch they name
        self.epoch = 0
        self._term = _Rows("node_term_ok", bool, True, S.node_term_row,
                           S._node_filter_matches)
        self._pref = _Rows("pref_score", I64, 0, S.node_pref_row,
                           S.node_pref_score)
        self.reset()

    def reset(self) -> None:
        self.invalidate()
        self._clock = 0

    def invalidate(self) -> None:
        """The node rows moved under the tables (a node deleted or
        compacted away, a rebase, a grown bucket), or the engine let go of
        its store: drop every row; a spec is evaluated again on first use."""
        self.epoch += 1
        self._term.clear()
        self._pref.clear()
        self._staged: Optional[dict] = None
        self._stale = True
        #: the last batch that held a node term: its pods and the rows they
        #: were given, for the anti-entropy check
        self._last: Optional[tuple] = None

    @property
    def held(self) -> int:
        """Spec rows held, both tables."""
        return len(self._term.rows) + len(self._pref.rows)

    # -- O(held specs) upkeep, from the drained events ----------------------
    def node_column(self, node, slot: int, is_new: bool) -> None:
        """A node upsert: its column of every held row, from its labels
        and name as they are now."""
        if not self.held:
            return
        if slot >= self._term.table.shape[1]:
            self.invalidate()  # past the bucket: `_grow` follows
            return
        moved = self._term.write_column(node, slot)
        moved = self._pref.write_column(node, slot) or moved
        if moved:
            self._stale = True
        if moved or is_new:
            obs.metrics.inc(obs.SERVE_NODE_TERM_COLUMNS)

    # -- the per-cycle O(batch) part ----------------------------------------
    def _carrying(self, pending) -> list:
        """[(slot in the batch, record), ...] of the pods that carry a node
        term. A pod whose record is missing or stale (the batch of a
        rebase) is asked directly, and lowered only where it carries one."""
        records = self._engine._records
        out = []
        for i, pod in enumerate(pending):
            rec = records.get(pod.uid)
            if rec is None or rec.pod is not pod:
                if S.node_spec_keys(pod) is None:
                    continue
                rec = self._engine._record(pod, "batch")
            elif rec.node_keys is None:
                continue
            out.append((i, rec))
        return out

    def scheduling_state(self, pending, P: int, N: int, base):
        """This cycle's `SchedulingState`: `base` (the selector tables', or
        None) where no pod of the batch carries a node term, else the
        resident rows and the batch's two index columns laid over it."""
        carrying = self._carrying(pending)
        if not carrying:
            return base
        with obs.tracer.span(
            "ServeRefresh/node_terms", tid="serve", pending=len(carrying)
        ):
            pod_node_term, pod_pref = self._index(carrying, P, N)
            if self._stale:
                self._staged = self._engine._stage_pods({
                    rows.field: rows.table.copy()
                    for rows in (self._term, self._pref)
                })
                self._stale = False
            tables = dict(
                self._staged,
                **self._engine._stage_pods(dict(
                    pod_node_term=pod_node_term, pod_pref=pod_pref,
                )),
            )
        if base is not None:
            return base.replace(**tables)
        trivial = self._engine._selectors._trivial(N, P)
        return S.SchedulingState(
            **tables,
            **{k: trivial[k] for k in ("tol_ok", "tol_prefer", "pod_tol")},
        )

    def _index(self, carrying, P: int, N: int) -> tuple:
        """(pod_node_term, pod_pref) (P,) int32, off the records; a spec
        seen for the first time is interned and its row evaluated."""
        self._clock += 1
        term, pref = self._term, self._pref
        if term.table is None:
            term.lay_out(N, [], 0)
            pref.lay_out(N, [], 0)
            self._stale = True
        missing = [
            rec for _i, rec in carrying
            if rec.node_rows is None or rec.node_rows[0] != self.epoch
        ]
        if missing:
            self._intern(carrying, missing)
        pod_node_term = np.zeros(P, I32)
        pod_pref = np.zeros(P, I32)
        epoch = self.epoch
        for i, rec in carrying:
            held = rec.node_rows
            if held is None or held[0] != epoch:
                term_key, pref_key = rec.node_keys
                held = rec.node_rows = (
                    epoch,
                    0 if term_key is None else term.rows[term_key],
                    0 if pref_key is None else pref.rows[pref_key],
                )
            pod_node_term[i], pod_pref[i] = held[1], held[2]
        term.used[np.unique(pod_node_term)] = self._clock
        pref.used[np.unique(pod_pref)] = self._clock
        slots = [i for i, _rec in carrying]
        self._last = (
            [rec.pod for _i, rec in carrying],
            pod_node_term[slots], pod_pref[slots],
        )
        return pod_node_term, pod_pref

    def _intern(self, carrying, missing) -> None:
        """Rows for the specs of `missing` the tables do not hold yet; an
        axis that would pass its bucket is laid out again first."""
        nodes = None
        for rows, which in ((self._term, 0), (self._pref, 1)):
            new = {}
            for rec in missing:
                key = rec.node_keys[which]
                if key is not None and key not in rows.rows:
                    new.setdefault(key, rec.pod)
            if not new:
                continue
            if len(rows.specs) + len(new) > rows.table.shape[0]:
                self._make_room(rows, carrying, which, len(new))
            if nodes is None:
                cluster_nodes = self._engine._cluster.nodes
                nodes = [cluster_nodes[name] for name in self._engine._names]
            for key, pod in new.items():
                rows.add(key, S.node_spec(pod), nodes, self._clock)
            obs.metrics.inc(obs.SERVE_NODE_TERM_ROWS, len(new))
            self._stale = True

    def _make_room(self, rows: _Rows, carrying, which: int, room: int
                   ) -> None:
        """`rows` is full: release what no batch has gathered for
        `IDLE_CYCLES` cycles (never a row of this batch), and lay the table
        out again on the bucket that holds the rest and `room` more."""
        named = {rec.node_keys[which] for _i, rec in carrying}
        keep = [
            key for key, row in rows.rows.items()
            if key in named or self._clock - rows.used[row] <= IDLE_CYCLES
        ]
        rows.lay_out(rows.table.shape[1], keep, room)
        self.epoch += 1
        obs.metrics.inc(obs.SERVE_NODE_TERM_REBASES)

    # -- anti-entropy ---------------------------------------------------------
    def divergence(self, cluster, names) -> Optional[str]:
        """The resident rows against the store, or None: every held row,
        on the host and as staged, against a fresh evaluation of its spec
        over the store's nodes; and, for the pods of the last batch that
        are still pending, the rows they gathered against the rows a fresh
        build of those pods gives (`state.scheduling.node_term_tables`:
        from the pods' own specs, not the held ones)."""
        if not self.held:
            return None
        nodes = [cluster.nodes[name] for name in names]
        staged = None if self._stale else self._staged
        for rows in (self._term, self._pref):
            want = rows.expected(nodes)
            if not np.array_equal(rows.table, want) or (
                staged is not None
                and not np.array_equal(np.asarray(staged[rows.field]), want)
            ):
                return "node-terms"
        if self._last is None:
            return None
        pods, term_rows, pref_rows = self._last
        still = [
            j for j, pod in enumerate(pods)
            if pod.node_name is None and cluster.pods.get(pod.uid) is pod
        ]
        if not still:
            return None
        N = self._term.table.shape[1]
        node_term_ok, pod_node_term, pref_score, pod_pref = (
            S.node_term_tables(nodes, [pods[j] for j in still], N, len(still))
        )
        if not (
            np.array_equal(
                self._term.table[term_rows[still]],
                node_term_ok[pod_node_term],
            )
            and np.array_equal(
                self._pref.table[pref_rows[still]], pref_score[pod_pref]
            )
        ):
            return "node-terms"
        return None
