"""Resident selector counts: PodTopologySpread's and InterPodAffinity's
tables across cycles.

A fresh `state.scheduling.build_scheduling` recounts every assigned pod
against every selector group, in Python, whenever a batch carries a spread
constraint: O(assigned x tracks) a cycle, 50,000 pods at 5,000 nodes. Here
the O(assigned) part of those tables lives across cycles, as the node
columns do (docs/SERVING.md "Resident selector counts"):

- `track_base` (TR, D) int64, on the device: the assigned pods each track's
  selector matches, by topology domain. A bind or a delete of a pod whose
  labels match a track is a +-1 on (track, domain(node)), packed beside
  the usage deltas (`serving.deltas.SelectorDeltas`) and folded by one
  donated scatter-add (`selector_apply_program`).
- `anti_count` (E, D) and `sym_base` (E2, D) int64, on the device (ISSUE
  34; docs/SERVING.md "Resident affinity terms"): the assigned CARRIERS of
  each required anti-affinity term, and of each of the score's symmetric
  terms, by the domain of their node under the term's key. A carrier's bind
  or delete is a +-1 (its term count for `sym_base`) in the same batch and
  the same scatter-add; `exist_anti_base`, the presence the scan reads, is
  `anti_count > 0`, so a delete lifts a block only when the last carrier of
  the domain leaves.
- `topo_code` / `topo_has` (K, N), `domain_exists` (K, D), `track_sel` /
  `track_topo` (TR,), `exist_anti_sel` / `exist_anti_topo` (E,), `sym_*`
  (E2,): host tables, staged again only when they change. A node that
  arrives writes one column.

The track, key, domain and selector axes are padded to `bucket_size`
buckets, so that selector groups that come and go give the solve no shape
each; a padded track points at a selector row no pod is in and counts
nothing. Rows keep their meaning from cycle to cycle: the axes are the
store's registry (`Cluster.selectors`) in its own order, taken when the
tables are built, and built again (O(assigned), counted as
`scheduler_serve_selector_rebases_total`) only when the SET of tracks the
store's pods declare moves, a tracked label of a known node changes, or a
key or domain outgrows its bucket.

- `spread_elig` (EL, N) bool and `track_node_base` (TR, N) int64 (docs/
  SERVING.md "Resident toleration rows and node-inclusion counts"): the
  eligibility row of each spread constraint the batches have named,
  interned by (its key, the keys of its class, the pod's node-term row,
  its toleration row, the two policies) with row 0 all-true, made by the
  fresh build's own function from `topo_has` and the resident node-term
  and toleration rows, and kept while those rows mean what they meant; a
  pod's row indices sit in its record. Where some held row leaves out a
  node that carries its constraint's key, the solve counts that
  constraint's domains by node under the pod's row
  (`spread_needs_node_counts`): the (track, node) counts are then resident
  on the device beside `track_base`, a bind or a delete one more +-1 of
  the same batch and the same donated program. Otherwise neither is
  staged and the batch keeps the trivial row. Where besides no domain of
  any held key holds more than one node (`domain_nodes`: a hostname key),
  the solve reads those counts in node space (`spread_node_domains`).

What a cycle still builds, O(batch), is `scheduling_state`: `pend_match`,
the (P, CT) `spread_*` rows and, where the store's pods declare pod
(anti-)affinity terms, the (P, AT/BT/WT) `aff_*` / `anti_*` / `waff_*` rows
and the (E, P) / (E2, P) carrier and match rows, through the functions the
fresh build uses (`state.scheduling.spread_rows`, `affinity_rows`).
"""

from __future__ import annotations

import warnings
from typing import Optional

import numpy as np

from scheduler_plugins_tpu.serving import deltas as D
from scheduler_plugins_tpu.state import scheduling as S
from scheduler_plugins_tpu.utils import observability as obs
from scheduler_plugins_tpu.utils.intmath import bucket_size

I32 = np.int32
I64 = np.int64


#: eligibility rows held at most: past it, before the next batch, they are
#: all made again from the batches that name them
ELIG_ROWS_MAX = 256


class ResidentSelectors:
    """The selector tables of one `ServeEngine`. `engine` lends its staging
    seams (`_stage_args`, `_stage_pods`)."""

    def __init__(self, engine):
        self._engine = engine
        self._apply = D.selector_apply_program()
        self._elig_gen = 0
        self.reset()

    def reset(self) -> None:
        #: the tables are built and in step with the store's events
        self.live = False
        self.version = -1  # the registry's, when they were built
        self.axes: Optional[S.SelectorAxes] = None
        self.track_keys: list = []  # the registry's keys, in row order
        self.domain_values: list = []  # per key: {label value: code}
        self.domain_nodes = None  # (K, D) int32 nodes in each domain
        self.topo_code = None  # (K, N) int32, host
        self.topo_has = None  # (K, N) bool, host
        self.track_sel = None
        self.track_topo = None
        self.sel_rows = 0  # the selector axis's bucket
        self.track_base = None  # (TR, D) int64, device, donated
        #: (E, D) int64 carriers of each required anti term, device, donated;
        #: None while the store's pods carry no such term
        self.anti_count = None
        self.exist_anti_base = None  # (E, D) bool, device: anti_count > 0
        self.sym_base = None  # (E2, D) int64 carriers of each score term
        self.anti_keys: list = []  # the registry's E keys, in row order
        self.sym_keys: list = []  # the registry's E2 keys, in row order
        #: E / E2 key -> (its row on the batch's shared row axis, key code)
        self._carrier_rows: dict = {}
        self._terms_static: dict = {}  # the (E,) and (E2,) host tables
        self._static = None  # the host tables, staged
        self._static_stale = True
        self._cells: dict = {}  # (row, domain) -> signed count, undrained
        self._rows = 0
        self._carried = 0  # of which on carrier rows
        self._pod_tracks: dict = {}  # labels key -> ((track, key), ...)
        self._match_rows: dict = {}  # labels key -> (S,) bool
        self._constants: dict = {}  # (N, P) -> the trivial node tables
        self.last_packed: Optional[dict] = None
        #: moves when a node column of `topo_code` is written
        self._topo_version = 0
        self._clear_elig()

    def invalidate(self) -> None:
        """The rows moved under the tables (a node row compacted away, the
        base dropped): build them again before they are served."""
        self.live = False
        self._cells.clear()
        self._rows = self._carried = 0
        self._clear_elig()

    def _clear_elig(self) -> None:
        """Drop the eligibility rows and the (track, node) counts: the rows
        are made again from the batches that name them, the counts from
        the store when a row needs them."""
        #: bumped with every clear: a record's `spread_elig` is valid for
        #: the generation it names
        self._elig_gen += 1
        self._elig_keys: dict = {}  # eligibility key -> row, from 1
        self._elig_rows: list = []  # by row - 1: (N,) bool
        self._elig_excl: list = []  # by row - 1: leaves out a keyed node
        self._elig_staged = None  # the (EL, N) table, staged
        #: (node-term epoch, some node tainted): the rows' indices hold
        self._elig_basis = None
        #: (node-term version, topology version): the rows' cells hold
        self._elig_content = None
        #: (TR, N) int64 the assigned pods each track matches by node,
        #: device, donated; None unless some held row needs it
        self.track_node_base = None
        self._node_cells: dict = {}  # (track, node row) -> signed count
        self._node_rows = 0

    # -- the O(assigned) build ------------------------------------------
    def ensure(self, cluster, names, npad: int) -> None:
        if not cluster.selectors.tracks:
            if self.axes is not None:
                self.reset()
            return
        if self.live and self.version == cluster.selectors.version:
            return
        self.rebuild(cluster, names, npad)

    def rebuild(self, cluster, names, npad: int) -> None:
        """Everything from the store, through the fresh build's own
        functions: O(assigned x tracks), the rare path."""
        import jax.numpy as jnp

        registry = cluster.selectors
        if not registry.tracks:
            self.reset()
            return
        with obs.tracer.span(
            "ServeRefresh/selector_rebuild", tid="serve",
            tracks=len(registry.tracks),
        ):
            axes = registry.axes()
            TR = bucket_size(len(axes.tracks))
            Sp = bucket_size(len(axes.sel_objs) + 1)
            K = bucket_size(len(axes.key_names))
            nodes = [cluster.nodes[name] for name in names]
            topo_code, topo_has, domain_values = S.topology_tables(
                axes.key_names, nodes, npad, K=K
            )
            Dp = bucket_size(max(len(dv) for dv in domain_values) or 1)
            domain_nodes = S.domain_node_counts(topo_code, Dp)
            slots = {name: i for i, name in enumerate(names)}
            assigned = cluster._assigned_pods()
            _, track_base = S.track_counts(
                axes, assigned, slots, topo_code, TR, npad, Dp,
                per_node=False,
            )
            self._rebuild_carriers(
                registry, axes, assigned, slots, topo_code, TR, Sp, Dp
            )
            # a padded track is in a selector row that holds no pod
            track_sel = np.full(TR, Sp - 1, I32)
            track_topo = np.zeros(TR, I32)
            for (s, k), t in axes.tracks.items():
                track_sel[t] = s
                track_topo[t] = k
            self.axes = axes
            self.track_keys = list(registry.tracks)
            self.domain_values = domain_values
            self.domain_nodes = domain_nodes
            self.topo_code, self.topo_has = topo_code, topo_has
            self.track_sel, self.track_topo = track_sel, track_topo
            self.sel_rows = Sp
            self.track_base = jnp.asarray(track_base)
            self._static_stale = True
            self._cells.clear()
            self._rows = self._carried = 0
            self._pod_tracks.clear()
            self._match_rows.clear()
            self._clear_elig()
            self.version = registry.version
            self.live = True
            obs.metrics.inc(obs.SERVE_SELECTOR_REBASES)

    def _rebuild_carriers(self, registry, axes, assigned, slots, topo_code,
                          TR: int, Sp: int, Dp: int) -> None:
        """The E and E2 halves of `rebuild`: the terms the assigned pods
        carry, counted by domain through the fresh build's functions."""
        import jax.numpy as jnp

        self.anti_keys = list(registry.anti_terms)
        self.sym_keys = list(registry.sym_terms)
        self.anti_count = self.exist_anti_base = self.sym_base = None
        self._carrier_rows = {}
        self._terms_static = {}
        if not (self.anti_keys or self.sym_keys):
            return
        E = bucket_size(len(self.anti_keys), minimum=1) if (
            self.anti_keys) else 0
        anti, sym = S.assigned_carriers(axes, assigned)
        if self.anti_keys:
            terms = S.anti_term_tables(axes, E=E, pad_sel=Sp - 1)
            anti_count = S.carrier_counts(
                anti, slots, topo_code, terms["exist_anti_topo"], E, Dp
            )
            self.anti_count = jnp.asarray(anti_count)
            self.exist_anti_base = jnp.asarray(anti_count > 0)
            self._terms_static.update(terms)
            for e, key in enumerate(self.anti_keys):
                self._carrier_rows[key] = (TR + e, axes.keys[key[2]])
        if self.sym_keys:
            E2 = bucket_size(len(self.sym_keys), minimum=1)
            terms = S.sym_term_tables(axes, E2=E2, pad_sel=Sp - 1)
            self.sym_base = jnp.asarray(S.carrier_counts(
                sym, slots, topo_code, terms["sym_topo"], E2, Dp
            ))
            self._terms_static.update(terms)
            for e2, key in enumerate(self.sym_keys):
                self._carrier_rows[key] = (TR + E + e2, axes.keys[key[2]])

    def grow(self, npad: int) -> None:
        """The node bucket grew: the (K, N) tables follow it."""
        if self.topo_code is None or npad <= self.topo_code.shape[1]:
            return
        pad = npad - self.topo_code.shape[1]
        self.topo_code = np.pad(
            self.topo_code, ((0, 0), (0, pad)), constant_values=-1
        )
        self.topo_has = np.pad(self.topo_has, ((0, 0), (0, pad)))
        self._static_stale = True
        self._constants.clear()
        self._clear_elig()  # the node axis moved under them

    # -- O(changed) upkeep, from the drained events -----------------------
    def node_row(self, node, slot: int, is_new: bool) -> None:
        """A node upsert: a new node writes its column of `topo_code`; a
        known one whose tracked labels read as before changes nothing; one
        whose tracked label changed moves its pods' counts to another
        domain, which only the store can say: build again."""
        if not self.live:
            return
        if slot >= self.topo_code.shape[1]:
            self.live = False  # past the bucket: `grow`, then build again
            return
        codes = np.full(self.topo_code.shape[0], -1, I32)
        for k, name in enumerate(self.axes.key_names):
            value = node.labels.get(name)
            if value is None:
                continue
            values = self.domain_values[k]
            code = values.get(value)
            if code is None:
                if len(values) >= self.domain_nodes.shape[1]:
                    self.live = False  # the domain axis is full
                    return
                code = values[value] = len(values)
            codes[k] = code
        if not is_new:
            if np.array_equal(codes, self.topo_code[:, slot]):
                return
            self.live = False
            return
        self.topo_code[:, slot] = codes
        self.topo_has[:, slot] = codes >= 0
        for k in np.flatnonzero(codes >= 0):
            self.domain_nodes[k, codes[k]] += 1
        self._static_stale = True
        self._topo_version += 1
        obs.metrics.inc(obs.SERVE_TOPO_ROWS)

    def pod_event(self, pod, slot: int, sign: int) -> None:
        """A pod took (`sign` +1) or gave up (-1) capacity on the node of
        row `slot`: +-1 on every track its labels match, in the node's
        domain under the track's key."""
        if not self.live:
            return
        key = S.labels_key(pod)
        hits = self._pod_tracks.get(key)
        if hits is None:
            axes = self.axes
            hits = self._pod_tracks[key] = tuple(
                (t, k) for (s, k), t in axes.tracks.items()
                if S._sel_matches(axes.sel_objs[s][1], axes.sel_objs[s][0],
                                  pod)
            )
        cells = self._cells
        for t, k in hits:
            d = int(self.topo_code[k, slot])
            if d >= 0:
                cells[(t, d)] = cells.get((t, d), 0) + sign
                self._rows += 1
        if self.track_node_base is not None:
            node_cells = self._node_cells
            for t, _k in hits:
                node_cells[(t, slot)] = node_cells.get((t, slot), 0) + sign
                self._node_rows += 1
        if self._carrier_rows and S.has_affinity_terms(pod):
            self._carrier_event(pod, slot, sign)

    def _carrier_event(self, pod, slot: int, sign: int) -> None:
        """+-1 on the row of every term the pod carries, in the node's
        domain under the term's key: a carrier took or gave up its node."""
        keys = S.affinity_term_keys(pod)
        rows = None if keys is None else [
            self._carrier_rows.get(key) for key in keys[1] + keys[2]
        ]
        if rows is None or None in rows:
            # a term the axes do not hold (its scope moves with the
            # Namespaces, or the registry moved): build again
            self.live = False
            return
        cells = self._cells
        for row, k in rows:
            d = int(self.topo_code[k, slot])
            if d >= 0:
                cells[(row, d)] = cells.get((row, d), 0) + sign
                self._carried += 1

    def apply(self) -> None:
        """Fold the window's cells into the resident counts."""
        self.last_packed = None
        if not (self._cells or self._node_cells):
            return
        cells, self._cells = self._cells, {}
        node_cells, self._node_cells = self._node_cells, {}
        rows, self._rows = self._rows, 0
        carried, self._carried = self._carried, 0
        node_rows, self._node_rows = self._node_rows, 0
        if not self.live:
            return
        tables = (self.track_base, self.anti_count, self.sym_base)
        cells = {cell: n for cell, n in cells.items() if n}
        if self.track_node_base is not None and node_cells:
            # the (TR, N) counts take the rows after the three tables'
            first = sum(t.shape[0] for t in tables if t is not None)
            cells.update(
                ((first + t, slot), n)
                for (t, slot), n in node_cells.items() if n
            )
            tables += (self.track_node_base,)
        packed = D.SelectorDeltas.pack(cells)
        with warnings.catch_warnings():
            # CPU backends never donate and list every buffer
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            tables, self.exist_anti_base = self._apply(
                tables, *self._engine._stage_args(packed.as_args()),
            )
        self.track_base, self.anti_count, self.sym_base = tables[:3]
        if len(tables) > 3:
            self.track_node_base = tables[3]
        self.last_packed = packed.as_dict()
        obs.metrics.inc(obs.SERVE_SELECTOR_ROWS, rows)
        if carried:
            obs.metrics.inc(obs.SERVE_AFFINITY_CARRIER_ROWS, carried)
        if node_rows:
            obs.metrics.inc(obs.SERVE_SELECTOR_NODE_ROWS, node_rows)

    # -- the per-cycle O(batch) tables ------------------------------------
    def _trivial(self, N: int, P: int) -> dict:
        """The node-filter tables of a batch without node selectors,
        affinity or taints (`compatible` lets no other through): one
        all-true / all-zero row each."""
        held = self._constants.get((N, P))
        if held is None:
            held = self._constants[(N, P)] = self._engine._stage_pods(dict(
                node_term_ok=np.ones((1, N), bool),
                pod_node_term=np.zeros(P, I32),
                pref_score=np.zeros((1, N), I64),
                pod_pref=np.zeros(P, I32),
                tol_ok=np.ones((1, N), bool),
                tol_prefer=np.zeros((1, N), I64),
                pod_tol=np.zeros(P, I32),
                spread_elig=np.ones((1, N), bool),
            ))
        return held

    def scheduling_state(self, pending, P: int, N: int, rows=None):
        """This cycle's `SchedulingState` over the resident tables, or None
        where no pod of the batch carries a spread constraint and no pod of
        the store a pod (anti-)affinity term (an assigned carrier blocks a
        plain pod its term matches: the fresh build's own rule). `rows` is
        the batch's `serving.node_terms.BatchRows` (None where it gathers
        no node-term or toleration row): the eligibility rows are made of
        them. Call after `ensure`."""
        spread_any = any(p.topology_spread for p in pending)
        if not self.live or not (spread_any or self._carrier_rows):
            return None
        axes = self.axes
        stage = self._engine._stage_pods
        batch: dict = {}
        node_counts: dict = {}
        if spread_any:
            widest = max(len(p.topology_spread) for p in pending)
            batch = S.spread_rows(
                axes, pending, P, CT=bucket_size(widest, minimum=1)
            )
            elig_idx = self._elig_index(pending, batch["spread_track"].shape,
                                        rows)
            if any(self._elig_excl):
                node_counts = self._node_count_tables(N)
                batch["spread_elig_idx"] = elig_idx
            else:
                self.track_node_base = None  # no held row needs them
                self._node_cells.clear()
                batch["spread_elig_idx"] = np.zeros(elig_idx.shape, I32)
        pend_carriers = pending_sym = ()
        if self._carrier_rows:
            with obs.tracer.span(
                "ServeRefresh/affinity", tid="serve", pending=len(pending)
            ):
                rows, pend_carriers = S.affinity_rows(
                    axes, pending, P, **self._term_widths(pending)
                )
                batch.update(rows)
                if self.sym_keys:
                    pending_sym = [
                        (i, e2, c) for i, pod in enumerate(pending)
                        for e2, c in S.pod_sym_rows(axes, pod).items()
                    ]
        if (len(axes.tracks), len(axes.anti_terms), len(axes.sym_terms)) != (
            len(self.track_keys), len(self.anti_keys), len(self.sym_keys)
        ):
            # `ensure` follows the registry, which holds every pod's tracks
            # and terms from `add_pod` on; serving without the row would
            # drop the constraint in silence
            raise RuntimeError(
                "a pending pod names a track or a term the resident "
                "selector tables do not hold"
            )
        pend_match = S.pend_match_rows(
            axes.sel_objs, pending, P, S=self.sel_rows,
            memo=self._match_rows,
        )
        batch["pend_match"] = pend_match
        if self._static_stale:
            self._static = stage(dict(
                topo_code=self.topo_code.copy(),
                topo_has=self.topo_has.copy(),
                domain_exists=self.domain_nodes > 0,
                track_sel=self.track_sel,
                track_topo=self.track_topo,
                **self._terms_static,
            ))
            self._static_stale = False
        resident = dict(track_base=self.track_base)
        with obs.tracer.span("ServeRefresh/affinity", tid="serve"):
            if self.anti_keys:
                batch.update(S.anti_batch_rows(
                    pend_carriers, pend_match,
                    self._terms_static["exist_anti_sel"], P,
                ))
                resident["exist_anti_base"] = self.exist_anti_base
            if self.sym_keys:
                batch["sym_carrier"] = S.sym_batch_rows(
                    pending_sym, self.sym_base.shape[0], P
                )
                resident["sym_base"] = self.sym_base
        return S.SchedulingState(
            **dict(self._trivial(N, P), **node_counts), **self._static,
            **stage(batch), **resident,
            spread_needs_node_counts=bool(node_counts),
            spread_node_domains=S.one_node_domains(
                bool(node_counts), self.domain_nodes
            ),
        )

    # -- the eligibility rows ---------------------------------------------
    def _elig_index(self, pending, shape, rows) -> np.ndarray:
        """(P, CT) int32: each constraint's row of the held eligibility
        table, off the pod's record; a key seen for the first time makes
        its row (`state.scheduling.spread_elig_row`)."""
        self._elig_sync()
        if len(self._elig_rows) > ELIG_ROWS_MAX:
            basis, content = self._elig_basis, self._elig_content
            self._clear_elig()  # every record asks again
            self._elig_basis, self._elig_content = basis, content
        term = None if rows is None else rows.term
        tol = None if rows is None else rows.tol
        records = self._engine._records
        gen = self._elig_gen
        idx = np.zeros(shape, I32)
        for i, pod in enumerate(pending):
            if not pod.topology_spread:
                continue
            rec = records.get(pod.uid)
            if rec is not None and rec.pod is not pod:
                rec = None
            held = None if rec is None else rec.spread_elig
            if held is None or held[0] != gen:
                t = 0 if term is None else int(term[i])
                u = 0 if tol is None else int(tol[i])
                held = (gen, tuple(
                    self._elig_row((
                        own, keys,
                        t if tsc.node_affinity_policy != "Ignore" else 0,
                        u if tsc.node_taints_policy == "Honor" else 0,
                    ))
                    for tsc, (own, keys) in zip(
                        pod.topology_spread, S.spread_class_keys(pod)
                    )
                ))
                if rec is not None:
                    rec.spread_elig = held
            idx[i, :len(held[1])] = held[1]
        return idx

    def _elig_sync(self) -> None:
        """The held rows against what they are made of: all dropped where a
        row index they name stopped meaning what it meant (the node-term
        epoch moved, the first taint came or the last went), made again
        where a cell of one moved (a node column written)."""
        terms = self._engine._node_terms
        basis = (terms.epoch, bool(terms.tainted))
        content = (terms.version, self._topo_version)
        if basis != self._elig_basis:
            if self._elig_basis is not None:
                self._clear_elig()
            self._elig_basis, self._elig_content = basis, content
        elif content != self._elig_content:
            self._elig_content = content
            for key, row in self._elig_keys.items():
                made, excl = self._make_elig(key)
                if not np.array_equal(made, self._elig_rows[row - 1]):
                    self._elig_rows[row - 1] = made
                    self._elig_staged = None
                    obs.metrics.inc(obs.SERVE_SPREAD_ELIG_ROWS)
                self._elig_excl[row - 1] = excl

    def _elig_row(self, key) -> int:
        row = self._elig_keys.get(key)
        if row is None:
            made, excl = self._make_elig(key)
            self._elig_rows.append(made)
            self._elig_excl.append(excl)
            row = self._elig_keys[key] = len(self._elig_rows)
            self._elig_staged = None
            obs.metrics.inc(obs.SERVE_SPREAD_ELIG_ROWS)
        return row

    def _make_elig(self, key, topo_has=None, topo_code=None) -> tuple:
        """(row, leaves out a keyed node) of an eligibility key (own key,
        class keys, node-term row, toleration row; 0 for none), from
        `topo_has` / `topo_code` (the held ones unless given) and the
        resident node-term and toleration rows."""
        own, keys, term, tol = key
        topo_has = self.topo_has if topo_has is None else topo_has
        topo_code = self.topo_code if topo_code is None else topo_code
        terms = self._engine._node_terms
        codes = self.axes.keys
        row = S.spread_elig_row(
            [codes[k] for k in keys], topo_has,
            terms._term.table[term] if term else None,
            terms._tol.table[tol] if tol else None,
            True, True,
        )
        return row, S.excludes_keyed(row, topo_code[codes[own]])

    def _node_count_tables(self, N: int) -> dict:
        """The eligibility table, staged, and the resident (TR, N) counts,
        counted from the store the first time a held row needs them."""
        import jax.numpy as jnp

        if self.track_node_base is None:
            cluster = self._engine._cluster
            slots = {name: i for i, name in enumerate(self._engine._names)}
            counts, _ = S.track_counts(
                self.axes, cluster._assigned_pods(), slots, self.topo_code,
                self.track_base.shape[0], N, self.track_base.shape[1],
            )
            self.track_node_base = jnp.asarray(counts)
            self._node_cells.clear()
        if self._elig_staged is None:
            table = np.ones((bucket_size(1 + len(self._elig_rows)), N), bool)
            for row, made in enumerate(self._elig_rows, start=1):
                table[row] = made
            self._elig_staged = self._engine._stage_pods(table)
        return dict(spread_elig=self._elig_staged,
                    track_node_base=self.track_node_base)

    def _term_widths(self, pending) -> dict:
        """The AT / BT / WT axes of the batch's own terms, on buckets. An
        axis of a kind of term that no pod of the STORE carries has no row
        at all: the scan then gathers nothing for it, where a row that is
        masked off would cost it an (N,) gather out of a (D,) table at
        every step; and the shape stays the store's, not the batch's."""
        def pad(held: bool, count) -> int:
            if not held:
                return 0
            widest = max((count(p) for p in pending), default=1)
            return bucket_size(widest, minimum=1)

        hard = any(key[4] for key in self.sym_keys)
        weighted = any(not key[4] for key in self.sym_keys)
        return dict(
            AT=pad(hard, lambda p: len(p.pod_affinity_required)),
            BT=pad(bool(self.anti_keys),
                   lambda p: len(p.pod_anti_affinity_required)),
            WT=pad(weighted, lambda p: len(p.pod_affinity_preferred)
                   + len(p.pod_anti_affinity_preferred)),
        )

    # -- anti-entropy -----------------------------------------------------
    def expected_counts(self, cluster) -> dict:
        """{(track key, domain value): count} from the store's objects and
        the registry's selectors alone: nothing of the delta path, the axes
        or the codes is read."""
        tracks = [
            (key, key[0], selector, key[2])
            for key, (_refs, selector) in cluster.selectors.tracks.items()
        ]
        by_labels: dict = {}
        counts: dict = {}
        nodes = cluster.nodes
        reserved = cluster.reserved
        for pod in cluster.pods.values():
            held = pod.node_name or reserved.get(pod.uid)
            if held is None:
                continue
            node = nodes.get(held)
            if node is None:
                continue
            lk = S.labels_key(pod)
            hits = by_labels.get(lk)
            if hits is None:
                hits = by_labels[lk] = [
                    (key, topo) for key, scope, selector, topo in tracks
                    if S._sel_matches(selector, scope, pod)
                ]
            for key, topo in hits:
                value = node.labels.get(topo)
                if value is not None:
                    cell = (key, value)
                    counts[cell] = counts.get(cell, 0) + 1
        return counts

    def expected_carriers(self, cluster) -> dict:
        """{(E or E2 key, domain value): carriers} from the pods that carry
        a term (`Cluster._affinity_spec_pods`) and their nodes' labels."""
        counts: dict = {}
        for uid in cluster._affinity_spec_pods:
            pod = cluster.pods[uid]
            node = cluster.nodes.get(
                pod.node_name or cluster.reserved.get(uid)
            )
            if node is None:
                continue
            keys = S.affinity_term_keys(pod)
            if keys is None:
                continue  # nothing of it is interned (`unscoped`)
            for key in keys[1] + keys[2]:
                value = node.labels.get(key[2])
                if value is not None:
                    counts[(key, value)] = counts.get((key, value), 0) + 1
        return counts

    def _decoded(self, table, rows) -> Optional[dict]:
        """{(key, domain value): count} of a device table through its rows
        [(row, key, key code), ...]; None where a cell that no row and
        domain owns is not zero."""
        host = np.asarray(table)
        seen = np.zeros(host.shape, bool)
        out: dict = {}
        for row, key, k in rows:
            for value, code in self.domain_values[k].items():
                seen[row, code] = True
                if host[row, code]:
                    out[(key, value)] = int(host[row, code])
        return None if (host[~seen] != 0).any() else out

    def divergence(self, cluster, names) -> Optional[str]:
        """The resident tables against the store, or None. Tables that are
        about to be built again (not live, or the registry moved) hold
        nothing that will be served."""
        if not self.live or self.version != cluster.selectors.version:
            return None
        axes = self.axes
        if self._decoded(self.track_base, [
            (t, self.track_keys[t], k) for (_s, k), t in axes.tracks.items()
        ]) != self.expected_counts(cluster):
            return "selector-counts"
        if self._carrier_rows:
            carried: dict = {}
            for table, keys in ((self.anti_count, self.anti_keys),
                                (self.sym_base, self.sym_keys)):
                if table is None:
                    continue
                held = self._decoded(table, [
                    (row, key, self._carrier_rows[key][1])
                    for row, key in enumerate(keys)
                ])
                if held is None:
                    return "affinity-carriers"
                carried.update(held)
            if carried != self.expected_carriers(cluster) or (
                self.anti_count is not None and not np.array_equal(
                    np.asarray(self.exist_anti_base),
                    np.asarray(self.anti_count) > 0,
                )
            ):
                return "affinity-carriers"
        code = np.full(self.topo_code.shape, -1, I32)
        for slot, name in enumerate(names):
            labels = cluster.nodes[name].labels
            for k, key in enumerate(axes.key_names):
                value = labels.get(key)
                if value is not None:
                    code[k, slot] = self.domain_values[k].get(value, -2)
        domain_nodes = S.domain_node_counts(code, self.domain_nodes.shape[1])
        if not (
            np.array_equal(code, self.topo_code)
            and np.array_equal(code >= 0, self.topo_has)
            and np.array_equal(domain_nodes, self.domain_nodes)
        ):
            return "selector-topology"
        if self._static is not None and not self._static_stale:
            staged = self._static
            if not (
                np.array_equal(np.asarray(staged["topo_code"]), code)
                and np.array_equal(
                    np.asarray(staged["domain_exists"]), domain_nodes > 0
                )
            ):
                return "selector-topology"
        return self._elig_divergence(code) or self._node_count_divergence(
            cluster, names
        )

    def _elig_divergence(self, code) -> Optional[str]:
        """Every held eligibility row, on the host and as staged, made
        again from the store's topology and the node-term and toleration
        rows (which `ResidentNodeTerms.divergence` holds to the store).
        Rows about to be dropped or made again hold nothing that will be
        served."""
        terms = self._engine._node_terms
        if not self._elig_keys or self._elig_basis != (
            terms.epoch, bool(terms.tainted)
        ) or self._elig_content != (terms.version, self._topo_version):
            return None
        staged = None if self._elig_staged is None else np.asarray(
            self._elig_staged
        )
        for key, row in self._elig_keys.items():
            made, excl = self._make_elig(key, code >= 0, code)
            if not np.array_equal(made, self._elig_rows[row - 1]) or (
                excl != self._elig_excl[row - 1]
            ) or (staged is not None and not np.array_equal(
                staged[row], made
            )):
                return "spread-elig"
        return None

    def _node_count_divergence(self, cluster, names) -> Optional[str]:
        """The resident (track, node) counts against the store's pods,
        counted by node as the fresh build counts them."""
        if self.track_node_base is None:
            return None
        axes = self.axes
        tracks = [(t, *axes.sel_objs[s]) for (s, _k), t in axes.tracks.items()]
        want = np.zeros(self.track_node_base.shape, I64)
        slots = {name: i for i, name in enumerate(names)}
        by_labels: dict = {}
        for pod in cluster.pods.values():
            i = slots.get(pod.node_name or cluster.reserved.get(pod.uid))
            if i is None:
                continue
            lk = S.labels_key(pod)
            hits = by_labels.get(lk)
            if hits is None:
                hits = by_labels[lk] = [
                    t for t, scope, selector in tracks
                    if S._sel_matches(selector, scope, pod)
                ]
            for t in hits:
                want[t, i] += 1
        if not np.array_equal(np.asarray(self.track_node_base), want):
            return "track-node-counts"
        return None
