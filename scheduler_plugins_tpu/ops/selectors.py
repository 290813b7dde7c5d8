"""Framework-maintained selector/topology-domain carries.

Four live carries, kept in lockstep by ONE built-in commit step of the
solve (like the built-in capacity Reserve — never per-plugin, which would
double-apply when multiple consumers are enabled):

- `SolverState.sel_counts` (TR, N): node-level matching-pod counts, read
  by PodTopologySpread when its node-inclusion policies exclude some
  keyed node (`spread_needs_node_counts`); otherwise not materialized.
- `SolverState.sel_dom_counts` (TR, D): the same counts per topology
  domain — read by InterPodAffinity always (no node-inclusion policy)
  and by PodTopologySpread on its fast path.
- `SolverState.anti_domains` (E, D): anti-affinity domain presence bits.
- `SolverState.sym_counts` (E2, D): symmetric-score carrier counts
  (existing pods' preferred/required affinity terms per domain).

And their three NODE-SPACE VIEWS (`SolverState.sel_dom_view` (TR, N),
`anti_view` (E, N), `sym_view` (E2, N)). What the plugins need of a domain
table is, per node, "the value in this node's domain": an (N,)-wide gather
out of a (D,) row, which on a TPU costs ~25-37 us for each u32[5120]
whatever D is (PERF.md finding 35) — once a pod when done inside the scan.
So the sequential solve gathers ONCE, before its scan
(`attach_node_views`), carries the answer, and `commit_tracks` keeps it
current by compare: a placement on a node of domain d adds its increment
to every node whose code is d. Invariant, after every step:

    view[t, n] == table[t, code[topo[t], n]]   where code[topo[t], n] >= 0
    view[t, n] == 0 / False                    where node n lacks the key

Integer arithmetic in the tables' own dtypes, so every reader's verdict
and score is bit for bit the gather's. The (., D) tables stay the ground
truth, the solve's outputs, and what every reduction over domains reads
(the first pod's escape, the spread minimum); `sel_counts` is a different
quantity and has no view. Readers go through `domain_at`, which selects
rows of a view where the state carries one and gathers where it does not
(outside the sequential scan: explain, attribution, the wave solver's
vmapped re-filter, the lanes).

Tables come from `state.scheduling.SchedulingState`:
    pend_match (S, P)  pod q matches selector group s
    track_sel/track_topo (TR,)  track -> (selector group, topology key)
    topo_code (K, N)  node -> domain code under key k (-1 = key absent)
    exist_anti_{sel,topo} (E,), exist_anti_carrier (E, P)
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp


class ViewCodes(NamedTuple):
    """The (T, N) `topo_code` rows of each domain table's rows: a view's
    node n belongs to row t's domain `codes[t, n]`. Loop-invariant, so the
    scan takes them as operands (closed over), not as carries."""

    track: Optional[jnp.ndarray]  # topo_code[track_topo]      (TR, N)
    anti: Optional[jnp.ndarray]  # topo_code[exist_anti_topo]  (E, N)
    sym: Optional[jnp.ndarray]  # topo_code[sym_topo]          (E2, N)


def view_codes(sched) -> ViewCodes:
    def rows(topo):
        return None if topo is None else sched.topo_code[topo]

    return ViewCodes(
        rows(sched.track_topo), rows(sched.exist_anti_topo),
        rows(sched.sym_topo),
    )


def _gather_domains(table_rows, codes):
    """(T, N) `table_rows[t, codes[t, n]]`, 0 / False where `codes < 0`."""
    at = jnp.take_along_axis(table_rows, jnp.maximum(codes, 0), axis=1)
    return jnp.where(codes >= 0, at, jnp.zeros((), table_rows.dtype))


def domain_at(view, rows, table_rows, codes):
    """(T, N) value of a domain table's `rows` in each node's domain, 0 /
    False on a node without the row's key. `view` is the table's node-space
    view or None; `rows` (T,) its rows, None for all of them; `table_rows`
    (T, D) and `codes` (T, N) the same rows of the table and of
    `topo_code`, read only where there is no view."""
    if view is None:
        return _gather_domains(table_rows, codes)
    return view if rows is None else view[rows]


def has_domain_tables(sched) -> bool:
    """Whether a solve of this snapshot carries domain tables, and so (the
    sequential one) node-space views of them. Static: pytree structure."""
    return sched is not None and (
        sched.track_base is not None
        or sched.exist_anti_base is not None
        or sched.sym_base is not None
    )


def attach_node_views(state, sched):
    """(`state` with the node-space view of each domain table it carries,
    the views' `ViewCodes` for `commit_tracks`; None without a table): the
    gather every reader used to make at every pod, made once a solve.
    Called inside the traced solve, before the scan."""
    if not has_domain_tables(sched):
        return state, None
    codes = view_codes(sched)
    if state.sel_dom_counts is not None and sched.track_base is not None:
        state = state.replace(
            sel_dom_view=_gather_domains(state.sel_dom_counts, codes.track)
        )
    if state.anti_domains is not None and sched.exist_anti_sel is not None:
        state = state.replace(
            anti_view=_gather_domains(state.anti_domains, codes.anti)
        )
    if state.sym_counts is not None and sched.sym_sel is not None:
        state = state.replace(
            sym_view=_gather_domains(state.sym_counts, codes.sym)
        )
    return state, codes


def drop_node_views(state):
    """`state` without its views: they are derived, so a solve's result
    carries the tables only."""
    return state.replace(sel_dom_view=None, anti_view=None, sym_view=None)


def commit_tracks(state, sched, p, choice, codes=None):
    """Fold pod `p`'s placement on `choice` (-1 = none) into the carries:
    an indexed update of each (., D) table and, where the state carries its
    view, the same increment on every node of the chosen node's domain by
    compare against the view's code rows (the built-in capacity commit's
    idiom: no gather, no scatter). `codes` are `attach_node_views`'s,
    handed in by a scan so that they are gathered outside it; a row whose
    chosen domain is negative carries no increment."""
    if codes is None and (
        state.sel_dom_view is not None or state.anti_view is not None
        or state.sym_view is not None
    ):
        codes = view_codes(sched)
    if sched.track_base is not None and (
        state.sel_counts is not None or state.sel_dom_counts is not None
    ):
        inc = sched.pend_match[sched.track_sel, p] & (choice >= 0)  # (TR,)
        TR = sched.track_base.shape[0]
        if state.sel_counts is not None:
            state = state.replace(
                sel_counts=state.sel_counts.at[
                    jnp.arange(TR), jnp.maximum(choice, 0)
                ].add(inc.astype(state.sel_counts.dtype))
            )
        if state.sel_dom_counts is not None:
            # domain-level mirror (key-less nodes have no domain: dom < 0
            # contributes nothing)
            dom = sched.topo_code[sched.track_topo, choice]  # (TR,)
            inc_d = inc & (dom >= 0)
            state = state.replace(
                sel_dom_counts=state.sel_dom_counts.at[
                    jnp.arange(TR), jnp.maximum(dom, 0)
                ].add(inc_d.astype(state.sel_dom_counts.dtype))
            )
            if state.sel_dom_view is not None:
                hit = inc_d[:, None] & (codes.track == dom[:, None])
                state = state.replace(
                    sel_dom_view=state.sel_dom_view
                    + hit.astype(state.sel_dom_view.dtype)
                )
    if state.sym_counts is not None and sched.sym_sel is not None:
        dom = sched.topo_code[sched.sym_topo, choice]  # (E2,)
        add = jnp.where(
            (choice >= 0) & (dom >= 0), sched.sym_carrier[:, p], 0
        )
        E2 = state.sym_counts.shape[0]
        state = state.replace(
            sym_counts=state.sym_counts.at[
                jnp.arange(E2), jnp.maximum(dom, 0)
            ].add(add.astype(state.sym_counts.dtype))
        )
        if state.sym_view is not None:
            state = state.replace(
                sym_view=state.sym_view + jnp.where(
                    codes.sym == dom[:, None],
                    add[:, None].astype(state.sym_view.dtype), 0,
                )
            )
    if state.anti_domains is not None and sched.exist_anti_sel is not None:
        dom = sched.topo_code[sched.exist_anti_topo, choice]  # (E,)
        mark = (
            sched.exist_anti_carrier[:, p] & (choice >= 0) & (dom >= 0)
        )
        E = state.anti_domains.shape[0]
        state = state.replace(
            anti_domains=state.anti_domains.at[
                jnp.arange(E), jnp.maximum(dom, 0)
            ].max(mark)
        )
        if state.anti_view is not None:
            state = state.replace(
                anti_view=state.anti_view | (
                    mark[:, None] & (codes.anti == dom[:, None])
                )
            )
    return state


