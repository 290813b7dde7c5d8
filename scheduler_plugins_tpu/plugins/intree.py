"""In-tree companion plugins: NodeAffinity, TaintToleration,
PodTopologySpread and InterPodAffinity.

These are upstream kube-scheduler plugins (k8s.io/kubernetes
pkg/scheduler/framework/plugins/{nodeaffinity,tainttoleration,
podtopologyspread,interpodaffinity}), NOT part of /root/reference — but real
profiles enable
them alongside the reference's plugins, so drop-in completeness requires
them (docs/PARITY.md "companion plugins", SURVEY.md §7 build plan item 2's
extension-point trait layer).

All matching work happens host-side at snapshot build
(`state.scheduling.build_scheduling` interns unique specs and evaluates each
against every node once); the jitted tensor methods are row gathers and
small segment sums.

- NodeAffinity: Filter = nodeSelector AND required-affinity terms; Score =
  sum of matching preferred-term weights, default-normalized (upstream
  nodeaffinity.go Score/NormalizeScore).
- TaintToleration: Filter = no untolerated NoSchedule/NoExecute taint;
  Score = count of untolerated PreferNoSchedule taints, reverse-normalized
  (upstream tainttoleration.go CountIntolerableTaintsPreferNoSchedule).
- PodTopologySpread: live per-selector NODE-level counts carried through
  the solve (`SolverState.sel_counts`); Filter enforces DoNotSchedule
  constraints (matchNum + self − globalMin <= maxSkew over the constraint
  key's domains); Score sums ScheduleAnyway match counts,
  reverse-normalized. minDomains, matchLabelKeys and nodeAffinityPolicy/
  nodeTaintsPolicy are honored: counts aggregate into domains per (pod,
  constraint) under the node-inclusion policies.
"""

from __future__ import annotations

import jax.numpy as jnp

from scheduler_plugins_tpu.framework.plugin import Plugin
from scheduler_plugins_tpu.ops.normalize import default_normalize
from scheduler_plugins_tpu.ops.selectors import domain_at
from scheduler_plugins_tpu.api import events as ev


class NodeAffinity(Plugin):
    name = "NodeAffinity"

    def events_to_register(self):
        return (ev.NODE_ADD, ev.NODE_UPDATE)

    def __init__(self, added_affinity=None):
        #: NodeAffinityArgs.AddedAffinity (upstream): per-profile extra
        #: REQUIRED node-selector terms (OR over terms) ANDed into every
        #: pod's node affinity — cluster operators use it to fence a
        #: profile to a node subset. Accepts NodeSelectorTerm objects or
        #: the wire shape (NodeSelectorTerm.from_wire).
        from scheduler_plugins_tpu.api.objects import NodeSelectorTerm

        self.added_affinity = [
            t if isinstance(t, NodeSelectorTerm)
            else NodeSelectorTerm.from_wire(t)
            for t in added_affinity or []
        ]
        self._added_mask = None

    def prepare_cluster(self, meta, cluster):
        if not self.added_affinity or cluster is None:
            self._added_mask = None
            return
        import numpy as np

        ok = np.ones(max(len(meta.node_names), 1), bool)
        for i, name in enumerate(meta.node_names):
            node = cluster.nodes.get(name)
            ok[i] = node is not None and any(
                t.matches(node) for t in self.added_affinity
            )
        self._added_mask = jnp.asarray(ok)

    def aux(self):
        return self._added_mask

    def filter(self, state, snap, p):
        base = None
        if snap.scheduling is not None:
            s = snap.scheduling
            base = s.node_term_ok[s.pod_node_term[p]]
        added = getattr(self, "_aux", None)
        if added is not None:
            N = snap.num_nodes
            padded = jnp.zeros(N, bool).at[: added.shape[0]].set(added)
            base = padded if base is None else base & padded
        return base

    def score(self, state, snap, p):
        if snap.scheduling is None:
            return None
        s = snap.scheduling
        return s.pref_score[s.pod_pref[p]]

    def normalize(self, scores, feasible):
        return default_normalize(scores, feasible)


class PodTopologySpread(Plugin):
    """maxSkew spreading over topology domains.

    Live counts are (TR, N) per (selector-track, NODE), carried through the
    solve and aggregated into (CT, D) domain counts per pod under the
    node-inclusion policies (read in node space where every domain holds
    one node); the check per node is then

        matchNum(node) = dc[constraint, domain(node)]
        verdict(node)  = has_key(node)
                         & (matchNum + selfMatch - globalMin <= maxSkew)

    with globalMin the minimum count over the constraint's ELIGIBLE domains
    (0 when fewer than minDomains exist). DoNotSchedule constraints filter;
    ScheduleAnyway constraints score (summed match counts, fewer = better).
    """

    name = "PodTopologySpread"

    def events_to_register(self):
        return (ev.POD_ADD, ev.POD_UPDATE, ev.POD_DELETE, ev.NODE_ADD,
                ev.NODE_UPDATE)

    #: the filter reads the carried live counts — later placements change
    #: earlier verdicts, and domains SPAN nodes, so the batched path also
    #: re-validates placements sequentially (`validate_at`)
    state_dependent_filter = True

    def _counts(self, state, snap):
        """(TR, N) live node-level counts — materialized only when some
        eligibility row actually excludes a keyed node."""
        if state is not None and state.sel_counts is not None:
            return state.sel_counts
        return snap.scheduling.track_node_base

    def _dom_counts(self, state, snap):
        """(TR, D) live domain mirror — the O(1)-gather fast path."""
        if state is not None and state.sel_dom_counts is not None:
            return state.sel_dom_counts
        return snap.scheduling.track_base

    def _constraint_state(self, state, snap, p):
        """Per-constraint live tensors shared by filter/score/validate:
        (CT, D) eligible-node domain counts, the global minimum (minDomains
        applied), and the (CT, N) code/has lookup rows.

        Node inclusion mirrors upstream: a node's pods count toward a
        constraint's domains/minimum only when the node carries all the
        pod's constraint keys OF THE SAME CLASS (hard keys in the
        PreFilter counting, soft keys in PreScore), matches the pod's
        nodeSelector/required affinity (nodeAffinityPolicy Honor — the
        default), and tolerates its NoSchedule/NoExecute taints
        (nodeTaintsPolicy Honor; default Ignore). The masks are fully
        static, so they are host-precomputed interned rows
        (`spread_elig`); when NO row excludes a keyed node
        (`spread_needs_node_counts` False — the common case) the counting
        is provably identical to the (TR, D) domain mirror and this
        reduces to row gathers.

        Where every domain of every held key holds one node
        (`spread_node_domains`: hostname keys) a domain's count is its
        node's own, so the counts stay in node space: `dc` is then (CT, N),
        the count in each node's domain, and no scatter builds a table."""
        s = snap.scheduling
        code = s.topo_code[s.spread_topo[p]]  # (CT, N)
        has = s.topo_has[s.spread_topo[p]]  # (CT, N)
        if s.spread_needs_node_counts:
            counts = self._counts(state, snap)  # (TR, N)
            dcn = counts[s.spread_track[p]]  # (CT, N)
            elig = s.spread_elig[s.spread_elig_idx[p]] & (code >= 0)
            if s.spread_node_domains:
                # an ineligible node's one-node domain counts nothing and
                # does not exist
                dc = jnp.where(elig, dcn, 0)  # (CT, N)
                exists = elig
            else:
                CT, N = code.shape
                D = s.domain_exists.shape[1]
                rows = jnp.broadcast_to(jnp.arange(CT)[:, None], (CT, N))
                col = jnp.maximum(code, 0)
                dc = jnp.zeros((CT, D), counts.dtype).at[rows, col].add(
                    jnp.where(elig, dcn, 0)
                )
                exists = jnp.zeros((CT, D), bool).at[rows, col].max(elig)
        else:
            dc = self._dom_counts(state, snap)[s.spread_track[p]]  # (CT, D)
            exists = s.domain_exists[s.spread_topo[p]]  # (CT, D)
        big = jnp.int64(1) << 62
        # no eligible domain -> minimum stays `big` and the skew check
        # passes trivially (upstream CriticalPaths stay MaxInt32)
        minm = jnp.min(jnp.where(exists, dc, big), axis=1)  # (CT,)
        # minDomains (upstream minMatchNum): fewer eligible domains than
        # required -> the global minimum is treated as 0
        dn = jnp.sum(exists, axis=1)  # (CT,)
        md = s.spread_min_domains[p]
        minm = jnp.where((md > 0) & (dn < md), 0, minm)
        return s, dc, minm, code, has

    def _match_at(self, state, s, p, dc, code):
        """(CT, N) count in each node's domain, 0 where the node lacks the
        key: rows of the mirror's node-space view
        (`SolverState.sel_dom_view`) where the solve carries one. The
        policy branch's `dc` is counted per pod under the pod's own
        eligibility row, which no shared view can serve: it gathers, where
        `dc` is not in node space already."""
        if s.spread_node_domains:
            return dc
        view = None
        if state is not None and not s.spread_needs_node_counts:
            view = state.sel_dom_view
        return domain_at(view, s.spread_track[p], dc, code)

    def filter(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return None
        s, dc, minm, code, has = self._constraint_state(state, snap, p)
        match_at = self._match_at(state, s, p, dc, code)  # (CT, N)
        selfm = s.spread_self[p][:, None].astype(jnp.int64)
        ok = match_at + selfm - minm[:, None] <= s.spread_max_skew[p][:, None]
        applies = (s.spread_mask[p] & s.spread_hard[p])[:, None]
        # a node missing the constraint's key is unschedulable for
        # DoNotSchedule constraints (upstream PreFilter node filtering)
        verdict = jnp.where(applies, has & ok, True)
        return jnp.all(verdict, axis=0)

    def score(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return None
        s, dc, _, code, has = self._constraint_state(state, snap, p)
        match_at = self._match_at(state, s, p, dc, code)
        applies = (s.spread_mask[p] & ~s.spread_hard[p])[:, None] & has
        return jnp.sum(jnp.where(applies, match_at, 0), axis=0)

    def normalize(self, scores, feasible):
        # fewer matching pods in the node's domains = better spread
        return default_normalize(scores, feasible, reverse=True)

    def validate_at(self, state, snap, p, node):
        """Hard-constraint re-check at one node against the live carry —
        used by the batched solver's post-wave demotion scan (domain
        constraints span nodes, so the same-node wave guard cannot see
        them)."""
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return jnp.bool_(True)
        s, dc, minm, code, has = self._constraint_state(state, snap, p)
        code_n = code[:, node]  # (CT,)
        has_n = has[:, node]
        if s.spread_node_domains:
            match_at = dc[:, node]
        else:
            match_at = jnp.take_along_axis(
                dc, jnp.maximum(code_n, 0)[:, None], axis=1
            ).squeeze(1)
        selfm = s.spread_self[p].astype(jnp.int64)
        ok = match_at + selfm - minm <= s.spread_max_skew[p]
        applies = s.spread_mask[p] & s.spread_hard[p]
        return jnp.all(jnp.where(applies, has_n & ok, True))


class InterPodAffinity(Plugin):
    """Required/preferred pod (anti-)affinity over topology domains.

    All selector matching is host-precomputed into the track tables
    (state.scheduling); the live (TR, D) counts and (E, D) anti-domain
    presence bits are carried through the solve, so in-cycle placements are
    visible exactly as the reference's one-pod-per-cycle loop would see
    them. Checks per (pod, node):

    - required affinity term: node has the key AND (matching pods exist in
      the node's domain OR nobody matches cluster-wide and the pod matches
      its own term — the upstream first-pod escape).
    - required anti term (the incoming pod's own): no matching pod in the
      node's domain.
    - SYMMETRY: a node is blocked when its domain hosts a pod CARRYING a
      required anti term whose selector matches the incoming pod
      (upstream existingAntiAffinityCounts).
    - preferred terms score weight x domain match count (anti negative),
      min-max normalized.

    namespaceSelector resolves host-side against the cluster's Namespace
    objects (empty selector = all namespaces). Score is fully symmetric
    (upstream PreScore): besides the incoming pod's own preferred terms,
    every EXISTING pod's preferred (anti-)term whose selector matches the
    incoming pod adds ±weight to the existing pod's domain, and its
    REQUIRED affinity terms add `hard_pod_affinity_weight` (upstream
    HardPodAffinityWeight arg, default 1); carrier counts are carried live
    (`SolverState.sym_counts`) so in-cycle placements contribute.
    """

    name = "InterPodAffinity"
    state_dependent_filter = True

    def events_to_register(self):
        return (ev.POD_ADD, ev.POD_UPDATE, ev.POD_DELETE, ev.NODE_ADD,
                ev.NODE_UPDATE, ev.NAMESPACE_ADD, ev.NAMESPACE_UPDATE)

    def __init__(self, hard_pod_affinity_weight: int = 1,
                 ignore_preferred_terms_of_existing_pods: bool = False):
        if not 0 <= hard_pod_affinity_weight <= 100:
            raise ValueError(
                "hardPodAffinityWeight must be in [0, 100], got "
                f"{hard_pod_affinity_weight}"
            )
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self.ignore_preferred = ignore_preferred_terms_of_existing_pods

    def static_key(self):
        return (self.hard_pod_affinity_weight, self.ignore_preferred)

    def _counts(self, state, snap):
        """(TR, D) domain-level counts — affinity has no node-inclusion
        policy, so it reads the pre-aggregated mirror (O(1) row gathers
        instead of per-pod node->domain scatters)."""
        if state is not None and state.sel_dom_counts is not None:
            return state.sel_dom_counts
        return snap.scheduling.track_base

    def _anti_domains(self, state, snap):
        if state is not None and state.anti_domains is not None:
            return state.anti_domains
        return snap.scheduling.exist_anti_base

    def _sym_counts(self, state, snap):
        if state is not None and state.sym_counts is not None:
            return state.sym_counts
        return snap.scheduling.sym_base

    def filter(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.aff_track is None:
            return None
        counts = self._counts(state, snap)
        # the counts' node-space view, where the solve carries one
        view = None if state is None else state.sel_dom_view
        N = snap.num_nodes
        verdict = jnp.ones(N, bool)

        # required affinity
        code = s.topo_code[s.aff_topo[p]]  # (AT, N)
        has = s.topo_has[s.aff_topo[p]]
        dc = counts[s.aff_track[p]]  # (AT, D)
        exists = s.domain_exists[s.aff_topo[p]]
        total = jnp.sum(jnp.where(exists, dc, 0), axis=1)  # (AT,)
        match_at = domain_at(view, s.aff_track[p], dc, code)
        ok = has & (
            (match_at > 0)
            | ((total == 0) & s.aff_self[p])[:, None]
        )
        verdict &= jnp.all(
            jnp.where(s.aff_mask[p][:, None], ok, True), axis=0
        )

        # the incoming pod's own required anti terms
        codeb = s.topo_code[s.anti_topo[p]]
        hasb = s.topo_has[s.anti_topo[p]]
        dcb = counts[s.anti_track[p]]  # (BT, D)
        match_b = domain_at(view, s.anti_track[p], dcb, codeb)
        okb = ~hasb | (match_b == 0)
        verdict &= jnp.all(
            jnp.where(s.anti_mask[p][:, None], okb, True), axis=0
        )

        # symmetry: carriers of matching anti terms block the domain
        if s.exist_anti_sel is not None:
            blocked = domain_at(
                None if state is None else state.anti_view, None,
                self._anti_domains(state, snap),  # (E, D)
                s.topo_code[s.exist_anti_topo],  # (E, N)
            )
            m = s.exist_anti_match[:, p]  # (E,)
            verdict &= ~jnp.any(m[:, None] & blocked, axis=0)
        return verdict

    def score(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.waff_track is None:
            return None
        counts = self._counts(state, snap)
        code = s.topo_code[s.waff_topo[p]]  # (WT, N)
        has = s.topo_has[s.waff_topo[p]]
        match_at = domain_at(
            None if state is None else state.sel_dom_view,
            s.waff_track[p], counts[s.waff_track[p]], code,
        )  # (WT, N)
        contrib = jnp.where(
            s.waff_mask[p][:, None] & has,
            s.waff_weight[p][:, None] * match_at,
            0,
        )
        total = jnp.sum(contrib, axis=0)
        if s.sym_sel is not None:
            # symmetric part: existing carriers' terms matching THIS pod
            at = domain_at(
                None if state is None else state.sym_view, None,
                self._sym_counts(state, snap),  # (E2, D)
                s.topo_code[s.sym_topo],  # (E2, N)
            )
            w_eff = jnp.where(
                s.sym_hard,
                self.hard_pod_affinity_weight * s.sym_weight,
                0 if self.ignore_preferred else s.sym_weight,
            )  # (E2,)
            m = s.pend_match[s.sym_sel, p]  # (E2,)
            total = total + jnp.sum(
                jnp.where(m[:, None], w_eff[:, None] * at, 0), axis=0
            )
        return total

    def normalize(self, scores, feasible):
        from scheduler_plugins_tpu.ops.normalize import minmax_normalize

        return minmax_normalize(scores, feasible)

    def validate_at(self, state, snap, p, node):
        """Single-node hard re-check against the live carry (batched-path
        demotion scan) — O(terms) gathers."""
        s = snap.scheduling
        if s is None or s.aff_track is None:
            return jnp.bool_(True)
        counts = self._counts(state, snap)
        ok = jnp.bool_(True)

        code = s.topo_code[s.aff_topo[p], node]  # (AT,)
        has = s.topo_has[s.aff_topo[p], node]
        dc = counts[s.aff_track[p]]  # (AT, D)
        exists = s.domain_exists[s.aff_topo[p]]
        total = jnp.sum(jnp.where(exists, dc, 0), axis=1)
        match_at = jnp.take_along_axis(
            dc, jnp.maximum(code, 0)[:, None], axis=1
        ).squeeze(1)
        aff_ok = has & (
            (match_at > 0) | ((total == 0) & s.aff_self[p])
        )
        ok &= jnp.all(jnp.where(s.aff_mask[p], aff_ok, True))

        codeb = s.topo_code[s.anti_topo[p], node]
        hasb = s.topo_has[s.anti_topo[p], node]
        dcb = counts[s.anti_track[p]]
        match_b = jnp.take_along_axis(
            dcb, jnp.maximum(codeb, 0)[:, None], axis=1
        ).squeeze(1)
        ok &= jnp.all(
            jnp.where(s.anti_mask[p], ~hasb | (match_b == 0), True)
        )

        if s.exist_anti_sel is not None:
            domains = self._anti_domains(state, snap)
            codee = s.topo_code[s.exist_anti_topo, node]  # (E,)
            blocked = (
                jnp.take_along_axis(
                    domains, jnp.maximum(codee, 0)[:, None], axis=1
                ).squeeze(1)
                & (codee >= 0)
            )
            ok &= ~jnp.any(s.exist_anti_match[:, p] & blocked)
        return ok


class TaintToleration(Plugin):
    name = "TaintToleration"

    def events_to_register(self):
        return (ev.NODE_ADD, ev.NODE_UPDATE)

    def filter(self, state, snap, p):
        if snap.scheduling is None:
            return None
        s = snap.scheduling
        return s.tol_ok[s.pod_tol[p]]

    def score(self, state, snap, p):
        if snap.scheduling is None:
            return None
        s = snap.scheduling
        return s.tol_prefer[s.pod_tol[p]]

    def normalize(self, scores, feasible):
        # fewer intolerable PreferNoSchedule taints wins
        return default_normalize(scores, feasible, reverse=True)
