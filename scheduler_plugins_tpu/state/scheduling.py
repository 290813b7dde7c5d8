"""In-tree scheduling-spec tensors: taints/tolerations, node affinity, and
pod-label selector/topology-domain counting (topology spread, pod affinity).

The upstream kube-scheduler plugins NodeAffinity, TaintToleration,
PodTopologySpread and InterPodAffinity are not part of the reference repo,
but every real KubeSchedulerConfiguration profile combines the reference's
plugins with them (docs/PARITY.md "companion plugins"). Their semantics are
label/taint matching — string work that does not belong on the TPU. The
TPU-first formulation:

- intern each pod's node-filter spec (nodeSelector + required node affinity)
  and toleration set into a small set of UNIQUE specs (workload replicas
  share specs), evaluate each unique spec against every node ONCE host-side
  (numpy bools), and hand the solver dense lookup tables:

      node_term_ok  (T+1, N) bool   required-affinity verdict per spec
      pref_score    (U+1, N) int64  summed weights of matching preferred terms
      tol_ok        (T2, N) bool    no untolerated NoSchedule/NoExecute taint
      tol_prefer    (T2, N) int64   untolerated PreferNoSchedule taint count

  The per-pod Filter/Score inside the jitted solve is then a single row
  gather — O(1) per (pod, node) regardless of expression complexity.

- intern the pod-label selectors of spread constraints / affinity terms into
  S unique (namespace-scope, selector) groups and the topology keys into K
  codes; count matching ASSIGNED pods per (group, node) once host-side
  (`sel_base`), and record which PENDING pods match each group
  (`pend_match`) so the solver can carry live counts through in-cycle
  placements (`SolverState.sel_counts`). Per-domain aggregation is then a
  segment-sum over `topo_code` rows inside the jitted solve.

Row T (pad row) of `node_term_ok` is all-true: pods with no node constraint
index it. `pref_score` row U is all-zero. Toleration sets always index a
real row (the empty set is a legitimate set that tolerates nothing).
"""

from __future__ import annotations

import collections
from typing import Optional, Sequence

import numpy as np
from flax import struct

from scheduler_plugins_tpu.api.objects import (
    LabelSelector,
    LabelSelectorRequirement,
    Node,
    Pod,
)
from scheduler_plugins_tpu.utils import observability as obs

I64 = np.int64
I32 = np.int32

#: Static scheduling-table bases with a LIVE SolverState carry counterpart
#: (pytree path relative to the snapshot root -> carry field name) — the
#: selector/topology-domain counts seeded host-side and then carried through
#: in-cycle placements. Companion map to
#: `state.snapshot.CARRY_COUNTERPARTS`; consumed by `tools/jaxpr_audit.py`
#: rule JA001 (a compiled solve must not derive live counts from these
#: static bases while the carry is dead). The three domain carries'
#: node-space views (`SolverState.sel_dom_view` / `anti_view` / `sym_view`)
#: are DERIVED from the carries inside the solve
#: (`ops.selectors.attach_node_views`) and have no base here: nothing
#: static could be read in their place.
TRACK_CARRY_COUNTERPARTS = {
    ".scheduling.track_node_base": "sel_counts",
    ".scheduling.track_base": "sel_dom_counts",
    ".scheduling.exist_anti_base": "anti_domains",
    ".scheduling.sym_base": "sym_counts",
}


@struct.dataclass
class SchedulingState:
    """Dense lookup tables for the in-tree companion plugins."""

    node_term_ok: np.ndarray  # (T+1, N) bool
    pod_node_term: np.ndarray  # (P,) int32 row index (T = unconstrained)
    pref_score: np.ndarray  # (U+1, N) int64
    pod_pref: np.ndarray  # (P,) int32 row index (U = no preferences)
    tol_ok: np.ndarray  # (T2, N) bool
    tol_prefer: np.ndarray  # (T2, N) int64
    pod_tol: np.ndarray  # (P,) int32 row index
    # --- selector/topology-domain counting (spread + inter-pod affinity);
    # None when no pending pod carries such constraints. A "track" is a
    # unique (selector group, topology key) pair; live counts are carried
    # per (track, domain) — (TR, D) — so the per-pod checks and per-
    # placement commits are O(constraints x domains), never O(N) ----------
    pend_match: Optional[np.ndarray] = None  # (S, P) bool pod in sel group
    topo_code: Optional[np.ndarray] = None  # (K, N) int32 domain code (-1)
    topo_has: Optional[np.ndarray] = None  # (K, N) bool key present
    domain_exists: Optional[np.ndarray] = None  # (K, D) bool
    track_sel: Optional[np.ndarray] = None  # (TR,) int32 selector group
    track_topo: Optional[np.ndarray] = None  # (TR,) int32 key code
    #: (TR, N) int64 matching ASSIGNED pods per NODE. Node-level (not
    #: domain-level) so PodTopologySpread's nodeAffinityPolicy /
    #: nodeTaintsPolicy can exclude ineligible nodes' pods per (pod,
    #: constraint) at aggregation time.
    track_node_base: Optional[np.ndarray] = None
    #: (TR, D) the same counts per topology domain (nodes with the key
    #: only) — InterPodAffinity's O(1)-gather view
    track_base: Optional[np.ndarray] = None
    # per-pod spread constraints, padded to CT
    spread_track: Optional[np.ndarray] = None  # (P, CT) int32 track index
    spread_topo: Optional[np.ndarray] = None  # (P, CT) int32 key code
    spread_max_skew: Optional[np.ndarray] = None  # (P, CT) int64
    spread_hard: Optional[np.ndarray] = None  # (P, CT) bool DoNotSchedule
    spread_self: Optional[np.ndarray] = None  # (P, CT) bool pod matches own sel
    spread_mask: Optional[np.ndarray] = None  # (P, CT) bool
    #: (P, CT) int64 minDomains (0 = unset): when fewer ELIGIBLE domains
    #: than this exist, the global minimum is treated as 0 (upstream
    #: podtopologyspread minMatchNum)
    spread_min_domains: Optional[np.ndarray] = None
    #: (P, CT) bool nodeAffinityPolicy == Honor: only nodes matching the
    #: pod's nodeSelector/required affinity count toward domains/minimum
    spread_policy_affinity: Optional[np.ndarray] = None
    #: (P, CT) bool nodeTaintsPolicy == Honor: only nodes whose
    #: NoSchedule/NoExecute taints the pod tolerates count
    spread_policy_taints: Optional[np.ndarray] = None
    #: (EL, N) bool interned node-eligibility rows (class-keys x policies),
    #: fully static -> precomputed host-side; (P, CT) row index
    spread_elig: Optional[np.ndarray] = None
    spread_elig_idx: Optional[np.ndarray] = None
    #: STATIC python bool (not a pytree leaf): True only when some (pod,
    #: constraint) eligibility row actually excludes a node that carries
    #: the constraint's key. False -> the spread plugin reads the O(1)
    #: (TR, D) domain mirror and the (TR, N) node carry is not materialized
    spread_needs_node_counts: bool = struct.field(
        pytree_node=False, default=False
    )
    # per-pod inter-pod affinity terms, padded to AT/BT/WT. `*_self` marks
    # the upstream first-pod special case: the term matches the incoming
    # pod itself, so an otherwise-empty cluster does not deadlock.
    aff_track: Optional[np.ndarray] = None  # (P, AT) int32 required affinity
    aff_topo: Optional[np.ndarray] = None  # (P, AT) int32 key code
    aff_self: Optional[np.ndarray] = None  # (P, AT) bool
    aff_mask: Optional[np.ndarray] = None  # (P, AT) bool
    anti_track: Optional[np.ndarray] = None  # (P, BT) int32 required anti
    anti_topo: Optional[np.ndarray] = None  # (P, BT) int32
    anti_mask: Optional[np.ndarray] = None  # (P, BT) bool
    # preferred (anti-)affinity terms: weighted domain-count scoring
    waff_track: Optional[np.ndarray] = None  # (P, WT) int32
    waff_topo: Optional[np.ndarray] = None  # (P, WT) int32
    waff_weight: Optional[np.ndarray] = None  # (P, WT) int64 (negative=anti)
    waff_mask: Optional[np.ndarray] = None  # (P, WT) bool
    # EXISTING pods' required anti-affinity (symmetry): an incoming pod
    # matching group `exist_anti_sel[e]` is blocked on nodes whose domain
    # (under `exist_anti_topo[e]`) hosts a pod carrying term e. Domain
    # presence is carried live (`SolverState.anti_domains`) because pending
    # pods' own anti terms join E and their placements create new blocks.
    exist_anti_sel: Optional[np.ndarray] = None  # (E,) int32 selector group
    exist_anti_topo: Optional[np.ndarray] = None  # (E,) int32 key code
    exist_anti_base: Optional[np.ndarray] = None  # (E, D) bool assigned
    #: (E, P) which pending pods carry term e (their placement marks the
    #: domain) — identity, not selector match
    exist_anti_carrier: Optional[np.ndarray] = None
    #: (E, P) which pending pods MATCH term e's selector (they get blocked)
    exist_anti_match: Optional[np.ndarray] = None
    # Symmetric SCORE terms (upstream interpodaffinity PreScore): each
    # existing pod's preferred (anti-)affinity terms add +-weight, and its
    # REQUIRED affinity terms add HardPodAffinityWeight, to every node in
    # the existing pod's domain when the term's selector matches the
    # INCOMING pod. E2 axis = unique (selector, key, weight, hard) tuples.
    sym_sel: Optional[np.ndarray] = None  # (E2,) int32 selector group
    sym_topo: Optional[np.ndarray] = None  # (E2,) int32 key code
    sym_weight: Optional[np.ndarray] = None  # (E2,) int64 (+-w; hard rows 1)
    sym_hard: Optional[np.ndarray] = None  # (E2,) bool required-term rows
    sym_base: Optional[np.ndarray] = None  # (E2, D) int64 carrier counts
    #: (E2, P) how many of pending pod q's terms are row e2 — q's
    #: placement adds that many carriers to its domain
    sym_carrier: Optional[np.ndarray] = None


def _node_filter_key(pod: Pod):
    return (
        tuple(sorted(pod.node_selector.items())),
        tuple(
            (
                tuple(
                    (r.key, r.operator, tuple(r.values))
                    for r in term.match_expressions
                ),
                tuple(
                    (r.key, r.operator, tuple(r.values))
                    for r in term.match_fields
                ),
            )
            for term in pod.node_affinity_required
        ),
    )


def _pref_key(pod: Pod):
    return tuple(
        (
            t.weight,
            tuple(
                (r.key, r.operator, tuple(r.values))
                for r in t.preference.match_expressions
            ),
            tuple(
                (r.key, r.operator, tuple(r.values))
                for r in t.preference.match_fields
            ),
        )
        for t in pod.node_affinity_preferred
    )


def _tol_key(pod: Pod):
    return tuple(
        sorted(
            (t.key, t.operator, t.value, t.effect) for t in pod.tolerations
        )
    )


def _node_filter_matches(pod: Pod, node: Node) -> bool:
    """spec.nodeSelector AND (OR over required affinity terms) — upstream
    component-helpers nodeaffinity.GetRequiredNodeAffinity semantics."""
    for k, v in pod.node_selector.items():
        if node.labels.get(k) != v:
            return False
    if pod.node_affinity_required:
        return any(t.matches(node) for t in pod.node_affinity_required)
    return True


def node_spec_keys(pod: Pod):
    """(`_node_filter_key` | None, `_pref_key` | None) of the pod's
    nodeSelector / required node affinity and of its preferred terms, or
    None where it has none of them: what its rows of `node_term_ok` and
    `pref_score` are interned by, in a fresh build and in the resident
    tables (`serving.node_terms`)."""
    required = pod.node_selector or pod.node_affinity_required
    if not (required or pod.node_affinity_preferred):
        return None
    return (
        _node_filter_key(pod) if required else None,
        _pref_key(pod) if pod.node_affinity_preferred else None,
    )


#: A pod's node-placement spec without the pod: what a resident row keeps
#: to evaluate a node's cell later. It stands in for the pod wherever the
#: functions below read these three fields.
NodeSpec = collections.namedtuple(
    "NodeSpec", "node_selector node_affinity_required node_affinity_preferred"
)


def node_spec(pod: Pod) -> NodeSpec:
    return NodeSpec(
        pod.node_selector, pod.node_affinity_required,
        pod.node_affinity_preferred,
    )


def node_pref_score(pod: Pod, node: Node) -> int:
    """The summed weights of the pod's preferred terms the node matches."""
    return sum(
        t.weight
        for t in pod.node_affinity_preferred
        if t.preference.matches(node)
    )


def node_term_row(pod: Pod, nodes: Sequence[Node], N: int) -> np.ndarray:
    """(N,) bool: one spec's required verdict over the nodes, in their
    order; a padded column admits nothing. O(nodes) label tests."""
    row = np.zeros(N, bool)
    for n, node in enumerate(nodes):
        row[n] = _node_filter_matches(pod, node)
    return row


def node_pref_row(pod: Pod, nodes: Sequence[Node], N: int) -> np.ndarray:
    """(N,) int64: one spec's preferred score over the nodes."""
    row = np.zeros(N, I64)
    for n, node in enumerate(nodes):
        row[n] = node_pref_score(pod, node)
    return row


def node_term_tables(nodes: Sequence[Node], pending: Sequence[Pod], N: int,
                     P: int) -> tuple:
    """(node_term_ok (T+1, N), pod_node_term (P,), pref_score (U+1, N),
    pod_pref (P,)) of a fresh build: every unique spec of the batch
    against every node, specs interned in the batch's order, the all-true
    row at T and the all-zero one at U. O(specs x nodes), in Python."""
    term_rows: dict = {}
    pref_rows: dict = {}
    # a slot past the batch keeps row 0, whatever that row is: it is masked
    pod_node_term = np.zeros(P, I32)
    pod_pref = np.zeros(P, I32)
    term_pods: list[Pod] = []
    pref_pods: list[Pod] = []
    for i, pod in enumerate(pending):
        term_key, pref_key = node_spec_keys(pod) or (None, None)
        if term_key is None:
            pod_node_term[i] = -1  # remapped to the all-true row below
        else:
            if term_key not in term_rows:
                term_rows[term_key] = len(term_rows)
                term_pods.append(pod)
            pod_node_term[i] = term_rows[term_key]
        if pref_key is None:
            pod_pref[i] = -1
        else:
            if pref_key not in pref_rows:
                pref_rows[pref_key] = len(pref_rows)
                pref_pods.append(pod)
            pod_pref[i] = pref_rows[pref_key]
    T, U = len(term_rows), len(pref_rows)
    node_term_ok = np.zeros((T + 1, N), bool)
    node_term_ok[T] = True  # unconstrained row
    pref_score = np.zeros((U + 1, N), I64)
    for t, pod in enumerate(term_pods):
        node_term_ok[t] = node_term_row(pod, nodes, N)
    for u, pod in enumerate(pref_pods):
        pref_score[u] = node_pref_row(pod, nodes, N)
    pod_node_term = np.where(pod_node_term < 0, T, pod_node_term).astype(I32)
    pod_pref = np.where(pod_pref < 0, U, pod_pref).astype(I32)
    return node_term_ok, pod_node_term, pref_score, pod_pref


def has_affinity_terms(pod: Pod) -> bool:
    """The pod carries a pod (anti-)affinity term, required or preferred."""
    return bool(
        pod.pod_affinity_required
        or pod.pod_anti_affinity_required
        or pod.pod_affinity_preferred
        or pod.pod_anti_affinity_preferred
    )


def _has_selector_specs(pending, assigned) -> bool:
    # assigned pods' terms matter too: required anti (symmetry blocks) and
    # preferred/required affinity (symmetric score toward incoming pods)
    return any(
        p.topology_spread or has_affinity_terms(p) for p in pending
    ) or any(has_affinity_terms(p) for p in assigned)


def relevant(nodes, pending, assigned=()) -> bool:
    """Whether any spec exists that makes the tables non-trivial."""
    return (
        any(n.taints for n in nodes)
        or any(
            p.node_selector
            or p.node_affinity_required
            or p.node_affinity_preferred
            for p in pending
        )
        or _has_selector_specs(pending, assigned)
    )


def build_scheduling(
    nodes: Sequence[Node],
    pending: Sequence[Pod],
    N: int,
    P: int,
    assigned: Sequence[Pod] = (),
    namespaces: Sequence = (),
) -> Optional[SchedulingState]:
    """Lower specs into `SchedulingState`; None when nothing is relevant.
    `namespaces` are the cluster's Namespace objects — the
    PodAffinityTerm.namespaceSelector targets."""
    if not relevant(nodes, pending, assigned):
        return None

    # the node half: nodeSelector / node-affinity specs against the nodes
    # (the span is the fallback path's twin of `ServeRefresh/node_terms`)
    with obs.tracer.span("Snapshot/node_terms", tid="snapshot",
                         pending=len(pending)):
        node_term_ok, pod_node_term, pref_score, pod_pref = node_term_tables(
            nodes, pending, N, P
        )

    tol_rows: dict = {}
    pod_tol = np.zeros(P, I32)
    tol_pods: list[Pod] = []
    for i, pod in enumerate(pending):
        k = _tol_key(pod)
        if k not in tol_rows:
            tol_rows[k] = len(tol_rows)
            tol_pods.append(pod)
        pod_tol[i] = tol_rows[k]

    T2 = max(len(tol_rows), 1)
    tol_ok = np.ones((T2, N), bool)
    tol_prefer = np.zeros((T2, N), I64)
    for s, pod in enumerate(tol_pods):
        for n, node in enumerate(nodes):
            for taint in node.taints:
                if any(t.tolerates(taint) for t in pod.tolerations):
                    continue
                if taint.effect in ("NoSchedule", "NoExecute"):
                    tol_ok[s, n] = False
                elif taint.effect == "PreferNoSchedule":
                    tol_prefer[s, n] += 1

    # the selector tables, whoever asks for a fresh build (the span is the
    # fallback path's twin of the engine's `ServeRefresh/selectors`)
    with obs.tracer.span("Snapshot/selectors", tid="snapshot",
                         assigned=len(assigned)):
        selector_tables = _build_selector_tables(
            nodes, pending, assigned, N, P, namespaces,
            pod_aff_rows=node_term_ok[pod_node_term],
            pod_tol_rows=tol_ok[pod_tol],
        )
    return SchedulingState(
        node_term_ok=node_term_ok,
        pod_node_term=pod_node_term,
        pref_score=pref_score,
        pod_pref=pod_pref,
        tol_ok=tol_ok,
        tol_prefer=tol_prefer,
        pod_tol=pod_tol,
        **selector_tables,
    )


def _merged_spread_selector(pod: Pod, tsc):
    """matchLabelKeys (upstream podtopologyspread): the incoming pod's
    values for the listed keys are appended to the selector as exact-match
    requirements; keys the pod lacks are ignored; a nil selector stays nil
    (matches nothing)."""
    sel = tsc.label_selector
    if sel is None or not tsc.match_label_keys:
        return sel
    extra = [
        k for k in tsc.match_label_keys if k in pod.labels
    ]
    if not extra:
        return sel
    return LabelSelector(
        match_labels=dict(sel.match_labels),
        match_expressions=list(sel.match_expressions)
        + [
            LabelSelectorRequirement(k, "In", (pod.labels[k],))
            for k in extra
        ],
    )


def _term_scope(pod: Pod, term, namespaces) -> tuple:
    """Effective namespace scope of a PodAffinityTerm: the explicit list
    plus namespaces matching namespaceSelector (EMPTY selector matches
    every namespace -> the "*" wildcard scope). The own-namespace fallback
    applies ONLY when the list is empty AND the selector is nil — a
    non-nil selector matching zero namespaces yields an empty scope that
    matches nothing (upstream GetNamespaceLabelsSnapshot semantics)."""
    scope = set(term.namespaces)
    sel = getattr(term, "namespace_selector", None)
    if sel is not None:
        if not sel.match_labels and not sel.match_expressions:
            return ("*",)
        scope.update(ns.name for ns in namespaces if sel.matches(ns.labels))
    elif not scope:
        scope = {pod.namespace}
    return tuple(sorted(scope))


class SelectorAxes:
    """First-seen interning of selector groups (namespace scope, selector),
    topology keys and tracks (group, key): the S, K and TR axes. A fresh
    build interns in the order its batch names them; the resident engine
    (`serving.selectors`) keeps one across cycles, in the order of the
    store's registry, so a row means the same thing from cycle to cycle."""

    def __init__(self):
        self.sels: dict = {}  # (ns scope, selector key) -> index
        self.sel_objs: list = []  # (ns tuple, LabelSelector-or-None)
        self.keys: dict = {}  # topology key -> index
        self.key_names: list[str] = []
        self.tracks: dict = {}  # (sel idx, key idx) -> track index
        #: the E axis: required anti-affinity terms, (sel idx, key idx) -> e
        self.anti_terms: dict = {}
        #: the E2 axis: (sel idx, key idx, signed weight, hard) -> e2
        self.sym_terms: dict = {}

    def sel_id(self, ns_scope: tuple, selector) -> int:
        k = (ns_scope, None if selector is None else selector._key())
        s = self.sels.get(k)
        if s is None:
            s = self.sels[k] = len(self.sels)
            self.sel_objs.append((ns_scope, selector))
        return s

    def key_id(self, name: str) -> int:
        k = self.keys.get(name)
        if k is None:
            k = self.keys[name] = len(self.keys)
            self.key_names.append(name)
        return k

    def track_id(self, s: int, k: int) -> int:
        t = self.tracks.get((s, k))
        if t is None:
            t = self.tracks[(s, k)] = len(self.tracks)
        return t

    def anti_id(self, s: int, k: int) -> int:
        e = self.anti_terms.get((s, k))
        if e is None:
            e = self.anti_terms[(s, k)] = len(self.anti_terms)
        return e

    def sym_id(self, s: int, k: int, weight: int, hard: bool) -> int:
        e2 = self.sym_terms.get((s, k, weight, hard))
        if e2 is None:
            e2 = self.sym_terms[(s, k, weight, hard)] = len(self.sym_terms)
        return e2


def spread_track_keys(pod: Pod) -> list:
    """[(ns scope, selector, topology key), ...] of the pod's spread
    constraints, matchLabelKeys merged: what a track is made of. The
    store's registry (`SelectorRegistry`) and `spread_rows` both intern
    from this."""
    return [
        ((pod.namespace,), _merged_spread_selector(pod, tsc),
         tsc.topology_key)
        for tsc in pod.topology_spread
    ]


def spread_rows(axes: SelectorAxes, pending, P: int, CT=None) -> dict:
    """The (P, CT) per-pod spread-constraint rows, O(batch): one function
    for the fresh build and the resident engine. `CT` pads the constraint
    axis (the engine's bucket); by default it is the batch's widest pod."""
    widest = max((len(p.topology_spread) for p in pending), default=1) or 1
    CT = widest if CT is None else max(CT, widest)
    spread_track = np.zeros((P, CT), I32)
    spread_topo = np.zeros((P, CT), I32)
    spread_max_skew = np.zeros((P, CT), I64)
    spread_hard = np.zeros((P, CT), bool)
    spread_self = np.zeros((P, CT), bool)
    spread_mask = np.zeros((P, CT), bool)
    spread_min_domains = np.zeros((P, CT), I64)
    spread_policy_affinity = np.zeros((P, CT), bool)
    spread_policy_taints = np.zeros((P, CT), bool)
    for i, pod in enumerate(pending):
        if not pod.topology_spread:
            continue
        for c, (tsc, (scope, sel, key)) in enumerate(
            zip(pod.topology_spread, spread_track_keys(pod))
        ):
            s = axes.sel_id(scope, sel)
            k = axes.key_id(key)
            spread_track[i, c] = axes.track_id(s, k)
            spread_topo[i, c] = k
            spread_max_skew[i, c] = tsc.max_skew
            spread_hard[i, c] = tsc.when_unsatisfiable == "DoNotSchedule"
            spread_self[i, c] = _sel_matches(sel, scope, pod)
            spread_mask[i, c] = True
            spread_min_domains[i, c] = tsc.min_domains or 0
            spread_policy_affinity[i, c] = (
                tsc.node_affinity_policy != "Ignore"
            )
            spread_policy_taints[i, c] = tsc.node_taints_policy == "Honor"
    return dict(
        spread_track=spread_track,
        spread_topo=spread_topo,
        spread_max_skew=spread_max_skew,
        spread_hard=spread_hard,
        spread_self=spread_self,
        spread_mask=spread_mask,
        spread_min_domains=spread_min_domains,
        spread_policy_affinity=spread_policy_affinity,
        spread_policy_taints=spread_policy_taints,
    )


def topology_tables(key_names, nodes, N: int, K=None) -> tuple:
    """(topo_code (K, N), topo_has (K, N), domain_values [dict, ...]): each
    node's domain under each key, a key's values interned in node order.
    `K` pads the key axis; the caller sizes the domain axis from
    `domain_values`."""
    K = max(len(key_names), 1) if K is None else K
    topo_code = np.full((K, N), -1, I32)
    topo_has = np.zeros((K, N), bool)
    domain_values: list[dict] = [dict() for _ in range(K)]
    for k, name in enumerate(key_names):
        dv = domain_values[k]
        for n, node in enumerate(nodes):
            val = node.labels.get(name)
            if val is None:
                continue
            code = dv.get(val)
            if code is None:
                code = dv[val] = len(dv)
            topo_code[k, n] = code
            topo_has[k, n] = True
    return topo_code, topo_has, domain_values


def domain_exists_table(domain_values, K: int, D: int) -> np.ndarray:
    exists = np.zeros((K, D), bool)
    for k, dv in enumerate(domain_values):
        for code in dv.values():
            exists[k, code] = True
    return exists


def track_counts(axes: SelectorAxes, assigned, node_pos, topo_code,
                 TR: int, N: int, D: int, per_node: bool = True) -> tuple:
    """(track_node_base (TR, N) | None, track_base (TR, D)): the assigned
    pods each track's selector matches, by node and by the node's domain
    under the track's key. O(assigned x tracks): the part of the selector
    tables that a resident engine keeps instead of recounting."""
    track_node_base = np.zeros((TR, N), I64) if per_node else None
    track_base = np.zeros((TR, D), I64)
    tracks = [
        (t, k, *axes.sel_objs[s]) for (s, k), t in axes.tracks.items()
    ]
    for pod in assigned:
        n = node_pos.get(pod.node_name)
        if n is None:
            continue
        for t, k, ns, selector in tracks:
            if _sel_matches(selector, ns, pod):
                if per_node:
                    track_node_base[t, n] += 1
                code = topo_code[k, n]
                if code >= 0:
                    track_base[t, code] += 1
    return track_node_base, track_base


def labels_key(pod: Pod) -> tuple:
    """What a selector can tell two pods apart by: namespace and labels."""
    return (pod.namespace, tuple(sorted(pod.labels.items())))


def pend_match_rows(sel_objs, pending, P: int, S=None, memo=None
                    ) -> np.ndarray:
    """(S, P) bool: pending pod i is in selector group s. O(batch x S) for
    the fresh build; the resident engine pads the selector axis (`S`) and
    lends a `memo` of columns by `labels_key`, which holds for as long as
    `sel_objs` does: replicas of one workload then cost a lookup each."""
    S = len(sel_objs) if S is None else S
    pend_match = np.zeros((S, P), bool)
    for i, pod in enumerate(pending):
        key = labels_key(pod) if memo is not None else None
        column = memo.get(key) if memo is not None else None
        if column is None:
            column = np.zeros(S, bool)
            for s, (ns, selector) in enumerate(sel_objs):
                column[s] = _sel_matches(selector, ns, pod)
            if memo is not None:
                memo[key] = column
        pend_match[:, i] = column
    return pend_match


def affinity_term_keys(pod: Pod):
    """([track key, ...], [E key, ...], [E2 key, ...], {key: selector}) of
    the pod's pod (anti-)affinity terms, or None where a term's scope
    depends on the cluster's Namespace objects (`static_term_scope`). A
    track and an E key are (ns scope, selector key | None, topology key);
    an E2 key adds (signed weight, hard). Every term names a track."""
    tracks, antis, syms, selectors = [], [], [], {}

    def key(term):
        scope = static_term_scope(pod, term)
        if scope is None:
            raise LookupError(term)
        sel = term.label_selector
        k = (scope, None if sel is None else sel._key(), term.topology_key)
        selectors[k] = sel
        tracks.append(k)
        return k

    try:
        for term in pod.pod_affinity_required:
            syms.append(key(term) + (1, True))
        for term in pod.pod_anti_affinity_required:
            antis.append(key(term))
        for term, weight in _weighted_terms(pod):
            syms.append(key(term) + (weight, False))
    except LookupError:
        return None
    return tracks, antis, syms, selectors


class SelectorRegistry:
    """The tracks and terms the store's pods declare, pending or bound:
    interned where a pod is added and released where it is removed (pod
    specs are immutable), so nothing has to rediscover them from every
    assigned pod at every snapshot. `tracks` are the (selector group,
    topology key) pairs of spread constraints and of pod (anti-)affinity
    terms; `anti_terms` the required anti-affinity terms pods carry (the E
    axis), `sym_terms` the score's symmetric terms (E2). `version` moves
    when the SET of any of them does: a resident engine rebuilds its
    selector tables then, and only then. O(constraints + terms) a pod."""

    def __init__(self):
        #: (ns scope, selector key | None, topology key) -> [pods, selector]
        self.tracks: dict = {}
        self.anti_terms: dict = {}  # the same key -> [pods, selector]
        #: (scope, selector key, topology key, weight, hard) -> [pods, sel]
        self.sym_terms: dict = {}
        #: uids of pods with a term under a non-empty namespaceSelector:
        #: its scope moves with the Namespaces' labels, nothing is interned
        self.unscoped: set = set()
        self._by_pod: dict = {}  # uid -> ((table, key), ...) it holds
        self.version = 0

    def add(self, pod: Pod) -> None:
        """`pod` replaces whatever was held under its uid."""
        held = [
            (self.tracks, (scope, None if sel is None else sel._key(), topo),
             sel)
            for scope, sel, topo in spread_track_keys(pod)
        ] if pod.topology_spread else []
        unscoped = False
        if has_affinity_terms(pod):
            keys = affinity_term_keys(pod)
            if keys is None:
                unscoped = True
            else:
                tracks, antis, syms, selectors = keys
                held += [(self.tracks, k, selectors[k]) for k in tracks]
                held += [(self.anti_terms, k, selectors[k]) for k in antis]
                held += [(self.sym_terms, k, selectors[k[:3]]) for k in syms]
        for table, key, sel in held:
            entry = table.get(key)
            if entry is None:
                table[key] = [1, sel]
                self.version += 1
            else:
                entry[0] += 1
        self.remove(pod.uid)
        if held:
            self._by_pod[pod.uid] = [(table, key) for table, key, _ in held]
        if unscoped:
            self.unscoped.add(pod.uid)

    def remove(self, uid: str) -> None:
        self.unscoped.discard(uid)
        for table, key in self._by_pod.pop(uid, ()):
            entry = table[key]
            entry[0] -= 1
            if not entry[0]:
                del table[key]
                self.version += 1

    def axes(self) -> SelectorAxes:
        """The live tracks and terms as axes, in the registry's order."""
        axes = SelectorAxes()
        for (scope, _selkey, topo), (_refs, sel) in self.tracks.items():
            axes.track_id(axes.sel_id(scope, sel), axes.key_id(topo))
        for (scope, _selkey, topo), (_refs, sel) in self.anti_terms.items():
            axes.anti_id(axes.sel_id(scope, sel), axes.key_id(topo))
        for (scope, _selkey, topo, weight, hard), (_refs, sel) in (
            self.sym_terms.items()
        ):
            axes.sym_id(
                axes.sel_id(scope, sel), axes.key_id(topo), weight, hard
            )
        return axes


def static_term_scope(pod: Pod, term):
    """`_term_scope` where it does not depend on the cluster's Namespace
    objects (an explicit list, none, or the EMPTY selector's every
    namespace), else None: the scope of a term with a non-empty
    namespaceSelector moves when a Namespace's labels do, so nothing keeps
    it across cycles."""
    sel = getattr(term, "namespace_selector", None)
    if sel is not None and (sel.match_labels or sel.match_expressions):
        return None
    return _term_scope(pod, term, ())


def _weighted_terms(pod: Pod):
    """(term, signed weight) of the pod's preferred terms, affinity first:
    the order of the (P, WT) rows and of the E2 axis."""
    for wt in pod.pod_affinity_preferred:
        yield wt.term, wt.weight
    for wt in pod.pod_anti_affinity_preferred:
        yield wt.term, -wt.weight


def _term_sel_key(axes: SelectorAxes, pod: Pod, term, namespaces) -> tuple:
    """(selector group, key code) of a PodAffinityTerm scoped to the pod."""
    s = axes.sel_id(_term_scope(pod, term, namespaces), term.label_selector)
    return s, axes.key_id(term.topology_key)


def pod_anti_rows(axes: SelectorAxes, pod: Pod, namespaces=()) -> list:
    """The E rows of the required anti-affinity terms the pod carries, one
    entry a term."""
    return [
        axes.anti_id(*_term_sel_key(axes, pod, term, namespaces))
        for term in pod.pod_anti_affinity_required
    ]


def pod_sym_rows(axes: SelectorAxes, pod: Pod, namespaces=()) -> dict:
    """{E2 row: how many of the pod's terms it is}: the pod's preferred
    terms at their signed weight and its required affinity terms as hard
    rows (upstream interpodaffinity PreScore's symmetric half)."""
    counts: dict = {}
    for term, weight in _weighted_terms(pod):
        e2 = axes.sym_id(
            *_term_sel_key(axes, pod, term, namespaces), weight, False
        )
        counts[e2] = counts.get(e2, 0) + 1
    for term in pod.pod_affinity_required:
        e2 = axes.sym_id(
            *_term_sel_key(axes, pod, term, namespaces), 1, True
        )
        counts[e2] = counts.get(e2, 0) + 1
    return counts


def affinity_rows(axes: SelectorAxes, pending, P: int, namespaces=(),
                  AT=None, BT=None, WT=None) -> tuple:
    """(the (P, AT/BT/WT) `aff_*` / `anti_*` / `waff_*` rows of the batch's
    own terms, [(E row, pod index), ...] for the required anti terms its
    pods carry). O(batch): one function for the fresh build and the
    resident engine, which pads the term axes (`AT`, `BT`, `WT`; by
    default the batch's widest pod, and one where it has none: a pad of 0
    leaves an axis no pod uses without a row)."""
    def width(count, pad):
        widest = max((count(p) for p in pending), default=0)
        return (widest or 1) if pad is None else max(pad, widest)

    AT = width(lambda p: len(p.pod_affinity_required), AT)
    BT = width(lambda p: len(p.pod_anti_affinity_required), BT)
    WT = width(
        lambda p: len(p.pod_affinity_preferred)
        + len(p.pod_anti_affinity_preferred), WT,
    )
    aff_track = np.zeros((P, AT), I32)
    aff_topo = np.zeros((P, AT), I32)
    aff_self = np.zeros((P, AT), bool)
    aff_mask = np.zeros((P, AT), bool)
    anti_track = np.zeros((P, BT), I32)
    anti_topo = np.zeros((P, BT), I32)
    anti_mask = np.zeros((P, BT), bool)
    waff_track = np.zeros((P, WT), I32)
    waff_topo = np.zeros((P, WT), I32)
    waff_weight = np.zeros((P, WT), I64)
    waff_mask = np.zeros((P, WT), bool)
    pend_carriers: list = []
    for i, pod in enumerate(pending):
        for c, term in enumerate(pod.pod_affinity_required):
            s, k = _term_sel_key(axes, pod, term, namespaces)
            aff_track[i, c] = axes.track_id(s, k)
            aff_topo[i, c] = k
            aff_self[i, c] = _sel_matches(
                term.label_selector, axes.sel_objs[s][0], pod
            )
            aff_mask[i, c] = True
        for c, term in enumerate(pod.pod_anti_affinity_required):
            s, k = _term_sel_key(axes, pod, term, namespaces)
            anti_track[i, c] = axes.track_id(s, k)
            anti_topo[i, c] = k
            anti_mask[i, c] = True
            pend_carriers.append((axes.anti_id(s, k), i))
        for w, (term, weight) in enumerate(_weighted_terms(pod)):
            s, k = _term_sel_key(axes, pod, term, namespaces)
            waff_track[i, w] = axes.track_id(s, k)
            waff_topo[i, w] = k
            waff_weight[i, w] = weight
            waff_mask[i, w] = True
    return dict(
        aff_track=aff_track,
        aff_topo=aff_topo,
        aff_self=aff_self,
        aff_mask=aff_mask,
        anti_track=anti_track,
        anti_topo=anti_topo,
        anti_mask=anti_mask,
        waff_track=waff_track,
        waff_topo=waff_topo,
        waff_weight=waff_weight,
        waff_mask=waff_mask,
    ), pend_carriers


def assigned_carriers(axes: SelectorAxes, assigned, namespaces=()) -> tuple:
    """([(node name, E row, 1), ...], [(node name, E2 row, count), ...]):
    the terms the assigned pods carry, every anti term interned before any
    score term. O(assigned): with `carrier_counts`, the part of the
    inter-pod tables a resident engine keeps instead of rebuilding."""
    anti = [
        (pod.node_name, e, 1)
        for pod in assigned
        for e in pod_anti_rows(axes, pod, namespaces)
    ]
    sym: list = []
    for pod in assigned:
        rows = pod_sym_rows(axes, pod, namespaces)
        if pod.node_name is not None:
            sym.extend((pod.node_name, e2, c) for e2, c in rows.items())
    return anti, sym


def carrier_counts(carriers, node_pos, topo_code, row_topo, rows: int,
                   D: int) -> np.ndarray:
    """(rows, D) int64: the carriers of each term by the domain of their
    node under the term's key (`row_topo`); a node unknown or without the
    key carries nothing."""
    counts = np.zeros((rows, D), I64)
    for node_name, row, count in carriers:
        n = node_pos.get(node_name)
        if n is None:
            continue
        code = topo_code[row_topo[row], n]
        if code >= 0:
            counts[row, code] += count
    return counts


def anti_term_tables(axes: SelectorAxes, E=None, pad_sel: int = 0) -> dict:
    """`exist_anti_sel` / `exist_anti_topo` (E,): a padded row is in
    selector group `pad_sel`, one no pod is in."""
    E = len(axes.anti_terms) if E is None else E
    exist_anti_sel = np.full(E, pad_sel, I32)
    exist_anti_topo = np.zeros(E, I32)
    for (s, k), e in axes.anti_terms.items():
        exist_anti_sel[e] = s
        exist_anti_topo[e] = k
    return dict(exist_anti_sel=exist_anti_sel,
                exist_anti_topo=exist_anti_topo)


def sym_term_tables(axes: SelectorAxes, E2=None, pad_sel: int = 0) -> dict:
    """`sym_sel` / `sym_topo` / `sym_weight` / `sym_hard` (E2,)."""
    E2 = len(axes.sym_terms) if E2 is None else E2
    sym_sel = np.full(E2, pad_sel, I32)
    sym_topo = np.zeros(E2, I32)
    sym_weight = np.zeros(E2, I64)
    sym_hard = np.zeros(E2, bool)
    for (s, k, weight, hard), e2 in axes.sym_terms.items():
        sym_sel[e2], sym_topo[e2] = s, k
        sym_weight[e2], sym_hard[e2] = weight, hard
    return dict(sym_sel=sym_sel, sym_topo=sym_topo, sym_weight=sym_weight,
                sym_hard=sym_hard)


def anti_batch_rows(pend_carriers, pend_match, exist_anti_sel, P: int
                    ) -> dict:
    """(E, P) which pods of the batch carry each required anti term, and
    which its selector matches."""
    exist_anti_carrier = np.zeros((len(exist_anti_sel), P), bool)
    for e, i in pend_carriers:
        exist_anti_carrier[e, i] = True
    return dict(exist_anti_carrier=exist_anti_carrier,
                exist_anti_match=pend_match[exist_anti_sel])


def sym_batch_rows(pending_sym, E2: int, P: int) -> np.ndarray:
    """`sym_carrier` (E2, P): how many of pod i's terms are row e2."""
    sym_carrier = np.zeros((E2, P), I64)
    for i, e2, count in pending_sym:
        sym_carrier[e2, i] = count
    return sym_carrier


def _build_selector_tables(
    nodes, pending, assigned, N, P, namespaces=(),
    pod_aff_rows=None, pod_tol_rows=None,
) -> dict:
    """Selector-group / topology-domain / track tables for PodTopologySpread
    and InterPodAffinity: a track = unique (selector group, topology key)
    pair; assigned pods aggregate into per-(track, domain) base counts;
    existing/pending required anti-affinity terms form the E axis."""
    if not _has_selector_specs(pending, assigned):
        return {}

    axes = SelectorAxes()
    sel_objs, keys, key_names, tracks = (
        axes.sel_objs, axes.keys, axes.key_names, axes.tracks
    )

    spread = spread_rows(axes, pending, P)
    spread_policy_affinity = spread["spread_policy_affinity"]
    spread_policy_taints = spread["spread_policy_taints"]
    CT = spread["spread_track"].shape[1]

    # the inter-pod half, first part: the batch's own terms, then the
    # assigned pods' (E: required anti terms carried by assigned OR pending
    # pods, whose carriers block matching pods; E2: the score's symmetric
    # terms), interned in that order
    with obs.tracer.span("Snapshot/affinity", tid="snapshot",
                         assigned=len(assigned)):
        affinity, pend_carriers = affinity_rows(
            axes, pending, P, namespaces
        )
        assigned_anti, assigned_sym = assigned_carriers(
            axes, assigned, namespaces
        )
        pending_sym = [
            (i, e2, c) for i, pod in enumerate(pending)
            for e2, c in pod_sym_rows(axes, pod, namespaces).items()
        ]

    K = max(len(key_names), 1)
    # topology domain codes per key (value interned per key)
    topo_code, topo_has, domain_values = topology_tables(key_names, nodes, N)
    D = max((len(dv) for dv in domain_values), default=1) or 1
    domain_exists = domain_exists_table(domain_values, K, D)

    # --- static spread node-eligibility rows (upstream node-inclusion:
    # per-class all-keys presence, nodeAffinityPolicy, nodeTaintsPolicy).
    # Interned: replicas share rows; the common all-true row is index 0.
    elig_rows: dict = {}
    elig_list: list = []
    spread_elig_idx = np.zeros((P, CT), I32)
    needs_node_counts = False

    def elig_intern(row: np.ndarray) -> int:
        key = row.tobytes()
        if key not in elig_rows:
            elig_rows[key] = len(elig_list)
            elig_list.append(row)
        return elig_rows[key]

    elig_intern(np.ones(N, bool))  # row 0: no exclusions
    any_taints = any(n.taints for n in nodes)
    for i, pod in enumerate(pending):
        if not pod.topology_spread:
            continue
        class_keys = {True: [], False: []}
        for tsc in pod.topology_spread:
            class_keys[tsc.when_unsatisfiable == "DoNotSchedule"].append(
                keys[tsc.topology_key]
            )
        for c, tsc in enumerate(pod.topology_spread):
            row = np.ones(N, bool)
            hard = tsc.when_unsatisfiable == "DoNotSchedule"
            for k in class_keys[hard]:
                row &= topo_has[k]
            if spread_policy_affinity[i, c] and (
                pod.node_selector or pod.node_affinity_required
            ):
                # reuse the interned node-affinity verdict row
                row &= pod_aff_rows[i]
            if spread_policy_taints[i, c] and any_taints:
                # reuse the interned untolerated-taint row
                row &= pod_tol_rows[i]
            spread_elig_idx[i, c] = elig_intern(row)
            k = keys[tsc.topology_key]
            if np.any(~row & (topo_code[k] >= 0)):
                needs_node_counts = True
    spread_elig = np.stack(elig_list)

    TR = max(len(tracks), 1)
    track_sel = np.zeros(TR, I32)
    track_topo = np.zeros(TR, I32)
    for (s, k), t in tracks.items():
        track_sel[t] = s
        track_topo[t] = k

    node_pos = {node.name: n for n, node in enumerate(nodes)}
    track_node_base, track_base = track_counts(
        axes, assigned, node_pos, topo_code, TR, N, D
    )
    pend_match = pend_match_rows(sel_objs, pending, P)

    out = dict(
        pend_match=pend_match,
        topo_code=topo_code,
        topo_has=topo_has,
        domain_exists=domain_exists,
        track_sel=track_sel,
        track_topo=track_topo,
        track_node_base=track_node_base if needs_node_counts else None,
        track_base=track_base,
        **spread,
        spread_elig=spread_elig,
        spread_elig_idx=spread_elig_idx,
        spread_needs_node_counts=needs_node_counts,
        **affinity,
    )

    # the inter-pod half, second part: the E and E2 tables and their bases
    with obs.tracer.span("Snapshot/affinity", tid="snapshot",
                         anti_terms=len(axes.anti_terms),
                         sym_terms=len(axes.sym_terms)):
        if axes.anti_terms:
            terms = anti_term_tables(axes)
            out.update(
                **terms,
                exist_anti_base=carrier_counts(
                    assigned_anti, node_pos, topo_code,
                    terms["exist_anti_topo"], len(axes.anti_terms), D,
                ) > 0,
                **anti_batch_rows(
                    pend_carriers, pend_match, terms["exist_anti_sel"], P
                ),
            )
        if axes.sym_terms:
            E2 = len(axes.sym_terms)
            terms = sym_term_tables(axes)
            out.update(
                **terms,
                sym_base=carrier_counts(
                    assigned_sym, node_pos, topo_code, terms["sym_topo"],
                    E2, D,
                ),
                sym_carrier=sym_batch_rows(pending_sym, E2, P),
            )
    return out


def _sel_matches(selector, ns_scope, pod: Pod) -> bool:
    """Namespace-scoped label-selector match (metav1: a nil selector matches
    nothing; an empty selector matches everything). `ns_scope` is a str or
    a tuple of namespaces (PodAffinityTerm.namespaces)."""
    if isinstance(ns_scope, str):
        ns_scope = (ns_scope,)
    if "*" not in ns_scope and pod.namespace not in ns_scope:
        return False
    if selector is None:
        return False
    return selector.matches(pod.labels)
