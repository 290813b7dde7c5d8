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
#: static bases while the carry is dead).
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


def _has_selector_specs(pending, assigned) -> bool:
    # assigned pods' terms matter too: required anti (symmetry blocks) and
    # preferred/required affinity (symmetric score toward incoming pods)
    return any(
        p.topology_spread
        or p.pod_affinity_required
        or p.pod_anti_affinity_required
        or p.pod_affinity_preferred
        or p.pod_anti_affinity_preferred
        for p in pending
    ) or any(
        p.pod_anti_affinity_required
        or p.pod_affinity_required
        or p.pod_affinity_preferred
        or p.pod_anti_affinity_preferred
        for p in assigned
    )


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

    term_rows: dict = {}
    pref_rows: dict = {}
    tol_rows: dict = {}
    pod_node_term = np.zeros(P, I32)
    pod_pref = np.zeros(P, I32)
    pod_tol = np.zeros(P, I32)
    term_pods: list[Pod] = []
    pref_pods: list[Pod] = []
    tol_pods: list[Pod] = []
    for i, pod in enumerate(pending):
        if pod.node_selector or pod.node_affinity_required:
            k = _node_filter_key(pod)
            if k not in term_rows:
                term_rows[k] = len(term_rows)
                term_pods.append(pod)
            pod_node_term[i] = term_rows[k]
        else:
            pod_node_term[i] = -1  # remapped to the all-true pad row below
        if pod.node_affinity_preferred:
            k = _pref_key(pod)
            if k not in pref_rows:
                pref_rows[k] = len(pref_rows)
                pref_pods.append(pod)
            pod_pref[i] = pref_rows[k]
        else:
            pod_pref[i] = -1
        k = _tol_key(pod)
        if k not in tol_rows:
            tol_rows[k] = len(tol_rows)
            tol_pods.append(pod)
        pod_tol[i] = tol_rows[k]

    T, U, T2 = len(term_rows), len(pref_rows), max(len(tol_rows), 1)
    node_term_ok = np.zeros((T + 1, N), bool)
    node_term_ok[T] = True  # unconstrained row
    pref_score = np.zeros((U + 1, N), I64)
    tol_ok = np.ones((T2, N), bool)
    tol_prefer = np.zeros((T2, N), I64)

    for t, pod in enumerate(term_pods):
        for n, node in enumerate(nodes):
            node_term_ok[t, n] = _node_filter_matches(pod, node)
    for u, pod in enumerate(pref_pods):
        for n, node in enumerate(nodes):
            pref_score[u, n] = sum(
                t.weight
                for t in pod.node_affinity_preferred
                if t.preference.matches(node)
            )
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
            pod_aff_rows=node_term_ok[
                np.where(pod_node_term < 0, T, pod_node_term)
            ],
            pod_tol_rows=tol_ok[pod_tol],
        )
    return SchedulingState(
        node_term_ok=node_term_ok,
        pod_node_term=np.where(pod_node_term < 0, T, pod_node_term).astype(I32),
        pref_score=pref_score,
        pod_pref=np.where(pod_pref < 0, U, pod_pref).astype(I32),
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


class SelectorRegistry:
    """The spread tracks the store's pods declare, pending or bound:
    interned where a pod is added and released where it is removed (pod
    specs are immutable), so nothing has to rediscover them from every
    assigned pod at every snapshot. `version` moves when the SET of live
    tracks does: a resident engine rebuilds its selector tables then, and
    only then. O(constraints) a pod."""

    def __init__(self):
        #: (ns scope, selector key | None, topology key) -> [pods, selector]
        self.tracks: dict = {}
        self._by_pod: dict = {}  # uid -> its track keys
        self.version = 0

    def add(self, pod: Pod) -> None:
        """`pod` replaces whatever was held under its uid."""
        keys = [
            ((scope, None if sel is None else sel._key(), topo), scope, sel)
            for scope, sel, topo in spread_track_keys(pod)
        ] if pod.topology_spread else []
        for key, _scope, sel in keys:
            held = self.tracks.get(key)
            if held is None:
                self.tracks[key] = [1, sel]
                self.version += 1
            else:
                held[0] += 1
        self.remove(pod.uid)
        if keys:
            self._by_pod[pod.uid] = [key for key, _, _ in keys]

    def remove(self, uid: str) -> None:
        for key in self._by_pod.pop(uid, ()):
            held = self.tracks[key]
            held[0] -= 1
            if not held[0]:
                del self.tracks[key]
                self.version += 1

    def axes(self) -> SelectorAxes:
        """The live tracks as axes, in the registry's order."""
        axes = SelectorAxes()
        for (scope, _selkey, topo), (_refs, sel) in self.tracks.items():
            axes.track_id(axes.sel_id(scope, sel), axes.key_id(topo))
        return axes


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
    sel_id, key_id, track_id = axes.sel_id, axes.key_id, axes.track_id
    sel_objs, keys, key_names, tracks = (
        axes.sel_objs, axes.keys, axes.key_names, axes.tracks
    )

    def term_ids(pod: Pod, term) -> tuple[int, int, int]:
        """(sel, key, track) for a PodAffinityTerm scoped to the pod."""
        scope = _term_scope(pod, term, namespaces)
        s = sel_id(scope, term.label_selector)
        k = key_id(term.topology_key)
        return s, k, track_id(s, k)

    spread = spread_rows(axes, pending, P)
    spread_policy_affinity = spread["spread_policy_affinity"]
    spread_policy_taints = spread["spread_policy_taints"]
    CT = spread["spread_track"].shape[1]

    # inter-pod affinity terms (incoming pod's own)
    AT = max((len(p.pod_affinity_required) for p in pending), default=1) or 1
    BT = (
        max((len(p.pod_anti_affinity_required) for p in pending), default=1)
        or 1
    )
    WT = (
        max(
            (
                len(p.pod_affinity_preferred)
                + len(p.pod_anti_affinity_preferred)
                for p in pending
            ),
            default=1,
        )
        or 1
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
    # E axis: unique required anti-affinity (selector, key) pairs carried by
    # assigned OR pending pods (symmetry: carriers block matching pods)
    anti_terms: dict = {}  # (sel, key) -> e index

    def anti_term_id(s: int, k: int) -> int:
        if (s, k) not in anti_terms:
            anti_terms[(s, k)] = len(anti_terms)
        return anti_terms[(s, k)]

    pend_carriers: list[list[int]] = []  # per e, pending carrier indices
    for i, pod in enumerate(pending):
        for c, term in enumerate(pod.pod_affinity_required):
            s, k, t = term_ids(pod, term)
            aff_track[i, c] = t
            aff_topo[i, c] = k
            aff_self[i, c] = _sel_matches(
                term.label_selector, _term_scope(pod, term, namespaces), pod
            )
            aff_mask[i, c] = True
        for c, term in enumerate(pod.pod_anti_affinity_required):
            s, k, t = term_ids(pod, term)
            anti_track[i, c] = t
            anti_topo[i, c] = k
            anti_mask[i, c] = True
            e = anti_term_id(s, k)
            while len(pend_carriers) <= e:
                pend_carriers.append([])
            pend_carriers[e].append(i)
        w = 0
        for wt in pod.pod_affinity_preferred:
            s, k, t = term_ids(pod, wt.term)
            waff_track[i, w] = t
            waff_topo[i, w] = k
            waff_weight[i, w] = wt.weight
            waff_mask[i, w] = True
            w += 1
        for wt in pod.pod_anti_affinity_preferred:
            s, k, t = term_ids(pod, wt.term)
            waff_track[i, w] = t
            waff_topo[i, w] = k
            waff_weight[i, w] = -wt.weight
            waff_mask[i, w] = True
            w += 1

    # assigned pods' anti terms join E; remember who carries each term
    assigned_carrier_terms: list[tuple[Pod, int]] = []
    for pod in assigned:
        for term in pod.pod_anti_affinity_required:
            scope = _term_scope(pod, term, namespaces)
            s = sel_id(scope, term.label_selector)
            k = key_id(term.topology_key)
            e = anti_term_id(s, k)
            while len(pend_carriers) <= e:
                pend_carriers.append([])
            assigned_carrier_terms.append((pod, e))

    # --- symmetric score terms (E2 axis) --------------------------------
    sym_terms: dict = {}  # (sel, key, weight, hard) -> e2
    sym_rows: list = []

    def sym_id(sel: int, k: int, weight: int, hard: bool) -> int:
        key = (sel, k, weight, hard)
        if key not in sym_terms:
            sym_terms[key] = len(sym_rows)
            sym_rows.append(key)
        return sym_terms[key]

    def pod_sym_terms(pod: Pod):
        """(e2, count) pairs for one pod's score-symmetric terms."""
        out_counts: dict = {}
        for wt in pod.pod_affinity_preferred:
            s2 = sel_id(_term_scope(pod, wt.term, namespaces),
                        wt.term.label_selector)
            e2 = sym_id(s2, key_id(wt.term.topology_key), wt.weight, False)
            out_counts[e2] = out_counts.get(e2, 0) + 1
        for wt in pod.pod_anti_affinity_preferred:
            s2 = sel_id(_term_scope(pod, wt.term, namespaces),
                        wt.term.label_selector)
            e2 = sym_id(s2, key_id(wt.term.topology_key), -wt.weight, False)
            out_counts[e2] = out_counts.get(e2, 0) + 1
        for term in pod.pod_affinity_required:
            s2 = sel_id(_term_scope(pod, term, namespaces),
                        term.label_selector)
            e2 = sym_id(s2, key_id(term.topology_key), 1, True)
            out_counts[e2] = out_counts.get(e2, 0) + 1
        return out_counts

    assigned_sym: list[tuple[str, int, int]] = []  # (node name, e2, count)
    for pod in assigned:
        terms = pod_sym_terms(pod)
        if terms and pod.node_name is not None:
            assigned_sym.extend(
                (pod.node_name, e2, c) for e2, c in terms.items()
            )
    pending_sym: list[tuple[int, int, int]] = []  # (pod idx, e2, count)
    for i, pod in enumerate(pending):
        for e2, c in pod_sym_terms(pod).items():
            pending_sym.append((i, e2, c))

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
    )

    if anti_terms:
        E = len(anti_terms)
        exist_anti_sel = np.zeros(E, I32)
        exist_anti_topo = np.zeros(E, I32)
        for (s, k), e in anti_terms.items():
            exist_anti_sel[e] = s
            exist_anti_topo[e] = k
        exist_anti_base = np.zeros((E, D), bool)
        for pod, e in assigned_carrier_terms:
            n = node_pos.get(pod.node_name)
            if n is None:
                continue
            code = topo_code[exist_anti_topo[e], n]
            if code >= 0:
                exist_anti_base[e, code] = True
        exist_anti_carrier = np.zeros((E, P), bool)
        for e, carriers in enumerate(pend_carriers):
            for i in carriers:
                exist_anti_carrier[e, i] = True
        exist_anti_match = np.zeros((E, P), bool)
        for e in range(E):
            exist_anti_match[e] = pend_match[exist_anti_sel[e]]
        out.update(
            exist_anti_sel=exist_anti_sel,
            exist_anti_topo=exist_anti_topo,
            exist_anti_base=exist_anti_base,
            exist_anti_carrier=exist_anti_carrier,
            exist_anti_match=exist_anti_match,
        )
    if sym_rows:
        E2 = len(sym_rows)
        sym_sel = np.zeros(E2, I32)
        sym_topo = np.zeros(E2, I32)
        sym_weight = np.zeros(E2, I64)
        sym_hard = np.zeros(E2, bool)
        for e2, (s2, k, w, hard) in enumerate(sym_rows):
            sym_sel[e2], sym_topo[e2] = s2, k
            sym_weight[e2], sym_hard[e2] = w, hard
        sym_base = np.zeros((E2, D), I64)
        for node_name, e2, cnt in assigned_sym:
            n = node_pos.get(node_name)
            if n is None:
                continue
            code = topo_code[sym_topo[e2], n]
            if code >= 0:
                sym_base[e2, code] += cnt
        sym_carrier = np.zeros((E2, P), I64)
        for i, e2, cnt in pending_sym:
            sym_carrier[e2, i] = cnt
        out.update(
            sym_sel=sym_sel,
            sym_topo=sym_topo,
            sym_weight=sym_weight,
            sym_hard=sym_hard,
            sym_base=sym_base,
            sym_carrier=sym_carrier,
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
