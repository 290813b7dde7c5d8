"""The sequential solve reads domain counts in node space (ISSUE 35;
`ops/selectors.py`).

`InterPodAffinity` and `PodTopologySpread` used to look "the count in this
node's domain" up by an (N,)-wide gather out of a (D,) table row at every
pod of the scan. The solve now gathers once, before the scan, carries the
answer as node-space views (`SolverState.sel_dom_view` / `anti_view` /
`sym_view`) and keeps them by compare in the built-in commit. Held here:

(i) over seeded stores — D = 1, D = 3, D = N (hostname), nodes without the
key; pods with two required affinity terms, own anti terms, symmetric
carriers, preferred terms with the symmetric score, spread constraints with
`minDomains` — the solve's assignment, attribution and final
`sel_dom_counts` / `anti_domains` / `sym_counts` equal, bit for bit, a
reference solve whose plugins look every count up by gather: the
expressions the package had before, kept HERE (`GatherSpread`,
`GatherAffinity`) and not in the package;
(ii) stepping the same scan a pod at a time: every plugin's verdict and raw
score equal the reference's at every step, and after every step each view
equals the gather of its table masked by "the node has the key";
(iii) the lowering: the scan body of an antiaffinity-shaped and of a
spread-shaped solve holds no gather with a node's worth of indices, and the
solve of a snapshot without selector tables lowers to the program it
lowered to before; (iv) the node-count branch of `PodTopologySpread`
(`SchedulingState.spread_node_domains`): the scan body of a
nodeinclusion-shaped solve, hostname domains counted by node, holds no
node-wide scatter and no node-wide gather, and a zone-keyed one keeps its
scatter (`ScatterSpread`, kept here, is what that branch was).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    ZONE_LABEL,
    Container,
    LabelSelector,
    Node,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PodAffinityTerm,
    Taint,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler
from scheduler_plugins_tpu.framework import runtime
from scheduler_plugins_tpu.ops import selectors
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.plugins.intree import (
    InterPodAffinity,
    NodeAffinity,
    PodTopologySpread,
    TaintToleration,
)
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

gib = 1 << 30
HOSTNAME = "kubernetes.io/hostname"
N_NODES = 12
#: pods bound before the solve (serials below it); the batch follows
BOUND = 10


# ---------------------------------------------------------------------------
# the reference: every count looked up by gather, as the package did
# ---------------------------------------------------------------------------


def gather(dc, code):
    return jnp.take_along_axis(dc, jnp.maximum(code, 0), axis=1)


class ScatterSpread(PodTopologySpread):
    """`PodTopologySpread` with the node-count branch it had before counts
    were read in node space: wherever an eligibility row leaves out a keyed
    node, every pod's counts are scattered into a (CT, D) table, whatever
    its domains hold, and looked up out of it by gather."""

    def _constraint_state(self, state, snap, p):
        s = snap.scheduling
        code = s.topo_code[s.spread_topo[p]]  # (CT, N)
        has = s.topo_has[s.spread_topo[p]]  # (CT, N)
        if s.spread_needs_node_counts:
            counts = self._counts(state, snap)  # (TR, N)
            dcn = counts[s.spread_track[p]]  # (CT, N)
            elig = s.spread_elig[s.spread_elig_idx[p]] & (code >= 0)
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
        minm = jnp.min(jnp.where(exists, dc, big), axis=1)  # (CT,)
        dn = jnp.sum(exists, axis=1)  # (CT,)
        md = s.spread_min_domains[p]
        minm = jnp.where((md > 0) & (dn < md), 0, minm)
        return s, dc, minm, code, has

    def _match_at(self, state, s, p, dc, code):
        view = None
        if state is not None and not s.spread_needs_node_counts:
            view = state.sel_dom_view
        return selectors.domain_at(view, s.spread_track[p], dc, code)

    def validate_at(self, state, snap, p, node):
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return jnp.bool_(True)
        s, dc, minm, code, has = self._constraint_state(state, snap, p)
        code_n = code[:, node]  # (CT,)
        has_n = has[:, node]
        match_at = jnp.take_along_axis(
            dc, jnp.maximum(code_n, 0)[:, None], axis=1
        ).squeeze(1)
        selfm = s.spread_self[p].astype(jnp.int64)
        ok = match_at + selfm - minm <= s.spread_max_skew[p]
        applies = s.spread_mask[p] & s.spread_hard[p]
        return jnp.all(jnp.where(applies, has_n & ok, True))


class GatherSpread(ScatterSpread):
    """`PodTopologySpread` with the filter and the score it had before."""

    def filter(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return None
        s, dc, minm, code, has = self._constraint_state(state, snap, p)
        match_at = gather(dc, code)  # (CT, N)
        selfm = s.spread_self[p][:, None].astype(jnp.int64)
        ok = match_at + selfm - minm[:, None] <= s.spread_max_skew[p][:, None]
        applies = (s.spread_mask[p] & s.spread_hard[p])[:, None]
        verdict = jnp.where(applies, has & ok, True)
        return jnp.all(verdict, axis=0)

    def score(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.spread_track is None:
            return None
        s, dc, _, code, has = self._constraint_state(state, snap, p)
        match_at = gather(dc, code)
        applies = (s.spread_mask[p] & ~s.spread_hard[p])[:, None] & has
        return jnp.sum(jnp.where(applies, match_at, 0), axis=0)


class GatherAffinity(InterPodAffinity):
    """`InterPodAffinity` with the filter and the score it had before."""

    def filter(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.aff_track is None:
            return None
        counts = state.sel_dom_counts
        verdict = jnp.ones(snap.num_nodes, bool)

        code = s.topo_code[s.aff_topo[p]]  # (AT, N)
        has = s.topo_has[s.aff_topo[p]]
        dc = counts[s.aff_track[p]]  # (AT, D)
        exists = s.domain_exists[s.aff_topo[p]]
        total = jnp.sum(jnp.where(exists, dc, 0), axis=1)  # (AT,)
        ok = has & (
            (gather(dc, code) > 0)
            | ((total == 0) & s.aff_self[p])[:, None]
        )
        verdict &= jnp.all(
            jnp.where(s.aff_mask[p][:, None], ok, True), axis=0
        )

        codeb = s.topo_code[s.anti_topo[p]]
        hasb = s.topo_has[s.anti_topo[p]]
        okb = ~hasb | (gather(counts[s.anti_track[p]], codeb) == 0)
        verdict &= jnp.all(
            jnp.where(s.anti_mask[p][:, None], okb, True), axis=0
        )

        if s.exist_anti_sel is not None:
            codee = s.topo_code[s.exist_anti_topo]  # (E, N)
            blocked = gather(state.anti_domains, codee) & (codee >= 0)
            m = s.exist_anti_match[:, p]  # (E,)
            verdict &= ~jnp.any(m[:, None] & blocked, axis=0)
        return verdict

    def score(self, state, snap, p):
        s = snap.scheduling
        if s is None or s.waff_track is None:
            return None
        code = s.topo_code[s.waff_topo[p]]  # (WT, N)
        has = s.topo_has[s.waff_topo[p]]
        match_at = gather(state.sel_dom_counts[s.waff_track[p]], code)
        total = jnp.sum(jnp.where(
            s.waff_mask[p][:, None] & has,
            s.waff_weight[p][:, None] * match_at,
            0,
        ), axis=0)
        if s.sym_sel is not None:
            codee = s.topo_code[s.sym_topo]  # (E2, N)
            at = jnp.where(codee >= 0, gather(state.sym_counts, codee), 0)
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


def view_scheduler():
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), PodTopologySpread(),
        InterPodAffinity(hard_pod_affinity_weight=3),
    ]))


def gather_scheduler():
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), GatherSpread(),
        GatherAffinity(hard_pod_affinity_weight=3),
    ]))


# ---------------------------------------------------------------------------
# stores
# ---------------------------------------------------------------------------

#: node -> labels, by the shape of the topology the store has
TOPOLOGIES = {
    # one zone for all: D = 1 under the zone key
    "one_zone": lambda i: {ZONE_LABEL: "z0", HOSTNAME: f"n{i:03d}"},
    "three_zones": lambda i: {ZONE_LABEL: f"z{i % 3}", HOSTNAME: f"n{i:03d}"},
    # every node a zone of its own: D = N under both keys
    "zone_per_node": lambda i: {ZONE_LABEL: f"z{i}", HOSTNAME: f"n{i:03d}"},
    # a third of the nodes without the zone key, a quarter without hostname
    "some_keyless": lambda i: {
        **({ZONE_LABEL: f"z{i % 2}"} if i % 3 else {}),
        **({HOSTNAME: f"n{i:03d}"} if i % 4 else {}),
    },
}


def term(color, key, **scope):
    return PodAffinityTerm(
        topology_key=key,
        label_selector=LabelSelector(match_labels={"color": color}),
        **scope,
    )


def pod(serial, color="green", **spec):
    # a pending pod is a third of a small node, so that a batch spills over
    # the nodes that lack a key (which no term keeps anyone off)
    cpu = 300 if serial < BOUND else 1300
    return Pod(
        name=f"p{serial:05d}", namespace="default", creation_ms=serial,
        labels={"color": color},
        containers=[Container(requests={CPU: cpu, MEMORY: gib // 2})],
        **spec,
    )


def weighted(weight, color, key):
    return WeightedPodAffinityTerm(weight=weight, term=term(color, key))


def spread(key, skew=1, when="DoNotSchedule", color="red", **kw):
    return TopologySpreadConstraint(
        max_skew=skew, topology_key=key, when_unsatisfiable=when,
        label_selector=LabelSelector(match_labels={"color": color}), **kw,
    )


#: the kinds of pod a mix draws from
KINDS = {
    "plain_green": lambda s: pod(s),
    "plain_red": lambda s: pod(s, color="red"),
    "host_anti": lambda s: pod(
        s, pod_anti_affinity_required=[term("green", HOSTNAME)]),
    "zone_anti": lambda s: pod(
        s, color="blue", pod_anti_affinity_required=[term("blue", ZONE_LABEL)]),
    # its own anti term matches others, not itself
    "avoids_red": lambda s: pod(
        s, color="blue", pod_anti_affinity_required=[term("red", ZONE_LABEL)]),
    "follows_red": lambda s: pod(
        s, color="red", pod_affinity_required=[term("red", ZONE_LABEL)]),
    # AT >= 2: the (AT,) escape against the (AT, N) matches
    "follows_red_twice": lambda s: pod(
        s, color="red", pod_affinity_required=[
            term("red", ZONE_LABEL), term("red", HOSTNAME),
        ]),
    "follows_green_and_red": lambda s: pod(
        s, color="green", pod_affinity_required=[
            term("green", ZONE_LABEL), term("red", ZONE_LABEL),
        ]),
    "prefers": lambda s: pod(
        s, color="red",
        pod_affinity_preferred=[weighted(30, "red", ZONE_LABEL)],
        pod_anti_affinity_preferred=[weighted(70, "blue", HOSTNAME)],
    ),
    "prefers_green": lambda s: pod(
        s, color="blue",
        pod_affinity_preferred=[
            weighted(55, "green", HOSTNAME), weighted(5, "red", ZONE_LABEL),
        ],
    ),
    "spread_zone": lambda s: pod(
        s, color="red", topology_spread=[spread(ZONE_LABEL)]),
    "spread_min_domains": lambda s: pod(
        s, color="red", topology_spread=[
            spread(ZONE_LABEL, skew=1, min_domains=4),
            spread(HOSTNAME, skew=2),
        ]),
    "spread_soft": lambda s: pod(
        s, color="red", topology_spread=[
            spread(ZONE_LABEL, when="ScheduleAnyway"),
            spread(HOSTNAME, skew=1, when="ScheduleAnyway", color="green"),
        ]),
}

#: mix -> (kinds bound before the solve, kinds of the pending batch)
MIXES = {
    "two_required_terms": (
        ("plain_red", "plain_green", "follows_red"),
        ("follows_red_twice", "follows_green_and_red", "follows_red",
         "plain_red"),
    ),
    "own_anti": (
        ("plain_green", "plain_red", "host_anti"),
        ("host_anti", "zone_anti", "avoids_red", "plain_red"),
    ),
    "symmetric_carriers": (
        ("host_anti", "zone_anti", "avoids_red"),
        ("plain_green", "plain_red", "host_anti", "avoids_red",
         "zone_anti"),
    ),
    "preferred_symmetric_score": (
        ("prefers", "prefers_green", "follows_red", "plain_green"),
        ("prefers", "plain_red", "prefers_green", "plain_green",
         "follows_red"),
    ),
    "spread_min_domains": (
        ("plain_red", "spread_zone", "plain_green"),
        ("spread_min_domains", "spread_zone", "spread_soft", "plain_red"),
    ),
    "everything": (
        ("host_anti", "prefers", "follows_red", "spread_zone", "avoids_red"),
        ("follows_red_twice", "host_anti", "prefers_green", "spread_soft",
         "spread_min_domains", "zone_anti", "plain_green", "avoids_red"),
    ),
}

CASES = [
    pytest.param(topology, mix, id=f"{topology}-{mix}")
    for topology in TOPOLOGIES for mix in MIXES
]


def store(topology, mix, seed=35, batch=14):
    """(cluster, pending): `BOUND` pods of the mix's first kinds bound to
    seeded nodes, `batch` of its second kinds pending."""
    rng = np.random.default_rng(
        [seed, *hashlib.sha256(f"{topology}/{mix}".encode()).digest()[:4]]
    )
    cluster = Cluster()
    for i in range(N_NODES):
        cluster.add_node(Node(
            name=f"n{i:03d}", labels=TOPOLOGIES[topology](i),
            allocatable={CPU: 4000 * (1 + i % 2), MEMORY: 16 * gib,
                         PODS: 110},
        ))
    before, pending_kinds = MIXES[mix]
    for serial in range(BOUND):
        held = KINDS[before[serial % len(before)]](serial)
        cluster.add_pod(held)
        cluster.bind(held.uid, f"n{int(rng.integers(N_NODES)):03d}")
    pending = []
    for serial in range(BOUND, BOUND + batch):
        new = KINDS[pending_kinds[int(rng.integers(len(pending_kinds)))]](
            serial
        )
        cluster.add_pod(new)
        pending.append(new)
    return cluster, pending


def tables(state) -> dict:
    return {
        name: None if leaf is None else np.asarray(leaf)
        for name, leaf in (("sel_dom_counts", state.sel_dom_counts),
                           ("anti_domains", state.anti_domains),
                           ("sym_counts", state.sym_counts))
    }


def views_by_gather(state, sched) -> dict:
    """What each view has to be: the gather of its table, 0 / False where
    the node lacks the row's key."""
    out = {}
    for name, table, topo in (
        ("sel_dom_view", state.sel_dom_counts, sched.track_topo),
        ("anti_view", state.anti_domains, sched.exist_anti_topo),
        ("sym_view", state.sym_counts, sched.sym_topo),
    ):
        if table is None:
            continue
        code = np.asarray(sched.topo_code)[np.asarray(topo)]  # (T, N)
        at = np.take_along_axis(np.asarray(table), np.maximum(code, 0), 1)
        out[name] = np.where(code >= 0, at, np.zeros((), at.dtype))
    return out


# ---------------------------------------------------------------------------
# (i) the solve against the gather reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology, mix", CASES)
def test_solve_equals_the_gather_reference(topology, mix):
    cluster, pending = store(topology, mix)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    assert selectors.has_domain_tables(snap.scheduling)
    results = []
    for scheduler in (view_scheduler(), gather_scheduler()):
        scheduler.prepare(meta, cluster)
        results.append(scheduler.solve(snap))
    ours, reference = results
    for field in ("assignment", "admitted", "wait", "failed_plugin"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ours, field)),
            np.asarray(getattr(reference, field)), err_msg=field,
        )
    theirs = tables(reference.state)
    for name, table in tables(ours.state).items():
        if table is None:
            assert theirs[name] is None
            continue
        assert table.dtype == theirs[name].dtype
        np.testing.assert_array_equal(table, theirs[name], err_msg=name)
    # derived state stays inside the solve
    assert ours.state.sel_dom_view is None
    assert ours.state.anti_view is None and ours.state.sym_view is None
    # the case is one: something placed, and the mix's terms are in the
    # tables (a batch that never reads a count proves nothing)
    assert (np.asarray(ours.assignment) >= 0).any()


# ---------------------------------------------------------------------------
# (ii) a pod at a time: verdicts, scores, the invariant
# ---------------------------------------------------------------------------


def stepper(ours, reference, snap):
    """A jitted `(state, p) -> (state', choice, what every plugin said)` of
    `ours` on a state that carries views, beside what the gather reference
    says on the same state."""
    plugins = tuple(ours.profile.plugins)
    twins = tuple(reference.profile.plugins)

    def step(snap, state, auxes, p):
        for group in (plugins, twins):
            for plugin, aux in zip(group, auxes):
                plugin.bind_aux(aux)
                plugin.bind_presolve(plugin.prepare_solve(snap))
        said = []
        for plugin, twin in zip(plugins, twins):
            for point in ("filter", "score"):
                a = getattr(plugin, point)(state, snap, p)
                b = getattr(twin, point)(state, snap, p)
                if a is not None or b is not None:
                    said.append((a, b))
        state, (choice, _ok, _code) = runtime._solve_step(
            plugins, state, p, snap
        )
        return state, choice, said

    return jax.jit(step)


@pytest.mark.parametrize("topology, mix", CASES)
def test_views_follow_their_tables_at_every_step(topology, mix):
    cluster, pending = store(topology, mix)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    ours, reference = view_scheduler(), gather_scheduler()
    for scheduler in (ours, reference):
        scheduler.prepare(meta, cluster)
    auxes = tuple(plugin.aux() for plugin in ours.profile.plugins)
    state, _codes = selectors.attach_node_views(
        ours.initial_state(snap), snap.scheduling
    )
    expect = views_by_gather(state, snap.scheduling)
    assert expect, "no table, no view: the case holds nothing"
    step = stepper(ours, reference, snap)
    moved = False
    for p in range(len(pending)):
        before = expect
        state, choice, said = step(snap, state, auxes, jnp.int32(p))
        for a, b in said:
            assert a is not None and b is not None
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"pod {p}"
            )
        expect = views_by_gather(state, snap.scheduling)
        for name, want in expect.items():
            got = np.asarray(getattr(state, name))
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(
                got, want, err_msg=f"{name} after pod {p} -> {int(choice)}"
            )
        moved = moved or any(
            (expect[name] != before[name]).any() for name in expect
        )
    assert moved, "no placement changed a view: the case holds nothing"


# ---------------------------------------------------------------------------
# (iii) the lowering
# ---------------------------------------------------------------------------


def jaxprs(jaxpr, in_loop=False):
    """`(jaxpr, inside a scan / while body)` for `jaxpr` and every jaxpr
    nested in it."""
    from jax import core

    yield jaxpr, in_loop
    for eqn in jaxpr.eqns:
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in core.jaxprs_in_params(eqn.params):
            yield from jaxprs(getattr(sub, "jaxpr", sub), loop)


def node_wide_gathers(closed, width=N_NODES):
    """(in a loop body, outside one): the gathers of `closed` that look up
    at least `width` separate indices — the (N,)-wide lookup out of a (D,)
    row. A row select (`table[rows]`: a few indices, a whole row each) is
    not one."""
    found = {True: [], False: []}
    for jaxpr, in_loop in jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "gather":
                continue
            indices = eqn.invars[1].aval  # (..., index depth)
            if int(np.prod(indices.shape[:-1])) >= width:
                found[in_loop].append(str(eqn))
    return found[True], found[False]


def node_wide_scatters(closed, width=N_NODES):
    """(in a loop body, outside one): the scatters of `closed` that write
    at least `width` separate indices — a node's worth of counts folded
    into a (D,) row. A row update (`table.at[rows, node]`: one index a
    row) is not one."""
    found = {True: [], False: []}
    for jaxpr, in_loop in jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if not eqn.primitive.name.startswith("scatter"):
                continue
            indices = eqn.invars[1].aval  # (..., index depth)
            if int(np.prod(indices.shape[:-1])) >= width:
                found[in_loop].append(str(eqn))
    return found[True], found[False]


def traced_solve(scheduler, topology, mix):
    return trace_solve(scheduler, *store(topology, mix))


def trace_solve(scheduler, cluster, pending):
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    auxes = tuple(plugin.aux() for plugin in scheduler.profile.plugins)
    return jax.make_jaxpr(scheduler._make_solve(1))(
        snap, scheduler.initial_state(snap), auxes
    )


@pytest.mark.parametrize("topology, mix", [
    # antiaffinity-5000n's shape: hostname anti terms, D = N
    pytest.param("zone_per_node", "own_anti", id="antiaffinity"),
    # spread-5000n's: zone constraints, D = 3
    pytest.param("three_zones", "spread_min_domains", id="spread"),
    pytest.param("three_zones", "everything", id="everything"),
    # (with a spread constraint on some_keyless, a node-inclusion policy
    # excludes a keyed node and the zone key's domains hold several
    # nodes: the scatter branch of `PodTopologySpread._constraint_state`
    # counts per pod, keeping its scatter and `_match_at`'s gather)
    pytest.param("some_keyless", "symmetric_carriers", id="keyless"),
])
def test_no_node_wide_gather_in_the_scan_body(topology, mix):
    in_loop, before = node_wide_gathers(
        traced_solve(view_scheduler(), topology, mix)
    )
    assert in_loop == []
    # the one before the scan is there: the views are gathered, once
    assert before


def test_the_gather_reference_has_what_the_test_looks_for():
    """The detector sees the old expressions: the reference's scan body
    holds the node-wide gathers this PR took out."""
    in_loop, _ = node_wide_gathers(
        traced_solve(gather_scheduler(), "zone_per_node", "own_anti")
    )
    assert in_loop


# ---------------------------------------------------------------------------
# (iv) the node-count branch: one-node domains counted in node space
# ---------------------------------------------------------------------------

#: the taint a fifth of `inclusion_store`'s nodes carry, which no pod
#: tolerates
POOL_TAINT = Taint("pool", "reserved", "NoSchedule")


def inclusion_store(key=HOSTNAME, n_nodes=N_NODES, batch=14):
    """(cluster, pending) of nodeinclusion-5000n's shape: every node its
    own hostname in one of three zones, the last fifth tainted; bound and
    pending pods under a required node term that admits every node and a
    hard spread constraint on `key` with both policies Honor, so that every
    eligibility row leaves the tainted nodes out."""
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.add_node(Node(
            name=f"n{i:03d}", labels={ZONE_LABEL: f"z{i % 3}",
                                      HOSTNAME: f"n{i:03d}"},
            taints=[POOL_TAINT] if i >= n_nodes - n_nodes // 5 else [],
            allocatable={CPU: 4000 * (1 + i % 2), MEMORY: 16 * gib,
                         PODS: 110},
        ))

    def honouring(serial):
        return pod(serial, color="red", topology_spread=[spread(
            key, node_affinity_policy="Honor", node_taints_policy="Honor",
        )], node_affinity_required=[NodeSelectorTerm(match_expressions=[
            NodeSelectorRequirement(ZONE_LABEL, "NotIn", ("z9",)),
        ])])

    for serial in range(BOUND):
        held = honouring(serial)
        cluster.add_pod(held)
        cluster.bind(held.uid, f"n{serial * 5 % n_nodes:03d}")
    pending = [honouring(serial) for serial in range(BOUND, BOUND + batch)]
    for new in pending:
        cluster.add_pod(new)
    return cluster, pending


def inclusion_scheduler(spread_plugin=PodTopologySpread):
    return Scheduler(Profile(plugins=[
        NodeResourcesAllocatable(), NodeAffinity(), TaintToleration(),
        spread_plugin(),
    ]))


@pytest.mark.parametrize("key, node_domains", [
    # nodeinclusion-5000n's: a domain a node, counted in node space
    pytest.param(HOSTNAME, True, id="hostname"),
    # zones of four nodes: counted by node and scattered into domains
    pytest.param(ZONE_LABEL, False, id="zone"),
])
def test_one_node_domains_leave_the_scan_body_no_scatter(key, node_domains):
    cluster, pending = inclusion_store(key)
    snap, _meta = cluster.snapshot(pending, now_ms=0)
    assert snap.scheduling.spread_needs_node_counts
    assert snap.scheduling.spread_node_domains is node_domains
    closed = trace_solve(inclusion_scheduler(), cluster, pending)
    scatters, _ = node_wide_scatters(closed)
    gathers, _ = node_wide_gathers(closed)
    if node_domains:
        assert scatters == [] and gathers == []
    else:
        # the detectors see the scatter branch: per pod, a scatter of
        # the counts and of the domains' existence, and a gather back
        assert len(scatters) >= 2 and gathers


def test_the_scatter_reference_has_what_the_test_looks_for():
    """The detector sees the old expression on the hostname store itself:
    the scatter branch's scan body holds its node-wide scatters."""
    closed = trace_solve(inclusion_scheduler(ScatterSpread),
                         *inclusion_store())
    scatters, _ = node_wide_scatters(closed)
    assert len(scatters) >= 2


def plain_store():
    """A store whose snapshot has no selector table (what `basic-5000n`,
    `trimaran-5000n` and `gangs-quota-1024n` solve)."""
    cluster = Cluster()
    for i in range(4):
        cluster.add_node(Node(
            name=f"n{i}", allocatable={CPU: 4000, MEMORY: 16 * gib,
                                       PODS: 110},
        ))
    pending = [pod(i) for i in range(3)]
    for new in pending:
        cluster.add_pod(new)
    return cluster, pending


def test_a_solve_without_tables_lowers_as_if_views_did_not_exist(
    monkeypatch,
):
    """No table, no view: the program of a snapshot without selector
    tables is, StableHLO for StableHLO, the one lowered with the view code
    taken out — the parent's (whose registered programs
    `docs/tpu_lowering.json` holds to their digests, unrefreshed)."""
    cluster, pending = plain_store()
    snap, meta = cluster.snapshot(pending, now_ms=0)
    assert not selectors.has_domain_tables(snap.scheduling)

    def lowered():
        scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
        scheduler.prepare(meta, cluster)
        auxes = tuple(plugin.aux() for plugin in scheduler.profile.plugins)
        return scheduler._make_solve(1).lower(
            snap, scheduler.initial_state(snap), auxes
        ).as_text()

    ours = lowered()
    monkeypatch.setattr(
        selectors, "attach_node_views", lambda st, _sched: (st, None)
    )
    monkeypatch.setattr(selectors, "drop_node_views", lambda st: st)
    assert lowered() == ours


def test_counter_and_no_views_without_tables():
    """A snapshot without selector tables carries no view and counts
    nothing; one with tables counts one a solve."""
    plain, pending = plain_store()
    scheduler = Scheduler(Profile(plugins=[NodeResourcesAllocatable()]))
    snap, meta = plain.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, plain)
    state0, codes = selectors.attach_node_views(
        scheduler.initial_state(snap), snap.scheduling
    )
    assert codes is None and state0.sel_dom_view is None
    assert state0.anti_view is None and state0.sym_view is None
    before = obs.metrics.get(obs.SOLVE_NODE_VIEWS)
    scheduler.solve(snap)
    assert obs.metrics.get(obs.SOLVE_NODE_VIEWS) == before

    cluster, batch = store("three_zones", "own_anti")
    snap, meta = cluster.snapshot(batch, now_ms=0)
    scheduler = view_scheduler()
    scheduler.prepare(meta, cluster)
    scheduler.solve(snap)
    scheduler.solve(snap)
    assert obs.metrics.get(obs.SOLVE_NODE_VIEWS) == before + 2
