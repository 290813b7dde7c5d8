"""PodTopologySpread counts one-node domains in node space
(`SchedulingState.spread_node_domains`).

Where an eligibility row leaves out a node that carries its constraint's
key, the spread counts each pod's domains by node under the pod's own row.
Where besides no domain of any held key holds more than one node (a
hostname key), a domain's count is its node's own: the plugin reads it in
node space, and neither scatters the counts into a (CT, D) table nor
gathers them back out. Held here:

(i) on seeded clusters of 12-48 nodes (hostname constraints, hard and soft,
both policies at both values, `minDomains`, taints of every effect,
tolerations, node terms, nodes without the key), from the fresh build and
from the resident engine: the solve under the flag equals, slot for slot,
the scatter formula (`ScatterSpread`, kept in `test_domain_node_views.py`)
and `benchmark/references/nodeinclusion.py`; (ii) `validate_at` gives the
scatter formula's verdict at every (pod, node); (iii) the flag is the same
from the fresh build and from the resident engine through node adds and
deletes, and a node that shares a hostname turns it False with the answers
still equal; (iv) `scheduler_solve_spread_node_domains_total` counts one a
solve on the path and nothing on the others; (v) a snapshot without the
flag lowers, StableHLO for StableHLO, to the program the scatter formula
lowers to.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scheduler_plugins_tpu.api.objects import (
    ZONE_LABEL,
    Container,
    LabelSelector,
    Node,
    NodeSelectorRequirement as Req,
    NodeSelectorTerm as Term,
    Pod,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
from scheduler_plugins_tpu.plugins.intree import PodTopologySpread
from scheduler_plugins_tpu.serving import ServeEngine
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import observability as obs

from test_domain_node_views import (
    ScatterSpread,
    inclusion_scheduler,
    inclusion_store,
    plain_store,
)

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

gib = 1 << 30
HOSTNAME = "kubernetes.io/hostname"

#: what a node may carry: nothing, or a taint of each effect
TAINTS = [
    [],
    [Taint("foo", "bar", "NoSchedule")],
    [Taint("gpu", "", "NoExecute")],
    [Taint("spot", "yes", "PreferNoSchedule")],
]

#: what a pod may tolerate
TOLERATIONS = [
    [],
    [Toleration(key="foo", operator="Exists")],
    [Toleration(operator="Exists", effect="NoExecute")],
]

#: a node term: none, one that refuses a zone, one that admits two
TERMS = [None, ("NotIn", ("z2",)), ("In", ("z0", "z1"))]


def node(i, taints=0, hostname=None):
    labels = {ZONE_LABEL: f"z{i % 3}"}
    # every seventh node lacks the key
    if hostname is not None or i % 7 != 3:
        labels[HOSTNAME] = hostname or f"n{i:03d}"
    return Node(
        name=f"n{i:03d}", labels=labels, taints=list(TAINTS[taints]),
        allocatable={CPU: 4000 * (1 + i % 2), MEMORY: 16 * gib, PODS: 110},
    )


def random_pod(rng, serial, n_nodes, honour=False):
    """A pod of label `web` or `db`, with any tolerations and node term,
    and none or one hostname constraint: hard or soft, under either
    policy at either value, with or without `minDomains`. `honour`: a hard
    one under both Honor policies with no toleration, which the tainted
    nodes leave out of its eligibility row."""
    policies = ("Honor", "Ignore")
    constraints = []
    if honour or rng.integers(5):
        hard = honour or bool(rng.integers(4))
        constraints.append(TopologySpreadConstraint(
            max_skew=int(rng.integers(1, 3)), topology_key=HOSTNAME,
            when_unsatisfiable="DoNotSchedule" if hard else "ScheduleAnyway",
            label_selector=LabelSelector(match_labels={"app": "web"}),
            min_domains=(
                int(rng.integers(1, n_nodes + 4))
                if hard and rng.integers(3) == 0 else None
            ),
            node_affinity_policy=(
                "Honor" if honour else policies[int(rng.integers(2))]
            ),
            node_taints_policy=(
                "Honor" if honour else policies[int(rng.integers(2))]
            ),
        ))
    term = TERMS[int(rng.integers(len(TERMS)))]
    return Pod(
        name=f"p{serial:05d}", creation_ms=serial,
        labels={"app": ("web", "db")[int(rng.integers(4) == 0)]},
        containers=[Container(requests={
            CPU: int(rng.integers(100, 900)), MEMORY: gib // 2,
        })],
        tolerations=[] if honour else list(
            TOLERATIONS[int(rng.integers(len(TOLERATIONS)))]
        ),
        topology_spread=constraints,
        node_affinity_required=[] if term is None else [Term(
            match_expressions=[Req(ZONE_LABEL, *term)],
        )],
    )


def seeded_store(seed, cluster=None):
    """(cluster, n_nodes): seeded nodes (n000 tainted NoSchedule, the rest
    drawn), as many pods bound at random, twice as many pending."""
    rng = np.random.default_rng(4100 + seed)
    n_nodes = (12, 30, 48)[seed % 3]
    cluster = Cluster() if cluster is None else cluster
    for i in range(n_nodes):
        cluster.add_node(node(
            i, taints=1 if i == 0 else int(rng.integers(len(TAINTS))),
        ))
    for serial in range(n_nodes):
        held = random_pod(rng, serial, n_nodes)
        cluster.add_pod(held)
        cluster.bind(held.uid, f"n{int(rng.integers(n_nodes)):03d}")
    for serial in range(n_nodes, 3 * n_nodes):
        cluster.add_pod(random_pod(rng, serial, n_nodes,
                                   honour=serial == n_nodes))
    return cluster, n_nodes


def scheduler(spread_plugin=PodTopologySpread):
    return inclusion_scheduler(spread_plugin)


def snapshot(cluster, sched, resident):
    pending = sched.sort_pending(cluster.pending_pods(), cluster)
    if resident:
        engine = ServeEngine().attach(cluster)
        held = engine.refresh(cluster, pending, now_ms=0)
        assert held is not None, engine.fallback_reason(cluster, pending)
        return held
    return cluster.snapshot(pending, now_ms=0)


RESULTS = ("assignment", "admitted", "wait", "failed_plugin")


# ---------------------------------------------------------------------------
# (i) the solve against the scatter formula and the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_solve_equals_the_scatter_formula_and_the_reference(seed, resident):
    from harness import checks
    from references import nodeinclusion

    cluster, n_nodes = seeded_store(seed)
    ours, theirs = scheduler(), scheduler(ScatterSpread)
    snap, meta = snapshot(cluster, ours, resident)
    s = snap.scheduling
    assert s.spread_needs_node_counts and s.spread_node_domains
    results = []
    for sched in (ours, theirs):
        sched.prepare(meta, cluster)
        results.append(sched.solve(snap))
    got, scattered = results
    for name in RESULTS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(scattered, name)), err_msg=name,
        )
    np.testing.assert_array_equal(np.asarray(got.state.sel_counts),
                                  np.asarray(scattered.state.sel_counts))
    profile = {"plugins": [p.name for p in ours.profile.plugins]}
    want = nodeinclusion.solve(checks.reference_inputs(snap), profile)
    for name in ("assignment", "admitted", "wait"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), want[name], err_msg=name,
        )
    # the case is one: pods placed, and some refused by the spread
    assignment = np.asarray(got.assignment)[:2 * n_nodes]
    assert (assignment >= 0).sum() > n_nodes // 2
    assert (assignment < 0).any()


# ---------------------------------------------------------------------------
# (ii) validate_at
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_validate_at_gives_the_scatter_formula_s_verdict(seed):
    cluster, _n = seeded_store(seed)
    sched = scheduler()
    snap, meta = snapshot(cluster, sched, resident=False)
    assert snap.scheduling.spread_node_domains
    sched.prepare(meta, cluster)
    ours, theirs = PodTopologySpread(), ScatterSpread()

    @jax.jit
    def verdicts(state):
        def at(p, n):
            return (ours.validate_at(state, snap, p, n),
                    theirs.validate_at(state, snap, p, n))

        nodes = jnp.arange(snap.num_nodes)
        pods = jnp.arange(snap.num_pods)
        return jax.vmap(lambda p: jax.vmap(lambda n: at(p, n))(nodes))(pods)

    # before the solve, and against the counts its placements left
    for state in (sched.initial_state(snap), sched.solve(snap).state):
        a, b = verdicts(state)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).any() and not np.asarray(a).all()


# ---------------------------------------------------------------------------
# (iii) the flag, fresh and resident, as the nodes move
# ---------------------------------------------------------------------------


def test_the_flag_follows_the_nodes_on_both_paths_with_equal_answers():
    """A resident engine and a twin that builds every cycle afresh, through
    node adds and deletes: the flag agrees every cycle, and every cycle
    places the same. A node that shares a hostname turns it False (a
    domain of two nodes keeps the scatter); deleting it turns it back."""
    clusters = Cluster(), Cluster()
    engine = ServeEngine().attach(clusters[0])
    for cl in clusters:
        seeded_store(0, cl)
    scheds = scheduler(), scheduler()
    rng = np.random.default_rng(41)
    serial = 1000
    flags = []
    for cycle, change in enumerate([
        None, ("add", 40), ("gone", "n005"), ("add", 41, "n002"),
        None, ("gone", "n041"), ("add", 42),
    ]):
        now = 1000 * (cycle + 1)
        for cl in clusters:
            if change is None:
                pass
            elif change[0] == "add":
                cl.add_node(node(change[1], taints=1,
                                 hostname=(change[2:] or [None])[0]))
            else:
                cl.remove_node(change[1])
        for _ in range(6):
            serial += 1
            state = rng.bit_generator.state
            for cl in clusters:
                rng.bit_generator.state = state
                cl.add_pod(random_pod(rng, serial, 12, honour=_ == 0))
        pending = scheds[0].sort_pending(clusters[0].pending_pods(),
                                         clusters[0])
        served = engine.refresh(clusters[0], pending, now_ms=now)
        assert served is not None
        fresh, _ = clusters[1].snapshot(pending, now_ms=now)
        flag = served[0].scheduling.spread_node_domains
        assert flag == fresh.scheduling.spread_node_domains, cycle
        assert served[0].scheduling.spread_needs_node_counts
        flags.append(flag)
        reports = [
            run_cycle(scheds[0], clusters[0], now=now, serve=engine),
            run_cycle(scheds[1], clusters[1], now=now),
        ]
        assert reports[0].bound == reports[1].bound, cycle
        assert reports[0].failed == reports[1].failed, cycle
        assert engine.refresh(clusters[0], [], now_ms=now + 500) is not None
        assert engine.verify(clusters[0]) is None
    assert flags == [True, True, True, False, False, True, True]


# ---------------------------------------------------------------------------
# (iv) the counter
# ---------------------------------------------------------------------------


def spread_store():
    """spread-5000n's shape: zone constraints, no policy that leaves a
    keyed node out, so the spread reads its (TR, D) mirror."""
    cluster = Cluster()
    for i in range(12):
        cluster.add_node(node(i, hostname=f"n{i:03d}"))
    pending = []
    for serial in range(14):
        new = Pod(
            name=f"p{serial:05d}", creation_ms=serial, labels={"app": "web"},
            containers=[Container(requests={CPU: 300, MEMORY: gib // 2})],
            topology_spread=[TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE_LABEL,
                label_selector=LabelSelector(match_labels={"app": "web"}),
            )],
        )
        cluster.add_pod(new)
        pending.append(new)
    return cluster, pending


#: store -> (its scheduler, whether the spread counts in node space)
STORES = {
    "nodeinclusion": (lambda: inclusion_store(), scheduler, True),
    "zone_node_counts": (lambda: inclusion_store(ZONE_LABEL), scheduler,
                         False),
    "spread": (spread_store, scheduler, False),
    "plain": (plain_store, lambda: Scheduler(Profile(
        plugins=[NodeResourcesAllocatable()])), False),
}


@pytest.mark.parametrize("name", list(STORES))
def test_the_counter_counts_one_a_solve_on_the_path_only(name):
    make, make_scheduler, counted = STORES[name]
    cluster, pending = make()
    snap, meta = cluster.snapshot(pending, now_ms=0)
    flag = snap.scheduling is not None and snap.scheduling.spread_node_domains
    assert flag is counted
    sched = make_scheduler()
    sched.prepare(meta, cluster)
    before = obs.metrics.get(obs.SOLVE_SPREAD_NODE_DOMAINS)
    sched.solve(snap)
    sched.solve(snap)
    assert obs.metrics.get(obs.SOLVE_SPREAD_NODE_DOMAINS) == before + (
        2 if counted else 0
    )


# ---------------------------------------------------------------------------
# (v) the programs without the flag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["spread", "zone_node_counts"])
def test_a_solve_without_the_flag_lowers_as_the_scatter_formula(name):
    """Without the flag the program is, StableHLO for StableHLO, the one
    the scatter formula lowers to: spread-5000n's (the mirror branch) and
    a zone-keyed node-count store's (the scatter branch) keep their text
    and their compile-cache keys."""
    make, _make_scheduler, _counted = STORES[name]
    cluster, pending = make()
    snap, meta = cluster.snapshot(pending, now_ms=0)
    assert not snap.scheduling.spread_node_domains

    def lowered(spread_plugin):
        sched = scheduler(spread_plugin)
        sched.prepare(meta, cluster)
        auxes = tuple(plugin.aux() for plugin in sched.profile.plugins)
        return sched._make_solve(1).lower(
            snap, sched.initial_state(snap), auxes
        ).as_text()

    assert lowered(PodTopologySpread) == lowered(ScatterSpread)
