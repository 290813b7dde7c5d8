"""In-tree companion plugins (NodeAffinity, TaintToleration): decision tables
mirroring upstream kube-scheduler plugin unit-test patterns (these plugins are
not in /root/reference; see docs/PARITY.md "companion plugins")."""

import numpy as np

from scheduler_plugins_tpu.api.objects import (
    Container,
    Node,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PreferredSchedulingTerm,
    Taint,
    Toleration,
)
from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
from scheduler_plugins_tpu.plugins import (
    NodeAffinity,
    NodeResourcesAllocatable,
    TaintToleration,
)
from scheduler_plugins_tpu.state.cluster import Cluster

gib = 1 << 30


def mknode(name, labels=None, taints=None):
    return Node(
        name=name,
        allocatable={CPU: 8000, MEMORY: 32 * gib, PODS: 110},
        labels=labels or {},
        taints=taints or [],
    )


def mkpod(name, **kw):
    return Pod(name=name, containers=[Container(requests={CPU: 100, MEMORY: gib})], **kw)


def run(nodes, pods, plugins=None):
    c = Cluster()
    for n in nodes:
        c.add_node(n)
    for p in pods:
        c.add_pod(p)
    sched = Scheduler(Profile(plugins=plugins or [NodeResourcesAllocatable(),
                                                  NodeAffinity(), TaintToleration()]))
    return run_cycle(sched, c, now=1000), c


class TestNodeSelector:
    def test_selector_restricts_to_labeled_node(self):
        r, c = run(
            [mknode("a", {"disk": "hdd"}), mknode("b", {"disk": "ssd"})],
            [mkpod("p", node_selector={"disk": "ssd"})],
        )
        assert r.bound["default/p"] == "b"

    def test_selector_no_match_unschedulable(self):
        r, c = run([mknode("a", {"disk": "hdd"})],
                   [mkpod("p", node_selector={"disk": "ssd"})])
        assert "default/p" in r.failed

    def test_no_selector_unconstrained(self):
        r, c = run([mknode("a"), mknode("b")], [mkpod("p")])
        assert "default/p" in r.bound


class TestNodeAffinityRequired:
    def _term(self, key, op, *values):
        return NodeSelectorTerm(match_expressions=[
            NodeSelectorRequirement(key=key, operator=op, values=tuple(values))])

    def test_terms_are_ored(self):
        # pod accepts ssd OR gpu nodes
        r, c = run(
            [mknode("a", {"disk": "hdd"}), mknode("b", {"gpu": "yes"})],
            [mkpod("p", node_affinity_required=[
                self._term("disk", "In", "ssd"), self._term("gpu", "Exists")])],
        )
        assert r.bound["default/p"] == "b"

    def test_expressions_are_anded(self):
        term = NodeSelectorTerm(match_expressions=[
            NodeSelectorRequirement(key="disk", operator="In", values=("ssd",)),
            NodeSelectorRequirement(key="zone", operator="In", values=("z1",)),
        ])
        r, c = run(
            [mknode("a", {"disk": "ssd"}), mknode("b", {"disk": "ssd", "zone": "z1"})],
            [mkpod("p", node_affinity_required=[term])],
        )
        assert r.bound["default/p"] == "b"

    def test_notin_matches_absent_label(self):
        r, c = run(
            [mknode("a", {"tier": "db"}), mknode("b")],
            [mkpod("p", node_affinity_required=[self._term("tier", "NotIn", "db")])],
        )
        assert r.bound["default/p"] == "b"

    def test_gt_numeric(self):
        r, c = run(
            [mknode("a", {"cores": "8"}), mknode("b", {"cores": "64"})],
            [mkpod("p", node_affinity_required=[self._term("cores", "Gt", "16")])],
        )
        assert r.bound["default/p"] == "b"

    def test_match_fields_metadata_name(self):
        term = NodeSelectorTerm(match_fields=[
            NodeSelectorRequirement(key="metadata.name", operator="In", values=("b",))])
        r, c = run([mknode("a"), mknode("b")],
                   [mkpod("p", node_affinity_required=[term])])
        assert r.bound["default/p"] == "b"


class TestNodeAffinityPreferred:
    def test_weighted_preference_steers(self):
        pref = [PreferredSchedulingTerm(weight=100, preference=NodeSelectorTerm(
            match_expressions=[NodeSelectorRequirement(key="disk", operator="In",
                                                       values=("ssd",))]))]
        r, c = run(
            [mknode("a", {"disk": "hdd"}), mknode("b", {"disk": "ssd"})],
            [mkpod("p", node_affinity_preferred=pref)],
            plugins=[NodeAffinity()],
        )
        assert r.bound["default/p"] == "b"

    def test_weights_sum_across_terms(self):
        mk = lambda key, val, w: PreferredSchedulingTerm(weight=w,
            preference=NodeSelectorTerm(match_expressions=[
                NodeSelectorRequirement(key=key, operator="In", values=(val,))]))
        # a matches one 60-weight term; b matches two 40-weight terms
        r, c = run(
            [mknode("a", {"x": "1"}), mknode("b", {"y": "1", "z": "1"})],
            [mkpod("p", node_affinity_preferred=[
                mk("x", "1", 60), mk("y", "1", 40), mk("z", "1", 40)])],
            plugins=[NodeAffinity()],
        )
        assert r.bound["default/p"] == "b"


class TestTaintToleration:
    def test_untolerated_noschedule_filters(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="dedicated", value="gpu")]), mknode("b")],
            [mkpod("p")],
        )
        assert r.bound["default/p"] == "b"

    def test_tolerated_taint_passes(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="dedicated", value="gpu")])],
            [mkpod("p", tolerations=[Toleration(key="dedicated", value="gpu")])],
        )
        assert r.bound["default/p"] == "a"

    def test_exists_toleration_ignores_value(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="dedicated", value="anything")])],
            [mkpod("p", tolerations=[Toleration(key="dedicated", operator="Exists")])],
        )
        assert r.bound["default/p"] == "a"

    def test_empty_key_exists_tolerates_everything(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="k1"), Taint(key="k2", effect="NoExecute")])],
            [mkpod("p", tolerations=[Toleration(operator="Exists")])],
        )
        assert r.bound["default/p"] == "a"

    def test_effect_scoped_toleration(self):
        # toleration scoped to NoSchedule does not cover a NoExecute taint
        r, c = run(
            [mknode("a", taints=[Taint(key="k", effect="NoExecute")]), mknode("b")],
            [mkpod("p", tolerations=[Toleration(key="k", operator="Exists",
                                                effect="NoSchedule")])],
        )
        assert r.bound["default/p"] == "b"

    def test_all_nodes_tainted_unschedulable(self):
        r, c = run([mknode("a", taints=[Taint(key="k")])], [mkpod("p")])
        assert "default/p" in r.failed

    def test_prefer_noschedule_scores_away(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="k", effect="PreferNoSchedule")]),
             mknode("b")],
            [mkpod("p")],
            plugins=[TaintToleration()],
        )
        assert r.bound["default/p"] == "b"

    def test_prefer_noschedule_is_soft(self):
        r, c = run(
            [mknode("a", taints=[Taint(key="k", effect="PreferNoSchedule")])],
            [mkpod("p")],
        )
        assert r.bound["default/p"] == "a"


class TestSpecInterning:
    def test_replicas_share_rows(self):
        from scheduler_plugins_tpu.state.scheduling import build_scheduling
        nodes = [mknode("a", {"disk": "ssd"}), mknode("b")]
        pods = [mkpod(f"p{i}", node_selector={"disk": "ssd"},
                      tolerations=[Toleration(key="k", operator="Exists")])
                for i in range(50)]
        s = build_scheduling(nodes, pods, N=4, P=64)
        assert s.node_term_ok.shape[0] == 2  # one unique spec + pad row
        assert s.tol_ok.shape[0] == 1
        assert (np.asarray(s.pod_node_term[:50]) == 0).all()


from scheduler_plugins_tpu.api.objects import LabelSelector, TopologySpreadConstraint
from scheduler_plugins_tpu.plugins import PodTopologySpread

ZONE = "topology.kubernetes.io/zone"


def spread_pod(name, order=0, hard=True, max_skew=1, key=ZONE, labels=None):
    sel = LabelSelector(match_labels={"app": "web"})
    return Pod(
        name=name,
        creation_ms=order,
        labels=labels if labels is not None else {"app": "web"},
        containers=[Container(requests={CPU: 100, MEMORY: gib})],
        topology_spread=[TopologySpreadConstraint(
            max_skew=max_skew, topology_key=key,
            when_unsatisfiable="DoNotSchedule" if hard else "ScheduleAnyway",
            label_selector=sel)],
    )


class TestPodTopologySpread:
    def _zones(self, *zone_of_node):
        return [mknode(f"n{i}", {ZONE: z}) for i, z in enumerate(zone_of_node)]

    def test_hard_skew_blocks_overloaded_domain(self):
        # z-a already has 2 matching pods, z-b has 0; maxSkew 1 forces z-b
        c = Cluster()
        for n in self._zones("z-a", "z-a", "z-b"):
            c.add_node(n)
        for i in range(2):
            existing = Pod(name=f"e{i}", labels={"app": "web"},
                           containers=[Container(requests={CPU: 100})])
            existing.node_name = "n0"
            c.add_pod(existing)
        c.add_pod(spread_pod("p"))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        assert r.bound["default/p"] == "n2"  # the z-b node

    def test_in_cycle_placements_update_skew(self):
        # 4 replicas over 2 zones: the carry must alternate domains, never
        # exceeding skew 1 at any point in the sequential placement
        c = Cluster()
        for n in self._zones("z-a", "z-a", "z-b", "z-b"):
            c.add_node(n)
        for j in range(4):
            c.add_pod(spread_pod(f"p{j}", order=j))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        zones = {"z-a": 0, "z-b": 0}
        for uid, node in r.bound.items():
            zones[{"n0": "z-a", "n1": "z-a", "n2": "z-b", "n3": "z-b"}[node]] += 1
        assert len(r.bound) == 4
        assert abs(zones["z-a"] - zones["z-b"]) <= 1

    def test_node_missing_key_fails_hard_constraint(self):
        c = Cluster()
        c.add_node(mknode("labeled", {ZONE: "z-a"}))
        c.add_node(mknode("bare"))
        c.add_pod(spread_pod("p"))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        assert r.bound["default/p"] == "labeled"

    def test_unschedulable_when_skew_cannot_hold(self):
        # one zone only has capacity... rather: both nodes in z-a with 3
        # existing matches, maxSkew 1 vs empty existing z-b domain that has
        # no node? -> z-b nodes all cordoned: pod cannot schedule into z-a
        c = Cluster()
        nodes = self._zones("z-a", "z-b")
        nodes[1].unschedulable = True
        for n in nodes:
            c.add_node(n)
        for i in range(2):
            e = Pod(name=f"e{i}", labels={"app": "web"},
                    containers=[Container(requests={CPU: 100})])
            e.node_name = "n0"
            c.add_pod(e)
        c.add_pod(spread_pod("p"))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        # skew on z-a would become 3 vs 0 on (existing, nodeless) z-b
        assert "default/p" in r.failed

    def test_soft_constraint_scores_toward_sparse_domain(self):
        c = Cluster()
        for n in self._zones("z-a", "z-b"):
            c.add_node(n)
        e = Pod(name="e", labels={"app": "web"},
                containers=[Container(requests={CPU: 100})])
        e.node_name = "n0"
        c.add_pod(e)
        c.add_pod(spread_pod("p", hard=False))
        sched = Scheduler(Profile(plugins=[PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        assert r.bound["default/p"] == "n1"

    def test_non_matching_pod_unaffected(self):
        # a pod whose labels do not match its own selector still spreads by
        # counts but does not increment them for later pods
        c = Cluster()
        for n in self._zones("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(spread_pod("p0", order=0, labels={"app": "other"}))
        c.add_pod(spread_pod("p1", order=1, labels={"app": "other"}))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        r = run_cycle(sched, c, now=1000)
        assert len(r.bound) == 2  # skew stays 0-0, both place

    def test_batched_mode_respects_hard_spread(self):
        # cross-node same-wave conflict: 6 replicas, 2 zones x 2 nodes,
        # maxSkew 1 -> at most ... replay oracle in queue order
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

        c = Cluster()
        for n in self._zones("z-a", "z-a", "z-b", "z-b"):
            c.add_node(n)
        for j in range(6):
            c.add_pod(spread_pod(f"p{j}", order=j))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           PodTopologySpread()]))
        pending = sched.sort_pending(c.pending_pods(), c)
        snap, meta = c.snapshot(pending, now_ms=0)
        sched.prepare(meta, c)
        an = np.asarray(profile_batch_solve(sched, snap)[0])[: len(pending)]
        zone_of = {0: "z-a", 1: "z-a", 2: "z-b", 3: "z-b"}
        counts = {"z-a": 0, "z-b": 0}
        for q, n in enumerate(an):
            if n < 0:
                continue
            # replay: at placement time (queue order) the skew must hold
            counts[zone_of[int(n)]] += 1
            assert abs(counts["z-a"] - counts["z-b"]) <= 1, (q, counts)
        assert (an >= 0).sum() >= 4


from scheduler_plugins_tpu.api.objects import (
    PodAffinityTerm,
    WeightedPodAffinityTerm,
)
from scheduler_plugins_tpu.plugins import InterPodAffinity


def term(key=ZONE, app="db", namespaces=()):
    return PodAffinityTerm(
        topology_key=key,
        label_selector=LabelSelector(match_labels={"app": app}),
        namespaces=tuple(namespaces),
    )


def zone_nodes(*zones):
    return [mknode(f"n{i}", {ZONE: z}) for i, z in enumerate(zones)]


def assigned(name, node, labels, **kw):
    p = Pod(name=name, labels=labels,
            containers=[Container(requests={CPU: 100})], **kw)
    p.node_name = node
    return p


def ipa_sched():
    return Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                      InterPodAffinity()]))


class TestInterPodAffinity:
    def test_required_affinity_colocates_by_domain(self):
        c = Cluster()
        for n in zone_nodes("z-a", "z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("db-0", "n0", {"app": "db"}))
        c.add_pod(Pod(name="web", labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term()]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert r.bound["default/web"] in ("n0", "n1")  # the z-a domain

    def test_required_affinity_unschedulable_without_match(self):
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(Pod(name="web", labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term()]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert "default/web" in r.failed

    def test_first_pod_self_match_escape(self):
        # nobody matches app=db, but the pod matches its own term -> allowed
        c = Cluster()
        for n in zone_nodes("z-a"):
            c.add_node(n)
        c.add_pod(Pod(name="db-0", labels={"app": "db"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term()]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert r.bound["default/db-0"] == "n0"

    def test_in_cycle_affinity_sees_earlier_placement(self):
        # db places first (self-escape), web must follow into db's domain
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(Pod(name="db-0", creation_ms=1, labels={"app": "db"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term(app="db")]))
        c.add_pod(Pod(name="web", creation_ms=2, labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term(app="db")]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert r.bound["default/web"] == r.bound["default/db-0"]

    def test_own_anti_affinity_avoids_domain(self):
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("db-0", "n0", {"app": "db"}))
        c.add_pod(Pod(name="db-1", labels={"app": "db"},
                      containers=[Container(requests={CPU: 100})],
                      pod_anti_affinity_required=[term(app="db")]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert r.bound["default/db-1"] == "n1"

    def test_existing_pod_anti_affinity_symmetry(self):
        # the ASSIGNED pod carries the anti term; the incoming pod has no
        # constraints but matches the term's selector -> blocked from z-a
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("lonely", "n0", {"app": "db"},
                           pod_anti_affinity_required=[term(app="db")]))
        c.add_pod(Pod(name="db-1", labels={"app": "db"},
                      containers=[Container(requests={CPU: 100})]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert r.bound["default/db-1"] == "n1"

    def test_in_cycle_anti_carrier_blocks_later_pod(self):
        # replicas with self-anti-affinity spread one per zone; the third
        # has nowhere to go
        c = Cluster()
        for n in zone_nodes("z-a", "z-a", "z-b"):
            c.add_node(n)
        for j in range(3):
            c.add_pod(Pod(name=f"db-{j}", creation_ms=j, labels={"app": "db"},
                          containers=[Container(requests={CPU: 100})],
                          pod_anti_affinity_required=[term(app="db")]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert len(r.bound) == 2
        zones = {r.bound[u][:2] for u in r.bound}  # n0/n1 vs n2
        bound_nodes = set(r.bound.values())
        assert not {"n0", "n1"} <= bound_nodes  # never two in z-a

    def test_preferred_affinity_steers(self):
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("db-0", "n0", {"app": "db"}))
        c.add_pod(Pod(name="web", labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_preferred=[
                          WeightedPodAffinityTerm(weight=100, term=term())]))
        r = run_cycle(Scheduler(Profile(plugins=[InterPodAffinity()])), c,
                      now=1000)
        assert r.bound["default/web"] == "n0"

    def test_preferred_anti_affinity_steers_away(self):
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("db-0", "n0", {"app": "db"}))
        c.add_pod(Pod(name="db-1", labels={"app": "db"},
                      containers=[Container(requests={CPU: 100})],
                      pod_anti_affinity_preferred=[
                          WeightedPodAffinityTerm(weight=100, term=term())]))
        r = run_cycle(Scheduler(Profile(plugins=[InterPodAffinity()])), c,
                      now=1000)
        assert r.bound["default/db-1"] == "n1"

    def test_namespace_scope(self):
        # term scoped to namespace "prod": a "dev" db does not satisfy it
        c = Cluster()
        for n in zone_nodes("z-a", "z-b"):
            c.add_node(n)
        c.add_pod(assigned("db-dev", "n0", {"app": "db"}, namespace="dev"))
        c.add_pod(Pod(name="web", namespace="prod", labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})],
                      pod_affinity_required=[term(namespaces=("prod",))]))
        r = run_cycle(ipa_sched(), c, now=1000)
        assert "prod/web" in r.failed

    def test_batched_anti_affinity_respected(self):
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

        c = Cluster()
        for n in zone_nodes("z-a", "z-a", "z-b", "z-b"):
            c.add_node(n)
        for j in range(4):
            c.add_pod(Pod(name=f"db-{j}", creation_ms=j, labels={"app": "db"},
                          containers=[Container(requests={CPU: 100})],
                          pod_anti_affinity_required=[term(app="db")]))
        sched = ipa_sched()
        pending = sched.sort_pending(c.pending_pods(), c)
        snap, meta = c.snapshot(pending, now_ms=0)
        sched.prepare(meta, c)
        an = np.asarray(profile_batch_solve(sched, snap)[0])[: len(pending)]
        zone_of = {0: "z-a", 1: "z-a", 2: "z-b", 3: "z-b"}
        used_zones = [zone_of[int(n)] for n in an if n >= 0]
        assert len(used_zones) == 2  # one per zone, two deferred
        assert len(set(used_zones)) == 2


class TestNativeStoreGate:
    def test_fast_path_disengages_for_selector_specs(self):
        # the native snapshot fast path passes assigned=[] to
        # build_snapshot; spread/affinity tables need assigned pod objects,
        # so pods carrying such specs must disengage it
        c = Cluster()
        for i, z in enumerate(["z-a", "z-b"]):
            c.add_node(mknode(f"n{i}", {ZONE: z}))
        c.attach_native_store()
        e = Pod(name="e", labels={"app": "web"},
                containers=[Container(requests={CPU: 100})],
                pod_anti_affinity_required=[PodAffinityTerm(
                    topology_key=ZONE,
                    label_selector=LabelSelector(match_labels={"app": "web"}))])
        e.node_name = "n0"
        c.add_pod(e)
        c.add_pod(Pod(name="p", labels={"app": "web"},
                      containers=[Container(requests={CPU: 100})]))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           InterPodAffinity()]))
        r = run_cycle(sched, c, now=1000)
        # symmetry from the ASSIGNED carrier must still block z-a
        assert r.bound["default/p"] == "n1"
        # and removing the spec-carrying pods re-engages the fast path
        c.remove_pod("default/e")
        c.remove_pod("default/p")
        assert not c._affinity_spec_pods


class TestWaveCapacityHostLevelBypass:
    def test_host_level_request_does_not_zero_capacity(self):
        # ephemeral-storage is host-level: zones never report it; the
        # batched NUMA capacity estimate must not starve such nodes
        import jax.numpy as jnp
        from scheduler_plugins_tpu.api.resources import EPHEMERAL_STORAGE
        from scheduler_plugins_tpu.framework import Profile, Scheduler
        from scheduler_plugins_tpu.parallel.solver import profile_batch_solve
        from scheduler_plugins_tpu.plugins import (
            NodeResourcesAllocatable,
            NodeResourceTopologyMatch,
        )
        from scheduler_plugins_tpu.api.objects import (
            NodeResourceTopology, NUMAZone, TopologyManagerPolicy,
            TopologyManagerScope,
        )

        c = Cluster()
        c.add_node(Node(name="n0", allocatable={
            CPU: 8000, MEMORY: 64 * gib, EPHEMERAL_STORAGE: 100 * gib,
            PODS: 110}))
        c.add_nrt(NodeResourceTopology(
            node_name="n0",
            zones=[NUMAZone(numa_id=z, available={CPU: 4000, MEMORY: 24 * gib})
                   for z in range(2)],
            policy=TopologyManagerPolicy.SINGLE_NUMA_NODE,
            scope=TopologyManagerScope.CONTAINER))
        for j in range(2):
            c.add_pod(Pod(name=f"p{j}", creation_ms=j, containers=[Container(
                requests={CPU: 1000, MEMORY: 2 * gib, EPHEMERAL_STORAGE: gib},
                limits={CPU: 1000, MEMORY: 2 * gib, EPHEMERAL_STORAGE: gib})]))
        sched = Scheduler(Profile(plugins=[NodeResourcesAllocatable(),
                                           NodeResourceTopologyMatch()]))
        pending = sched.sort_pending(c.pending_pods(), c)
        snap, meta = c.snapshot(pending, now_ms=0)
        sched.prepare(meta, c)
        an = np.asarray(profile_batch_solve(sched, snap)[0])[: len(pending)]
        assert (an >= 0).all(), an.tolist()


class TestAddedAffinity:
    def test_profile_fenced_to_node_subset(self):
        # NodeAffinityArgs.addedAffinity: every pod of the profile is
        # confined to matching nodes, even with no pod-level affinity
        from scheduler_plugins_tpu.api.config import load_profile
        from scheduler_plugins_tpu.framework import Scheduler

        sched = Scheduler(load_profile({
            "plugins": ["NodeResourcesAllocatable", "NodeAffinity"],
            "pluginConfig": [{"name": "NodeAffinity", "args": {
                "addedAffinity": [{"match_expressions": [
                    {"key": "pool", "operator": "In", "values": ["gpu"]}]}],
            }}],
        }))
        c = Cluster()
        c.add_node(mknode("plain"))
        c.add_node(mknode("fenced", {"pool": "gpu"}))
        c.add_pod(mkpod("p"))
        r = run_cycle(sched, c, now=1000)
        assert r.bound["default/p"] == "fenced"

    def test_added_affinity_ands_with_pod_affinity(self):
        from scheduler_plugins_tpu.api.objects import (
            NodeSelectorRequirement, NodeSelectorTerm,
        )

        plug = NodeAffinity(added_affinity=[NodeSelectorTerm(
            match_expressions=[NodeSelectorRequirement(
                key="pool", operator="In", values=("gpu",))])])
        r, c = run(
            [mknode("gpu-hdd", {"pool": "gpu", "disk": "hdd"}),
             mknode("gpu-ssd", {"pool": "gpu", "disk": "ssd"}),
             mknode("cpu-ssd", {"disk": "ssd"})],
            [mkpod("p", node_selector={"disk": "ssd"})],
            plugins=[plug],
        )
        assert r.bound["default/p"] == "gpu-ssd"
