"""What decides `correct`, piece by piece, on stubs and on cycles built in
this process: the arrays a reference is given, the resident-state rule, the
client's counts, and the audits by name."""

import json
from types import SimpleNamespace

import pytest

from harness import checks, spec

FIXTURE_INDEX = spec.BENCH_DIR / "tests" / "fixtures" / "gangs-mini" / "index.json"

NODE_PATHS = {f"nodes.{name}" for name in (
    "alloc", "capacity", "requested", "nonzero_requested", "limits", "mask",
    "region", "zone", "pod_count", "terminating", "nominated",
)}
POD_PATHS = {f"pods.{name}" for name in (
    "req", "limits", "predicted_cpu_millis", "container_req",
    "container_is_init", "container_mask", "priority", "ns", "gang", "qos",
    "mask", "creation_ms", "gated",
)}
METRIC_PATHS = {f"metrics.{name}" for name in (
    "cpu_avg", "cpu_tlp", "cpu_peaks", "cpu_std", "mem_avg", "mem_std",
    "cpu_valid", "cpu_tlp_valid", "mem_valid", "missing_cpu_millis",
)}


@pytest.fixture
def fixture_index():
    spec.use_index(FIXTURE_INDEX)
    yield
    spec.use_index(spec.REPO_DIR / "BENCHMARK.json")


def _cycle(cell_name: str, seed: int, units: int):
    """(snapshot, program's solve, config) of one cycle over the
    configuration's rehearsal cluster with `units` arrivals pending."""
    import scheduler_plugins_tpu  # noqa: F401  (switches x64 on)
    from scheduler_plugins_tpu.api.config import load_profile
    from scheduler_plugins_tpu.bridge.feed import apply_event
    from scheduler_plugins_tpu.framework import Scheduler
    from scheduler_plugins_tpu.state.cluster import Cluster

    config = spec.Cell(cell_name, rehearse=True).config
    population = spec.population(config, seed)
    cluster = Cluster()
    lines = list(population.nodes()) + list(population.objects())
    for unit in population.prefill(150):
        lines += unit.head + unit.pods
    for index in range(units):
        unit = population.unit("arrivals", index)
        lines += unit.head + unit.pods
    lines += [population.side(side, 0) for side in config["feed_side_events"]]
    for line in lines:
        assert apply_event(cluster, json.loads(line)).get("ok", True)
    scheduler = Scheduler(load_profile(config["profile"]))
    for plugin in scheduler.profile.plugins:
        plugin.configure_cluster(cluster)
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    return snap, scheduler.solve(snap), config


def test_reference_inputs_of_a_basic_cycle():
    snap, _got, _config = _cycle("basic-5000n.steady", 0, 40)
    x = checks.reference_inputs(snap)
    assert set(x) == NODE_PATHS | POD_PATHS | set(checks.ALIASES)
    for alias, path in checks.ALIASES.items():
        assert x[alias] is x[path]
    assert x["nodes.alloc"].shape == x["alloc"].shape == (64, 4)


def test_reference_inputs_of_a_trimaran_cycle():
    snap, _got, _config = _cycle("trimaran-5000n.steady", 0, 40)
    x = checks.reference_inputs(snap)
    assert set(x) == (NODE_PATHS | POD_PATHS | METRIC_PATHS
                      | set(checks.ALIASES) | set(checks.METRIC_ALIASES))
    for name in checks.METRIC_ALIASES:
        assert x[name] is x[f"metrics.{name}"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_fixtures_cycle_carries_gangs_and_quota_and_its_reference_agrees(
        fixture_index, seed):
    import numpy as np

    # 36 gangs, three of them over quota; 48 nodes hold them all
    snap, got, config = _cycle("gangs-mini.backlog", seed, 36)
    x = checks.reference_inputs(snap)
    assert {"pods.gang", "pods.ns", "gangs.min_member", "gangs.assigned",
            "gangs.total_members", "quota.max", "quota.min", "quota.used",
            "quota.has_quota"} <= set(x)
    assert not any(key.startswith("metrics.") for key in x)
    assert (x["gangs.min_member"][x["gangs.mask"]] == 4).all()
    reference = spec.load_module("references", config["reference"])
    want = reference.solve(x, config["profile"])
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    # each gang over quota: three members placed and waiting, one refused
    assert int(want["wait"].sum()) == 3
    assert int((want["assignment"] >= 0).sum()) == 33 * 4 + 3
    assert int((~want["admitted"] & x["pods.mask"]).sum()) == 3 * 4 - 3


class _Engine:
    def __init__(self, rebases, divergence=None, divergences=0, owns=True):
        self.rebases = rebases
        self.owns = owns
        self.antientropy_divergences = divergences
        self._divergence = divergence
        self.verified = 0

    def refresh(self, cluster, pending, now_ms=0):
        return object() if self.owns else None

    def verify(self, cluster):
        self.verified += 1
        return self._divergence


class _Feed:
    def locked(self):
        import contextlib

        return contextlib.nullcontext()


def _daemon(engine):
    return SimpleNamespace(engine=engine, feed=_Feed(), cluster=None)


def test_resident_state_judges_the_state_not_the_implementation():
    # (a) today's trimaran: not declared, the engine never owned a cycle
    never = _Engine(rebases=0)
    assert checks.resident_state(_daemon(never), False) == []
    assert never.verified == 0
    assert checks.resident_state(_daemon(None), False) == []
    # (b) not declared, owned cycles (any number of rebases), state equal
    assert checks.resident_state(_daemon(_Engine(rebases=3)), False) == []
    # (c) owned and diverged: a problem, declared or not
    for declared in (False, True):
        found = checks.resident_state(
            _daemon(_Engine(rebases=1, divergence="column-digest")), declared)
        assert any("differs from the store" in p for p in found)
        found = checks.resident_state(
            _daemon(_Engine(rebases=1, divergences=2)), declared)
        assert any("anti-entropy" in p for p in found)
    # an engine that has fallen back is not held to columns it does not
    # serve from, unless the configuration declares resident state
    fell_back = _Engine(rebases=1, divergence="axis-width", owns=False)
    assert checks.resident_state(_daemon(fell_back), False) == []
    assert checks.resident_state(_daemon(fell_back), True)
    # (d) declared: exactly one rebase, today's rule
    assert checks.resident_state(_daemon(_Engine(rebases=1)), True) == []
    for rebases in (0, 2):
        assert checks.resident_state(_daemon(_Engine(rebases=rebases)), True)
    assert checks.resident_state(_daemon(None), True)


def _report(**over):
    report = {
        "arrivals": 100, "held": 0, "deletes": 60, "prefilled": 20,
        "refused": 0, "bound_base": 7,
        "sync": {"pods": 60, "pending": 0},
        "healthz": {"bound_total": 107, "parked_cycles": 0, "degraded": False},
    }
    report.update(over)
    return report


def _ledger(bound, deleted=0, twice=False):
    stamps = [(f"default/a-{i}", i) for i in range(bound)]
    if twice:
        stamps.append(stamps[0])
    return SimpleNamespace(pods_bound=bound, pods_deleted=deleted,
                           bind_stamps=stamps)


def test_client_counts_hold_the_daemon_to_what_the_client_expected():
    assert checks.client_counts(_report(), _ledger(108), (8, 0)) == []
    # a plain population expects every arrival bound: one short is a problem
    assert checks.client_counts(
        _report(healthz={"bound_total": 106, "parked_cycles": 0,
                         "degraded": False}), _ledger(107), (8, 0))
    assert checks.client_counts(
        _report(sync={"pods": 60, "pending": 1}), _ledger(108), (8, 0))
    # pods the population holds back stay pending, and in the store
    held = _report(held=8, sync={"pods": 68, "pending": 8})
    assert checks.client_counts(held, _ledger(108), (8, 0)) == []
    assert checks.client_counts(
        _report(held=8, sync={"pods": 68, "pending": 9}), _ledger(108), (8, 0))
    # a warm wave may delete its own pending pods; the window may not
    assert checks.client_counts(_report(), _ledger(108, deleted=4), (8, 4)) == []
    assert checks.client_counts(_report(), _ledger(108, deleted=5), (8, 4))
    assert checks.client_counts(_report(), _ledger(108, twice=True), (8, 0))


def _pod(uid, node, requests, labels=None):
    namespace, name = uid.split("/")
    return SimpleNamespace(
        uid=uid, name=name, namespace=namespace, node_name=node,
        labels=labels or {}, containers=[SimpleNamespace(requests=requests)],
    )


def _store(pods, nodes=None, pod_groups=None, quotas=None):
    return SimpleNamespace(
        pods={p.uid: p for p in pods},
        nodes={name: SimpleNamespace(allocatable=alloc)
               for name, alloc in (nodes or {}).items()},
        pod_groups=pod_groups or {}, quotas=quotas or {},
    )


def test_capacity_audit_covers_every_resource_a_node_declares():
    audit = spec.load_module("audits", "capacity").audit
    nodes = {"n0": {"cpu": 1000, "memory": 100, "pods": 2, "vendor/gpu": 1}}
    fits = [_pod("default/a", "n0", {"cpu": 600, "memory": 50}),
            _pod("default/b", "n0", {"cpu": 400, "vendor/gpu": 1}),
            _pod("default/c", None, {"cpu": 9000})]
    assert audit(_store(fits, nodes)) == []
    for over in ({"cpu": 401}, {"cpu": 400, "memory": 51},
                 {"cpu": 400, "vendor/gpu": 2}, {"cpu": 400, "other/thing": 1}):
        pods = fits[:1] + [_pod("default/b", "n0", over)]
        assert audit(_store(pods, nodes)) == [
            "1 nodes hold more than their allocatable"]
    third = fits[:2] + [_pod("default/d", "n0", {})]
    assert audit(_store(third, nodes)) == [
        "1 nodes hold more than their allocatable"]
    assert "unknown node" in audit(
        _store([_pod("default/a", "gone", {})], nodes))[0]


def test_gang_atomicity_audit():
    audit = spec.load_module("audits", "gang_atomicity").audit
    label = {"scheduling.x-k8s.io/pod-group": "g"}
    groups = {"team/g": SimpleNamespace(min_member=3)}

    def members(bound):
        return [_pod(f"team/m{i}", "n0" if i < bound else None, {}, label)
                for i in range(4)]

    for bound in (0, 3, 4):
        assert audit(_store(members(bound), pod_groups=groups)) == []
    for bound in (1, 2):
        found = audit(_store(members(bound), pod_groups=groups))
        assert found and "team/g" in found[0]
    # the label of another namespace's group is not this group's
    stray = [_pod("other/m0", "n0", {}, label)]
    assert audit(_store(stray, pod_groups=groups)) == []


def test_quota_bounds_audit():
    audit = spec.load_module("audits", "quota_bounds").audit
    quotas = {"team": SimpleNamespace(max={"cpu": 1000})}
    pods = [_pod("team/a", "n0", {"cpu": 600, "memory": 10 ** 12}),
            _pod("team/b", "n0", {"cpu": 400}),
            _pod("team/c", None, {"cpu": 5000}),
            _pod("free/d", "n0", {"cpu": 5000})]
    assert audit(_store(pods, quotas=quotas)) == []
    pods.append(_pod("team/e", "n1", {"cpu": 1}))
    found = audit(_store(pods, quotas=quotas))
    assert found and "team" in found[0] and "cpu" in found[0]


def test_audits_are_found_by_the_configurations_names(fixture_index):
    cell = spec.Cell("gangs-mini.backlog", rehearse=True)
    assert cell.config["audits"] == ["capacity", "gang_atomicity",
                                     "quota_bounds"]
    label = {"scheduling.x-k8s.io/pod-group": "g"}
    store = _store(
        [_pod("team/m0", "n0", {"cpu": 2000}, label),
         _pod("team/m1", None, {"cpu": 2000}, label)],
        nodes={"n0": {"cpu": 1000, "pods": 10}},
        pod_groups={"team/g": SimpleNamespace(min_member=2)},
        quotas={"team": SimpleNamespace(max={"cpu": 1500})},
    )
    found = checks.audits(cell, store)
    assert [p.split(":")[0] for p in found] == [
        "capacity", "gang_atomicity", "quota_bounds"]
    plain = spec.Cell("gangs-mini.backlog", rehearse=True)
    del plain.config["audits"]
    assert [p.split(":")[0] for p in checks.audits(plain, store)] == ["capacity"]
