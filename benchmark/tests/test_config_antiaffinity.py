"""`antiaffinity-5000n`: the configuration's files, held by hand (`pytest
benchmark/tests`; `tests/test_resident_affinity.py` is the tier-1 mirror of
the reference comparison and of one rehearsal).

- the population's lines and counts on two seeds: 5,000 nodes each its own
  hostname domain, every pod line the template (its labels and its one
  required anti-affinity term), prefilled pods in `sched-0` and one to a
  node, arrivals and waves in `sched-1`, and the cluster's draws the plain
  population's on the same seed;
- the arithmetic by which no unit that is to bind can be refused, from the
  cell's own numbers;
- the plain reference `references/antiaffinity.py` against the program's
  sequential solve on seeded rehearsal clusters: the template as it is, more
  pods than free nodes, a zone key, carriers that block plain pods, required
  affinity with the first pod's escape; a cycle with a preferred term raises;
- the audit `audits/anti_affinity.py` on a planted two-on-a-node store;
- the cell rehearsed through the real command on the CPU backend: `correct:
  true`, every cycle served from resident state, no rebase in the window,
  one carrier row and one track row a bind or a delete.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import checks, spec

CELL = "antiaffinity-5000n.backlog"
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")
HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
SEEDS = [3, 2147483777]
CASES = ["template", "more_pods_than_nodes", "zone_key",
         "carriers_block_plain_pods", "required_affinity"]


def rehearse(seed: int, trace: int = 0, seconds: int = 4):
    """(result line, {info: [lines]}, standard error) of one rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse-cpu"],
        capture_output=True, text=True, cwd=str(spec.REPO_DIR), env=env,
        timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    info: dict = {}
    for line in lines[:-1]:
        info.setdefault(line["info"], []).append(line)
    return lines[-1], info, done.stderr


def assert_sound(result: dict, info: dict, stderr: str) -> None:
    problems = "\n".join(line["what"] for line in info.get("problem", []))
    assert result["correct"] is True, problems
    assert result["attempted"] > 0 and result["failed"] == 0
    for name, numbers in result["compared"].items():
        if name == "probe_cycles_min":
            assert numbers["value"] >= numbers["limit"], name
        else:
            assert numbers["value"] == numbers["limit"], name
    probe = info["probe"][0]
    assert probe["mismatches"] == 0 and probe["hard_violations"] == 0
    assert probe["unserved_cycles"] == 0 and probe["reference_unbound"] == 0
    assert stderr.rstrip().endswith("correct: True")


# -- the population ---------------------------------------------------------

def population_counts(config: dict, seed: int, prefill: int) -> dict:
    population = spec.population(config, seed)
    cluster = config["cluster"]
    template = cluster["pod_template"]
    nodes = [json.loads(line) for line in population.nodes()]
    units = population.prefill(prefill)
    arrivals = [population.unit("arrivals", i) for i in range(200)]
    waves = [population.unit("probe/64", i) for i in range(64)]
    templated = 0
    namespaces: dict = {}
    for unit in units + arrivals + waves:
        assert len(unit.pods) == 1 and unit.binds and not unit.head
        pod = json.loads(unit.pods[0])
        templated += (
            pod["labels"] == template["labels"]
            and pod["pod_anti_affinity"] == template["pod_anti_affinity"]
        )
        namespaces[pod["namespace"]] = namespaces.get(pod["namespace"], 0) + 1
        assert unit.uids == (f"{pod['namespace']}/{pod['name']}",)
        removal = json.loads(unit.removal[0])
        assert (removal["op"], removal["namespace"], removal["name"]) == (
            "delete_pod", pod["namespace"], pod["name"]
        )
    return {
        "nodes": len(nodes),
        "domains": len({
            n["labels"][cluster["hostname_label"]] for n in nodes
        }),
        "own_name": sum(
            n["labels"] == {cluster["hostname_label"]: n["name"]}
            for n in nodes
        ),
        "pods": len(units) + len(arrivals) + len(waves),
        "templated": templated, "namespaces": namespaces,
        "objects": len(list(population.objects())),
        "prefilled_nodes": len({
            json.loads(u.pods[0])["node"] for u in units
        }),
        "unbound_arrivals": sum(
            "node" not in json.loads(u.pods[0]) for u in arrivals + waves
        ),
    }


@pytest.mark.parametrize("seed", SEEDS)
def test_population_counts_do_not_depend_on_the_seed(seed):
    config = spec.Cell(CELL).config
    counts = population_counts(config, seed, 1000)
    assert counts["nodes"] == counts["domains"] == counts["own_name"] == 5000
    assert counts["objects"] == 0
    assert counts["templated"] == counts["pods"] == 1264
    assert counts["namespaces"] == {"sched-0": 1000, "sched-1": 264}
    # one prefilled pod a node: the store opens within the term
    assert counts["prefilled_nodes"] == 1000
    assert counts["unbound_arrivals"] == 264


def test_the_cluster_is_the_plain_population_s():
    """Same seed, same SKU for every node and same requests for every
    arrival as `basic-5000n`: the control shares the draws."""
    ours = spec.population(spec.Cell(CELL).config, 3)
    theirs = spec.population(spec.Cell("basic-5000n.backlog").config, 3)
    assert ours.node_specs == theirs.node_specs
    for i in range(50):
        mine = json.loads(ours.unit("arrivals", i).pods[0])
        plain = json.loads(theirs.unit("arrivals", i).pods[0])
        assert {k: mine[k] for k in plain} == plain


def test_the_prefill_refuses_more_pods_than_nodes():
    config = spec.Cell(CELL, rehearse=True).config
    with pytest.raises(ValueError, match="one a node"):
        spec.population(config, 3).prefill(config["cluster"]["nodes"] + 1)


@pytest.mark.parametrize("rehearse", [False, True])
def test_no_unit_that_is_to_bind_can_be_refused(rehearse):
    """Pods alive never pass the prefilled + `outstanding` bound and not
    yet deleted + `outstanding` pending, and a warm or probe wave of at most
    the largest warmed bucket comes over that: under one pod a node that
    has to stay within the cluster, whose smallest SKU holds any one pod."""
    cell = spec.Cell(CELL, rehearse=rehearse)
    cluster = cell.config["cluster"]
    prefilled = cell.mix["prefill_bound_pods"]
    outstanding = cell.params["outstanding"]
    wave = max(cell.params["warm_pod_counts"])
    assert outstanding <= wave  # the probe is a batch: at most `outstanding`
    alive = prefilled + 2 * outstanding + wave
    assert alive <= cluster["nodes"]
    if not rehearse:
        assert (prefilled, outstanding, wave, alive) == (
            1000, 1000, 1024, 4024
        )
    smallest = min(cluster["skus"], key=lambda s: s["cpu_milli"])
    assert cluster["pod_requests"]["cpu_milli"][1] <= smallest["cpu_milli"]
    assert cluster["pod_requests"]["memory_bytes"][1] <= (
        smallest["memory_bytes"]
    )


# -- the reference against the sequential solve ------------------------------

def _case_events(case: str, config: dict, seed: int, n_pods: int) -> list:
    """The feed events of one small cluster: the population's nodes, a
    prefill of 20, and `n_pods` pending pods whose terms are the case's."""
    population = spec.population(config, seed)
    events = [json.loads(line) for line in population.nodes()]
    if case == "zone_key":
        for i, node in enumerate(events):
            if i % 8:  # every eighth node is in no zone: it takes any pod
                node["labels"][ZONE] = f"zone-{i % 5}"
    prefill = 0 if case == "required_affinity" else 20
    events += [
        json.loads(line) for unit in population.prefill(prefill)
        for line in unit.pods
    ]
    for i in range(n_pods):
        pod = json.loads(population.unit("arrivals", i).pods[0])
        term = pod["pod_anti_affinity"]["required"][0]
        if case == "zone_key":
            # a zone that holds a pod of the term refuses every other
            term["topology_key"] = ZONE
        elif case == "carriers_block_plain_pods" and i % 2:
            del pod["pod_anti_affinity"]  # matched by the carriers' term
        elif case == "required_affinity":
            # replicas that must share a node with one of their own: the
            # first is let through by its own match, the rest follow it
            # until the node is full, half of them anti to another colour
            del pod["pod_anti_affinity"]
            pod["pod_affinity"] = {"required": [dict(term)]}
            if i % 2:
                pod["labels"] = {"color": "red"}
                pod["pod_affinity"]["required"][0]["label_selector"] = {
                    "match_labels": {"color": "red"}
                }
                pod["pod_anti_affinity"] = {"required": [term]}
        events.append(pod)
    return events


def solve_both(case: str, seed: int, n_pods: int, resident: bool):
    """(the program's result, the reference's) on the case's cluster; with
    `resident` the snapshot is the serving engine's, padded axes and all."""
    import importlib

    import scheduler_plugins_tpu  # noqa: F401  (switches x64 on)
    from scheduler_plugins_tpu.api.config import load_profile
    from scheduler_plugins_tpu.bridge.feed import apply_event
    from scheduler_plugins_tpu.framework import Scheduler
    from scheduler_plugins_tpu.serving.engine import ServeEngine
    from scheduler_plugins_tpu.state.cluster import Cluster

    config = spec.Cell(CELL, rehearse=True).config
    cluster = Cluster()
    engine = ServeEngine().attach(cluster) if resident else None
    for event in _case_events(case, config, seed, n_pods):
        apply_event(cluster, event)
    scheduler = Scheduler(load_profile(config["profile"]))
    for plugin in scheduler.profile.plugins:
        plugin.configure_cluster(cluster)
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    if resident:
        snap, meta = engine.refresh(cluster, pending, now_ms=0)
    else:
        snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    got = scheduler.solve(snap)
    reference = importlib.import_module(f"references.{config['reference']}")
    want = reference.solve(checks.reference_inputs(snap), config["profile"])
    return got, want, meta


def assert_reference_equals_solve(case: str, seed: int, resident: bool):
    nodes = spec.Cell(CELL, rehearse=True).config["cluster"]["nodes"]
    n_pods = nodes if case == "more_pods_than_nodes" else 60
    got, want, _meta = solve_both(case, seed, n_pods, resident)
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    placed = want["assignment"][want["assignment"] >= 0]
    if case == "template":
        assert len(placed) == n_pods == len(set(placed))
    elif case == "more_pods_than_nodes":
        # 20 nodes are taken: the rest get one pod each, the others none
        assert len(placed) == nodes - 20 == len(set(placed))
    elif case == "zone_key":
        # a zone takes one pod of the term at the most (none where the
        # prefill sits in it); the nodes in no zone take all the others
        zoned = [n for n in placed if n % 8]
        assert len(placed) == n_pods and len(zoned) <= 5 < len(placed)
    elif case == "carriers_block_plain_pods":
        # queue order is arrival order: the even ones carry the term. A
        # carrier has its node to itself; the plain pods share theirs
        carriers = set(want["assignment"][0:n_pods:2])
        plain = set(want["assignment"][1:n_pods:2])
        assert len(placed) == n_pods and len(carriers) == n_pods // 2
        assert not carriers & plain and len(plain) < n_pods // 2
    elif case == "required_affinity":
        # each colour on one node, and not the other colour's
        assert len(placed) > 2 and len(set(placed)) == 2


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 1])
def test_reference_equals_the_sequential_solve(case, seed, resident):
    assert_reference_equals_solve(case, seed, resident)


def test_the_reference_refuses_a_preferred_term():
    from references import antiaffinity

    P, N = 8, 8
    x = {
        "scheduling.aff_track": np.zeros((P, 1), np.int32),
        "scheduling.waff_mask": np.zeros((P, 1), bool),
        "nodes.alloc": np.ones((N, 4), np.int64),
    }
    x["scheduling.waff_mask"][0, 0] = True
    with pytest.raises(NotImplementedError, match="preferred"):
        antiaffinity.solve(x, {"plugins": ["InterPodAffinity"]})


def test_min_bytes_count_the_topology_row_and_both_domain_rows():
    from references import allocatable, antiaffinity

    extra = antiaffinity.min_bytes_per_pod(
        5120, 4
    ) - allocatable.min_bytes_per_pod(5120, 4)
    assert extra == 5120 * 5 + 5120 * 8 + 5120 + 8 + 1


# -- the audit ----------------------------------------------------------------

def _store(events):
    import scheduler_plugins_tpu  # noqa: F401
    from scheduler_plugins_tpu.bridge.feed import apply_event
    from scheduler_plugins_tpu.state.cluster import Cluster

    cluster = Cluster()
    for event in events:
        apply_event(cluster, event)
    return cluster


def test_the_audit_finds_two_on_a_node():
    from audits import anti_affinity

    config = spec.Cell(CELL, rehearse=True).config
    events = _case_events("template", config, 3, 0)
    assert anti_affinity.audit(_store(events)) == []
    pods = [e for e in events if e["op"] == "upsert_pod"]
    planted = dict(pods[1], node=pods[0]["node"])
    found = anti_affinity.audit(_store(events + [planted]))
    # each of the two carries the term and matches the other's
    assert len(found) == 1 and found[0].startswith("2 (carrier, matching")
    # a pod the term does not match may share the node
    stranger = dict(planted, labels={"color": "red"})
    del stranger["pod_anti_affinity"]
    assert anti_affinity.audit(_store(events + [stranger])) == []
    # and one in a namespace the term does not name
    outsider = dict(stranger, labels={"color": "green"}, namespace="other")
    assert anti_affinity.audit(_store(events + [outsider])) == []


# -- the cell through the real command --------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_rehearses_to_a_correct_result(seed):
    result, info, stderr = rehearse(seed, trace=1)
    assert_sound(result, info, stderr)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["backlog.serve_fallback_share"] <= 0.0
    assert metrics["backlog.compiles_in_window"] == 0
    assert metrics["backlog.selector_rebases_in_window"] == 0
    assert metrics["backlog.selector_tables_ms_per_cycle"] > 0
    assert metrics["backlog.affinity_tables_ms_per_cycle"] > 0
    # a bind and a delete for each arrival: one track and one term each
    assert metrics["backlog.selector_rows_per_cycle"] > 1.0
    assert metrics["backlog.affinity_carrier_rows_per_cycle"] == (
        metrics["backlog.selector_rows_per_cycle"]
    )
