"""`nodeaffinity-5000n`: the configuration's files, held by hand (`pytest
benchmark/tests`; `tests/test_resident_node_terms.py` is the tier-1 mirror
of the reference comparison, of one rehearsal and of the planted faults).

- the population's counts on three seeds: 5,000 nodes all in `zone1`, every
  pod line carrying the template's one required term and nothing else
  beyond the plain population's line, whose names, requests, prefill and
  SKUs it keeps on the same seed;
- the cell rehearsed through the real command on the CPU backend: `correct:
  true`, every cycle served from resident state, no spec row evaluated and
  nothing laid out again inside the window;
- two planted faults, each ending `correct: false`: a resident row flipped
  after the window (`node-terms`), and a node relabelled `zone3` in the
  store but not in the row (the audit names the pod);
- the control (one legal but different placement a solve) on the cell;
- the audit's own matcher on a table of the six operators, `matchFields`
  and a `nodeSelector` ANDed with an OR of terms;
- the plain reference `references/nodeaffinity.py` against the program's
  sequential solve on seeded 48-node clusters of three zones and two pools:
  a term that refuses two zones of three, a `nodeSelector` ANDed with an OR
  of terms, a preferred term that turns Allocatable's choice, a pod no node
  admits.

A rehearsal's window is 8 s: under six test workers a 4 s window can end
before a cycle binds, and every assertion here reads the result line.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from harness import checks, spec

CELL = "nodeaffinity-5000n.steady"
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")
CONTROL = os.path.join(
    str(spec.BENCH_DIR), "tests", "control_altered_answer.py"
)
ZONE = "topology.kubernetes.io/zone"
SEEDS = [0, 3, 2147483777]
CASES = ["template", "two_zones_refused", "selector_and_terms",
         "preferred_turns", "nobody_admits"]


def rehearse(seed: int, trace: int = 0, seconds: int = 8, index=None,
             command=RUN):
    """(result line, {info: [lines]}, standard error) of one rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    argv = [sys.executable, command, "--workload", CELL, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace),
            "--rehearse-cpu"]
    if index is not None:
        argv += ["--index", str(index)]
    done = subprocess.run(
        argv, capture_output=True, text=True, cwd=str(spec.REPO_DIR),
        env=env, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    info: dict = {}
    for line in lines[:-1]:
        info.setdefault(line["info"], []).append(line)
    return lines[-1], info, done.stderr


def problems(info) -> str:
    return "\n".join(line["what"] for line in info.get("problem", []))


def assert_sound(result: dict, info: dict, stderr: str) -> None:
    assert result["correct"] is True, problems(info)
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


def assert_resident(metrics: dict) -> None:
    """What a traced rehearsal's per-layer metrics have to say."""
    assert metrics["serve_fallback_share"] <= 0.0
    assert metrics["compiles_in_window"] == 0
    assert metrics["node_term_rows_in_window"] == 0
    assert metrics["node_term_rebases_in_window"] == 0
    assert metrics["node_terms_ms_per_cycle"] > 0


# -- the population ---------------------------------------------------------

def population_counts(config: dict, seed: int, prefill: int) -> dict:
    population = spec.population(config, seed)
    cluster = config["cluster"]
    nodes = [json.loads(line) for line in population.nodes()]
    template = cluster["pod_template"]["node_affinity"]
    units = population.prefill(prefill)
    arrivals = [population.unit("arrivals", i) for i in range(200)]
    waves = [population.unit("probe/64", i) for i in range(64)]
    templated = bound = 0
    for unit in units + arrivals + waves:
        assert len(unit.pods) == 1 and unit.binds and not unit.head
        pod = json.loads(unit.pods[0])
        templated += pod["node_affinity"] == template and (
            set(pod) <= {"op", "name", "creation_ms", "requests", "node",
                         "node_affinity"}
        )
        bound += "node" in pod
    return {
        "nodes": len(nodes),
        "labels": {json.dumps(n["labels"], sort_keys=True) for n in nodes},
        "pods": len(units) + len(arrivals) + len(waves),
        "templated": templated, "bound": bound,
        "objects": len(list(population.objects())),
        "uids": len({u.uids[0] for u in units}),
    }


@pytest.mark.parametrize("seed", SEEDS)
def test_population_counts_do_not_depend_on_the_seed(seed):
    config = spec.Cell(CELL).config
    counts = population_counts(config, seed, 50_000)
    assert counts["nodes"] == 5000 and counts["objects"] == 0
    assert counts["labels"] == {json.dumps({ZONE: "zone1"})}
    assert counts["templated"] == counts["pods"] == 50_264
    assert counts["uids"] == counts["bound"] == 50_000


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cluster_and_the_pods_are_the_plain_population_s(seed):
    """Same seed: the SKU of every node, and every pod line but for the
    term, as `basic-5000n` has them: the control shares the draws."""
    ours = spec.population(spec.Cell(CELL).config, seed)
    theirs = spec.population(spec.Cell("basic-5000n.steady").config, seed)
    assert ours.node_specs == theirs.node_specs
    for mine, plain in zip(ours.nodes(), theirs.nodes()):
        mine, plain = json.loads(mine), json.loads(plain)
        assert mine.pop("labels") == {ZONE: "zone1"} and mine == plain
    pairs = list(zip(ours.prefill(300), theirs.prefill(300))) + [
        (ours.unit(stream, i), theirs.unit(stream, i))
        for stream in ("arrivals", "warm/64") for i in range(50)
    ]
    for mine, plain in pairs:
        assert mine.uids == plain.uids and mine.removal == plain.removal
        pod = json.loads(mine.pods[0])
        assert pod.pop("node_affinity")["required"][0]["match_expressions"]
        assert pod == json.loads(plain.pods[0])


# -- the cell through the real command --------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_rehearses_to_a_correct_result(seed):
    result, info, stderr = rehearse(seed, trace=1)
    assert_sound(result, info, stderr)
    assert_resident({k: v["value"] for k, v in result["metrics"].items()})


TAMPERS = {
    # a cell of the one spec's resident row, on the host and as staged
    "row_flipped": (
        ["capacity", "node_affinity", "tamper"],
        "import gc\n"
        "def audit(cluster):\n"
        "    from scheduler_plugins_tpu.serving.engine import ServeEngine\n"
        "    for engine in gc.get_objects():\n"
        "        if isinstance(engine, ServeEngine) and "
        "engine._cluster is cluster:\n"
        "            held = engine._node_terms\n"
        "            held._term.table[1, 0] ^= True\n"
        "            held._stale = True\n"
        "    return []\n",
    ),
    # a node that holds a pod moves to zone3 behind the engine's back: no
    # event, so its column of the row still admits it
    "node_relabelled": (
        ["capacity", "tamper", "node_affinity"],
        "def audit(cluster):\n"
        "    pod = next(p for p in cluster.pods.values() if p.node_name)\n"
        "    cluster.nodes[pod.node_name].labels = "
        "{'topology.kubernetes.io/zone': 'zone3'}\n"
        "    return []\n",
    ),
}


def with_planted_fault(tmp_path, fault: str):
    """An index beside which the configuration names one more audit, which
    plants the fault after the window."""
    audits, source = TAMPERS[fault]
    index = spec.index()
    config = spec.load_json(
        spec.REPO_DIR / "benchmark" / "configs" / "nodeaffinity-5000n.json"
    )
    config["audits"] = audits
    (tmp_path / "configs").mkdir()
    config_path = tmp_path / "configs" / "nodeaffinity-5000n.json"
    config_path.write_text(json.dumps(config))
    for entry in index["configs"]:
        entry["file"] = str(
            config_path if entry["name"] == "nodeaffinity-5000n"
            else spec.REPO_DIR / entry["file"]
        )
    (tmp_path / "audits").mkdir()
    (tmp_path / "audits" / "tamper.py").write_text(source)
    (tmp_path / "index.json").write_text(json.dumps(index))
    return tmp_path / "index.json"


def assert_planted_fault_is_found(tmp_path, fault: str) -> None:
    result, info, _ = rehearse(3, index=with_planted_fault(tmp_path, fault))
    assert result["correct"] is False
    found = problems(info)
    if fault == "row_flipped":
        assert "resident state differs from the store: node-terms" in found
        assert "node_affinity:" not in found  # the store itself is sound
    else:
        assert "node_affinity: " in found and " bound pods sit on a node" in found
        assert " refuses: default/" in found  # the audit names the pod
        assert "resident state differs from the store" in found


@pytest.mark.parametrize("fault", sorted(TAMPERS))
def test_a_planted_fault_ends_not_correct(tmp_path, fault):
    assert_planted_fault_is_found(tmp_path, fault)


def test_an_altered_answer_ends_not_correct():
    result, _info, stderr = rehearse(5, command=CONTROL)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["probe_slots_differing"]["value"] >= 1
    assert compared["probe_hard_violations"]["value"] == 0
    assert compared["problems"]["value"] == 1
    assert stderr.rstrip().endswith("correct: False")


# -- the audit's own matcher --------------------------------------------------

LABELS = {"zone": "z1", "cores": "16", "pool": "a"}
#: (key, operator, values) -> whether a node with `LABELS` satisfies it
OPERATORS = [
    (("zone", "In", ("z1", "z2")), True),
    (("zone", "In", ("z2",)), False),
    (("rack", "In", ("r1",)), False),
    (("zone", "NotIn", ("z2",)), True),
    (("zone", "NotIn", ("z1",)), False),
    (("rack", "NotIn", ("r1",)), True),  # an absent key is not in the set
    (("pool", "Exists", ()), True),
    (("rack", "Exists", ()), False),
    (("rack", "DoesNotExist", ()), True),
    (("pool", "DoesNotExist", ()), False),
    (("cores", "Gt", ("8",)), True),
    (("cores", "Gt", ("16",)), False),
    (("cores", "Lt", ("32",)), True),
    (("cores", "Lt", ("16",)), False),
    (("zone", "Gt", ("1",)), False),  # not an integer
    (("cores", "Gt", ("8", "9")), False),  # one value or none holds
    (("rack", "Lt", ("1",)), False),
]


def _req(key, operator, values=()):
    return types.SimpleNamespace(key=key, operator=operator, values=values)


def _term(expressions=(), fields=()):
    return types.SimpleNamespace(
        match_expressions=[_req(*r) for r in expressions],
        match_fields=[_req(*r) for r in fields],
    )


def _node(name="n1", labels=None):
    return types.SimpleNamespace(
        name=name, labels=LABELS if labels is None else labels
    )


def _pod(uid, node_name, node_selector=None, required=()):
    return types.SimpleNamespace(
        uid=uid, node_name=node_name, node_selector=node_selector or {},
        node_affinity_required=list(required),
    )


@pytest.mark.parametrize("requirement, holds", OPERATORS,
                         ids=[f"{r[0]}-{r[1]}-{'-'.join(r[2])}"
                              for r, _ in OPERATORS])
def test_the_audit_s_matcher_on_the_six_operators(requirement, holds):
    from audits import node_affinity

    assert node_affinity.requirement_holds(*requirement, LABELS) is holds
    # and the program's own matcher says the same of the same table
    from scheduler_plugins_tpu.api.objects import NodeSelectorRequirement

    assert NodeSelectorRequirement(*requirement).matches(LABELS) is holds


def test_the_audit_names_a_pod_its_term_refuses():
    from audits import node_affinity

    in_z1 = _term([("zone", "In", ("z1",))])
    in_z2_by_name = _term([("zone", "In", ("z2",))],
                          fields=[("metadata.name", "In", ("n2",))])
    cluster = types.SimpleNamespace(
        nodes={"n1": _node("n1"),
               "n2": _node("n2", {"zone": "z2", "pool": "b"})},
        pods={
            "ok-term": _pod("ok-term", "n1", required=[in_z1]),
            "ok-or": _pod("ok-or", "n2", required=[in_z1, in_z2_by_name]),
            "ok-selector": _pod("ok-selector", "n1", {"pool": "a"}),
            "ok-plain": _pod("ok-plain", "n2"),
            "ok-pending": _pod("ok-pending", None, {"pool": "zz"}),
            "bad-term": _pod("bad-term", "n2", required=[in_z1]),
            # the selector is ANDed with the terms: n2 is not in pool a
            "bad-and": _pod("bad-and", "n2", {"pool": "a"},
                            required=[in_z1, in_z2_by_name]),
            # the field is part of the term: n1 is not named n2
            "bad-field": _pod("bad-field", "n1", required=[in_z2_by_name]),
        },
    )
    found = node_affinity.audit(cluster)
    assert len(found) == 1 and found[0].startswith("3 bound pods")
    for uid in ("bad-term on n2", "bad-and on n2", "bad-field on n1"):
        assert uid in found[0]
    assert "ok-" not in found[0]
    del cluster.pods["bad-term"], cluster.pods["bad-and"]
    del cluster.pods["bad-field"]
    assert node_affinity.audit(cluster) == []


# -- the reference against the sequential solve ------------------------------

def _zone_in(*zones):
    return {"match_expressions": [
        {"key": ZONE, "operator": "In", "values": list(zones)},
    ]}


def _case_events(case: str, config: dict, seed: int, n_pods: int) -> list:
    """The feed events of one small cluster: the population's nodes in
    three zones and two pools, a prefill, and `n_pods` pending pods whose
    node terms are the case's."""
    population = spec.population(config, seed)
    events = [json.loads(line) for line in population.nodes()]
    if case != "template":
        for i, node in enumerate(events):
            node["labels"] = {ZONE: f"zone{1 + i % 3}", "pool": "ab"[i % 2]}
    events += [
        json.loads(line) for unit in population.prefill(90)
        for line in unit.pods
    ]
    for i in range(n_pods):
        pod = json.loads(population.unit("arrivals", i).pods[0])
        if case == "two_zones_refused":
            pod["node_affinity"] = {"required": [_zone_in("zone2")]}
        elif case == "selector_and_terms":
            # pool b AND (zone1 OR zone3 by a second term)
            pod["node_selector"] = {"pool": "b"}
            pod["node_affinity"] = {"required": [
                _zone_in("zone1"), {"match_expressions": [
                    {"key": ZONE, "operator": "NotIn",
                     "values": ["zone1", "zone2"]},
                ]},
            ]}
        elif case == "preferred_turns":
            # every other pod prefers zone3 (weight 80) and, less, pool a
            pod["node_affinity"] = {"preferred": [
                {"weight": 80, "preference": _zone_in("zone3")},
                {"weight": 20, "preference": {"match_expressions": [
                    {"key": "pool", "operator": "In", "values": ["a"]},
                ]}},
            ]} if i % 2 else None
        elif case == "nobody_admits":
            if i % 3 == 0:
                pod["node_affinity"] = {"required": [_zone_in("zone9")]}
        events.append(pod)
    return events


def solve_both(case: str, seed: int, n_pods: int, resident: bool):
    """(the program's result, the reference's, the snapshot) on the case's
    cluster; with `resident` the snapshot is the serving engine's."""
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
    return got, want, snap


def assert_reference_equals_solve(case: str, seed: int, resident: bool):
    n_pods = 120
    got, want, snap = solve_both(case, seed, n_pods, resident)
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    assignment = want["assignment"][:n_pods]
    placed = assignment >= 0
    zone = np.arange(np.asarray(snap.nodes.mask).shape[0]) % 3  # zone - 1
    pool_b = np.arange(zone.shape[0]) % 2 == 1
    if case == "template":
        assert placed.all()
    elif case == "two_zones_refused":
        assert placed.any() and (zone[assignment[placed]] == 1).all()
    elif case == "selector_and_terms":
        assert placed.any() and pool_b[assignment[placed]].all()
        assert (zone[assignment[placed]] != 1).all()
    elif case == "preferred_turns":
        # the preference turns the choice: the pods that carry it sit in
        # zone3 far more often than the pods that do not
        preferring = np.arange(n_pods) % 2 == 1
        in_zone3 = zone[np.maximum(assignment, 0)] == 2
        assert placed.all()
        assert in_zone3[preferring].mean() > 0.9 > in_zone3[~preferring].mean()
    elif case == "nobody_admits":
        refused = np.arange(n_pods) % 3 == 0
        assert not placed[refused].any() and placed[~refused].all()


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_sequential_solve(case, seed, resident):
    assert_reference_equals_solve(case, seed, resident)


def test_the_reference_refuses_what_it_does_not_implement():
    from references import nodeaffinity

    _got, _want, snap = solve_both("template", 0, 8, False)
    x = checks.reference_inputs(snap)
    with pytest.raises(NotImplementedError):
        nodeaffinity.solve(
            dict(x, **{"scheduling.pend_match": np.zeros((1, 8), bool)}),
            {"plugins": ["NodeResourcesAllocatable", "NodeAffinity"]},
        )
    refusing = x["scheduling.tol_ok"].copy()
    refusing[0, 0] = False
    with pytest.raises(NotImplementedError):
        nodeaffinity.solve(
            dict(x, **{"scheduling.tol_ok": refusing}),
            {"plugins": ["NodeResourcesAllocatable", "NodeAffinity"]},
        )


def test_min_bytes_count_the_two_rows():
    from references import allocatable, nodeaffinity

    extra = nodeaffinity.min_bytes_per_pod(5120, 4) - (
        allocatable.min_bytes_per_pod(5120, 4)
    )
    assert extra == 5120 + 8 * 5120
