"""`spread-5000n`: the configuration's files, held by hand (`pytest
benchmark/tests`; `tests/test_resident_selectors.py` is the tier-1 mirror of
the reference comparison and of one rehearsal).

- the population's counts on three seeds: 5,000 nodes in 3 zones of 1,666 /
  1,667, every pod line carrying the template's label and its one
  constraint, the prefill's zone counts within 1 of each other, and the
  cluster's draws the plain population's on the same seed;
- the cell rehearsed through the real command on the CPU backend: `correct:
  true`, every cycle served from resident state, one selector rebase (the
  cold build), two +-1 rows a bound pod;
- a planted fault, one resident count off by one, ends `correct: false`;
- the plain reference `references/spread.py` against the program's
  sequential solve on seeded 48-node clusters: the template as it is, two of
  three zones blocked, `minDomains` lifting the minimum to 0, a
  ScheduleAnyway constraint, a hostname key beside the zone key.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import checks, spec

CELL = "spread-5000n.steady"
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")
ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
SEEDS = [0, 3, 2147483777]
CASES = ["template", "two_zones_blocked", "min_domains", "schedule_anyway",
         "hostname_key"]


def rehearse(seed: int, trace: int = 0, seconds: int = 4, index=None):
    """(result line, {info: [lines]}, standard error) of one rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    command = [sys.executable, RUN, "--workload", CELL, "--seed", str(seed),
               "--seconds", str(seconds), "--trace", str(trace),
               "--rehearse-cpu"]
    if index is not None:
        command += ["--index", str(index)]
    done = subprocess.run(
        command, capture_output=True, text=True, cwd=str(spec.REPO_DIR),
        env=env, timeout=600,
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


# -- the population ---------------------------------------------------------

def population_counts(config: dict, seed: int, prefill: int) -> dict:
    population = spec.population(config, seed)
    cluster = config["cluster"]
    nodes = [json.loads(line) for line in population.nodes()]
    zone_of = {n["name"]: n["labels"][cluster["zone_label"]] for n in nodes}
    by_zone: dict = {}
    for zone in zone_of.values():
        by_zone[zone] = by_zone.get(zone, 0) + 1
    template = cluster["pod_template"]
    prefilled: dict = {}
    templated = 0
    units = population.prefill(prefill)
    arrivals = [population.unit("arrivals", i) for i in range(200)]
    waves = [population.unit("probe/64", i) for i in range(64)]
    for unit in units + arrivals + waves:
        assert len(unit.pods) == 1 and unit.binds and not unit.head
        pod = json.loads(unit.pods[0])
        templated += (
            pod["labels"] == template["labels"]
            and pod["topology_spread"] == template["topology_spread"]
        )
        if "node" in pod:
            zone = zone_of[pod["node"]]
            prefilled[zone] = prefilled.get(zone, 0) + 1
    return {
        "nodes": len(nodes), "zones": by_zone, "prefilled": prefilled,
        "pods": len(units) + len(arrivals) + len(waves),
        "templated": templated, "objects": len(list(population.objects())),
        "uids": len({u.uids[0] for u in units}),
    }


@pytest.mark.parametrize("seed", SEEDS)
def test_population_counts_do_not_depend_on_the_seed(seed):
    config = spec.Cell(CELL).config
    counts = population_counts(config, seed, 50_000)
    assert counts["nodes"] == 5000 and counts["objects"] == 0
    assert counts["zones"] == {"moon-1": 1667, "moon-2": 1667, "moon-3": 1666}
    assert counts["templated"] == counts["pods"] == 50_264
    assert counts["uids"] == 50_000
    assert counts["prefilled"] == {
        "moon-1": 16667, "moon-2": 16667, "moon-3": 16666,
    }


def test_the_cluster_is_the_plain_population_s():
    """Same seed, same SKU for every node and same requests for every
    arrival as `basic-5000n`: the control shares the draws."""
    spread = spec.Cell(CELL).config
    basic = spec.Cell("basic-5000n.steady").config
    ours, theirs = spec.population(spread, 3), spec.population(basic, 3)
    assert ours.node_specs == theirs.node_specs
    for i in range(50):
        mine = json.loads(ours.unit("arrivals", i).pods[0])
        plain = json.loads(theirs.unit("arrivals", i).pods[0])
        assert {k: mine[k] for k in plain} == plain


# -- the cell through the real command --------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_rehearses_to_a_correct_result(seed):
    result, info, stderr = rehearse(seed, trace=1)
    assert_sound(result, info, stderr)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["serve_fallback_share"] <= 0.0
    assert metrics["compiles_in_window"] == 0
    assert metrics["selector_rebases_in_window"] == 0
    assert metrics["selector_tables_ms_per_cycle"] > 0
    # a bind and a delete for each arrival, one track each
    assert 1.0 < metrics["selector_rows_per_cycle"]


def with_planted_fault(tmp_path):
    """An index beside which the configuration names one more audit, one
    that adds 1 to a cell of the engine's resident selector counts."""
    index = spec.index()
    config = spec.load_json(
        spec.REPO_DIR / "benchmark" / "configs" / "spread-5000n.json"
    )
    config["audits"] = ["tamper_selector_count", "capacity"]
    (tmp_path / "configs").mkdir()
    config_path = tmp_path / "configs" / "spread-5000n.json"
    config_path.write_text(json.dumps(config))
    for entry in index["configs"]:
        entry["file"] = str(
            config_path if entry["name"] == "spread-5000n"
            else spec.REPO_DIR / entry["file"]
        )
    (tmp_path / "audits").mkdir()
    (tmp_path / "audits" / "tamper_selector_count.py").write_text(
        "import gc\n"
        "def audit(cluster):\n"
        "    from scheduler_plugins_tpu.serving.engine import ServeEngine\n"
        "    for engine in gc.get_objects():\n"
        "        if isinstance(engine, ServeEngine) and "
        "engine._cluster is cluster:\n"
        "            held = engine._selectors\n"
        "            held.track_base = held.track_base.at[0, 0].add(1)\n"
        "    return []\n"
    )
    (tmp_path / "index.json").write_text(json.dumps(index))
    return tmp_path / "index.json"


def test_a_resident_count_off_by_one_is_reported(tmp_path):
    result, info, _ = rehearse(3, index=with_planted_fault(tmp_path))
    assert result["correct"] is False
    assert (
        "resident state differs from the store: selector-counts"
        in problems(info)
    )


# -- the reference against the sequential solve ------------------------------

def _case_events(case: str, config: dict, seed: int, n_pods: int) -> list:
    """The feed events of one small cluster: the population's nodes, a
    prefill, and `n_pods` pending pods whose constraints are the case's."""
    population = spec.population(config, seed)
    events = [json.loads(line) for line in population.nodes()]
    if case == "hostname_key":
        for node in events:
            node["labels"][HOSTNAME] = node["name"]
    if case == "two_zones_blocked":
        # cordoned: their pods still count, they take none
        for node in events:
            if node["labels"][ZONE] != "moon-2":
                node["unschedulable"] = True
    events += [
        json.loads(line) for unit in population.prefill(90)
        for line in unit.pods
    ]
    for i in range(n_pods):
        pod = json.loads(population.unit("arrivals", i).pods[0])
        constraint = pod["topology_spread"][0]
        if case == "min_domains":
            constraint["min_domains"] = 4  # three exist: the minimum is 0
            constraint["max_skew"] = 32
        elif case == "schedule_anyway":
            constraint["when_unsatisfiable"] = "ScheduleAnyway"
        elif case == "hostname_key":
            pod["topology_spread"].append(dict(
                constraint, topology_key=HOSTNAME, max_skew=3,
            ))
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
    return got, want


def assert_reference_equals_solve(case: str, seed: int, resident: bool):
    n_pods = 400
    got, want = solve_both(case, seed, n_pods, resident)
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    placed = int((want["assignment"] >= 0).sum())
    if case == "two_zones_blocked":
        # the open zone takes pods until it leads the others by maxSkew,
        # then every pod is refused by the spread filter alone
        assert 0 < placed <= 3
    elif case == "min_domains":
        # the minimum counts as 0: a zone holds 30 of the prefill and takes
        # two more before count + 1 - 0 passes 32; three zones, six pods
        assert placed == 6
    else:
        assert 0 < placed <= n_pods


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_sequential_solve(case, seed, resident):
    assert_reference_equals_solve(case, seed, resident)


def test_min_bytes_count_the_topology_row_and_the_counts():
    from references import allocatable, spread

    extra = spread.min_bytes_per_pod(5120, 4) - allocatable.min_bytes_per_pod(
        5120, 4
    )
    assert extra == 5120 * 5 + 8 * 8 + 8
