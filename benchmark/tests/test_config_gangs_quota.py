"""`gangs-quota-1024n`: the configuration's files, held by hand (`pytest
benchmark/tests`; `tests/test_gangs_quota_cell.py` is the tier-1 mirror and
runs two of the seeds).

- the cell through the real command with `--rehearse-cpu` on 16 seeds: every
  one `correct: true`, what is pending after the drain is what the
  population holds back, the daemon's `bound_total` and the ledger's count
  both equal the arrivals, nothing compiled in the window;
- the population's lines under a sha256 digest for two seeds, at full and at
  rehearsal size, as `test_populations.py` holds `plain`: the traffic of an
  accepted cell is frozen;
- a population's shapes do not depend on the seed: every seed gives the same
  number of nodes, namespaces, quotas and PodGroups;
- the plain reference against the program's sequential solve, several seeds,
  at a small size, with gangs over their quota in the batch.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import checks, spec

CELL = "gangs-quota-1024n.backlog"
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")
SEEDS = [0, 1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 144, 233, 377,
         2147483777, 2147484019]

#: {size/seed: {what: sha256 over the lines in the order they are sent}}
GOLDEN = {
 "full/0": {
  "nodes": "7988c95321633fe1651148ecb413026be1c83fc2ed44b0c0273abfb007a05b78",
  "objects": "d32ab7fc600ff7367078d42231111b2201223bfa16579ab980d77fef1a7e9bd0",
  "prefill/1000": "cd188e0300b1047c65ac69dca9e69b25902999f7d808dd96669d44e1d9ff7cea",
  "arrivals/2000": "11f746b62daef75d7318953c51bad7e6128ef87a14849e197f591553945fa01f",
  "warm/512": "71b4fb342029c8f87354235d75c094254c07199d5a50000ae37d3fcf9fa2059b",
  "probe/512": "6a21face684b3b44a74ae565b2dd9a319085df51027d818d4cba7df89aaccb77"
 },
 "full/3": {
  "nodes": "7988c95321633fe1651148ecb413026be1c83fc2ed44b0c0273abfb007a05b78",
  "objects": "8c4077a051f01f01327c54aef24e3171ffa77c0b745c6d4ee88b341f8e5ad45a",
  "prefill/1000": "b3cb0f9293baf01f973870de447d72d523c5e46ac56c290b9ff2cbc50a18a979",
  "arrivals/2000": "347206ca8c4c525227692af3961c2647eef84654e2075e6e9d6b98c6ad18eed7",
  "warm/512": "86fb72515a01548efb3b8cf3de444fbb118b7079454ca3f86b22c4939684c500",
  "probe/512": "184d3764f7038c7aefec117787f35097cf56ad2017a165173c0d2fe8fc26ab9b"
 },
 "rehearsal/0": {
  "nodes": "a6c3d5aeaad5df8d6c7039da5f835daea9f64c0f988433e0690be84d7a77f188",
  "objects": "bd12e476e013ba043c7fe5425555e9e551e557d89aebc45c81d8cd6fab5faf68",
  "prefill/20": "1b70c319baa0efe180249fb424d309300b92c70c3ba8546b1830098ee525f267",
  "arrivals/2000": "4c4b9b5c81f3279c06e7526bb007faaa77573182b788e6fd374fa299e4e7f7a0",
  "warm/512": "bbc80ebe50245b63a2a04c5c7d9aca09566725c858f448aaa27cbcc03470ff1c",
  "probe/512": "2a98e75ba1118747c00da1b5461a8886ac99836ff6f03605726135ee412ef695"
 },
 "rehearsal/3": {
  "nodes": "a6c3d5aeaad5df8d6c7039da5f835daea9f64c0f988433e0690be84d7a77f188",
  "objects": "2121bc5d70a5440fe357908d828f67f5be750900da0cb05769f4cebc03784513",
  "prefill/20": "3202bcc42e136a162f108db4358b143d161bd4b160806c87a9d7bc4cb11e5b20",
  "arrivals/2000": "0e94a90cd5d9154f08701bc428bd80573c7c79aabd6b2b279ef1d2958437e378",
  "warm/512": "ec62ef2522687523287a30f1caa9237440c210f486221ad7525f6139238fb041",
  "probe/512": "27b08f4909f80a594b30306c57d0b99fa391a81b654668150b9325c57139f288"
 },
}


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
    compared = result["compared"]
    for name, numbers in compared.items():
        if name == "probe_cycles_min":
            assert numbers["value"] >= numbers["limit"], name
        else:
            assert numbers["value"] == numbers["limit"], name
    # what the population holds back is all that is pending, and it is
    # whole gangs of 16
    held = compared["pending_after_drain"]["value"]
    assert held > 0 and held % 16 == 0
    # the daemon's count is the store's: /healthz, the exit line and the
    # ledger agree on the arrivals (`client_counts` names any that differ)
    arrivals = compared["bound_of_arrivals"]["limit"]
    assert compared["bound_of_arrivals"]["value"] == arrivals
    assert "ledger bound" not in problems and "were deleted" not in problems
    probe = info["probe"][0]
    assert probe["mismatches"] == 0 and probe["hard_violations"] == 0
    assert probe["unserved_cycles"] == 0 and probe["reference_unbound"] == 0
    assert stderr.rstrip().endswith("correct: True")


def digests(config: dict, seed: int, prefill: int) -> dict:
    population = spec.population(config, seed)

    def sha(lines) -> str:
        digest = hashlib.sha256()
        for line in lines:
            digest.update(line)
        return digest.hexdigest()

    def units(stream: str, count: int):
        for index in range(count):
            unit = population.unit(stream, index)
            yield from unit.head + unit.pods + unit.removal
            yield b"binds" if unit.binds else b"held"

    return {
        "nodes": sha(population.nodes()),
        "objects": sha(population.objects()),
        f"prefill/{prefill}": sha(
            line for unit in population.prefill(prefill)
            for line in unit.head + unit.pods + unit.removal
        ),
        "arrivals/2000": sha(units("arrivals", 2000)),
        "warm/512": sha(units("warm/512", 60)),
        "probe/512": sha(units("probe/512", 60)),
    }


def store_counts(config: dict, seed: int) -> dict:
    """What the set-up lines of a seed leave in a store, by kind."""
    population = spec.population(config, seed)
    ops: dict = {}
    for line in list(population.nodes()) + list(population.objects()):
        event = json.loads(line)
        ops[event["op"]] = ops.get(event["op"], 0) + 1
    return ops


def solve_both(seed: int, jobs: int):
    """(the program's sequential solve, the plain reference's) of one
    batch: the rehearsal cluster with its prefill, `jobs` jobs of the
    window's stream pending, the held gangs among them."""
    import importlib

    import scheduler_plugins_tpu  # noqa: F401  (switches x64 on)
    from scheduler_plugins_tpu.api.config import load_profile
    from scheduler_plugins_tpu.bridge.feed import apply_event
    from scheduler_plugins_tpu.framework import Scheduler
    from scheduler_plugins_tpu.state.cluster import Cluster

    config = spec.Cell(CELL, rehearse=True).config
    population = spec.population(config, seed)
    cluster = Cluster()
    lines = list(population.nodes()) + list(population.objects())
    lines += [line for unit in population.prefill(150) for line in unit.pods]
    held = 0
    for index in range(jobs):
        unit = population.unit("arrivals", index)
        lines += unit.head + unit.pods
        held += 0 if unit.binds else len(unit.pods)
    for line in lines:
        assert apply_event(cluster, json.loads(line))["ok"], line
    scheduler = Scheduler(load_profile(config["profile"]))
    for plugin in scheduler.profile.plugins:
        plugin.configure_cluster(cluster)
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    got = scheduler.solve(snap)
    reference = importlib.import_module(f"references.{config['reference']}")
    want = reference.solve(checks.reference_inputs(snap), config["profile"])
    return got, want, held, meta


def assert_reference_equals_solve(seed: int) -> None:
    # 100 jobs of 4.5 pods on 48 8-GPU nodes over 150 prefilled: the GPUs
    # run out, so pods fit nowhere and gangs wait short of their quorum;
    # three held gangs of 16 sit under a quota of 8
    got, want, held, meta = solve_both(seed, 100)
    for name in ("assignment", "admitted", "wait"):
        assert (np.asarray(getattr(got, name)) == want[name]).all(), name
    assert meta.index.names[-1] == "nvidia.com/gpu"
    assert held == 48
    placed = want["assignment"] >= 0
    assert 0 < int(placed.sum()) < len(meta.pod_names)
    # reserved and waiting: members the quota admitted whose gang is short
    assert int(want["wait"].sum()) >= 8
    assert int((~want["admitted"][: len(meta.pod_names)]).sum()) >= 8


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_rehearses_to_a_correct_result(seed):
    result, info, stderr = rehearse(seed)
    assert_sound(result, info, stderr)
    assert set(result["metrics"]) == {"setup_s", "bound_pods_per_s"}


def test_a_traced_rehearsal_reads_every_new_metric():
    result, info, stderr = rehearse(3, trace=1)
    assert_sound(result, info, stderr)
    metrics = result["metrics"]
    for name in ("gang_tables_ms_per_cycle", "quota_tables_ms_per_cycle",
                 "preemption_ms_per_cycle", "gang_permit_ms_per_cycle",
                 "gang_rejections_per_cycle", "quota_refusals_per_cycle",
                 "gang_wait_share", "axis_rebases_in_window"):
        assert isinstance(metrics[f"backlog.{name}"]["value"], float), name
    # 0 but for the window's edge: a cycle whose `Snapshot` opens inside the
    # window and whose `ServeRefresh/assemble` opens after it counts as one
    # that fell back (the basic cells read 0.02 now and then for the same)
    assert metrics["backlog.serve_fallback_share"]["value"] < 0.2
    assert metrics["backlog.axis_rebases_in_window"]["value"] == 0.0
    assert metrics["backlog.quota_refusals_per_cycle"]["value"] > 0
    assert metrics["backlog.gang_wait_share"]["value"] > 0


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_population_lines_are_frozen(key):
    size, seed = key.split("/")
    config = spec.Cell(CELL, rehearse=size == "rehearsal").config
    prefill = 1000 if size == "full" else 20
    assert digests(config, int(seed), prefill) == GOLDEN[key]


@pytest.mark.parametrize("rehearsal", [False, True])
def test_no_shape_depends_on_the_seed(rehearsal):
    config = spec.Cell(CELL, rehearse=rehearsal).config
    jobs = config["cluster"]["jobs"]
    counts = [store_counts(config, seed) for seed in SEEDS[:6]]
    assert all(c == counts[0] for c in counts), counts
    assert counts[0] == {
        "upsert_node": config["cluster"]["nodes"],
        "upsert_namespace": jobs["tenants"] + 1,
        "upsert_quota": jobs["tenants"] + 1,
        "upsert_pod_group": (jobs["slots"] + jobs["wave_slots"]
                             + jobs["capped"]["held_slots"]),
    }


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reference_equals_the_sequential_solve(seed):
    assert_reference_equals_solve(seed)


def test_min_bytes_count_the_wider_row_and_the_side_reads():
    import importlib.util

    from references import allocatable

    # by its path: a test of the fixture may have loaded the fixture's own
    # copy under the same module name in this process
    module_spec = importlib.util.spec_from_file_location(
        "promoted_gangs_quota",
        spec.BENCH_DIR / "references" / "gangs_quota.py",
    )
    gangs_quota = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(gangs_quota)
    four = gangs_quota.min_bytes_per_pod(1024, 4)
    assert four > allocatable.min_bytes_per_pod(1024, 4)
    assert gangs_quota.min_bytes_per_pod(1024, 5) - four == 1024 * 8 + 10 * 8
