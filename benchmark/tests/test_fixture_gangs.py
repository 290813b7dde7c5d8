"""A configuration that is in no benchmark, brought as files alone
(`fixtures/gangs-mini/`: index, configuration, cell, population, reference,
and three faults to plant), rehearsed on the CPU backend through the real
command. The harness carries its PodGroups, namespaces and quota, audits
what its guarantees state, and says `correct: false`, with the problem
named, for each fault."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import spec

FIXTURE = spec.BENCH_DIR / "tests" / "fixtures" / "gangs-mini"
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")


def _rehearse(index):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, RUN, "--index", str(index), "--workload",
         "gangs-mini.backlog", "--seed", "3", "--seconds", "4", "--trace",
         "0", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=str(spec.REPO_DIR), env=env,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    info = {}
    for line in lines[:-1]:
        info.setdefault(line["info"], []).append(line)
    return lines[-1], info, done.stderr


def _with_fault(tmp_path, **changes):
    """A copy of the fixture whose configuration differs in `changes`."""
    root = tmp_path / "gangs-mini"
    shutil.copytree(FIXTURE, root)
    config_path = root / "configs" / "gangs-mini.json"
    index = spec.load_json(root / "index.json")
    index["configs"][0]["file"] = str(config_path)
    (root / "index.json").write_text(json.dumps(index))
    config = spec.load_json(config_path)
    config.update(changes)
    config_path.write_text(json.dumps(config))
    return root / "index.json"


def _problems(info) -> str:
    return "\n".join(line["what"] for line in info.get("problem", []))


def test_the_fixture_rehearses_to_a_correct_result():
    result, info, stderr = _rehearse(FIXTURE / "index.json")
    assert result["correct"] is True, _problems(info)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    # gangs over quota were sent, stayed pending, and were expected to
    held = compared["pending_after_drain"]
    assert held["value"] == held["limit"] and held["value"] % 4 == 0
    assert held["value"] > 0
    probe = info["probe"][0]
    # the probe wave held a gang the quota leaves out: the reference and the
    # program agree on who waits and who is refused, slot for slot
    assert probe["size"] >= 8 and probe["mismatches"] == 0
    assert probe["reference_placed"] < probe["placed"] < probe["size"]
    assert probe["reference_unbound"] == 0 and probe["hard_violations"] == 0
    assert stderr.rstrip().endswith("correct: True")
    assert "compared probe_slots_differing: 0 (limit 0)" in stderr


def test_a_reference_that_places_one_pod_elsewhere_is_reported(tmp_path):
    result, info, _ = _rehearse(
        _with_fault(tmp_path, reference="gangs_quota_perturbed"))
    assert result["correct"] is False
    assert "slots differ from the plain reference" in _problems(info)
    assert result["compared"]["probe_slots_differing"]["value"] >= 1


def test_a_gang_with_a_member_unbound_is_reported(tmp_path):
    result, info, _ = _rehearse(_with_fault(
        tmp_path, audits=["tamper_unbind_member", "capacity",
                          "gang_atomicity", "quota_bounds"]))
    assert result["correct"] is False
    assert "gang_atomicity: 1 gangs bound below min_member" in _problems(info)


def test_a_namespace_over_its_quota_is_reported(tmp_path):
    result, info, _ = _rehearse(_with_fault(
        tmp_path, audits=["tamper_over_max", "capacity", "gang_atomicity",
                          "quota_bounds"]))
    assert result["correct"] is False
    assert ("quota_bounds: namespace team-b over its ElasticQuota max in cpu"
            in _problems(info))
