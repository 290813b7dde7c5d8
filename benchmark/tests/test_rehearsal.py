"""Every cell, rehearsed on the CPU backend through the real command: the
last line carries the contract's keys and says `correct: true`."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

CELLS = [w["name"] for w in spec.index()["workloads"]]
RUN = os.path.join(str(spec.BENCH_DIR), "run.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, RUN, *args], capture_output=True, text=True,
        cwd=str(spec.REPO_DIR), env=env, timeout=300,
    )


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_to_a_correct_result_line(name, trace):
    done = _run("--workload", name, "--seed", "3", "--seconds", "4",
                "--trace", str(trace), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, "\n".join(lines[-12:])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    cell = spec.Cell(name)
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) <= {m["name"] for m in cell.metrics[kind]}
    if not trace:
        assert set(result["metrics"]) == {
            m["name"] for m in cell.metrics["end_to_end"]
        }
    for metric in result["metrics"].values():
        assert isinstance(metric["value"], float) and metric["unit"]
    for line in lines[:-1]:
        assert "info" in json.loads(line)


def test_without_a_tpu_the_command_prints_no_result():
    done = _run("--workload", CELLS[0], "--seed", "0", "--seconds", "1",
                "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
