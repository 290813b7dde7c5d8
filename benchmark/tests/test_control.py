"""`correct` can fail. The control (`control_altered_answer.py`: the real
command with one legal but different placement per solve, altered where the
program produces it) drives a whole rehearsed run past the look for a chip
and has to end `correct: false`, with the differing slots counted and
nothing else found. On the chip it is run at the cells' own size (PERF.md)."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

CONTROL = os.path.join(str(spec.BENCH_DIR), "tests", "control_altered_answer.py")


@pytest.mark.parametrize("name", ["basic-5000n.backlog", "trimaran-5000n.steady"])
def test_an_altered_answer_ends_not_correct(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, CONTROL, "--workload", name, "--seed", "5",
         "--seconds", "4", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=str(spec.REPO_DIR), env=env,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["probe_slots_differing"]["value"] >= 1
    # the altered placement is a legal one: only the reference objects
    assert compared["probe_hard_violations"]["value"] == 0
    assert compared["pending_after_drain"]["value"] == 0
    assert compared["problems"]["value"] == 1
    assert done.stderr.rstrip().endswith("correct: False")
