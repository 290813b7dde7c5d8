"""Tier-1 mirror of `benchmark/tests/test_config_gangs_quota.py` (which is
run by hand with the other `benchmark/tests`): the cell
`gangs-quota-1024n.backlog` through the real command with `--rehearse-cpu`
on two of its sixteen seeds (every comparison at its limit, what is pending
after the drain is what the population holds back, `bound_total` equal to
the ledger's count), the plain reference `benchmark/references/
gangs_quota.py` against the program's sequential solve at a small size on
several seeds, the reserved-and-waiting members of a gang over its quota
included, the population's lines under their digests, and its shapes the
same for every seed."""

import os
import sys

import pytest

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
for path in (os.path.join(BENCH_DIR, "tests"), BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_config_gangs_quota as by_hand  # noqa: E402

from harness import spec  # noqa: E402


@pytest.mark.parametrize("seed", [3, 2147483777])
def test_the_cell_rehearses_to_a_correct_result(seed):
    result, info, stderr = by_hand.rehearse(seed)
    by_hand.assert_sound(result, info, stderr)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_sequential_solve(seed):
    by_hand.assert_reference_equals_solve(seed)


@pytest.mark.parametrize("key", ["full/3", "rehearsal/0"])
def test_population_lines_are_frozen(key):
    size, seed = key.split("/")
    config = spec.Cell(by_hand.CELL, rehearse=size == "rehearsal").config
    prefill = 1000 if size == "full" else 20
    assert by_hand.digests(config, int(seed), prefill) == by_hand.GOLDEN[key]


def test_no_shape_depends_on_the_seed():
    config = spec.Cell(by_hand.CELL).config
    counts = [by_hand.store_counts(config, seed) for seed in (0, 3, 2147483777)]
    assert counts[0] == counts[1] == counts[2]
    assert counts[0]["upsert_pod_group"] == 1600
