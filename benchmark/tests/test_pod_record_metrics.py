"""The two per-layer metrics PR 37 added as data files on readers that were
there: `serve_pod_memo_hits_per_bound_pod` (`counter_ratio`) and
`verify_ms_per_check` (`tracer_spans`). Each file loads, names a reader that
exists and is listed for the cells that can read it; a traced rehearsal
reads both; against a program without the counter (the parent commit's) the
first reads 0.0 and nothing raises."""

import json
import types

import pytest

from harness import spec
from readers import counter_ratio
from test_rehearsal import CELLS, _run

STEADY = [name for name in CELLS if name.endswith(".steady")]
FILES = ["serve_pod_memo_hits_per_bound_pod", "verify_ms_per_check"]


@pytest.mark.parametrize("metric", FILES)
def test_the_file_loads_and_names_a_reader_that_exists(metric):
    definition = spec.load_json(
        spec.BENCH_DIR / "layer_metrics" / f"{metric}.json"
    )
    assert spec.load_module("readers", definition["reader"]).read
    assert definition["selector"]


def test_each_metric_is_listed_for_the_cells_that_can_read_it():
    for name in CELLS:
        listed = {m["name"] for m in spec.Cell(name).metrics["per_layer"]}
        prefix = "" if name in STEADY else "backlog."
        assert f"{prefix}serve_pod_memo_hits_per_bound_pod" in listed, name
        assert ("verify_ms_per_check" in listed) == (name in STEADY), name
    for metric in spec.index()["per_layer"][-3:]:
        assert metric["layer"] == "resident_state"


def test_memo_hits_read_zero_against_a_registry_without_the_counter():
    selector = spec.load_json(
        spec.BENCH_DIR / "layer_metrics"
        / "serve_pod_memo_hits_per_bound_pod.json"
    )["selector"]
    bound = selector["denominator"]
    run = types.SimpleNamespace(registry={
        "setup": {"counters": {bound: 50_000}, "histograms": {}},
        "window": {"counters": {bound: 74_000}, "histograms": {}},
    })
    assert counter_ratio.read(selector, run) == 0.0
    run.registry["window"]["counters"][selector["numerator"]] = 48_000
    assert counter_ratio.read(selector, run) == 2.0


@pytest.mark.parametrize("name", ["basic-5000n.steady", "basic-5000n.backlog"])
def test_traced_rehearsal_reads_the_record_metrics(name):
    done = _run("--workload", name, "--seed", "5", "--seconds", "6",
                "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = {k.rsplit(".", 1)[-1]: v for k, v in result["metrics"].items()}
    # an assign and an unassign a pod, both off its record
    hits = metrics["serve_pod_memo_hits_per_bound_pod"]["value"]
    assert 1.5 <= hits <= 2.5, hits
    if name in STEADY:
        assert metrics["verify_ms_per_check"]["value"] > 0.0
    else:
        assert "verify_ms_per_check" not in metrics
