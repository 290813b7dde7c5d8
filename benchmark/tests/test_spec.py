"""BENCHMARK.json and the files it names hold together."""

import importlib

import pytest

from harness import spec

INDEX = spec.index()
CELLS = [w["name"] for w in INDEX["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_finds_its_files_and_its_metrics_their_readers(name):
    for rehearse in (False, True):
        cell = spec.Cell(name, rehearse=rehearse)
        importlib.import_module(f"generators.{cell.mix['generator']}")
        importlib.import_module(f"references.{cell.config['reference']}")
        for kind in ("end_to_end", "per_layer"):
            assert cell.metrics[kind], f"{name} reports no {kind} metric"
            for metric in cell.metrics[kind]:
                definition = cell.metric_definition(kind, metric["name"])
                reader = importlib.import_module(
                    f"readers.{definition['reader']}"
                )
                assert callable(reader.read)
    assert len(cell.entry["why"]) <= 200
    assert "setup_s" in [m["name"] for m in cell.metrics["end_to_end"]]
    assert len(cell.metrics["end_to_end"]) >= 2


def test_a_layer_metric_is_reported_only_where_what_it_moves_is():
    where = {
        m["name"]: set(m.get("workloads", CELLS)) for m in INDEX["end_to_end"]
    }
    for metric in INDEX["per_layer"]:
        assert set(metric.get("workloads", CELLS)) <= where[metric["moves"]], (
            metric["name"]
        )


def test_rehearsal_only_shrinks():
    for name in CELLS:
        full, small = spec.Cell(name), spec.Cell(name, rehearse=True)
        assert small.config["cluster"]["nodes"] < full.config["cluster"]["nodes"]
        assert small.config["cluster"]["skus"] == full.config["cluster"]["skus"]
        assert small.mix["prefill_bound_pods"] < full.mix["prefill_bound_pods"]
