"""The per-layer metrics PR 24 added, through the real command: every cell,
rehearsed traced on the CPU backend, reports each of them that needs no
device as a float; the two that read the device's idle gaps are left out
there (the CPU backend has no device plane) and nothing raises."""

import json

import pytest

from harness import spec
from test_rehearsal import CELLS, _run

NEW = [
    "feed_lock_wait_us_per_event", "feed_apply_us_per_event",
    "feed_codec_us_per_event", "healthz_handler_ms_mean",
    "tick_sleep_ms_per_cycle", "tick_tail_ms_per_cycle",
    "pending_scan_ms_per_cycle", "tick_unattributed_ms_per_cycle",
]
DEVICE_ONLY = ["idle_unattributed_share", "idle_in_sleep_share"]


def test_every_new_metric_has_a_file_and_is_listed_for_its_cells():
    for name in CELLS:
        listed = {m["name"].rsplit(".", 1)[-1]: m
                  for m in spec.Cell(name).metrics["per_layer"]}
        for metric in NEW + DEVICE_ONLY:
            assert metric in listed, (name, metric)
            assert (spec.BENCH_DIR / "layer_metrics" / f"{metric}.json").exists()
        assert ("antientropy_checks_in_window" in listed) == (
            name == "basic-5000n.steady"
        )


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_reports_the_served_loop_metrics(name):
    done = _run("--workload", name, "--seed", "5", "--seconds", "4",
                "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = {k.rsplit(".", 1)[-1]: v for k, v in result["metrics"].items()}
    for metric in NEW:
        assert isinstance(metrics[metric]["value"], float), metric
        assert metrics[metric]["value"] >= 0.0, metric
    for metric in DEVICE_ONLY:
        assert metric not in metrics
    # what the three feed stages took is less than what the client waited
    # for an ack, per event
    stages = sum(metrics[f"feed_{s}_us_per_event"]["value"]
                 for s in ("lock_wait", "apply", "codec"))
    assert stages > 0.0
    # the tick's thread sleeps most of a one-second interval at this size
    assert metrics["tick_sleep_ms_per_cycle"]["value"] > 0.0
