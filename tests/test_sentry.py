"""Bench-regression sentry (tools/perf_sentry.py) decision tables.

Pure host-side: verdicts are arithmetic over sample lists, so these
tables run with synthetic series and stubbed host-health dicts — the
really-timed end of the same properties is `make sentry-smoke`
(perf_sentry selftest)."""

import json
import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)
import perf_sentry  # noqa: E402

HEALTHY = {"healthy": True, "reasons": []}
SICK = {"healthy": False, "reasons": ["load_high"]}


class TestVerdictTables:
    def test_reshuffle_is_exactly_quiet(self):
        base = [100.0, 96.0, 104.0, 99.0, 101.0, 103.0, 97.0]
        shuffled = [103.0, 97.0, 100.0, 104.0, 96.0, 101.0, 99.0]
        v = perf_sentry.verdict(base, shuffled, metric="throughput_per_sec",
                                health=HEALTHY)
        assert v["verdict"] == "ok"
        assert v["median_slowdown"] == 0.0  # sorted pairing: zero, not small
        assert all(d == 0.0 for d in v["pair_deltas"])

    def test_injected_uniform_slowdown_flagged(self):
        base = [100.0, 96.0, 104.0, 99.0, 101.0]
        slower = [x * 0.8 for x in base]  # 20% throughput loss
        v = perf_sentry.verdict(base, slower, metric="throughput_per_sec",
                                health=HEALTHY)
        assert v["verdict"] == "regression"
        assert v["median_slowdown"] == pytest.approx(0.20)

    def test_latency_metric_regresses_upward(self):
        base = [10.0, 10.2, 9.8, 10.1, 9.9]
        slower = [x * 1.3 for x in base]
        faster = [x * 0.7 for x in base]
        up = perf_sentry.verdict(base, slower, metric="cycle_ms",
                                 health=HEALTHY)
        down = perf_sentry.verdict(base, faster, metric="cycle_ms",
                                   health=HEALTHY)
        assert up["verdict"] == "regression"
        assert down["verdict"] == "improved"

    def test_unhealthy_host_downgrades_never_blames(self):
        base = [100.0, 96.0, 104.0, 99.0, 101.0]
        slower = [x * 0.5 for x in base]
        v = perf_sentry.verdict(base, slower, metric="throughput_per_sec",
                                health=SICK)
        assert v["verdict"] == "degraded-host"
        assert v["host"] is SICK

    def test_noise_floor_absorbs_spread_sized_shifts(self):
        # baseline spread (p10-p90 ~ 40% of median) dominates the 10%
        # threshold: a 15% shift inside that spread must stay quiet
        base = [80.0, 90.0, 100.0, 110.0, 120.0]
        v = perf_sentry.verdict(base, [x * 0.85 for x in base],
                                metric="throughput_per_sec", health=HEALTHY)
        assert v["noise_floor"] > 0.10
        assert v["verdict"] == "ok"

    def test_too_few_baselines_is_no_baseline(self):
        v = perf_sentry.verdict([100.0, 101.0], [50.0],
                                metric="throughput_per_sec", health=HEALTHY)
        assert v["verdict"] == "no-baseline"

    def test_unequal_lengths_pair_by_quantile(self):
        base = [float(x) for x in range(90, 111)]  # 21 samples
        v = perf_sentry.verdict(base, [100.0, 99.0, 101.0],
                                metric="throughput_per_sec", health=HEALTHY)
        assert v["verdict"] == "ok"
        assert len(v["pair_deltas"]) == 3


class TestHistoryIngestion:
    def test_committed_wrapper_failed_run_is_unusable(self):
        samples = perf_sentry.extract_samples(
            {"n": 1, "cmd": "python run_it.py", "rc": 1, "tail": "boom",
             "parsed": None},
            "run1.json",
        )
        assert [s["usable"] for s in samples] == [False]
        assert samples[0]["error"] == "run-failed"

    def test_tpu_backend_unavailable_is_unusable(self):
        samples = perf_sentry.extract_samples(
            {"n": 2, "rc": 0, "parsed": {
                "metric": "pods_scheduled_per_sec", "value": 0,
                "unit": "pods/s", "error": "tpu-backend-unavailable",
            }},
            "run2.json",
        )
        assert [s["usable"] for s in samples] == [False]

    def test_value_zero_without_error_is_unusable(self):
        (s,) = perf_sentry.extract_samples(
            {"metric": "pods_scheduled_per_sec", "value": 0}, "x")
        assert not s["usable"]

    def test_good_line_and_list_forms(self):
        good = {"metric": "pods_scheduled_per_sec", "value": 123.4}
        assert perf_sentry.extract_samples(good, "x")[0]["usable"]
        two = perf_sentry.extract_samples([good, good], "x")
        assert len(two) == 2

    def test_degenerate_history_never_regresses(self):
        history = [
            perf_sentry.extract_samples(
                {"n": i, "rc": 0, "parsed": {
                    "metric": "pods_scheduled_per_sec", "value": 0,
                    "error": "tpu-backend-unavailable",
                }}, f"r{i}")[0]
            for i in range(5)
        ]
        new = perf_sentry.extract_samples(
            {"metric": "pods_scheduled_per_sec", "value": 10.0}, "fresh")
        report = perf_sentry.check_series(
            history, new, rel_threshold=0.10, health=HEALTHY)
        assert report["overall"] == "no-baseline"
        assert report["unusable_samples"] == 5

    def test_load_files_accepts_json_lines(self, tmp_path):
        p = tmp_path / "runs.jsonl"
        p.write_text(
            json.dumps({"metric": "m_per_sec", "value": 10.0}) + "\n"
            + json.dumps({"metric": "m_per_sec", "value": 11.0}) + "\n"
        )
        samples = perf_sentry.load_files([str(p)])
        assert [s["value"] for s in samples] == [10.0, 11.0]


class TestCheckSeries:
    def test_regression_on_one_metric_dominates_overall(self):
        def mk(metric, values):
            return [
                perf_sentry.extract_samples(
                    {"metric": metric, "value": v}, "x")[0]
                for v in values
            ]

        history = mk("a_per_sec", [100, 101, 99, 100]) + mk(
            "b_per_sec", [50, 51, 49, 50])
        new = mk("a_per_sec", [100]) + mk("b_per_sec", [25])
        report = perf_sentry.check_series(
            history, new, rel_threshold=0.10, health=HEALTHY)
        assert report["verdicts"]["a_per_sec"]["verdict"] == "ok"
        assert report["verdicts"]["b_per_sec"]["verdict"] == "regression"
        assert report["overall"] == "regression"
