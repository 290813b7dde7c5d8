"""ISSUE 36's reader, `feed_bursts`, on fixed inputs, and the nine metrics it
and `counter_ratio` bring, through the real command: one steady and one
backlog cell rehearsed traced on the CPU backend report each as a float.
On what the parent commit's program leaves behind (no `feed/<n>` row, a
`Loop/sleep` that says only `woke`, no `write` / `turnaround` counter) the
same files read nothing, or 0."""

import json
import types

import pytest

from harness import spec
from readers import counter_ratio, feed_bursts
from test_rehearsal import _run

MS = 1_000_000
ORIGIN = 5_000 * MS  # the tracer's `ts` 0 on CLOCK_MONOTONIC

NEW = [
    "feed_write_us_per_event", "feed_turnaround_us_per_event",
    "sleep_feed_busy_ms_per_cycle", "sleep_turnaround_ms_per_cycle",
    "sleep_quiet_ms_per_cycle", "tick_hold_ms_mean",
    "demand_period_over_locked_p50", "feed_bursts_per_cycle",
    "feed_stalls_in_window",
]


def _segment(row: int, start_ms: float, end_ms: float, **args) -> list:
    """One segment as `Tracer.complete(paired=True)` writes it; times in
    ms since the tracer's start."""
    args = {"events": 1, "lock_wait_us": 0.0, "quiet_before_us": 0.0, **args}
    args.setdefault(
        "turnaround_us", (end_ms - start_ms) * 1000.0 - args["busy_us"])
    head = {"name": "Feed/segment", "pid": 1, "tid": row}
    return [dict(head, ph="B", ts=start_ms * 1000.0, args=args),
            dict(head, ph="E", ts=end_ms * 1000.0)]


def _export(*segments, rows=((7, "feed/0"),)) -> dict:
    meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
             "args": {"name": name}} for tid, name in rows]
    meta.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
                 "args": {"name": "daemon"}})
    return {
        "traceEvents": meta + [e for pair in segments for e in pair],
        "otherData": {"origin_monotonic_ns": ORIGIN},
    }


def _run_of(export, spans, window_ms=(0, 1_000)):
    tracer = types.SimpleNamespace(export=lambda: export)
    return types.SimpleNamespace(
        obs=types.SimpleNamespace(tracer=tracer),
        spans=sorted(spans, key=lambda s: (s[1], -s[2])),
        window=(ORIGIN + window_ms[0] * MS, ORIGIN + window_ms[1] * MS),
    )


def _span(name, start_ms, end_ms, **args):
    return (name, ORIGIN + int(start_ms * MS), ORIGIN + int(end_ms * MS), args)


def _sleep(start_ms, end_ms, woke="demand", locked_ms=10.0, held_ms=0.0,
           since_start_ms=None):
    if since_start_ms is None:
        since_start_ms = (end_ms - start_ms) + locked_ms
    return _span("Loop/sleep", start_ms, end_ms, woke=woke,
                 locked_ms=locked_ms, held_ms=held_ms,
                 since_start_ms=since_start_ms)


def _read(stat, run, scale=1e-6):
    return feed_bursts.read({"stat": stat, "scale": scale}, run)


def _three(run):
    return tuple(_read(stat, run) for stat in (
        "sleep_busy_per_cycle", "sleep_turnaround_per_cycle",
        "sleep_quiet_per_cycle"))


# -- the records ---------------------------------------------------------------

def test_segments_are_the_pairs_of_the_feed_rows_on_the_monotonic_clock():
    export = _export(
        _segment(7, 10, 30, busy_us=5_000.0),
        _segment(7, 50, 60, busy_us=1_000.0, quiet_before_us=20_000.0),
    )
    # an X event, and a pair on another row, are not segments
    export["traceEvents"].append(
        {"name": "Cycle", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 9})
    export["traceEvents"] += _segment(1, 0, 5, busy_us=1.0)
    segs = feed_bursts.segments(export)
    assert [(a, b) for a, b, _ in segs] == [
        (ORIGIN + 10 * MS, ORIGIN + 30 * MS),
        (ORIGIN + 50 * MS, ORIGIN + 60 * MS),
    ]
    assert segs[1][2]["quiet_before_us"] == 20_000.0


def test_an_export_without_an_origin_or_a_feed_row_holds_no_segment():
    export = _export(_segment(7, 10, 30, busy_us=5_000.0))
    assert feed_bursts.segments(dict(export, otherData={})) == []
    assert feed_bursts.segments(_export(rows=())) == []


# -- the sleep, split three ways -------------------------------------------------

def test_a_sleep_that_straddles_two_segments():
    # sleep 100..300; a segment 80..140 (a quarter busy) reaches into it,
    # a quiet gap 140..200, a segment 200..260 (half busy) inside it, quiet
    # to the end
    run = _run_of(
        _export(
            _segment(7, 80, 140, busy_us=15_000.0, events=30),
            _segment(7, 200, 260, busy_us=30_000.0, events=32,
                     quiet_before_us=60_000.0),
        ),
        [_sleep(100, 300), _span("Snapshot", 305, 330)],
    )
    busy, turnaround, quiet = _three(run)
    assert busy == pytest.approx(40 * 0.25 + 60 * 0.5)
    assert turnaround == pytest.approx(40 * 0.75 + 60 * 0.5)
    assert quiet == pytest.approx(200 - 100)
    assert busy + turnaround + quiet == pytest.approx(200.0)


def test_a_segment_that_straddles_a_tick_leaves_its_lock_wait_outside():
    # sleep 0..100, tick 100..170, sleep 170..270. One segment 60..180: its
    # event waited 70 ms for the lock the tick held; of the 50 ms left it
    # was busy 10 (a fifth) and waited for the client 40
    segment = _segment(7, 60, 180, busy_us=80_000.0, lock_wait_us=70_000.0,
                       turnaround_us=40_000.0, events=20)
    run = _run_of(_export(segment), [
        _sleep(0, 100), _span("Tick", 100, 170), _span("Snapshot", 101, 150),
        _sleep(170, 270), _span("Snapshot", 271, 290),
    ])
    busy, turnaround, quiet = _three(run)
    # 40 ms of the first sleep and 10 of the second lie in the segment
    assert busy == pytest.approx((40 + 10) * 0.2 / 2)
    assert turnaround == pytest.approx((40 + 10) * 0.8 / 2)
    assert quiet == pytest.approx((60 + 90) / 2)
    # with the wait counted as the handler's own, two thirds of those 50 ms
    # would have read busy
    naive = dict(segment[0], args=dict(segment[0]["args"], lock_wait_us=0.0))
    run = _run_of(_export([naive, segment[1]]), run.spans)
    assert _three(run)[0] == pytest.approx((40 + 10) * (80 / 120) / 2)


def test_a_window_edge_inside_a_segment():
    # the window is 100..400. A sleep that began before it is not the
    # window's (as `tick_sleep_ms_per_cycle` counts); one that begins in it
    # counts whole, past the window's end, and so does what the segments
    # cover of it. A segment counts as a burst where it begins.
    run = _run_of(
        _export(
            _segment(7, 50, 150, busy_us=50_000.0, quiet_before_us=9_000.0),
            _segment(7, 350, 450, busy_us=100_000.0, quiet_before_us=7_000.0),
            _segment(7, 460, 470, busy_us=10_000.0,
                     quiet_before_us=2_000_000.0),
        ),
        [_sleep(40, 160), _span("Snapshot", 165, 180),
         _sleep(300, 460), _span("Snapshot", 465, 480)],
        window_ms=(100, 400),
    )
    busy, turnaround, quiet = _three(run)  # one cycle, one sleep: 300..460
    assert (busy, turnaround) == (pytest.approx(100.0), pytest.approx(0.0))
    assert quiet == pytest.approx(60.0)
    assert _read("bursts_per_cycle", run, scale=1.0) == 1.0  # the one at 350
    assert _read("stalls", run, scale=1.0) == 0.0  # the stall began at 460
    run.window = (run.window[0], ORIGIN + 500 * MS)
    assert _read("stalls", run, scale=1.0) == 1.0


def test_the_three_parts_add_up_to_the_sleep_whatever_the_segments():
    import random

    rng = random.Random(36)
    at, pairs = 0.0, []
    for _ in range(200):
        at += rng.choice([0.0, 0.0, rng.uniform(5.0, 80.0)])
        length = rng.uniform(0.5, 100.0)
        lock_wait = rng.choice([0.0, rng.uniform(0.0, 0.9 * length)])
        busy = lock_wait + rng.uniform(0.0, length - lock_wait)
        pairs.append(_segment(7, at, at + length, busy_us=busy * 1000.0,
                              lock_wait_us=lock_wait * 1000.0))
        at += length
    spans, t = [], 0.0
    while t < at:
        spans.append(_sleep(t, t + 150.0))
        spans.append(_span("Snapshot", t + 151.0, t + 190.0))
        t += 200.0
    run = _run_of(_export(*pairs), spans, window_ms=(0, t))
    parts = _three(run)
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(150.0)


# -- the loop's own account --------------------------------------------------------

def test_hold_mean_and_the_spacing_ratio_come_from_the_sleeps_args():
    run = _run_of(_export(_segment(7, 0, 1, busy_us=1.0)), [
        _sleep(0, 50, woke="demand", locked_ms=10.0, since_start_ms=60.0,
               held_ms=40.0),
        _sleep(100, 160, woke="demand", locked_ms=10.0, since_start_ms=61.0,
               held_ms=20.0),
        _sleep(200, 900, woke="interval", locked_ms=200.0,
               since_start_ms=1_000.0, held_ms=0.0),
        _sleep(1_200, 1_300, woke="demand", locked_ms=1.0,
               since_start_ms=500.0, held_ms=9.0),  # outside the window
        _span("Snapshot", 55, 60),
    ])
    assert _read("hold_mean", run, scale=1.0) == pytest.approx(20.0)
    assert _read("demand_period_over_locked_p50", run, scale=1.0) == (
        pytest.approx(6.05))


def test_no_tick_woke_on_demand_reads_nothing():
    run = _run_of(_export(_segment(7, 0, 1, busy_us=1.0)), [
        _sleep(0, 900, woke="interval", locked_ms=300.0),
        _span("Snapshot", 905, 990),
    ])
    assert _read("demand_period_over_locked_p50", run) is None
    assert _read("hold_mean", run, scale=1.0) == 0.0


# -- what the parent commit's program leaves behind ---------------------------

def test_a_program_without_the_records_reads_nothing():
    spans = [_span("Loop/sleep", 0, 100, woke="demand"),
             _span("Snapshot", 105, 120)]
    run = _run_of(_export(rows=()), spans)
    for name in NEW[2:]:
        definition = spec.load_json(
            spec.BENCH_DIR / "layer_metrics" / f"{name}.json")
        assert definition["reader"] == "feed_bursts"
        assert feed_bursts.read(definition["selector"], run) is None, name
    untraced = _run_of(_export(_segment(7, 0, 1, busy_us=1.0)), spans)
    untraced.spans = None
    assert _read("sleep_quiet_per_cycle", untraced) is None


@pytest.mark.parametrize("name", NEW[:2])
def test_a_program_without_the_stage_reads_zero_per_event(name):
    definition = spec.load_json(
        spec.BENCH_DIR / "layer_metrics" / f"{name}.json")
    assert definition["reader"] == "counter_ratio"
    run = types.SimpleNamespace(registry={
        "setup": {"counters": {"scheduler_feed_events_total": 10}},
        "window": {"counters": {"scheduler_feed_events_total": 110}},
    })
    assert counter_ratio.read(definition["selector"], run) == 0.0
    key = definition["selector"]["numerator"]
    run.registry["window"]["counters"][key] = 2_500_000
    assert counter_ratio.read(definition["selector"], run) == (
        pytest.approx(25.0))  # us an event


# -- the index, and the real command -------------------------------------------

def test_every_new_metric_is_listed_for_every_cell_under_its_kind():
    for workload in spec.index()["workloads"]:
        cell = spec.Cell(workload["name"])
        backlog = workload["traffic"] == "backlog"
        listed = {m["name"]: m for m in cell.metrics["per_layer"]}
        for metric in NEW:
            name = f"backlog.{metric}" if backlog else metric
            if name == "backlog.demand_period_over_locked_p50":
                # listed where a tick wakes on demand in every run: a
                # cell paced by the heartbeat has nothing to read
                assert (name in listed) == (workload["name"] in (
                    "basic-5000n.backlog", "trimaran-5000n.backlog"))
                continue
            assert name in listed, (workload["name"], name)
            assert listed[name]["moves"] == (
                "bound_pods_per_s" if backlog else "decision_p50_ms")
            assert (spec.BENCH_DIR / "layer_metrics"
                    / f"{metric}.json").exists()


@pytest.mark.parametrize("name", [
    "basic-5000n.steady", "antiaffinity-5000n.backlog",
])
def test_traced_rehearsal_reports_every_new_metric(name):
    done = _run("--workload", name, "--seed", "36", "--seconds", "4",
                "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k.rsplit(".", 1)[-1]: v["value"]
               for k, v in result["metrics"].items()}
    for metric in NEW:
        if metric == "demand_period_over_locked_p50" and metric not in metrics:
            continue
        assert isinstance(metrics[metric], float), metric
        assert metrics[metric] >= 0.0, metric
    parts = sum(metrics[m] for m in NEW[2:5])
    assert parts == pytest.approx(metrics["tick_sleep_ms_per_cycle"],
                                  rel=0.02)
    # the rehearsal's ticks are far under a sixth of the interval, so the
    # loop runs on demand and the rule's 6 is what the ratio reads (the
    # metric is not listed for a cell the heartbeat paces on the chip)
    if "demand_period_over_locked_p50" in metrics:
        assert metrics["demand_period_over_locked_p50"] >= 6.0 - 1e-6
    else:
        assert name == "antiaffinity-5000n.backlog"
    assert metrics["feed_stalls_in_window"] == 0.0
    assert metrics["feed_write_us_per_event"] > 0.0
    assert metrics["feed_turnaround_us_per_event"] > 0.0
