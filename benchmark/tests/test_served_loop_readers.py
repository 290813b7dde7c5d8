"""The three readers PR 24 added, on fixed inputs: `counter_ratio`,
`span_self_time`, `idle_attribution`. Each returns a float, or None where
the program (the parent commit's, say) has no such counter or span."""

import types

import pytest

from readers import counter_ratio, idle_attribution, span_self_time

MS = 1_000_000


def _run(**fields):
    return types.SimpleNamespace(**fields)


# -- counter_ratio -----------------------------------------------------------

LOCK_WAIT = {
    "numerator": 'scheduler_feed_event_ns_total{stage="lock_wait"}',
    "denominator": "scheduler_feed_events_total",
    "scale": 0.001,
}


def _registry(before: dict, after: dict) -> dict:
    return {
        "setup": {"counters": before, "histograms": {}},
        "window": {"counters": after, "histograms": {}},
    }


def test_counter_ratio_is_delta_over_delta_between_the_windows_edges():
    run = _run(registry=_registry(
        {LOCK_WAIT["numerator"]: 1_000_000, LOCK_WAIT["denominator"]: 100},
        {LOCK_WAIT["numerator"]: 7_000_000, LOCK_WAIT["denominator"]: 160},
    ))
    value = counter_ratio.read(LOCK_WAIT, run)
    assert isinstance(value, float)
    assert value == pytest.approx(100.0)  # 6e6 ns / 60 events, in us


def test_counter_ratio_takes_a_counter_born_in_the_window_from_zero():
    run = _run(registry=_registry(
        {}, {LOCK_WAIT["numerator"]: 500_000, LOCK_WAIT["denominator"]: 5},
    ))
    assert counter_ratio.read(LOCK_WAIT, run) == pytest.approx(100.0)
    # a stage that cost nothing is a number too, not a missing one
    run = _run(registry=_registry({}, {LOCK_WAIT["denominator"]: 5}))
    assert counter_ratio.read(LOCK_WAIT, run) == 0.0


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # the parent commit: the program has no such counters
    ({LOCK_WAIT["denominator"]: 9}, {LOCK_WAIT["denominator"]: 9}),
])
def test_counter_ratio_reads_nothing_when_the_denominator_stood_still(
        before, after):
    assert counter_ratio.read(LOCK_WAIT, _run(
        registry=_registry(before, after))) is None


# -- span_self_time ----------------------------------------------------------

OWN = {"spans": ["Tick", "Cycle"], "stat": "per_cycle", "scale": 1e-6}


def _tick(t0: int) -> list:
    """One tick of 100 ms at `t0`: 2 ms to the cycle, a cycle of 80 ms
    with 5 ms of its own, a tail of 10 ms, 8 ms after it."""
    return [
        ("Tick", t0, t0 + 100 * MS, {}),
        ("Cycle", t0 + 2 * MS, t0 + 82 * MS, {"cycle": 1}),
        ("PendingScan", t0 + 3 * MS, t0 + 13 * MS, {}),
        ("Snapshot", t0 + 13 * MS, t0 + 63 * MS, {}),
        # nested two deep, and a sibling that overlaps nothing
        ("ServeRefresh/classify", t0 + 20 * MS, t0 + 40 * MS, {}),
        ("Bind", t0 + 65 * MS, t0 + 80 * MS, {}),
        ("TickTail/reconcile", t0 + 82 * MS, t0 + 92 * MS, {}),
        ("PendingScan", t0 + 84 * MS, t0 + 90 * MS, {}),
    ]


def test_span_self_time_is_duration_less_the_union_of_what_starts_inside():
    spans = _tick(1_000 * MS) + [
        ("Loop/sleep", 1_100 * MS, 2_000 * MS, {}),
    ] + _tick(2_000 * MS)
    run = _run(spans=spans, window=(0, 3_000 * MS))
    # Tick: 100 - (80 + 10) = 10; Cycle: 80 - (10 + 50 + 15) = 5
    value = span_self_time.read(OWN, run)
    assert isinstance(value, float)
    assert value == pytest.approx(15.0)
    assert span_self_time.read(dict(OWN, spans=["Cycle"]), run) == (
        pytest.approx(5.0)
    )


def test_span_self_time_counts_only_spans_that_start_in_the_window():
    spans = _tick(1_000 * MS) + _tick(2_000 * MS)
    run = _run(spans=spans, window=(1_500 * MS, 3_000 * MS))
    assert span_self_time.read(OWN, run) == pytest.approx(15.0)


def test_span_self_time_reads_nothing_without_spans_or_cycles():
    assert span_self_time.read(OWN, _run(spans=None, window=(0, 1))) is None
    # the parent commit: a Tick of the harness's, no Cycle, and no cycle
    # counted where no Snapshot was opened
    run = _run(spans=[("Tick", 10, 20, {})], window=(0, 100))
    assert span_self_time.read(OWN, run) is None
    with pytest.raises(ValueError):
        span_self_time.read(dict(OWN, stat="mean"), _run(
            spans=[], window=(0, 1)))


# -- idle_attribution --------------------------------------------------------

def _traced(spans, gaps, offset=0):
    return _run(
        spans=spans, trace_offset_ns=offset,
        device_trace={"gaps": gaps, "window_ns": (gaps[0][0], gaps[-1][1])},
    )


def test_idle_attribution_shares_the_idle_time_among_spans_and_no_span():
    # device idle 0..40, busy 40..50, idle 50..100 (trace clock); the host
    # spans are on a clock 1000 ahead
    gaps = [(0, 40), (50, 100)]
    spans = [
        ("Loop/sleep", 1000, 1030, {}),
        ("Tick", 1030, 1090, {}),
        ("Cycle", 1035, 1080, {}),
        ("Loop/sleep", 1095, 1100, {}),
    ]
    run = _traced(spans, gaps, offset=1000)
    sleep = idle_attribution.read({"spans": ["Loop/sleep"]}, run)
    assert isinstance(sleep, float)
    assert sleep == pytest.approx((30 + 5) / 90)
    # Cycle holds 35..40 and 50..80; Tick 30..35 and 80..90 of its own
    assert idle_attribution.read({"spans": ["Cycle"]}, run) == (
        pytest.approx(35 / 90)
    )
    assert idle_attribution.read({"spans": ["Tick", "Loop/*"]}, run) == (
        pytest.approx((15 + 35) / 90)
    )
    outside = idle_attribution.read({"default": True}, run)
    assert outside == pytest.approx(5 / 90)  # 90..95


def test_idle_attribution_agrees_with_the_breakdown_of_run_py():
    from harness import trace_reduce

    gaps = [(0, 40), (50, 100)]
    spans = [("Tick", 10, 60, {}), ("Snapshot", 20, 30, {})]
    by_span = trace_reduce.idle_by_span(
        gaps, [(name, s, e) for name, s, e, _ in spans]
    )
    run = _traced(spans, gaps)
    assert idle_attribution.read({"default": True}, run) == pytest.approx(
        by_span["outside every span"] / 90
    )
    assert idle_attribution.read({"spans": ["Tick"]}, run) == pytest.approx(
        by_span["Tick"] / 90
    )


def test_idle_attribution_reads_nothing_where_there_is_nothing():
    gaps = [(0, 100)]
    # a span the program does not record (the parent commit)
    run = _traced([("Tick", 10, 60, {})], gaps)
    assert idle_attribution.read({"spans": ["Loop/sleep"]}, run) is None
    assert idle_attribution.read({"default": True}, run) == pytest.approx(0.5)
    # no device plane (the CPU backend), or an untraced run
    assert idle_attribution.read(
        {"default": True}, _run(device_trace=None, spans=[])) is None
    assert idle_attribution.read({"default": True}, _run(
        device_trace={"gaps": gaps}, spans=None, trace_offset_ns=0)) is None
    # a device that was never idle
    assert idle_attribution.read(
        {"default": True}, _traced([], [(5, 5)])) is None
