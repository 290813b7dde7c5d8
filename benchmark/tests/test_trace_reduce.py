"""The trace reduction on a small trace recorded on a TPU v5e chip
(`data/record_trace.py` says how): three runs of a 64-step scan with a host
sleep after each. The expected numbers were read off that trace once; the
busy time is also recomputed here by another method (a raster of the traced
stretch at 1 ns), so the interval arithmetic is checked and not only pinned.
"""

import json
import os

import numpy as np
import pytest

from harness import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    profile = trace_reduce.load(os.path.join(DATA, "tpu_scan.xplane.pb"))
    with open(os.path.join(DATA, "tpu_scan.spans.json")) as f:
        host = json.load(f)
    return profile, host


def test_busy_idle_and_modules_of_the_recorded_trace(recorded):
    profile, _host = recorded
    reduced = trace_reduce.reduce_trace(profile)
    assert reduced["devices"] == 1
    assert reduced["window_ns"] == (50822345, 59887434)
    assert reduced["busy_ns"] == 907311
    assert reduced["modules"] == {"jit_small_scan": [907888, 3]}
    # three runs: a gap after the first and after the second, and the
    # short ones between the operations of a run
    gaps = reduced["gaps"]
    assert sum(e - s for s, e in gaps) == (59887434 - 50822345) - 907311
    assert sorted(e - s for s, e in gaps)[-2:] == [4058761, 4098982]
    idle_share = 1 - reduced["busy_ns"] / (59887434 - 50822345)
    assert idle_share == pytest.approx(0.8999, abs=1e-4)


def test_busy_time_agrees_with_a_raster(recorded):
    profile, _host = recorded
    reduced = trace_reduce.reduce_trace(profile)
    w0, w1 = reduced["window_ns"]
    covered = np.zeros(w1 - w0, bool)
    for plane in profile.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                start = int(event.start_ns)
                covered[start - w0:start + int(event.duration_ns) - w0] = True
    assert int(covered.sum()) == reduced["busy_ns"]


def test_a_window_cuts_the_trace(recorded):
    profile, _host = recorded
    # the second run only: its module event is 55183365 + 302462 ns
    reduced = trace_reduce.reduce_trace(profile, window=(55_000_000, 56_000_000))
    assert reduced["modules"] == {"jit_small_scan": [302462, 1]}
    assert 300_000 < reduced["busy_ns"] <= 302462
    assert reduced["gaps"][0][0] == 55_000_000
    assert reduced["gaps"][-1][1] == 56_000_000


def test_operations_are_named_shortly_and_counted_once(recorded):
    profile, _host = recorded
    reduced = trace_reduce.reduce_trace(profile)
    ops = reduced["op_ns"]
    assert all(" = " not in name for name in ops)
    # the while covers its body: with self times the operations add up to
    # the busy time (no operation of this trace overlaps another one)
    assert sum(ops.values()) == reduced["busy_ns"]
    top = trace_reduce.top(ops, 3)
    assert [name for name, _ in top] == [
        "%while.7", "%fusion.12", "%and_reduce_fusion.2"
    ]
    assert top[0][1] == pytest.approx(210.842e-6)


def test_the_sync_annotation_puts_host_spans_on_the_trace_clock(recorded):
    profile, host = recorded
    offset = trace_reduce.sync_offset_ns(profile)
    assert offset == 35410757620
    p0, p1 = host["profiled_ns"]
    reduced = trace_reduce.reduce_trace(profile, window=(p0 - offset, p1 - offset))
    spans = [(n, s - offset, e - offset) for n, s, e, _ in host["spans"]]
    idle = trace_reduce.idle_by_span(reduced["gaps"], spans)
    total_idle = (p1 - p0) - reduced["busy_ns"]
    assert sum(idle.values()) == total_idle
    # each run and each sleep is about 2 ms long and the device is busy for
    # 0.3 ms of a run: most of each span is idle, and most of the 200 ms
    # traced lies outside every span
    for name in ("run_a", "run_b", "run_c"):
        assert 1_500_000 < idle[name] < 2_800_000
        assert 2_000_000 < idle[f"sleep_after_{name}"] < 2_800_000
    assert idle["outside every span"] > 180_000_000


def test_idle_goes_to_the_innermost_span():
    gaps = [(0, 10), (20, 30), (50, 100)]
    spans = [("cycle", 5, 60), ("snapshot", 5, 25), ("bind", 40, 55)]
    assert trace_reduce.idle_by_span(gaps, spans) == {
        "snapshot": 10, "bind": 5, "cycle": 10, "outside every span": 45,
    }


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    class Plane:
        name = "/host:CPU"
        lines = []

    class Profile:
        planes = [Plane()]

    assert trace_reduce.reduce_trace(Profile()) is None
