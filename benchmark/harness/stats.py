"""Arithmetic the metrics rest on. Stdlib only: the client process imports it.

Every function takes plain lists of numbers and returns a float, or None
when there is nothing to reduce (a reader that gets None leaves its metric
out of the result line).
"""

from __future__ import annotations

import math


def percentile(samples, q: float):
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest order statistics; +inf samples are legal and sort last (a pod
    that never bound counts as +inf). None for an empty list."""
    if not samples:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(samples)
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if lo == hi or xs[lo] == xs[hi]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return float("inf")
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def mean(samples):
    return None if not samples else float(sum(samples)) / len(samples)


def window_delta_mean(sum_before, count_before, sum_after, count_after):
    """Mean of the observations a histogram took between two readings:
    delta of its sum over delta of its count. None when it took none."""
    n = count_after - count_before
    if n <= 0:
        return None
    return (sum_after - sum_before) / n


def lump_rate(polls, t0_ns: int, t1_ns: int):
    """Completions per second from a polled monotone counter.

    `polls` is [(t_ns, count), ...] in time order. The counter rises in
    lumps (one per scheduling cycle). Inside [t0, t1) take the first poll
    that saw a rise and the last one that did: the rate is what was
    completed after the first rise, up to and including the last, over the
    time between the two. Measuring between whole lumps keeps the window's
    edges from adding or dropping a part of a lump.

    Returns (rate per second, number of lumps) or (None, lumps) with fewer
    than two rises in the window.
    """
    rises = []  # (t_ns of the poll that saw the rise, count after it)
    prev = None
    for t_ns, count in polls:
        if prev is not None and count > prev and t0_ns <= t_ns < t1_ns:
            rises.append((t_ns, count))
        prev = count
    if len(rises) < 2:
        return None, len(rises)
    (t_first, c_first), (t_last, c_last) = rises[0], rises[-1]
    if t_last <= t_first:
        return None, len(rises)
    return (c_last - c_first) / ((t_last - t_first) / 1e9), len(rises)


def interval_union(intervals):
    """Sorted, merged [(start, end), ...] of possibly overlapping
    intervals, and their total length."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged], sum(e - s for s, e in merged)
