"""What the host did while the loop slept: the feed's segment records laid
beside the `Loop/sleep` spans of a traced run, and the loop's own account of
each wait (`Loop/sleep`'s args). Untraced run, or a program that records
neither: nothing to read.

A **segment** is one `B`/`E` pair on a `feed/<n>` row of `obs.tracer`'s
export (`bridge.feed.FeedTally.flush`): a stretch of one connection's life
with no quiet gap inside it, at most 32 events or ~100 ms long. Its args:
`events`, `busy_us` (the handler had a line in hand: `codec` + `lock_wait` +
`apply` + `write`), `lock_wait_us` (the part of that spent waiting for the
feed lock), `turnaround_us` (the handler waited for the client's answer to an
ack) and `quiet_before_us` (the gap of over 5 ms that preceded it; 0 inside a
burst). `busy_us` + `turnaround_us` is the segment's length. The harness's
`run.spans` holds `X` events only, so these come from the export itself, on
its origin (`otherData.origin_monotonic_ns`).

A sleep is split three ways. What lies in no segment is **quiet**: nobody
had anything in hand. What lies in a segment is split as the segment's own
time is, *less its wait for the lock*: the tick holds the lock while the loop
is awake, so a segment that straddles a tick spent that wait outside the
sleep; the share of the rest that the handler was busy, (`busy_us` −
`lock_wait_us`) ÷ (length − `lock_wait_us`), goes to **busy** and what is
left to **turnaround**. The three add up to the sleep. (One feed connection
a cell: with several, their segments overlap and the sum would pass it.)

selector: {"stat": <one of>, "scale": factor}
  sleep_busy_per_cycle        of the `Loop/sleep` spans that start in the
  sleep_turnaround_per_cycle  window, the three parts in ns, summed, over the
  sleep_quiet_per_cycle       number of cycles (`Snapshot` spans, as
                              `tracer_spans` counts them)
  hold_mean                   mean of `Loop/sleep.args.held_ms` over those
                              sleeps: how long the first waiting pod waited
                              for its tick
  demand_period_over_locked_p50
                              median, over those of them that woke on
                              demand, of `since_start_ms` ÷ `locked_ms`: 6
                              where `DEMAND_TICK_SPACING` holds tick by tick;
                              nothing to read where none woke on demand
  bursts_per_cycle            segments that start in the window after a quiet
                              gap, over the number of cycles
  stalls                      those of them whose gap was a second or more
"""

import bisect

from harness import stats
from readers import tracer_spans

SEGMENT = "Feed/segment"
ROW_PREFIX = "feed/"
STALL_US = 1_000_000.0


def segments(export: dict) -> list:
    """[(start ns, end ns, args), ...] on CLOCK_MONOTONIC, sorted by start:
    the `B`/`E` pairs of the export's `feed/<n>` rows."""
    origin = (export.get("otherData") or {}).get("origin_monotonic_ns")
    if origin is None:
        return []
    events = export["traceEvents"]
    rows = {
        e["tid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and e["args"]["name"].startswith(ROW_PREFIX)
    }
    out, began = [], {}
    for e in events:
        if e.get("tid") not in rows or e.get("name") != SEGMENT:
            continue
        if e["ph"] == "B":
            began[e["tid"]] = e
        elif e["ph"] == "E" and e["tid"] in began:
            b = began.pop(e["tid"])
            out.append((origin + int(b["ts"] * 1000),
                        origin + int(e["ts"] * 1000), b.get("args") or {}))
    return sorted(out, key=lambda s: s[0])


def split_sleep(sleep: tuple, segs: list, starts: list) -> tuple:
    """(busy ns, turnaround ns, quiet ns) of the stretch `sleep` = (start,
    end); `segs` sorted by start, `starts` their starts."""
    s0, s1 = sleep
    busy = turnaround = 0.0
    # a segment is at most ~100 ms plus one wait for the lock: look back
    # far enough for any that began before the sleep and reaches into it
    i = bisect.bisect_left(starts, s0)
    while i > 0 and segs[i - 1][1] > s0:
        i -= 1
    for a, b, args in segs[i:]:
        if a >= s1:
            break
        inside = min(b, s1) - max(a, s0)
        if inside <= 0:
            continue
        lock_wait = args.get("lock_wait_us", 0.0) * 1000.0
        unlocked = (b - a) - lock_wait
        worked = args.get("busy_us", 0.0) * 1000.0 - lock_wait
        share = min(max(worked / unlocked, 0.0), 1.0) if unlocked > 0 else 1.0
        busy += inside * share
        turnaround += inside * (1.0 - share)
    return busy, turnaround, (s1 - s0) - busy - turnaround


def _segments_of(run) -> list:
    cached = getattr(run, "_feed_segments", None)
    if cached is None:
        cached = run._feed_segments = segments(run.obs.tracer.export())
    return cached


def read(selector: dict, run):
    if run.spans is None:
        return None
    stat = selector["stat"]
    scale = selector.get("scale", 1.0)
    sleeps = tracer_spans._matching(run, ["Loop/sleep"])
    cycles = len(tracer_spans._matching(run, ["Snapshot"]))

    if stat == "hold_mean":
        held = [s[3]["held_ms"] for s in sleeps if "held_ms" in s[3]]
        return sum(held) / len(held) * scale if held else None
    if stat == "demand_period_over_locked_p50":
        ratios = [
            s[3]["since_start_ms"] / s[3]["locked_ms"] for s in sleeps
            if s[3].get("woke") == "demand" and s[3].get("locked_ms")
            and "since_start_ms" in s[3]
        ]
        return stats.percentile(ratios, 50) * scale if ratios else None

    segs = _segments_of(run)
    if not segs or not cycles:
        return None
    t0, t1 = run.window
    if stat in ("bursts_per_cycle", "stalls"):
        gaps = [
            args.get("quiet_before_us", 0.0) for a, _b, args in segs
            if t0 <= a < t1
        ]
        if stat == "stalls":
            return float(sum(1 for g in gaps if g >= STALL_US)) * scale
        return sum(1 for g in gaps if g > 0) / cycles * scale
    parts = ("sleep_busy_per_cycle", "sleep_turnaround_per_cycle",
             "sleep_quiet_per_cycle")
    if stat not in parts:
        raise ValueError(f"unknown stat {stat!r}")
    if not sleeps:
        return None
    starts = [s[0] for s in segs]
    total = sum(
        split_sleep((start, end), segs, starts)[parts.index(stat)]
        for _name, start, end, _args in sleeps
    )
    return total / cycles * scale
