"""Time of their own of the host spans of a traced run: a span's duration
less the union of the spans that start inside it. For the harness's `Tick`
and the program's `Cycle` together it is the part of a tick that no named
span inside them covers: what the tracing cannot account for yet. Untraced
run: nothing to read.

selector: {"spans": [<name or prefix*>], "stat": "per_cycle",
           "scale": factor}
  per_cycle  the own time of the matching spans that start in the window,
             summed, over the number of cycles (`Snapshot` spans, as
             `tracer_spans` counts them)
"""

import bisect

from harness.stats import interval_union
from readers import tracer_spans


def self_time_ns(span, spans, starts) -> int:
    """`span`'s duration less the union of the other spans of `spans`
    (sorted by start, `starts` their starts) that start inside it, each
    cut to it."""
    _name, start, end, _args = span
    lo = bisect.bisect_left(starts, start)
    hi = bisect.bisect_left(starts, end)
    inner = [
        (s[1], min(s[2], end)) for s in spans[lo:hi] if s is not span
    ]
    return (end - start) - interval_union(inner)[1]


def read(selector: dict, run):
    if run.spans is None:
        return None
    stat = selector["stat"]
    if stat != "per_cycle":
        raise ValueError(f"unknown stat {stat!r}")
    matching = tracer_spans._matching(run, selector["spans"])
    cycles = len(tracer_spans._matching(run, ["Snapshot"]))
    if not matching or not cycles:
        return None
    spans = sorted(run.spans, key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in spans]
    total_ns = sum(self_time_ns(span, spans, starts) for span in matching)
    return total_ns / cycles * selector.get("scale", 1.0)
