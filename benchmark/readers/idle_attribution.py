"""Where the device's idle time of the profiled stretch falls among the
host spans: `harness.trace_reduce.idle_by_span` over the gaps of the first
device and the run's spans, as `run.py` computes it for `breakdown`, and
from it the share of all idle time that lies in the named spans, or in no
span at all. No device plane in the trace (the CPU backend), or an
untraced run: nothing to read.

selector, one of:
  {"spans": [<name or prefix*>, ...]}  idle time put down to these spans
                                       (each instant goes to the innermost
                                       span covering it) over all idle
                                       time; nothing to read when the run
                                       recorded no such span
  {"default": true}                    idle time outside every span over
                                       all idle time
"""

from harness import trace_reduce

OUTSIDE = "outside every span"


def read(selector: dict, run):
    trace = run.device_trace
    if trace is None or run.spans is None:
        return None
    gaps = trace["gaps"]
    idle_ns = sum(end - start for start, end in gaps)
    if idle_ns <= 0:
        return None
    offset = run.trace_offset_ns
    by_span = trace_reduce.idle_by_span(
        gaps,
        [(name, s - offset, e - offset) for name, s, e, _ in run.spans],
        default=OUTSIDE,
    )
    if selector.get("default"):
        return by_span.get(OUTSIDE, 0) / idle_ns
    names = selector["spans"]
    exact = {n for n in names if not n.endswith("*")}
    prefixes = tuple(n[:-1] for n in names if n.endswith("*"))

    def named(name: str) -> bool:
        return name in exact or bool(prefixes and name.startswith(prefixes))

    if not any(named(name) for name, *_ in run.spans):
        return None
    return sum(
        ns for name, ns in by_span.items() if name != OUTSIDE and named(name)
    ) / idle_ns
