"""From a profiler trace to numbers: the one reduction every PR is read by.

The JAX profiler writes an `.xplane.pb`: planes (one per device, one for the
host), their lines, and events with a start and a duration in nanoseconds
since the trace began. On a TPU each device plane `/device:TPU:<n>` has a
line `XLA Ops` (every operation the device ran) and a line `XLA Modules`
(one event per run of a compiled program, named `jit_<function>(<id>)`).

- busy: the union of the intervals of `XLA Ops` on a device, cut to the
  traced window; `busy_s` is its mean over the devices used;
- idle share: 1 - busy / window;
- device time per module: the sum of its `XLA Modules` events;
- idle gaps: the stretches of the window in which no operation ran on the
  first device, each instant put down to the innermost host span that
  covers it.

Host spans (`obs.tracer`, the harness's tick stamps) are on
CLOCK_MONOTONIC. The harness writes one `TraceAnnotation` named
`bench_sync mono=<ns>` when tracing starts; its start in the trace gives
the offset between the two clocks.
"""

from __future__ import annotations

import glob
import os
import re

from harness.stats import interval_union

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = re.compile(r"bench_sync mono=(\d+)")
#: `jit_solve(1234567)` -> `jit_solve`
MODULE_ID = re.compile(r"\(\d+\)$")


def newest_xplane(trace_dir: str):
    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(files, key=os.path.getmtime) if files else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def sync_offset_ns(profile):
    """(monotonic ns) - (trace ns), from the sync annotation; None when the
    trace holds none."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                match = SYNC.search(event.name)
                if match:
                    return int(match.group(1)) - int(event.start_ns)
    return None


def reduce_trace(profile, window=None) -> dict:
    """The reduction. `window` is (start, end) in trace nanoseconds; None
    takes the span from the first to the last device operation.

    Returns None when no device plane holds an operation (the CPU backend
    has no device plane: a rehearsal reports no device number).
    """
    per_device = []
    modules: dict = {}
    ops: dict = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        intervals, module_events = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                named = []
                for event in line.events:
                    start = int(event.start_ns)
                    end = start + int(event.duration_ns)
                    intervals.append((start, end))
                    named.append((start, end, op_name(event.name)))
                for name, ns in self_times(named).items():
                    ops[name] = ops.get(name, 0) + ns
            elif line.name == MODULES_LINE:
                for event in line.events:
                    start = int(event.start_ns)
                    module_events.append((
                        MODULE_ID.sub("", event.name), start,
                        start + int(event.duration_ns),
                    ))
        if intervals:
            per_device.append((plane.name, intervals, module_events))
    if not per_device:
        return None
    if window is None:
        window = (
            min(s for _, iv, _ in per_device for s, _ in iv),
            max(e for _, iv, _ in per_device for _, e in iv),
        )
    w0, w1 = window
    busy, gaps, module_list = [], [], []
    for _name, intervals, module_events in per_device:
        clipped = [
            (max(s, w0), min(e, w1)) for s, e in intervals if e > w0 and s < w1
        ]
        merged, total = interval_union(clipped)
        busy.append(total)
        edges = [w0] + [t for pair in merged for t in pair] + [w1]
        if not gaps:  # the first device's: what `idle_by_span` is given
            gaps = [
                (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]
            ]
        for name, start, end in module_events:
            if end > w0 and start < w1:
                module_list.append((name, start, end))
                entry = modules.setdefault(name, [0, 0])
                entry[0] += min(end, w1) - max(start, w0)
                entry[1] += 1
    return {
        "window_ns": (w0, w1),
        "devices": len(per_device),
        "busy_ns": sum(busy) / len(busy),
        "busy_ns_per_device": busy,
        # program -> [device ns inside the window, runs]
        "modules": modules,
        "module_events": sorted(module_list, key=lambda m: m[1]),
        # operation -> ns of its own, over the whole trace
        "op_ns": ops,
        "gaps": gaps,
    }


def op_name(hlo_text: str) -> str:
    """`%fusion.12 = (u32[]{...}, ...) fusion(...)` -> `%fusion.12`: the
    trace names an operation by its whole HLO line."""
    return hlo_text.split(" = ", 1)[0]


def self_times(events) -> dict:
    """{name: ns} of [(start, end, name), ...], each event's time less that
    of the events nested in it: a `while` covers every operation of its
    body, and would otherwise count them twice."""
    out: dict = {}
    stack = []  # [end, name, duration, duration of children]

    def close() -> None:
        _end, name, duration, children = stack.pop()
        out[name] = out.get(name, 0) + duration - children

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close()
        if stack:
            stack[-1][3] += end - start
        stack.append([end, name, end - start, 0])
    while stack:
        close()
    return out


def idle_by_span(gaps, spans, default: str = "outside every span") -> dict:
    """{span name: idle ns}: every instant of every gap goes to the
    innermost span that covers it, and to `default` where none does.

    `gaps` is sorted and disjoint; `spans` is [(name, start, end), ...] on
    the gaps' clock, nested or disjoint (one thread's spans). A span's
    idle time is the gap time inside it less that inside its children.
    """
    import bisect

    edges = [t for gap in gaps for t in gap]
    before = [0]  # gap time before edges[i]
    for i in range(0, len(edges), 2):
        before.append(before[-1])
        before.append(before[-1] + edges[i + 1] - edges[i])

    def gap_time_before(t: int) -> int:
        i = bisect.bisect_right(edges, t)
        if i % 2:  # inside gap (edges[i-1], edges[i])
            return before[i] + t - edges[i - 1]
        return before[i]

    out: dict = {}
    total = before[-1]
    stack = []  # (end, name, idle inside, idle inside children)
    top_level = 0

    def close() -> None:
        nonlocal top_level
        _end, name, inside, children = stack.pop()
        out[name] = out.get(name, 0) + inside - children
        if stack:
            stack[-1][3] += inside
        else:
            top_level += inside

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            close()
        end = min(end, stack[-1][0]) if stack else end
        inside = gap_time_before(end) - gap_time_before(start)
        stack.append([end, name, inside, 0])
    while stack:
        close()
    if total - top_level:
        out[default] = total - top_level
    return {name: ns for name, ns in out.items() if ns > 0}


def top(mapping: dict, n: int, scale: float = 1e-9) -> list:
    """[[name, seconds], ...], the n largest."""
    ranked = sorted(mapping.items(), key=lambda kv: -kv[1])[:n]
    return [[name, value * scale] for name, value in ranked]
