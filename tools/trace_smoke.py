#!/usr/bin/env python
"""Trace smoke gate (`make trace-smoke`): the cycle tracer must emit a
Perfetto-loadable trace covering the framework extension-point spans, the
chunk pipeline's H2D/solve/D2H rows and the pipelined cycle's rows.

One traced pass of the chunk pipeline on a REDUCED north-star shape (the
same `parallel.pipeline.north_star_chunk_solver` program, smaller
tensors), one traced serial cycle and two traced pipelined ticks. What the
tracer costs is not measured here: the benchmark's `--trace 1` runs on the
chip are that record (PERF.md).

Trace validation (`validate_trace`, reused by tests/test_observability.py):
JSON with a `traceEvents` list, phases only X/B/E/M (Perfetto's
chrome-trace subset), numeric non-negative ts/dur, B/E stack-paired per
tid, and per-tid X spans either disjoint or properly nested — plus the
pipeline rows and at least one framework extension-point span present.

One JSON line on stdout; rc 1 on any failure.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # `python tools/trace_smoke.py` from anywhere
    sys.path.insert(0, str(REPO))

#: reduced north-star shape: eight chunks, so both pipeline buffers show
SMOKE_SHAPE = dict(n_nodes=256, n_pods=4096, chunk=512)


# ---------------------------------------------------------------------------
# trace validation (shared with tests)
# ---------------------------------------------------------------------------


def validate_trace(trace) -> list[str]:
    """Structural errors in a Chrome-trace-event / Perfetto JSON dict
    (empty list = valid)."""
    errors: list[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    open_stacks: dict = {}
    spans_per_tid: dict = {}
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph not in ("X", "B", "E", "M"):
            errors.append(f"event {i}: phase {ph!r} not in X/B/E/M")
            continue
        if "name" not in e or "pid" not in e or "tid" not in e:
            errors.append(f"event {i}: missing name/pid/tid")
            continue
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"event {i}: bad ts {ts!r}")
            continue
        key = (e["pid"], e["tid"])
        if ph == "B":
            open_stacks.setdefault(key, []).append(e["name"])
        elif ph == "E":
            stack = open_stacks.get(key)
            if not stack:
                errors.append(f"event {i}: E without matching B on {key}")
            else:
                stack.pop()
        else:  # X
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i}: bad dur {dur!r}")
                continue
            spans_per_tid.setdefault(key, []).append((ts, ts + dur, e["name"]))
    for key, stack in open_stacks.items():
        if stack:
            errors.append(f"unclosed B events on {key}: {stack}")
    # per-tid spans must be timeline-renderable: sorted by start they are
    # pairwise either disjoint or properly nested (no partial overlap)
    for key, spans in spans_per_tid.items():
        spans.sort()
        active: list[tuple] = []
        for start, end, name in spans:
            while active and active[-1][1] <= start:
                active.pop()
            if active and end > active[-1][1]:
                errors.append(
                    f"tid {key}: span {name!r} [{start},{end}] partially "
                    f"overlaps {active[-1][2]!r} [{active[-1][0]},"
                    f"{active[-1][1]}]"
                )
            active.append((start, end, name))
    return errors


#: rows the concurrent cycle pipeline emits per tick
#: (framework.pipeline_cycle: ingest/dispatch+fence/overlap-finalize on
#: the main thread, bind/post-bind on the flusher row)
PIPELINED_CYCLE_ROWS = (
    "Cycle/ingest", "Cycle/solve", "Cycle/finalize", "Cycle/bind",
)


def required_rows(trace, extra=()) -> list[str]:
    """Rows the tentpole promises: pipeline H2D/solve/D2H per buffer and a
    framework extension-point row, plus any caller-required `extra` rows
    (the gate adds `PIPELINED_CYCLE_ROWS`). Returns the MISSING rows."""
    names = {
        e["args"]["name"]
        for e in trace.get("traceEvents", ())
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    missing = [
        row
        for row in (
            "pipeline/h2d/buf0", "pipeline/h2d/buf1",
            "pipeline/solve/buf0", "pipeline/solve/buf1",
            "pipeline/d2h/buf0", "pipeline/d2h/buf1",
            "framework",
            *extra,
        )
        if row not in names
    ]
    return missing


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def main(out_path=None):
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import (
        north_star_chunk_solver,
        run_chunk_pipeline,
    )
    from scheduler_plugins_tpu.utils import observability as obs

    out_path = out_path or os.environ.get(
        "SPT_TRACE_OUT", "/tmp/trace_smoke.json"
    )

    shape = SMOKE_SHAPE
    _, snap, _, _, raw, _ = problems.north_star_problem(
        shape["n_nodes"], shape["n_pods"], shape["chunk"]
    )

    obs.tracer.start(clear=True)
    run_chunk_pipeline(
        north_star_chunk_solver(), (raw, snap.nodes.mask),
        problems.pod_chunks(snap, shape["chunk"]),
        free_capacity(snap.nodes.alloc, snap.nodes.requested),
    )

    # one traced scheduling cycle on a tiny cluster adds the framework
    # extension-point rows to the exported trace (tracer still running)
    from scheduler_plugins_tpu.api.objects import Container, Node, Pod
    from scheduler_plugins_tpu.api.resources import CPU, MEMORY, PODS
    from scheduler_plugins_tpu.framework import Profile, Scheduler, run_cycle
    from scheduler_plugins_tpu.plugins import NodeResourcesAllocatable
    from scheduler_plugins_tpu.state.cluster import Cluster

    gib = 1 << 30
    cluster = Cluster()
    for i in range(8):
        cluster.add_node(Node(
            name=f"n{i}",
            allocatable={CPU: 16000, MEMORY: 64 * gib, PODS: 110},
        ))
    for p in range(32):
        cluster.add_pod(Pod(
            name=f"p{p}", creation_ms=p,
            containers=[Container(requests={CPU: 500, MEMORY: gib})],
        ))
    cluster.add_pod(Pod(
        name="too-big", creation_ms=99,
        containers=[Container(requests={CPU: 10 ** 9})],
    ))
    report = run_cycle(
        Scheduler(Profile(plugins=[NodeResourcesAllocatable()])), cluster,
        now=0,
    )
    # two pipelined ticks on a fresh serve-mode cluster add the
    # concurrent-cycle rows (Cycle/{ingest,solve,finalize,bind}) to the
    # exported trace — the overlap stages the tentpole promises are
    # observable, and their spans must stay Perfetto-valid alongside the
    # serial spans (the bind row is emitted from the flusher thread)
    from scheduler_plugins_tpu.framework import PipelinedCycle
    from scheduler_plugins_tpu.serving import StreamingServeEngine

    pcluster = Cluster()
    for i in range(8):
        pcluster.add_node(Node(
            name=f"pn{i}",
            allocatable={CPU: 16000, MEMORY: 64 * gib, PODS: 110},
        ))
    for p in range(8):
        pcluster.add_pod(Pod(
            name=f"pp{p}", creation_ms=p,
            containers=[Container(requests={CPU: 500, MEMORY: gib})],
        ))
    engine = StreamingServeEngine().attach(pcluster)
    pipe = PipelinedCycle(
        Scheduler(Profile(plugins=[NodeResourcesAllocatable()])),
        pcluster, serve=engine,
    )
    pipe.tick(now=1000)
    pcluster.add_pod(Pod(
        name="pp9", creation_ms=20,
        containers=[Container(requests={CPU: 500, MEMORY: gib})],
    ))
    pipe.tick(now=2000)
    pipe.close()
    obs.tracer.stop()
    obs.tracer.write(out_path)
    with open(out_path) as f:
        final_trace = json.load(f)

    errors = validate_trace(final_trace)
    missing = required_rows(final_trace, extra=PIPELINED_CYCLE_ROWS)
    attribution_ok = (
        bool(report.failed_by)
        and set(report.failed_by.values()) == {"NodeResourcesFit"}
    )
    ok = not errors and not missing and attribution_ok
    print(json.dumps({
        "metric": "trace_smoke",
        "trace_events": len(final_trace.get("traceEvents", ())),
        "trace_errors": errors[:5],
        "missing_rows": missing,
        "attribution_ok": attribution_ok,
        "trace_path": out_path,
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
