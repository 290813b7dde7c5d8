"""Donated, double-buffered chunk pipeline.

The north-star solve streams pods through the chunked targeted waterfill
with free capacity carried between chunks (queue order preserved across
chunk boundaries). The naive loop serializes three phases per chunk —
host->device transfer of the next chunk's inputs, the solve, and the
device->host transfer of the previous chunk's assignments — leaving the
device idle during both transfers and the host blocked during the solve.

`run_chunk_pipeline` overlaps all three with a one-chunk lag:

    dispatch solve(k)            # async — device starts immediately
    device_put(chunk k+1 inputs) # H2D overlaps solve(k)
    collect(result k-1)          # D2H blocks only until solve(k-1) done

so the device is never idle between chunks and the host is never more
than one chunk behind (a bounded in-flight window: chaining everything
device-side would balloon the working set). The chunk solver DONATES its
carry argument
(`donated_chunk_solver`), so the free-capacity tensor threads chunk to
chunk in place instead of being copied at every dispatch boundary.

Consumers: the north-star chunk program below (`north_star_chunk_solver`:
the 10,240x102,400 run of `chip_smoke.py` phase B) and the daemon cycle
loop (`framework.cycle.run_cycle(stream_chunk=...)`) via
`streamed_profile_solve`.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from scheduler_plugins_tpu.utils import observability as obs


@dataclass
class PipelineTimeline:
    """Host-sync stamps of one `run_chunk_pipeline` run.

    Every number here comes from HOST-observable boundaries — the async
    dispatch returning, `jax.device_put` ENQUEUE (the host-side staging
    cost; the transfer itself completes asynchronously and is only known
    to be done when the next dispatch consumes the buffers) and
    `jax.device_get` (D2H) actually completing — never from wall clocks
    inside jit (CLAUDE.md; GL008). The "h2d" stamps therefore measure
    host staging exposure, not wire time; only the D2H stamps are true
    completion fences. With the lag-1 window the host observes chunk k's
    completion only at its D2H, so per-chunk device busy time is NOT
    directly observable;
    `summary(solve_ms=...)` therefore takes a device-busy ESTIMATE the
    caller derives from a synchronously-timed calibration solve scaled by
    the per-chunk `collect_stats` wave counters, and charges the remainder
    of the wall time as the pipeline bubble.
    """

    n_chunks: int = 0
    #: [{stage: dispatch|h2d|d2h, chunk, start_s, end_s}] on the caller's
    #: clock (seconds); start_s/end_s are relative to nothing in
    #: particular — only differences matter
    events: list = field(default_factory=list)
    start_s: float = 0.0
    end_s: float = 0.0
    #: tracer-clock ns at pipeline start when the tracer was enabled
    #: (aligns replayed rows with live spans), else None
    _anchor_ns: int | None = None

    def open(self, start_s: float) -> None:
        self.start_s = start_s
        if obs.tracer.enabled:
            self._anchor_ns = obs.tracer.now_ns()

    def add(self, stage: str, chunk: int, start_s: float, end_s: float) -> None:
        self.events.append(
            {"stage": stage, "chunk": chunk,
             "start_s": start_s, "end_s": end_s}
        )

    def close(self, end_s: float) -> None:
        self.end_s = end_s

    def stage_ms(self, stage: str) -> float:
        return sum(
            (e["end_s"] - e["start_s"]) * 1000.0
            for e in self.events if e["stage"] == stage
        )

    @property
    def elapsed_ms(self) -> float:
        return (self.end_s - self.start_s) * 1000.0

    def summary(self, solve_ms: float | None = None) -> dict:
        """Pipeline-overlap report. `solve_ms` is the caller's estimate of
        TOTAL device busy time (calibration solve x wave-counter scaling);
        without it only the raw stage totals are reported.

        - `pipeline_bubble_ms` = wall time the device was NOT solving
          (elapsed - solve_ms, floored at 0): the un-overlapped remainder
          the double buffering exists to eliminate.
        - `overlap_efficiency` = solve_ms / elapsed (capped at 1): the
          fraction of the wall clock the device was busy.
        - per-stage `*_overlap_efficiency` = the fraction of that host
          stage's time hidden behind device work, attributing the bubble
          to host stages pro-rata by their time share (an estimate — the
          lag-1 window cannot observe which stage exposed which gap, and
          the h2d stage total is the ENQUEUE cost, not wire time: on an
          async backend an exposed in-flight transfer shows up in the
          bubble, not in `h2d_ms`).
        """
        h2d = self.stage_ms("h2d")
        d2h = self.stage_ms("d2h")
        dispatch = self.stage_ms("dispatch")
        out = {
            "elapsed_ms": round(self.elapsed_ms, 3),
            "chunks": self.n_chunks,
            "h2d_ms": round(h2d, 3),
            "d2h_ms": round(d2h, 3),
            "dispatch_ms": round(dispatch, 3),
            "pipeline_bubble_ms": None,
            "overlap_efficiency": None,
            "h2d_overlap_efficiency": None,
            "d2h_overlap_efficiency": None,
        }
        if solve_ms is None or self.elapsed_ms <= 0:
            return out
        bubble = max(0.0, self.elapsed_ms - solve_ms)
        out["pipeline_bubble_ms"] = round(bubble, 3)
        out["overlap_efficiency"] = round(
            min(1.0, solve_ms / self.elapsed_ms), 4
        )
        host_total = h2d + d2h + dispatch
        for key, stage_total in (("h2d_overlap_efficiency", h2d),
                                 ("d2h_overlap_efficiency", d2h)):
            if stage_total <= 0 or host_total <= 0:
                out[key] = 1.0
                continue
            exposed = min(stage_total, bubble * stage_total / host_total)
            out[key] = round(1.0 - exposed / stage_total, 4)
        return out

    def emit_trace(self, tracer=None) -> None:
        """Replay the stamps as Perfetto rows: H2D/solve/D2H per buffer
        (buffers alternate chunk parity under the double buffering). The
        solve row for chunk k spans dispatch-return to D2H-complete — a
        conservative envelope (the host cannot observe the device-side
        start/finish tighter than its own sync points)."""
        tracer = tracer or obs.tracer
        if not tracer.enabled or self._anchor_ns is None:
            return

        def ns(t_s: float) -> int:
            return self._anchor_ns + int((t_s - self.start_s) * 1e9)

        dispatch_end = {}
        d2h_end = {}
        for e in self.events:
            if e["stage"] == "dispatch":
                dispatch_end[e["chunk"]] = e["end_s"]
            elif e["stage"] == "d2h":
                d2h_end[e["chunk"]] = e["end_s"]
            tracer.complete(
                f'{e["stage"]} chunk {e["chunk"]}',
                ns(e["start_s"]),
                int((e["end_s"] - e["start_s"]) * 1e9),
                tid=f'pipeline/{e["stage"]}/buf{e["chunk"] % 2}',
                args={"chunk": e["chunk"]},
            )
        for k, disp_end in sorted(dispatch_end.items()):
            end = d2h_end.get(k)
            if end is None:
                continue
            tracer.complete(
                f"solve chunk {k}",
                ns(disp_end),
                int((end - disp_end) * 1e9),
                tid=f"pipeline/solve/buf{k % 2}",
                args={"chunk": k, "envelope": "dispatch->d2h (conservative)"},
            )


def donated_chunk_solver(fn, carry_argnum: int):
    """Jit `fn` with its carry argument donated — the pipeline's calling
    convention. Callers must treat the carry they pass in as CONSUMED
    (rebind it from the call's result; `tools/graft_lint.py` GL006 flags
    reuse of a donated buffer after the donating call).

    Under `SPT_SANITIZE=1` (utils.sanitize) the chunk program is built as a
    checkify-instrumented jit with the donation DROPPED (debug mode: the
    carry stays readable, checkify errors surface as structured JSON); the
    calling convention — rebind the carry from the result — is unchanged.
    """
    from scheduler_plugins_tpu.utils import sanitize

    name = getattr(fn, "__name__", "solve_chunk")
    if sanitize.enabled():
        jitted = sanitize.checkified(fn, program=f"chunk:{name}")
    else:
        jitted = jax.jit(fn, donate_argnums=(carry_argnum,))
    return obs.compile_watch(jitted, program=f"chunk:{name}")


def north_star_solve_chunk(raw, node_mask, req_chunk, mask_chunk, free0):
    """One north-star chunk: static allocatable scores -> targeted
    waterfill, O(P*R) per lite wave instead of the (P, N) matrix (masked
    nodes fit nothing with zeroed free capacity). rescue_window=256 halves
    the end-game (K, N) rescue cost at this scale (8 waves x 256 slots
    still drains every straggler, all pods placed).

    Returns ((assignment, wave_stats), free) — the pipeline calling
    convention (`run_chunk_pipeline`): the free carry is DONATED at the jit
    boundary (`donated_chunk_solver`) so it threads chunk to chunk in
    place. Chunk-invariant tensors (raw scores, node mask) are ARGUMENTS,
    not jit closure captures, so the compiled program is exactly the one
    tools/tpu_lower.py lowers and digests. Problems for it:
    `models.problems.north_star_problem`."""
    from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted

    assignment, free, stats = waterfill_assign_targeted(
        raw, req_chunk, mask_chunk,
        jnp.where(node_mask[:, None], free0, 0), max_waves=8,
        rescue_window=256, collect_stats=True,
    )
    return (assignment, stats), free


def north_star_chunk_solver():
    """The jitted, carry-donating north-star chunk program: one constructor
    so what `chip_smoke.py` runs and what the audit registry lowers cannot
    drift apart."""
    return donated_chunk_solver(north_star_solve_chunk, carry_argnum=4)


def run_chunk_pipeline(solve_chunk, invariant_args, chunk_inputs, carry,
                       clock=None, fetch_deadline_s=None):
    """Stream `chunk_inputs` through `solve_chunk`, double-buffered.

    - ``solve_chunk(*invariant_args, *chunk_dev, carry) -> (result, carry)``
      — typically a `donated_chunk_solver`; `result` may be any pytree
      (e.g. ``(assignment, wave_stats)``).
    - ``chunk_inputs``: sequence of per-chunk argument tuples (host numpy
      or device arrays; they are `jax.device_put` one chunk ahead).
    - ``carry``: the threaded state (free capacity); returned updated.
    - ``clock``: optional ``time.perf_counter``-like callable for the
      completion stamps (injectable for tests).
    - ``fetch_deadline_s``: optional per-chunk deadline on the D2H
      completion fences (`jax.device_get` is the only point this loop
      blocks on the device, so it is where a hung backend strands the
      host): each fetch runs through
      `resilience.watchdog.call_with_deadline` and raises
      `BackendUnavailable` on timeout instead of hanging the cycle loop
      forever. None (the default) keeps the direct call.

    Returns ``(results, carry, done_s, timeline)`` where ``results[k]`` is
    chunk k's `result` pytree fetched to host and ``done_s[k]`` its
    completion time (seconds since the pipeline started) — the per-chunk
    decision-latency stamps the north-star p50/p99 derive from. Completion
    of chunk k is observed one dispatch later (lag-1), so the stamps are
    conservative by at most one dispatch overhead, never optimistic.
    ``timeline`` is a `PipelineTimeline` of the host-sync stamps (dispatch,
    H2D, D2H per chunk): `timeline.summary(solve_ms=...)` computes the
    `pipeline_bubble_ms` / overlap-efficiency metrics, and when the global
    tracer is enabled the stamps are replayed as Perfetto H2D/solve/D2H
    rows per buffer automatically.
    """
    clock = clock or time.perf_counter
    if fetch_deadline_s is None:
        fetch = jax.device_get
    else:
        from scheduler_plugins_tpu.resilience.watchdog import (
            call_with_deadline,
        )

        def fetch(x):
            return call_with_deadline(
                lambda: jax.device_get(x), fetch_deadline_s,
                label="pipeline-d2h",
            )

    n = len(chunk_inputs)
    results, done_s = [], []
    timeline = PipelineTimeline(n_chunks=n)
    start = clock()
    timeline.open(start)
    pending = None
    dev = ()
    if n:
        t0 = clock()
        dev = tuple(jax.device_put(a) for a in chunk_inputs[0])
        timeline.add("h2d", 0, t0, clock())
    for k in range(n):
        t0 = clock()
        result, carry = solve_chunk(*invariant_args, *dev, carry)
        timeline.add("dispatch", k, t0, clock())
        if k + 1 < n:
            # H2D for chunk k+1 overlaps solve(k)
            t0 = clock()
            dev = tuple(jax.device_put(a) for a in chunk_inputs[k + 1])
            timeline.add("h2d", k + 1, t0, clock())
        if pending is not None:
            # D2H for chunk k-1: blocks only until ITS solve finished
            t0 = clock()
            results.append(fetch(pending))
            t1 = clock()
            timeline.add("d2h", k - 1, t0, t1)
            done_s.append(t1 - start)
        pending = result
    if pending is not None:
        t0 = clock()
        results.append(fetch(pending))
        t1 = clock()
        timeline.add("d2h", n - 1, t0, t1)
        done_s.append(t1 - start)
    timeline.close(clock())
    timeline.emit_trace()
    return results, carry, done_s, timeline


# ---------------------------------------------------------------------------
# Streamed profile solve (the cycle loop's adoption point)
# ---------------------------------------------------------------------------


def _targeted_fast_gate(scheduler):
    """The profile shape the chunked targeted waterfill supports — THE gate
    is `parallel.solver.fast_path_scoring`, shared with
    `profile_batch_fn`'s fast branch so the two paths cannot drift."""
    from scheduler_plugins_tpu.parallel.solver import fast_path_scoring

    plugins = tuple(scheduler.profile.plugins)
    return fast_path_scoring(plugins), plugins


def streamed_profile_solve(scheduler, snap, chunk: int = 4096,
                           max_waves: int = 8, rescue_window: int = 256,
                           fetch_deadline_s=None):
    """Chunked, double-buffered variant of the targeted fast-path solve:
    admission and the static node ranking are computed once, then pod
    chunks stream through the donated targeted waterfill with free capacity
    carried chunk to chunk; gang quorum and the queue-order quota prefix
    run once over the full batch at the end (`finalize_assignment` needs
    whole-batch queue order, and chunk boundaries preserve it).

    Returns (assignment, admitted, wait) like `profile_batch_solve`, or
    None when the profile does not qualify (callers fall back). Placements
    match the unchunked targeted waterfill up to wave-budget effects; hard
    constraints (fit, queue-order admission, quota caps, gang quorum) hold
    identically.
    """
    from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted
    from scheduler_plugins_tpu.parallel.solver import finalize_assignment

    scoring, plugins = _targeted_fast_gate(scheduler)
    if scoring is None:
        return None
    P = snap.num_pods
    chunk = min(chunk, P)
    if P % chunk != 0:
        return None  # snapshot padding didn't land on a chunk multiple

    state0 = scheduler.initial_state(snap)
    auxes = tuple(p.aux() for p in plugins)

    cache = scheduler._solve_cache
    key = ("streamed_head",) + tuple(p.static_key() for p in plugins)
    if key not in cache:
        from scheduler_plugins_tpu.parallel.solver import fast_solve_head

        def head(snap, state0, auxes):
            # the shared traced head of the targeted fast path (admission
            # vmap + raw static ranking + masked initial free)
            return fast_solve_head(plugins, scoring, snap, state0, auxes)

        cache[key] = obs.compile_watch(jax.jit(head), program="streamed_head")
    admitted, raw, free0 = cache[key](snap, state0, auxes)

    from scheduler_plugins_tpu.utils import sanitize

    ckey = ("streamed_chunk", chunk, max_waves, rescue_window,
            sanitize.enabled())
    if ckey not in cache:

        def solve_one(raw, req_chunk, mask_chunk, free):
            return waterfill_assign_targeted(
                raw, req_chunk, mask_chunk, free,
                max_waves=max_waves, rescue_window=rescue_window,
            )

        cache[ckey] = donated_chunk_solver(solve_one, carry_argnum=3)

    chunk_inputs = [
        (snap.pods.req[lo:lo + chunk], admitted[lo:lo + chunk])
        for lo in range(0, P, chunk)
    ]
    parts, free, _, _ = run_chunk_pipeline(
        cache[ckey], (raw,), chunk_inputs, free0,
        fetch_deadline_s=fetch_deadline_s,
    )
    assignment = jnp.concatenate([jnp.asarray(a) for a in parts])
    assignment, wait = finalize_assignment(assignment, snap)
    return assignment, admitted, wait
