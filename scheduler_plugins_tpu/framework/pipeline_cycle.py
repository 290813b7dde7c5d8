"""Concurrent cycle pipeline: overlap ingest/solve/bind across cycles.

`framework.cycle.run_cycle` is strictly serial — ingest, snapshot, device
solve, host-transfer fence, bind, all on one thread, with the host idle
while the device solves and the device idle while the host ingests. This
module composes the SAME `_cycle_*` stage functions into a pipelined
engine (`PipelinedCycle`) that keeps the device solve of cycle N in
flight while neighboring cycles' host stages run:

    tick N:   [conflict fence]──[ingest N]──[dispatch N]╮
                                                        │ device solves N
              [finalize N-1  ← overlap window]──────────┤
              [fence N: host transfers]─────────────────╯
              [bind N → async flusher]  (tick returns; the flusher's
                                         mutations are joined by tick
                                         N+1's conflict fence)

Ordering contract (what keeps pipelined placements BIT-IDENTICAL to the
serial engine, gated by tests/test_differential.py's
TestPipelinedCycleEquivalence):

- **Conflict fence.** The bind/post-bind stage of cycle N mutates the
  store (binds, reservations, `mark_unschedulable` backoff charges,
  preemption nomination set/clear). Cycle N+1's ingest boundary — the
  pending-index read and the serve engine's sink drain — joins the
  flusher FIRST, so every one of those mutations is attributed to the
  cycle that observed the snapshot, never to the cycle currently
  ingesting. A bind that flushes after a drain boundary (possible only
  outside the tick loop, e.g. `flush()` racing an external drain) still
  reaches the resident serving state exactly: each store mutator pushes
  its DeltaSink event, and a late bind is an ordinary delta of the PR 6
  classification (`scheduler_cycle_late_binds_total` counts them).
- **Overlap window.** Only report-local work runs while cycle N's solve
  is in flight: cycle N-1's failure attribution (when its per-pod codes
  already rode the solve result), quality observation (on host copies
  captured at N-1's fence — the resident node tensors were donated to
  cycle N's delta apply by then) and the flight-recorder commit. None of
  it touches the store, so overlap cannot reorder decisions.
- **Gang/preemption machinery** stays inside the tick, after the fence,
  exactly where the serial engine runs it.

The engine enables the cluster's O(changed) pending index
(`Cluster.enable_pending_index`) — the serial engine's per-cycle
O(pods) scan is the single biggest host cost at serving scale — and
pairs naturally with `serving.engine.StreamingServeEngine`'s O(changed)
node-delete compaction (docs/SCALING.md has the measured breakdown).
"""

from __future__ import annotations

import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

from scheduler_plugins_tpu.framework.cycle import (
    CycleReport,
    _cycle_bind,
    _cycle_finalize,
    _cycle_open,
    _cycle_pending,
    _cycle_post_solve,
    _cycle_postbind,
    _cycle_snapshot,
    _cycle_solve_dispatch,
    _cycle_solve_fence,
)
from scheduler_plugins_tpu.framework.runtime import now_ms as _now_ms
from scheduler_plugins_tpu.obs import ledger as podledger
from scheduler_plugins_tpu.utils import flightrec, observability as obs


class CycleTimeline:
    """Host-stamp timeline of ONE pipelined cycle — every number comes
    from host-observable boundaries (dispatch returning, np.asarray
    completion fences), never from wall clocks inside jit (CLAUDE.md;
    GL008). The solve ENVELOPE (dispatch return -> fence return) is a
    conservative device window: the host cannot observe the device-side
    start/finish tighter than its own sync points (the
    `parallel.pipeline.PipelineTimeline` convention)."""

    __slots__ = (
        "cycle", "t0_s", "ingest_ms", "dispatch_ms", "overlap_ms",
        "fence_wait_ms", "bind_ms", "bind_done_s", "total_ms", "late_bind",
    )

    def __init__(self, cycle: int):
        self.cycle = cycle
        self.t0_s = 0.0
        self.ingest_ms = 0.0
        self.dispatch_ms = 0.0
        self.overlap_ms = 0.0
        self.fence_wait_ms = 0.0
        self.bind_ms = 0.0
        #: seconds-on-the-tick-clock when the bind flush completed (the
        #: per-decision latency stamp: ingest boundary -> host-visible
        #: binds); stamped by the flusher thread
        self.bind_done_s = 0.0
        self.total_ms = 0.0
        self.late_bind = False

    @property
    def pipeline_bubble_ms(self) -> float:
        """Wall time the fence idled with the device still solving and no
        overlap work left — the un-overlapped remainder."""
        return self.fence_wait_ms

    @property
    def overlap_efficiency(self) -> float:
        """Fraction of the post-dispatch solve envelope covered by useful
        host work (1.0 = the fence never waited)."""
        envelope = self.overlap_ms + self.fence_wait_ms
        if envelope <= 0:
            return 1.0
        return min(1.0, self.overlap_ms / envelope)

    def as_dict(self) -> dict:
        return {
            "cycle": self.cycle,
            "ingest_ms": round(self.ingest_ms, 3),
            "dispatch_ms": round(self.dispatch_ms, 3),
            "overlap_ms": round(self.overlap_ms, 3),
            "pipeline_bubble_ms": round(self.pipeline_bubble_ms, 3),
            "bind_ms": round(self.bind_ms, 3),
            "total_ms": round(self.total_ms, 3),
            "overlap_efficiency": round(self.overlap_efficiency, 4),
        }


class PipelinedCycle:
    """Pipelined cycle engine over one scheduler + cluster store.

    `tick(now)` runs one cycle and returns its `CycleReport`. With
    `async_bind` (the default) the report's bind/post-bind stage may
    still be flushing on the worker thread when `tick` returns — call
    `fence()` (or run the next tick, whose ingest boundary fences
    implicitly) before reading the store or the report's DECISION
    fields (bound/reserved/failed/preempted). The report's deferred
    fields — `quality`, and `failed_by` when the per-pod codes rode the
    solve result — are populated only by the NEXT tick's overlap window
    or by `flush()`, which fences AND finalizes the last in-flight
    cycle (always call it, or `close()`, at shutdown).

    Composition mirrors `run_cycle`: `serve` (a ServeEngine), `gangs`
    (a GangPhase), `resilience` (a watchdog — its deadline semantics
    need a synchronous solve, so resilient ticks fence inside the
    dispatch stage and the overlap window only covers the previous
    cycle's finalize) and `stream_chunk` all behave identically.
    """

    #: host stages in flight at once: cycle N's bind flush + cycle N+1's
    #: ingest/dispatch, with cycle N's finalize deferred into N+1's
    #: overlap window
    DEPTH = 2

    def __init__(self, scheduler, cluster, serve=None, resilience=None,
                 gangs=None, stream_chunk=None, async_bind=True,
                 timeline_keep=512):
        self.scheduler = scheduler
        self.cluster = cluster
        self.serve = serve
        self.resilience = resilience
        self.gangs = gangs
        self.stream_chunk = stream_chunk
        cluster.enable_pending_index()
        self._flusher = (
            ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="spt-bind-flusher"
            )
            if async_bind else None
        )
        self._bind_future = None
        #: (ctx, eager_attribution_done) awaiting deferred finalize
        self._pending_finalize = None
        self._cycle_id = 0
        self.timelines: deque = deque(maxlen=timeline_keep)
        self._clock = time.perf_counter

    # -- introspection (daemon /healthz) --------------------------------
    @property
    def depth(self) -> int:
        return self.DEPTH

    @property
    def inflight(self) -> int:
        """Cycles with host work still outstanding: an unflushed bind
        stage and/or a deferred finalize."""
        n = 0
        if self._bind_future is not None and not self._bind_future.done():
            n += 1
        if self._pending_finalize is not None:
            n += 1
        return n

    # -- the conflict fence ---------------------------------------------
    def fence(self) -> None:
        """Join the async bind flusher — THE conflict fence. Every store
        mutation of the previous cycle's bind/post-bind stage is visible
        after this returns (exceptions, including the chaos harness's
        CrashInjected, re-raise here)."""
        future, self._bind_future = self._bind_future, None
        if future is not None:
            future.result()

    def flush(self) -> CycleReport | None:
        """Fence outstanding binds and run the deferred finalize of the
        last completed cycle. Returns that cycle's report (now fully
        populated) or None."""
        self.fence()
        return self._finalize_prev()

    def close(self) -> None:
        self.flush()
        if self._flusher is not None:
            self._flusher.shutdown(wait=True)

    # -- the tick --------------------------------------------------------
    def tick(self, now: int | None = None) -> CycleReport:
        if now is None:
            now = _now_ms()
        # the pod-lifecycle ledger's lane-0 scope is pushed inside
        # `_cycle_open` (on THIS thread — the bind flusher pushes its own
        # lane-1 scopes); pop it on EVERY exit so ambient events between
        # ticks fall back to ambient attribution and a raise cannot leak
        # a stale scope onto the tick thread
        ctx_box: list = []
        try:
            return self._tick(now, ctx_box)
        finally:
            if ctx_box:
                podledger.LEDGER.pop_scope(ctx_box[0].led)
                podledger.LEDGER.cycle_close(ctx_box[0].led)

    def _tick(self, now: int, ctx_box: list) -> CycleReport:
        clock = self._clock
        cid = self._cycle_id
        self._cycle_id += 1
        tl = CycleTimeline(cid)
        tl.t0_s = clock()

        # ---- ingest boundary: conflict fence, then host ingest --------
        with obs.tracer.span(f"ingest cycle {cid}", tid="Cycle/ingest"):
            self.fence()
            ctx = _cycle_open(
                self.scheduler, self.cluster, now,
                stream_chunk=self.stream_chunk, serve=self.serve,
                resilience=self.resilience, gangs=self.gangs,
            )
            ctx.tid = "Cycle/bind"
            ctx_box.append(ctx)
            _cycle_pending(ctx)
            if ctx.done:
                # empty/gang-only cycle: nothing in flight to overlap —
                # finalize any deferred cycle now so reports stay ordered
                self._finalize_prev()
                tl.ingest_ms = (clock() - tl.t0_s) * 1000.0
                tl.total_ms = tl.ingest_ms
                tl.bind_done_s = clock() - tl.t0_s
                self.timelines.append(tl)
                return ctx.report

            from scheduler_plugins_tpu.utils import sanitize

            if sanitize.enabled():
                sanitize.drain()
            ctx.rec = flightrec.recorder.begin(
                now_ms=now, profile=self.scheduler.profile.name
            )
            ctx.serve_t0 = clock() if self.serve is not None else None
            generation = getattr(
                self.cluster.nrt_cache, "generation", None
            )
            ctx._flow = obs.flow(
                "cycle", generation=generation, pending=len(ctx.pending)
            )
            ctx._flow.__enter__()
            try:
                _cycle_snapshot(ctx)
            except BaseException:
                ctx._flow.__exit__(*sys.exc_info())
                raise
        tl.ingest_ms = (clock() - tl.t0_s) * 1000.0

        try:
            # ---- dispatch: the device solve goes in flight -------------
            t0 = clock()
            with obs.tracer.span(f"solve cycle {cid}", tid="Cycle/solve",
                                 pending=len(ctx.pending)):
                _cycle_solve_dispatch(ctx)
            tl.dispatch_ms = (clock() - t0) * 1000.0

            # ---- overlap window: previous cycle's report-only epilogue -
            t0 = clock()
            with obs.tracer.span(
                f"finalize cycle {cid - 1}", tid="Cycle/finalize"
            ):
                self._finalize_prev()
            tl.overlap_ms = (clock() - t0) * 1000.0

            # ---- fence: host transfers complete the in-flight solve ----
            t0 = clock()
            with obs.tracer.span(f"fence cycle {cid}", tid="Cycle/solve"):
                _cycle_solve_fence(
                    ctx, quality_view=ctx.serve is not None
                )
            tl.fence_wait_ms = (clock() - t0) * 1000.0
            _cycle_post_solve(ctx)
        except BaseException:
            ctx._flow.__exit__(*sys.exc_info())
            raise
        ctx._flow.__exit__(None, None, None)

        # ---- bind + post-bind: async flush behind the conflict fence ---
        # Failure attribution must run against THIS cycle's prepared
        # plugins when the codes did not ride the solve result (the
        # batched/streamed reduction re-reads plugin aux): eager, inside
        # the flush. The sequential path's codes are host-decodable any
        # time: deferred into the next overlap window.
        eager_attr = getattr(ctx.result, "failed_plugin", None) is None
        # sink drain generation at submit: inside the tick loop the
        # conflict fence guarantees the flush lands before the next
        # drain, so a crossing is only observable when an EXTERNAL
        # drain (a direct `engine.refresh`, a shutdown-path flush)
        # overtakes an in-flight bind — exactly the case the
        # binds-as-deltas classification absorbs
        sink = (
            getattr(self.serve, "_sink", None)
            if self.serve is not None else None
        )
        drains_at_submit = sink.drains if sink is not None else None

        def bind_job():
            t0 = clock()
            with obs.tracer.span(f"bind cycle {cid}", tid="Cycle/bind"):
                _cycle_bind(ctx)
                _cycle_postbind(ctx, attribution=eager_attr)
            tl.bind_ms = (clock() - t0) * 1000.0
            tl.bind_done_s = clock() - tl.t0_s
            if sink is not None and sink.drains != drains_at_submit:
                # this flush crossed a drain boundary: its store
                # mutations reach the resident serving state as
                # ordinary DeltaSink deltas of a LATER window (the
                # conflict-fence classification) — resident state stays
                # exact, the binds are just observed one window later
                tl.late_bind = True
                obs.metrics.inc(obs.CYCLE_LATE_BINDS)

        if self._flusher is not None:
            self._bind_future = self._flusher.submit(bind_job)
        else:
            bind_job()

        self._pending_finalize = (ctx, eager_attr)
        tl.total_ms = (clock() - tl.t0_s) * 1000.0
        obs.metrics.set_gauge(
            obs.CYCLE_OVERLAP_EFFICIENCY, tl.overlap_efficiency
        )
        obs.metrics.set_gauge(
            obs.CYCLE_PIPELINE_BUBBLE, tl.pipeline_bubble_ms
        )
        self.timelines.append(tl)
        return ctx.report

    def _finalize_prev(self) -> CycleReport | None:
        pending, self._pending_finalize = self._pending_finalize, None
        if pending is None:
            return None
        prev_ctx, attributed = pending
        _cycle_finalize(prev_ctx, attribution=not attributed)
        return prev_ctx.report
