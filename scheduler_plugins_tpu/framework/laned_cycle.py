"""K-lane optimistic-concurrency cycle engine: one conflict fence.

`framework.cycle.run_cycle` admits the whole pending queue through ONE
sequential solve lane; `framework.pipeline_cycle.PipelinedCycle` (PR 11)
overlaps cycles but still serializes admission through that one lane.
This module composes the SAME `_cycle_*` stage functions around the
K-lane speculative solver (`parallel.lanes.LaneSolver`): the pending
queue partitions across K lanes by a deterministic key (gang members
never split), every lane solves speculatively against the same resident
base snapshot, and a single host-side conflict fence walks the DEFINED
SERIAL ORDER (the global queue order — exactly the order `run_cycle`'s
scan commits), committing validated placements wholesale and re-solving
from the first conflict against committed state.

The concurrency model mirrors the reference's deployment shape — a
second scheduler solving optimistically against shared cluster state,
serialized by the apiserver's bind (SURVEY.md §L0, deploy/k8s.yaml) —
with the fence playing the apiserver's role, inside one process.

Ordering contract (what keeps laned placements BIT-IDENTICAL to
`run_cycle` at every K — gated by tests/test_differential.py's
TestLanedCycleEquivalence and bench config 15):

- **One solve boundary.** The laned solve replaces ONLY the
  dispatch+fence pair inside the Solve extension span. Everything
  before (requeue gating, queue sort, gang phase, serve refresh,
  prepare, flight-recorder input capture) and after (bind, Permit
  fan-out, PostFilter gang rejection, preemption, finalize) is the
  serial engine's own stage function — one copy, zero drift.
- **Fence exactness.** The fence validates per-pod step signatures
  (admit verdicts + built-in fit mask) on host int64 twins of the
  device math and re-solves the remaining suffix through the same
  step body on the first mismatch — `parallel.lanes` carries the
  induction argument, docs/SCALING.md the prose proof.
- **Serial fallback.** K == 1, profiles outside the fence-exact gate
  (armed side tables, preemption nominees, unknown admit plugins) and
  packing-mode profiles all route to `Scheduler.solve` — the parity
  path itself, so the engine NEVER trades exactness for lanes.
- **Binds land as ordinary deltas.** All K lanes share the one
  cluster store and (when serving) the one DeltaSink: the fence's
  merged decisions flow through `_cycle_bind`'s store mutators, whose
  sink events land at the next ingest boundary exactly like any other
  delta (the PR 6 classification). With `async_bind` the flush runs on the
  "spt-lane-flusher" worker behind the same join-first fence as the
  pipelined engine; a flush crossing an external drain boundary is
  counted late (`scheduler_cycle_late_binds_total`) and absorbed.
"""

from __future__ import annotations

import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

from scheduler_plugins_tpu.framework.cycle import (
    CycleReport,
    SolveResultView,
    _cycle_bind,
    _cycle_finalize,
    _cycle_open,
    _cycle_pending,
    _cycle_post_solve,
    _cycle_postbind,
    _cycle_snapshot,
    _cycle_solve_fence,
)
from scheduler_plugins_tpu.framework.runtime import now_ms as _now_ms
from scheduler_plugins_tpu.obs import ledger as podledger
from scheduler_plugins_tpu.utils import flightrec, observability as obs


class LanedCycle:
    """K-lane cycle engine over one scheduler + cluster store.

    `tick(now)` runs one cycle and returns its `CycleReport` with
    `report.lanes` carrying the lane attribution (k, per-lane sizes /
    committed / conflicts, re-resolve count, solve vs fence wall ms).
    With `async_bind` the bind/post-bind/finalize epilogue flushes on a
    worker thread — call `fence()` (or tick again: the ingest boundary
    fences first) before reading the store, and `flush()`/`close()` at
    shutdown, exactly the `PipelinedCycle` discipline.

    `serve`/`gangs` compose like `run_cycle`'s parameters. `resilience`
    is deliberately NOT accepted: the watchdog's deadline semantics wrap
    one synchronous solve, and its degraded host path IS the sequential
    engine — lanes would add nothing but fence overhead to it.
    """

    def __init__(self, scheduler, cluster, k: int = 4, serve=None,
                 gangs=None, partition: str = "namespace",
                 dispatch: str = "fused", async_bind: bool = False,
                 report_keep: int = 512):
        # deferred: parallel.lanes imports the framework package (the
        # step body + SolverState), so a module-level import here would
        # be circular through framework/__init__
        from scheduler_plugins_tpu.parallel.lanes import LaneSolver

        if scheduler.profile.solve_mode == "packing":
            raise ValueError(
                "LanedCycle requires the sequential parity solve "
                "(profile solve_mode 'packing' has no per-pod serial "
                "order for the conflict fence to replay)"
            )
        self.scheduler = scheduler
        self.cluster = cluster
        self.serve = serve
        self.gangs = gangs
        # the O(changed) pending index also pins the admission serials
        # the "hash" partition mode keys on (Cluster.admission_serial)
        cluster.enable_pending_index()
        self.solver = LaneSolver(
            scheduler, k=k, partition=partition, dispatch=dispatch
        )
        self._flusher = (
            ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="spt-lane-flusher"
            )
            if async_bind else None
        )
        self._bind_future = None
        self._cycle_id = 0
        #: rolling lane attributions (report.lanes dicts), most recent
        #: last — the daemon's /healthz lanes block reads the tail
        self.lane_reports: deque = deque(maxlen=report_keep)
        self.cycles = 0
        self.conflicts_total = 0
        self.re_resolved_total = 0
        self.serial_fallbacks = 0

    @property
    def k(self) -> int:
        return self.solver.k

    # -- the conflict fence (bind flusher join) --------------------------
    def fence(self) -> None:
        """Join the async bind flusher: every store mutation of the
        previous cycle's bind/post-bind stage is visible after this
        returns (exceptions re-raise here)."""
        future, self._bind_future = self._bind_future, None
        if future is not None:
            future.result()

    def flush(self) -> None:
        self.fence()

    def close(self) -> None:
        self.flush()
        if self._flusher is not None:
            self._flusher.shutdown(wait=True)
        self.solver.close()

    # -- the tick --------------------------------------------------------
    def tick(self, now: int | None = None) -> CycleReport:
        if now is None:
            now = _now_ms()
        # pod-lifecycle ledger scope discipline (the PipelinedCycle
        # pattern): `_cycle_open` pushes the lane-0 scope on this thread;
        # pop it on every exit so a raise cannot leak a stale scope
        ctx_box: list = []
        try:
            return self._tick(now, ctx_box)
        finally:
            if ctx_box:
                podledger.LEDGER.pop_scope(ctx_box[0].led)
                podledger.LEDGER.cycle_close(ctx_box[0].led)

    def _tick(self, now: int, ctx_box: list) -> CycleReport:
        cid = self._cycle_id
        self._cycle_id += 1

        # ingest boundary: join the previous flush FIRST, so every bind/
        # backoff/nomination of cycle N is visible to cycle N+1's
        # pending read and serve drain (the PipelinedCycle contract)
        self.fence()
        ctx = _cycle_open(
            self.scheduler, self.cluster, now, serve=self.serve,
            gangs=self.gangs,
        )
        if self._flusher is not None:
            # bind/post-bind spans move off the main thread: their own
            # tid keeps every Perfetto row single-threaded (the per-tid
            # validity gate)
            ctx.tid = "Lane/bind"
        ctx_box.append(ctx)
        _cycle_pending(ctx)
        if ctx.done:
            return ctx.report

        from scheduler_plugins_tpu.utils import sanitize

        if sanitize.enabled():
            sanitize.drain()
        ctx.rec = flightrec.recorder.begin(
            now_ms=now, profile=self.scheduler.profile.name
        )
        ctx.serve_t0 = (
            time.perf_counter() if self.serve is not None else None
        )
        generation = getattr(self.cluster.nrt_cache, "generation", None)
        ctx._flow = obs.flow(
            "cycle", generation=generation, pending=len(ctx.pending)
        )
        ctx._flow.__enter__()
        try:
            _cycle_snapshot(ctx)
            with obs.extension_span(
                "Solve", self.scheduler.profile.name,
                pending=len(ctx.pending), lanes=self.k,
            ):
                if ctx.led is not None:
                    # this engine dispatches its own solver (not
                    # `_cycle_solve_dispatch`), so the ledger's solve
                    # stamp lands here
                    ctx.led.t_solve = podledger.LEDGER._now()
                assignment, admitted, wait, codes, stats = (
                    self.solver.solve(
                        ctx.snap, ctx.pending, self.cluster,
                        meta=ctx.meta,
                    )
                )
                # host arrays + per-pod codes: the record replays through
                # the sequential twin (rec_mode "sequential") and failure
                # attribution decodes exactly, like the parity path
                ctx.result = SolveResultView(
                    assignment, admitted, wait, failed_plugin=codes
                )
                ctx.assignment = assignment
                ctx.admitted = admitted
                ctx.wait = wait
                ctx.fenced = True
                # already host arrays; this only captures the quality
                # view when the finalize may run after the resident
                # node tensors were donated (async epilogue)
                _cycle_solve_fence(
                    ctx, quality_view=(
                        self._flusher is not None
                        and self.serve is not None
                    ),
                )
            ctx.report.lanes = stats.as_dict()
            self.cycles += 1
            self.conflicts_total += sum(stats.conflicts or [])
            self.re_resolved_total += stats.re_resolved
            if (
                stats.path == "serial"
                and stats.serial_fallback_reason != "k=1"
            ):
                # gate rejections only: K == 1 routing through the
                # parity solve is the engine's intended degenerate
                # configuration, not a fallback
                self.serial_fallbacks += 1
            self.lane_reports.append(ctx.report.lanes)
            _cycle_post_solve(ctx)
        except BaseException:
            ctx._flow.__exit__(*sys.exc_info())
            raise
        ctx._flow.__exit__(None, None, None)

        # bind + post-bind + finalize: inline, or flushed behind the
        # join-first fence. Attribution always runs eagerly inside the
        # flush — the laned result carries per-pod codes (host ints,
        # decodable any time), and the postbind gang/preemption
        # machinery needs the failure set anyway.
        sink = (
            getattr(self.serve, "_sink", None)
            if self.serve is not None else None
        )
        drains_at_submit = sink.drains if sink is not None else None

        def bind_job():
            with obs.tracer.span(f"bind cycle {cid}", tid=ctx.tid):
                _cycle_bind(ctx)
                _cycle_postbind(ctx, attribution=True)
                _cycle_finalize(ctx)
            if sink is not None and sink.drains != drains_at_submit:
                # crossed an external drain boundary: the binds reach
                # the resident serving state as ordinary deltas of a
                # later window (the PR 6 conflict-fence classification)
                obs.metrics.inc(obs.CYCLE_LATE_BINDS)

        if self._flusher is not None:
            self._bind_future = self._flusher.submit(bind_job)
        else:
            bind_job()
        return ctx.report

    # -- introspection (daemon /healthz) --------------------------------
    def stats(self) -> dict:
        """Totals + the most recent cycle's lane attribution."""
        last = self.lane_reports[-1] if self.lane_reports else None
        return {
            "k": self.k,
            "partition": self.solver.partition,
            "dispatch": self.solver.dispatch,
            "cycles": self.cycles,
            "conflicts_total": self.conflicts_total,
            "re_resolved_total": self.re_resolved_total,
            "serial_fallbacks": self.serial_fallbacks,
            "last": last,
        }
