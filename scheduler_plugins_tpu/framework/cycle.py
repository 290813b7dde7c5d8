"""One full scheduling cycle: queue -> snapshot -> jitted solve -> apply.

Host-side application of the solve result reproduces the reference's Permit /
PostFilter machinery (/root/reference/pkg/coscheduling/coscheduling.go:162-274):

- assigned & quorum met        -> bind immediately (Permit Success); also
  releases previously-waiting siblings (IterateOverWaitingPods...Allow).
- assigned & quorum unmet      -> reserve (Permit Wait) with the gang deadline
  = PodGroup.ScheduleTimeoutSeconds or the plugin's PermitWaitingTimeSeconds.
- unschedulable gang member    -> PostFilter: if the gang can still reach
  quorum within the reject-percentage slack, let the rest retry; otherwise
  reject the whole gang — release reservations, record failure time (queue
  demotion), back off the group.
- expired gang deadline        -> same whole-gang rejection path.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import contextlib
import os
import time
from collections import deque

from scheduler_plugins_tpu.api import events as ev_api
from scheduler_plugins_tpu.framework.preemption import GATED, encode_demand
from scheduler_plugins_tpu.framework.runtime import (
    Scheduler,
    SolveResult,
    now_ms as _now_ms,
)
from scheduler_plugins_tpu.obs import ledger as podledger
from scheduler_plugins_tpu.plugins.coscheduling import Coscheduling
from scheduler_plugins_tpu.resilience import faults
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import flightrec, observability as obs


@dataclass
class SolveResultView:
    """The (assignment, admitted, wait) triple the cycle consumes — what the
    streamed pipeline solve returns (no SolverState carry to surface).
    `failed_plugin` stays None: attribution for streamed solves is reduced
    from the cycle-initial per-plugin masks (`Scheduler.attribution_codes`)."""

    assignment: object
    admitted: object
    wait: object
    failed_plugin: object = None


@dataclass
class CycleReport:
    bound: dict[str, str] = field(default_factory=dict)  # uid -> node
    reserved: dict[str, str] = field(default_factory=dict)
    failed: list[str] = field(default_factory=list)
    #: uid -> plugin name that made the pod unschedulable (the upstream
    #: `UnschedulablePlugins` attribution): the first plugin in profile
    #: order whose PreFilter rejected it or whose Filter emptied the
    #: feasible node set; "NodeResourcesFit" for built-in fit/capacity
    #: failures. Exact against the carried state on the sequential parity
    #: path (`SolveResult.failed_plugin`), reduced from the cycle-initial
    #: per-plugin masks for batched/streamed solves.
    failed_by: dict[str, str] = field(default_factory=dict)
    #: pods parked unschedulable with no registered event since their last
    #: failure (EnqueueExtensions gating) — excluded from this cycle's batch
    skipped: list[str] = field(default_factory=list)
    rejected_gangs: list[str] = field(default_factory=list)
    expired_gangs: list[str] = field(default_factory=list)
    #: preemptor uid -> (nominated node, victim uids)
    preempted: dict[str, tuple[str, list[str]]] = field(default_factory=dict)
    #: checkify findings from this cycle's solve when the sanitizer mode is
    #: on (SPT_SANITIZE=1, utils.sanitize). Read together with
    #: `sanitize_checked`: empty errors are only "all checks passed" when
    #: checked calls actually ran — a cycle whose solve took an
    #: uninstrumented path (sequential fallback) reports 0 checked calls
    sanitize_errors: list[dict] = field(default_factory=list)
    #: number of checkify-instrumented solve invocations this cycle (None
    #: when sanitize mode is off; 0 means the solve path was uninstrumented)
    sanitize_checked: int | None = None
    #: placement-quality objectives for this cycle's solve
    #: (`tuning.quality`: fragmentation, util_imbalance, gang_wait_frac,
    #: unplaced_frac, plus the host preemption/nomination counts) — None
    #: when the cycle ran no solve. Also exported as
    #: `scheduler_placement_quality{objective}` gauges.
    quality: dict | None = None
    #: which solve served this cycle when a `resilience` state machine is
    #: attached: "device" (fast path) or "host" (degraded failover /
    #: probation miss — `resilience.hostsolve`); None without one
    solve_path: str | None = None
    #: True when the process was serving from the host parity path at
    #: the END of this cycle (`scheduler_degraded` gauge's report twin)
    degraded: bool = False
    #: per-gang outcome of the rank-aware gang phase (`gangs.phase`):
    #: gang full_name -> {admitted, placed_new, resident, desired,
    #: max_cost, sum_cost} — empty when the cycle ran without a gang
    #: phase or no rank-aware gang had pending members
    rank_gangs: dict = field(default_factory=dict)
    #: lane attribution when the cycle ran under the K-lane optimistic
    #: engine (`framework.laned_cycle.LanedCycle`): k, path
    #: ("laned"/"serial" fallback), per-lane sizes/committed/conflicts,
    #: re_resolved count and solve/fence wall ms (`LaneStats.as_dict`);
    #: None for every other engine
    lanes: dict | None = None

    def explain(self, uid: str, top_k: int = 5) -> dict:
        """The "why this node" score table for one pod of THIS cycle's
        pending batch (see `utils.flightrec.explain_solver`): top-k
        candidate nodes with per-plugin weighted normalized score columns,
        the built-in fit margin and the winner gap — the upstream
        `--v=10` score dump, per pod, on demand. Works for placed AND
        failed pods; raises KeyError for a uid outside the batch and
        RuntimeError when the cycle never reached a solve (no pending) or
        when the context has been released (only the most recent
        SPT_EXPLAIN_RETAIN cycle reports keep their snapshot — retaining
        every report must not pin every snapshot ever solved)."""
        ctx = getattr(self, "_explain_ctx", None)
        if ctx is _CTX_RELEASED:
            raise RuntimeError(
                f"explain context released: only the most recent "
                f"{_explain_retain()} cycle reports keep their snapshot "
                "(SPT_EXPLAIN_RETAIN; 0 disables explain entirely); use "
                "the flight recorder for postmortems beyond that window"
            )
        if ctx is None:
            raise RuntimeError(
                "this cycle ran no solve (empty pending batch) — nothing "
                "to explain"
            )
        scheduler, snap, meta, assignment, auxes = ctx
        return flightrec.explain_solver(
            scheduler, snap, meta, uid, top_k=top_k, assignment=assignment,
            auxes=auxes,
        )


#: sentinel on `CycleReport._explain_ctx`: distinguishes "released by the
#: retention window" from "this cycle never solved"
_CTX_RELEASED = object()

#: reports whose explain context (scheduler/snapshot/meta/assignment refs)
#: is still attached, most recent last — a full `ClusterSnapshot` hangs off
#: each ctx, so a caller retaining every report must not pin every
#: snapshot ever solved
_EXPLAIN_RING: deque = deque()


def _explain_retain() -> int:
    try:
        return int(os.environ.get("SPT_EXPLAIN_RETAIN", "8"))
    except ValueError:
        return 8


def _attach_explain_ctx(report: CycleReport, ctx: tuple) -> None:
    retain = _explain_retain()
    if retain <= 0:
        # explain disabled: pin nothing, not even this cycle's snapshot
        report._explain_ctx = _CTX_RELEASED
        return
    report._explain_ctx = ctx
    _EXPLAIN_RING.append(report)
    while len(_EXPLAIN_RING) > retain:
        _EXPLAIN_RING.popleft()._explain_ctx = _CTX_RELEASED


@dataclass
class CycleCtx:
    """Mutable state threaded through one cycle's stages.

    `run_cycle` composes the `_cycle_*` stage functions below strictly
    serially; the pipelined engine (`framework.pipeline_cycle`) composes
    the SAME functions with cycle N's device solve left in flight while
    host stages of neighboring cycles run — one copy of every stage, so
    the two engines cannot drift (the serial engine stays the parity
    anchor, gated by `tests/test_differential.py`'s pipelined-equivalence
    twin)."""

    scheduler: Scheduler
    cluster: Cluster
    now: int
    report: CycleReport
    stream_chunk: int | None = None
    serve: object = None
    resilience: object = None
    gangs: object = None
    cosched: object = None
    pending: list = field(default_factory=list)
    snap: object = None
    meta: object = None
    served: bool = False
    serve_t0: float | None = None
    rec: object = None
    result: object = None
    assignment: object = None
    admitted: object = None
    wait: object = None
    #: host transfers already forced (resilience path fences internally)
    fenced: bool = False
    #: early return taken (empty batch / gang-only cycle)
    done: bool = False
    #: tracer row for the bind/post-bind stages — the pipelined engine's
    #: async bind flusher runs them on a worker thread, and spans from
    #: two threads on one row would partially overlap (the Perfetto
    #: validity gate rejects that); the serial engine keeps "cycle"
    tid: str = "cycle"
    failed_idx: list = field(default_factory=list)
    failed_by_gang: dict = field(default_factory=dict)
    #: host copies of the snapshot columns `_observe_quality` reads —
    #: captured at the fence by the pipelined engine, whose deferred
    #: finalize runs AFTER the next refresh consumed (donated) the
    #: resident node tensors; None on the serial path (quality reads the
    #: live snapshot before any donation: its thread finalizes, then
    #: refreshes)
    quality_view: object = None
    #: `serve.generation` as this cycle's refresh left it, on a served
    #: cycle: `_cycle_finalize` reads the resident node columns only while
    #: the engine still stands there
    serve_generation: int | None = None
    #: this cycle's pod-lifecycle ledger context (`obs.ledger.LedgerCycle`)
    #: — None whenever the ledger is disabled, so every hook below guards
    #: on it and the off path costs one attribute read
    led: object = None


def _cycle_open(scheduler, cluster, now, stream_chunk=None, serve=None,
                resilience=None, gangs=None) -> CycleCtx:
    """Cycle prologue: counters, per-cycle plugin wiring, gang expiry, NRT
    resync and collector ticks — everything before the pending batch."""
    ctx = CycleCtx(
        scheduler=scheduler, cluster=cluster, now=now, report=CycleReport(),
        stream_chunk=stream_chunk, serve=serve, resilience=resilience,
        gangs=gangs,
    )
    # the ledger scope opens BEFORE gang expiry: whole-gang rejections in
    # the prologue are this cycle's decisions and must attribute to it.
    # Callers (run_cycle / PipelinedCycle.tick / LanedCycle.tick) pop the
    # scope in their finally — the stage functions only push nested ones.
    ctx.led = podledger.LEDGER.cycle_open(now)
    podledger.LEDGER.push_scope(ctx.led, 0)
    obs.metrics.inc(obs.SCHEDULING_CYCLES)
    ctx.cosched = next(
        (p for p in scheduler.profile.plugins if isinstance(p, Coscheduling)),
        None,
    )
    for plugin in scheduler.profile.plugins:
        plugin.configure_cluster(cluster)
    with obs.tracer.span("ExpireGangs", tid="cycle"):
        _expire_gangs(cluster, now, ctx.report)
    with obs.tracer.span("NRTResync", tid="cycle"):
        _resync_nrt_cache(cluster, now)
    with obs.tracer.span("Collectors", tid="cycle"):
        _refresh_metrics(scheduler, cluster, now)
    return ctx


def _cycle_pending(ctx: CycleCtx) -> None:
    """Pending batch assembly: requeue gating, queue sort, and the
    rank-gang phase. Sets `ctx.done` when the cycle ends here (no batch,
    or a gang-only cycle fully handled by the phase)."""
    scheduler, cluster, now, report = (
        ctx.scheduler, ctx.cluster, ctx.now, ctx.report,
    )
    gangs, serve = ctx.gangs, ctx.serve
    with obs.tracer.span("PendingScan", tid="cycle",
                         pods=len(cluster.pods)):
        pending = cluster.pending_pods()
    with obs.tracer.span("Requeue", tid="cycle"):
        pending = _requeue_eligible(
            scheduler, cluster, pending, now, report,
            gang_phase=gangs is not None,
        )
    if gangs is None and not pending:
        ctx.done = True
        return
    pending = scheduler.sort_pending(pending, cluster)

    if gangs is not None:
        # the phase runs even on an empty batch: elastic reconcile must
        # observe desired-width changes (shrink deletes need no pending
        # pods), and growth clones it creates join THIS cycle's batch
        with obs.extension_span("GangPhase", type(gangs).__name__,
                                pending=len(pending)):
            pending = gangs.run(
                scheduler, cluster, pending, now, report, serve=serve
            )
        if not pending:
            # gang-only cycle: every pending pod was a rank-gang member
            # (bound or parked by the phase); nothing for the per-pod
            # solve, so close out the counters and return. A serving
            # engine still DRAINS (refresh with an empty batch): the
            # phase's binds must land in the resident columns and the
            # per-gang rank mirror now, not pile up in the sink until the
            # next non-gang cycle. The cycle is still RECORDED when the
            # flight recorder is live — the gang capture alone replays
            # bit-identically through the twin
            if serve is not None:
                serve.refresh(cluster, [], now_ms=now)
            rec = flightrec.recorder.begin(
                now_ms=now, profile=scheduler.profile.name
            )
            if rec is not None:
                gangs.annotate_record(rec)
                rec.commit(report)
            obs.metrics.inc(obs.PODS_BOUND, len(report.bound))
            obs.metrics.inc(obs.PODS_FAILED, len(report.failed))
            obs.metrics.inc(obs.GANG_REJECTIONS, len(report.rejected_gangs))
            ctx.done = True
            return
    ctx.pending = pending
    if ctx.led is not None:
        # the batch membership gate for per-attempt stage splitting:
        # binds/reservations of pods OUTSIDE this set (gang-phase binds
        # above, permit fan-out of earlier cycles' reservations) charge
        # their whole open interval to the resting wait-state instead
        ctx.led.batch = frozenset(p.uid for p in pending)


def _cycle_snapshot(ctx: CycleCtx) -> None:
    """Snapshot/serve-refresh assembly, plugin prepare, flight-recorder
    input capture. Runs inside the caller's `obs.flow` context."""
    scheduler, cluster, now = ctx.scheduler, ctx.cluster, ctx.now
    pending, serve, gangs = ctx.pending, ctx.serve, ctx.gangs
    with obs.tracer.span("Snapshot", tid="cycle", pending=len(pending)):
        snap = meta = None
        if serve is not None:
            refreshed = serve.refresh(cluster, pending, now_ms=now)
            if refreshed is not None:
                snap, meta = refreshed
                ctx.served = True
                ctx.serve_generation = serve.generation
        if snap is None:
            snap, meta = cluster.snapshot(pending, now_ms=now)
    ctx.snap, ctx.meta = snap, meta
    scheduler.prepare(meta, cluster)
    if ctx.rec is not None:
        # inputs land in the ring BEFORE the solve: the cycle that
        # crashes the solver is exactly the one worth replaying
        with obs.tracer.span("Record", tid="cycle"):
            ctx.rec.capture_inputs(
                snap, meta, scheduler, stream_chunk=ctx.stream_chunk,
                profile_config=flightrec.recorder.profile_config,
            )
            if ctx.served:
                # serve provenance: resident generation, base digest,
                # and the packed delta stream that produced this
                # cycle's snapshot view
                serve.annotate_record(ctx.rec)
            if gangs is not None:
                # gang-phase provenance: the full RankGangState +
                # outputs, so a recorded gang cycle replays
                # bit-identically through the numpy twin
                gangs.annotate_record(ctx.rec)


def _cycle_solve_dispatch(ctx: CycleCtx) -> None:
    """Dispatch the solve. On the plain path the result tensors stay
    DEVICE arrays (async dispatch — `_cycle_solve_fence` forces the host
    transfer); the resilience path completes through the watchdog's own
    deadlined fence and returns host arrays (`ctx.fenced`)."""
    scheduler, snap = ctx.scheduler, ctx.snap
    if ctx.led is not None:
        # dispatch ENTRY, not return: the in-batch wait stage ends the
        # moment the solve starts consuming the snapshot
        ctx.led.t_solve = podledger.LEDGER._now()
    result = None
    if ctx.resilience is not None:
        # watchdog-guarded: dispatch + completion fence in a
        # worker thread with a deadline; retries, then failover
        # to the host parity path (resilience.watchdog)
        (assignment, admitted, wait, codes_np,
         ctx.report.solve_path) = ctx.resilience.solve_cycle(
            scheduler, snap, stream_chunk=ctx.stream_chunk
        )
        result = SolveResultView(
            assignment, admitted, wait, failed_plugin=codes_np
        )
        ctx.assignment, ctx.admitted, ctx.wait = assignment, admitted, wait
        ctx.fenced = True
    else:
        if ctx.stream_chunk:
            from scheduler_plugins_tpu.parallel.pipeline import (
                streamed_profile_solve,
            )

            streamed = streamed_profile_solve(
                scheduler, snap, chunk=ctx.stream_chunk
            )
            if streamed is not None:
                result = SolveResultView(*streamed)
        if result is None:
            result = scheduler.solve(snap)
    ctx.result = result


def _cycle_solve_fence(ctx: CycleCtx, quality_view: bool = False) -> None:
    """Force the host transfers, so the caller's Solve span/histogram
    covers the device round-trip. `quality_view` also
    copies the snapshot columns the deferred quality observation reads
    (the pipelined engine's finalize runs after the resident node
    tensors were donated to the next cycle's delta apply)."""
    if ctx.led is not None and ctx.led.t_fence0 is None:
        ctx.led.t_fence0 = podledger.LEDGER._now()
    if not ctx.fenced:
        ctx.assignment = np.asarray(ctx.result.assignment)
        ctx.admitted = np.asarray(ctx.result.admitted)
        ctx.wait = np.asarray(ctx.result.wait)
        ctx.fenced = True
    if ctx.led is not None and ctx.led.t_fence1 is None:
        ctx.led.t_fence1 = podledger.LEDGER._now()
    if quality_view:
        ctx.quality_view = _quality_view(ctx.snap)


def _cycle_post_solve(ctx: CycleCtx) -> None:
    """Post-fence bookkeeping: degraded flag, flight-recorder output
    capture, explain-context retention, sanitizer drain."""
    from scheduler_plugins_tpu.utils import sanitize

    report, result = ctx.report, ctx.result
    report.degraded = (
        ctx.resilience is not None and ctx.resilience.degraded
    )
    if ctx.led is not None:
        ctx.led.degraded = report.degraded
        ctx.led.solve_path = report.solve_path
    if ctx.rec is not None:
        with obs.tracer.span("Record", tid="cycle"):
            from scheduler_plugins_tpu.parallel.solver import PackingSolveView

            codes = getattr(result, "failed_plugin", None)
            if isinstance(result, PackingSolveView):
                # packing placements replay through the sequential path
                # as EVIDENCE only (soft ordering differs by design) —
                # the mode string keeps the replayer honest about it
                rec_mode = "packing"
            elif isinstance(result, SolveResult) or codes is not None:
                # the host failover path carries the sequential parity
                # semantics (and per-pod codes), so its records replay
                # through the same path as device-sequential ones
                rec_mode = "sequential"
            else:
                rec_mode = "streamed"
            ctx.rec.capture_outputs(
                rec_mode,
                ctx.assignment, ctx.admitted, ctx.wait,
                failed_plugin=(
                    None if codes is None else np.asarray(codes)
                ),
            )
    if ctx.served:
        # serve cycles keep NO explain context: the snapshot's node
        # tensors are the resident carry, donated to the next cycle's
        # delta apply — a retained ctx would read freed device buffers.
        # Postmortems go through the flight recorder (host copies).
        report._explain_ctx = _CTX_RELEASED
    else:
        # cheap refs, not copies: lets `report.explain(uid)` rebuild the
        # per-plugin score table for any pod of this batch after the fact;
        # retention-bounded so old reports release their snapshot. The aux
        # pytrees are frozen HERE — a later cycle's prepare() rebinds the
        # shared plugins, and explaining an old report against the live
        # aux() would score cycle K's snapshot with cycle K+n's config
        _attach_explain_ctx(report, (
            ctx.scheduler, ctx.snap, ctx.meta, ctx.assignment,
            tuple(p.aux() for p in ctx.scheduler.profile.plugins),
        ))

    if sanitize.enabled():
        # surface this cycle's checkify findings on the report (the solve
        # paths above report into the sanitizer's buffer as they run);
        # checked-call count kept so "no errors" cannot be mistaken for
        # "checks ran" when the solve fell back to an uninstrumented path
        reports = sanitize.drain()
        report.sanitize_checked = len(reports)
        report.sanitize_errors = [r for r in reports if not r["ok"]]


def _cycle_bind(ctx: CycleCtx) -> None:
    """The bind stage: flush this cycle's placement decisions through the
    store mutators (bind / reserve / mark_unschedulable). Every mutation
    here carries THIS cycle's `now` — under the pipelined engine the
    flush may run while the wall clock is already inside the next cycle's
    ingest, and backoff windows must still be charged to the cycle that
    observed the snapshot. The ledger scope follows the same rule: lane 1
    on THIS thread (the pipelined engine's flusher has its own scope
    stack), attributing every store-hook event to the observing cycle."""
    podledger.LEDGER.push_scope(ctx.led, 1)
    try:
        _bind_decisions(ctx)
    finally:
        podledger.LEDGER.pop_scope(ctx.led)


def _bind_decisions(ctx: CycleCtx) -> None:
    cluster, report, now = ctx.cluster, ctx.report, ctx.now
    pending, meta = ctx.pending, ctx.meta
    assignment, admitted, wait = ctx.assignment, ctx.admitted, ctx.wait
    cosched = ctx.cosched
    with obs.tracer.span("Bind", tid=ctx.tid):
        for i, pod in enumerate(pending):
            node_idx = int(assignment[i])
            pg = cluster.pod_group_of(pod)
            if node_idx < 0 or not admitted[i]:
                report.failed.append(pod.uid)
                ctx.failed_idx.append((i, pod.uid))
                cluster.mark_unschedulable(pod.uid, now)
                if pg is not None:
                    ctx.failed_by_gang.setdefault(
                        pg.full_name, []
                    ).append(pod.uid)
                continue
            node_name = meta.node_names[node_idx]
            if wait[i]:
                cluster.reserve(pod.uid, node_name)
                report.reserved[pod.uid] = node_name
                # per-POD waiting timer from THIS pod's reservation time
                # (upstream waitingPods, coscheduling.go:227-235;
                # GetWaitTimeDuration: ScheduleTimeoutSeconds else
                # PermitWaitingTimeSeconds)
                timeout_s = (
                    pg.schedule_timeout_seconds if pg is not None else None
                )
                if timeout_s is None and cosched is not None:
                    timeout_s = cosched.permit_waiting_seconds
                cluster.pod_deadline_ms[pod.uid] = now + 1000 * (timeout_s or 0)
            else:
                cluster.bind(pod.uid, node_name, now)
                report.bound[pod.uid] = node_name
    # before the Permit fan-out and a rejection take any of them back
    obs.metrics.inc(obs.GANG_WAIT_PODS, len(report.reserved))

    if ctx.serve_t0 is not None:
        # serve-mode decision latency: delta ingest through host-visible
        # bind decisions (the per-decision number the sustained-churn
        # bench reports as p50/p99) — observed even on fallback cycles so
        # the histogram shows what serve traffic actually experienced
        obs.metrics.observe_ms(
            obs.SERVE_DECISION_LATENCY,
            (time.perf_counter() - ctx.serve_t0) * 1000.0,
        )

    if faults.ACTIVE is not None:
        # chaos harness only (zero overhead otherwise): simulate process
        # death AFTER bindings landed in the store — the worst-ordered
        # crash for resident serve state, since the dying sink's
        # undrained deltas are lost with the process. The report rides
        # the exception so the harness can account the real, landed binds
        spec = faults.ACTIVE.fire(faults.CRASH_POST_BIND)
        if spec is not None:
            raise faults.CrashInjected(ctx.report)


def _cycle_postbind(ctx: CycleCtx, attribution: bool = True) -> None:
    """Post-bind store machinery, fenced to the cycle that observed the
    snapshot: Permit quorum fan-out, whole-gang PostFilter rejection,
    over-reserve marks and preemption nomination set/clear. The pipelined
    engine MUST run this before the next cycle's ingest boundary — a
    nomination or backoff landing mid-overlap would otherwise be observed
    by (and attributed to) the wrong cycle. `attribution=False` lets the
    pipelined engine defer the host-only failure decode to its overlap
    window when the per-pod codes already rode the solve result."""
    podledger.LEDGER.push_scope(ctx.led, 1)
    try:
        _postbind_store(ctx, attribution)
    finally:
        podledger.LEDGER.pop_scope(ctx.led)


def _postbind_store(ctx: CycleCtx, attribution: bool) -> None:
    cluster, report, now = ctx.cluster, ctx.report, ctx.now
    cosched = ctx.cosched
    if attribution:
        _attribute_failures(
            ctx.scheduler, ctx.snap, ctx.result, ctx.failed_idx, report,
            tid=ctx.tid, led=ctx.led,
        )

    # Permit Allow fan-out: quorum reached this cycle releases waiting
    # siblings
    with obs.tracer.span("Permit", tid=ctx.tid):
        for pg in list(cluster.pod_groups.values()):
            _maybe_release_gang(cluster, pg, report, now)

    # PostFilter: whole-gang rejection (coscheduling.go:160-209)
    for gang_name in ctx.failed_by_gang:
        pg = cluster.pod_groups.get(gang_name)
        if pg is None:
            continue
        members = cluster.gang_members(pg)
        assigned = sum(
            1 for p in members
            if p.node_name is not None or p.uid in cluster.reserved
        )
        if assigned >= pg.min_member:
            continue  # quorum already met; stragglers can retry freely
        # tolerate a small quorum gap: (MinMember - assigned)/MinMember
        # <= rejectPercentage (coscheduling.go:180-185)
        reject_pct = cosched.reject_percentage if cosched else 10
        gap = (pg.min_member - assigned) / max(pg.min_member, 1)
        if gap <= reject_pct / 100:
            continue  # a subsequent pod may still complete the quorum
        _reject_gang(cluster, pg, now, report, cosched, len(members))

    _mark_overreserved_on_failures(cluster, report)
    engine = ctx.scheduler.profile.preemption
    with obs.extension_span(
        "PostFilter", type(engine).__name__ if engine else "none",
        tid="framework" if ctx.tid == "cycle" else ctx.tid,
        failed=len(report.failed),
    ) as said:
        # the host's victim search: how many of the failed pods it was
        # run for (none of a gang rejected whole), whether it got as far
        # as its post-bind snapshot, and what it found
        said["candidates"] = _run_preemption(
            ctx.scheduler, cluster, ctx.pending, report, now
        )
        said["searched"] = said["candidates"] > 0
        said["victims"] = sum(
            len(victims) for _node, victims in report.preempted.values()
        )
    obs.metrics.inc(obs.PODS_BOUND, len(report.bound))
    obs.metrics.inc(obs.PODS_FAILED, len(report.failed))
    obs.metrics.inc(obs.GANG_REJECTIONS, len(report.rejected_gangs))


def _cycle_finalize(ctx: CycleCtx, attribution: bool = False) -> None:
    """Report-only epilogue — placement-quality observation and the
    flight-recorder commit (plus the deferred failure decode under the
    pipelined engine). Touches no store state, so the pipelined engine
    runs it inside the NEXT cycle's overlap window, on the host copies
    `_cycle_solve_fence(quality_view=True)` captured."""
    if attribution:
        _attribute_failures(
            ctx.scheduler, ctx.snap, ctx.result, ctx.failed_idx, ctx.report,
            tid=ctx.tid, led=ctx.led,
        )
    view = ctx.quality_view

    def donated() -> bool:
        # no host copy, and the engine has refreshed since this cycle's
        # snapshot: the node columns it solved on were donated away
        return (view is None and ctx.served
                and ctx.serve.generation != ctx.serve_generation)

    with obs.tracer.span("Finalize", tid=ctx.tid):
        try:
            if donated():
                raise RuntimeError("donated before Finalize")
            _observe_quality(
                ctx.report, view or ctx.snap, ctx.assignment, ctx.admitted,
                ctx.wait,
            )
        except RuntimeError:
            if not donated():
                raise
            # another thread refreshed the engine under the feed lock
            # between this tick's locked stages and its epilogue (the
            # benchmark's resident-state check does; PR 34's chip run of
            # `gangs-quota-1024n.backlog` lost its daemon to the raise
            # that stood here). The epilogue is report-only: this cycle
            # goes without its placement quality, and never reads what
            # the refresh donated.
            obs.logger.warning(
                "Finalize after the serving engine's next refresh "
                "(generation %s -> %s): no host copy was taken, the "
                "cycle's placement quality is not observed",
                ctx.serve_generation, ctx.serve.generation,
            )
        if ctx.rec is not None:
            ctx.rec.commit(ctx.report)


def _quality_view(snap):
    """Host copies of exactly the snapshot columns `cycle_quality_np`
    reads, in the same attribute shape — safe to read after the resident
    node tensors were donated to a later cycle's delta apply. The pipelined
    engine needs them: its deferred finalize runs after the next refresh.
    The serial engine's two calls (`cycle_store_stages`, then
    `cycle_report_stages`) are the opposite case and take no copy: the
    tick's thread finalizes before it refreshes again. Should another
    thread refresh in between, `_cycle_finalize` leaves the quality out."""
    from types import SimpleNamespace

    return SimpleNamespace(
        nodes=SimpleNamespace(
            alloc=np.asarray(snap.nodes.alloc),
            requested=np.asarray(snap.nodes.requested),
            mask=np.asarray(snap.nodes.mask),
        ),
        pods=SimpleNamespace(
            req=np.asarray(snap.pods.req),
            mask=np.asarray(snap.pods.mask),
        ),
    )


def run_cycle(scheduler: Scheduler, cluster: Cluster, now: int | None = None,
              stream_chunk: int | None = None, serve=None,
              resilience=None, gangs=None, tuner=None) -> CycleReport:
    """One daemon cycle. `stream_chunk` opts the solve into the donated,
    double-buffered chunk pipeline (`parallel.pipeline.streamed_profile_solve`)
    when the profile qualifies for the targeted fast path — huge pending
    queues then stream through bounded chunks instead of one (P, N) solve,
    with wave-path placement semantics (hard constraints exact, soft
    tie-breaking may differ from the sequential scan). Profiles that don't
    qualify fall back to `scheduler.solve` unchanged.

    `serve` opts the SNAPSHOT stage into a resident-state serving engine
    (`serving.engine.ServeEngine`, attached to this cluster): instead of
    rebuilding and re-shipping the full cluster snapshot, the engine keeps
    the node tensors device-resident across cycles and applies O(changed)
    deltas captured from the store's mutation hooks. The solve itself is
    unchanged — the assembled snapshot feeds the same bit-faithful
    sequential parity path, so serve-mode placements are identical to a
    fresh-snapshot cycle (tests/test_serving.py). When the engine cannot
    own the state (side-table objects present, docs/SERVING.md gate), the
    cycle falls back to `cluster.snapshot` transparently. Serve cycles do
    NOT retain an explain context (the resident tensors are donated to
    the next cycle's delta apply — a retained snapshot would read freed
    buffers); the flight recorder is the postmortem surface there.

    `gangs` (a `gangs.phase.GangPhase`) opts the cycle into the
    rank-aware gang phase AHEAD of the per-pod solve: rank-aware
    PodGroups' members are lifted out of the pending batch, placed as
    whole gangs by the topology-block waterfill, and bound through the
    store — so the snapshot the per-pod path solves already carries the
    committed free/eq_used state (the CLAUDE.md carry discipline, at
    phase granularity). Quorum-failed gangs park whole (zero partial
    ranks); elastic gangs grow/shrink in the phase's reconcile first.

    `resilience` (a `resilience.watchdog.Resilience`) routes the solve
    through the solve watchdog: device dispatch + host-transfer
    completion fence in a worker thread with a deadline, seeded-jitter
    retries, failover to the host sequential parity path on an exhausted
    budget, probation probes while degraded (docs/ROBUSTNESS.md). Raises
    `resilience.BackendUnavailable` only when the backend is gone AND the
    profile has no host fallback — callers (the daemon) park the cycle.

    `tuner` (a `tuning.shadow.ShadowTuner`) hooks the guarded-rollout
    controller into the cycle at its two safe seams: `begin_cycle` BEFORE
    anything reads the profile weights (the one point a staged promotion
    or a decided rollback may swap the live weight vector — mid-cycle
    swaps could solve and record under different weights), and
    `observe_report` after finalize (the probation window's
    quality-gauge comparison feeds on the report's quality stamp)."""
    with _cycle_span() as seen:
        ctx = _store_stages(
            seen, scheduler, cluster, now, stream_chunk=stream_chunk,
            serve=serve, resilience=resilience, gangs=gangs, tuner=tuner,
        )
        return cycle_report_stages(ctx, tuner)


def cycle_store_stages(scheduler: Scheduler, cluster: Cluster,
                       now: int | None = None, **options) -> CycleCtx:
    """`run_cycle` as two calls, the first (`options`: `run_cycle`'s own):
    every stage that reads or writes the store, through `_cycle_postbind`
    and the ledger scope's close, inside a `Cycle` span of its own. What a
    caller that guards the store with a lock (`bridge.feed.FeedServer`)
    holds the lock for. The returned context goes to `cycle_report_stages`
    before the next `serve.refresh` of this cluster's engine: on a served
    cycle the epilogue reads the resident node columns in place, and that
    refresh donates them (`_cycle_finalize` raises when asked later). Both
    calls come from one thread, so their order is all that takes."""
    with _cycle_span() as seen:
        return _store_stages(seen, scheduler, cluster, now, **options)


def cycle_report_stages(ctx: CycleCtx, tuner=None) -> CycleReport:
    """`run_cycle` as two calls, the second: the report-only epilogue
    (`_cycle_finalize`) and the tuner's `observe_report`. Touches no store
    state and needs no lock; a cycle that ended early (no batch, a
    gang-only cycle) has nothing to finalize."""
    if not ctx.done:
        _cycle_finalize(ctx)
    if tuner is not None:
        tuner.observe_report(ctx.report)
    return ctx.report


@contextlib.contextmanager
def _cycle_span():
    """The `Cycle` span (tracer row "cycle"): the body, first statement to
    last, recorded at the close with the cycle's number (the registry's
    count of cycles, one higher each time: the identifier its inner spans
    share by nesting) and what it found and bound, once `_store_stages` has
    left its context under "ctx" in the dict this yields. The daemon enters
    with the feed lock held, so a tick's lead-in to this span is its wait
    for the lock."""
    span_from = obs.tracer.now_ns() if obs.tracer.enabled else None
    seen: dict = {}
    try:
        yield seen
    finally:
        if span_from is not None:
            args = {"cycle": obs.metrics.get(obs.SCHEDULING_CYCLES)}
            ctx = seen.get("ctx")
            if ctx is not None:
                args["pending"] = len(ctx.pending)
                args["bound"] = len(ctx.report.bound)
            obs.tracer.complete(
                "Cycle", span_from, obs.tracer.now_ns() - span_from,
                tid="cycle", args=args,
            )


def _store_stages(seen: dict, scheduler, cluster, now, stream_chunk=None,
                  serve=None, resilience=None, gangs=None,
                  tuner=None) -> CycleCtx:
    """The prologue and the stage functions through `_cycle_postbind`,
    strictly serially, and the ledger scope's close."""
    if now is None:
        now = _now_ms()
    if tuner is not None:
        # the weight-swap seam: promotions/rollbacks apply only here,
        # at the cycle boundary, never mid-cycle (docs/ROBUSTNESS.md)
        tuner.begin_cycle(now_ms=now)
    ctx = seen["ctx"] = _cycle_open(
        scheduler, cluster, now, stream_chunk=stream_chunk, serve=serve,
        resilience=resilience, gangs=gangs,
    )
    try:
        _cycle_pending(ctx)
        if ctx.done:
            return ctx

        from scheduler_plugins_tpu.utils import sanitize

        if sanitize.enabled():
            # discard reports left by solves OUTSIDE this cycle (warmups,
            # other schedulers): the post-solve drain below must attribute
            # only THIS cycle's checked calls to this report
            sanitize.drain()
        generation = getattr(cluster.nrt_cache, "generation", None)
        ctx.rec = flightrec.recorder.begin(
            now_ms=now, profile=scheduler.profile.name
        )
        ctx.serve_t0 = time.perf_counter() if serve is not None else None
        with obs.flow(
            "cycle", generation=generation, pending=len(ctx.pending)
        ):
            _cycle_snapshot(ctx)
            # the Solve span covers dispatch AND completion (the fence's
            # np.asarray host transfers force it) for the sequential path;
            # the streamed path's device-side overlap shows up as pipeline
            # rows emitted by run_chunk_pipeline itself
            with obs.extension_span(
                "Solve", scheduler.profile.name, pending=len(ctx.pending)
            ) as said:
                views = obs.metrics.get(obs.SOLVE_NODE_VIEWS)
                _cycle_solve_dispatch(ctx)
                # whether the solve dispatched read node-space views of
                # its domain tables (`Scheduler.solve` counts those)
                said["node_views"] = (
                    obs.metrics.get(obs.SOLVE_NODE_VIEWS) > views
                )
                _cycle_solve_fence(ctx)
            _cycle_post_solve(ctx)
        _cycle_bind(ctx)
        _cycle_postbind(ctx, attribution=True)
        return ctx
    finally:
        # the lane-0 scope opened in `_cycle_open` — popped HERE (not in a
        # stage function) so early returns and raises cannot leak it, and
        # ambient events between cycles fall back to ambient attribution
        podledger.LEDGER.pop_scope(ctx.led)
        podledger.LEDGER.cycle_close(ctx.led)


def _observe_quality(report, snap, assignment, admitted, wait) -> None:
    """Stamp the cycle's placement-quality objectives on the report and
    export them as `scheduler_placement_quality{objective}` gauges
    (tuning.quality's numpy twin — per-cycle reductions on host arrays,
    no per-shape jit compiles on this always-on path; the jitted tensor
    core is what the bench lines and the counterfactual sweep use, and
    tests/test_tuning.py holds the two in agreement)."""
    from scheduler_plugins_tpu.tuning import quality as Q

    q = Q.cycle_quality_np(snap, assignment, admitted, wait)
    q["nominations"] = float(len(report.preempted))
    q["preemptions"] = float(
        sum(len(v) for _, v in report.preempted.values())
    )
    report.quality = q
    for objective, value in q.items():
        obs.metrics.set_gauge(
            obs.PLACEMENT_QUALITY, value, objective=objective
        )


def _attribute_failures(scheduler, snap, result, failed_idx, report,
                        tid="cycle", led=None):
    """Fill `CycleReport.failed_by` and the
    `scheduler_unschedulable_by_plugin_total{plugin}` counters — the
    upstream UnschedulablePlugins attribution. The sequential parity path
    carries exact per-pod codes out of the solve
    (`SolveResult.failed_plugin`, evaluated against the carried state);
    batched/streamed solves reduce the same per-plugin masks cycle-
    initially (`Scheduler.attribution_codes`). Codes <= 0 (built-in fit,
    gates, or in-cycle capacity exhaustion) decode to "NodeResourcesFit"."""
    if not failed_idx:
        return
    with obs.tracer.span("Attribution", tid=tid, failed=len(failed_idx)):
        codes = getattr(result, "failed_plugin", None)
        if codes is not None:
            # sequential parity path: (P,) in-solve codes, pod-indexed
            codes_np = np.asarray(codes)
            per_failure = [codes_np[i] for i, _ in failed_idx]
        else:
            # batched/streamed: reduce the failed rows only (S, N work)
            per_failure = scheduler.attribution_codes(
                snap, [i for i, _ in failed_idx]
            )
        names = scheduler.fail_plugin_names()
        for (_, uid), code in zip(failed_idx, per_failure):
            code = int(code)
            name = names[code] if code > 0 else names[0]
            report.failed_by[uid] = name
            obs.metrics.inc(obs.UNSCHEDULABLE_BY_PLUGIN, plugin=name)
            if name == "CapacityScheduling":
                obs.metrics.inc(obs.QUOTA_REFUSALS)
            if led is not None:
                # blame fills IN PLACE on the observing cycle's
                # Unschedulable event: this decode may run in the NEXT
                # tick's overlap window under the pipelined engine, and
                # an appended event there would order differently
                podledger.LEDGER.set_blame(uid, led.cid, name)


def _requeue_eligible(scheduler, cluster, pending, now, report,
                      gang_phase=False):
    """EnqueueExtensions gating (upstream scheduling-queue semantics): a pod
    parked unschedulable re-enters the batch only when

    - a cluster event registered by an enabled plugin (or the built-in
      resource fit's Node/Pod events) occurred after its last failure,
    - it holds a live nomination (upstream nominated pods stay active),
    - its flush deadline passed (podMaxInUnschedulablePodsDuration), or
    - a gang sibling is eligible (upstream ActivateSiblings moves the whole
      group together),

    AND its requeue backoff window has expired: every re-queue pays the
    seeded deterministic jittered exponential backoff
    `Cluster.mark_unschedulable` computed at its last failure — upstream
    backoffQ semantics, where an event moves a pod from the
    unschedulable pool to the backoff queue but it pops into the active
    queue only once its per-pod backoff completes, so a
    permanently-unschedulable pod cannot hot-loop the queue. Nominated
    pods bypass the backoff like they bypass the event gate (they hold
    capacity; delaying their retry delays everyone behind them).

    Pods never marked unschedulable (new arrivals, retried reservations)
    always run. Reference: EventsToRegister registrations, e.g.
    coscheduling.go:113-122, capacity_scheduling.go:194-203,
    noderesourcetopology plugin.go:141-151; backoff:
    k8s.io/kubernetes pkg/scheduler/internal/queue/scheduling_queue.go
    (calculateBackoffDuration — the framework queue every reference
    plugin registers into).

    `gang_phase` registers `api.events.GANG_EVENTS` on top: a pod parked
    by the rank-gang phase (`RankGangPlacement`) has no owning plugin in
    the profile to register its events, but its schedulability changes on
    exactly those kinds (sibling add/delete frees quorum or capacity, a
    NetworkTopology update moves the cost surface)."""
    from scheduler_plugins_tpu.framework.plugin import BUILTIN_EVENTS

    if not cluster.unschedulable_since:
        return pending
    registered = set(BUILTIN_EVENTS)
    if gang_phase:
        registered.update(ev_api.GANG_EVENTS)
    for plugin in scheduler.profile.plugins:
        registered.update(plugin.events_to_register())

    led = podledger.LEDGER

    def eligible(pod):
        rec = cluster.unschedulable_since.get(pod.uid)
        if rec is None:
            return True
        seq, flush_at = rec
        if pod.nominated_node_name is not None:
            return True
        if now < cluster.pod_backoff_until_ms.get(pod.uid, 0):
            obs.metrics.inc(obs.REQUEUE_BACKOFF_SKIPS)
            if led.enabled:
                led.on_wait(pod.uid, "backoff_held")
            return False
        if now >= flush_at:
            return True
        if any(
            cluster.event_last.get(kind, 0) > seq for kind in registered
        ):
            return True
        if led.enabled:
            # backoff expired, no registered event yet: the pod is now
            # waiting on the QUEUE gate, not the backoff clock (the
            # ledger's one-transition-per-park-episode classification;
            # gang parks keep their gang_wait label — `Ledger.on_wait`)
            led.on_wait(pod.uid, "queue_wait")
        return False

    keep = [pod for pod in pending if eligible(pod)]
    kept_uids = {p.uid for p in keep}
    # gang activation: one eligible member activates its whole group
    eligible_gangs = {
        pg.full_name
        for p in keep
        if (pg := cluster.pod_group_of(p)) is not None
    }
    for pod in pending:
        if pod.uid in kept_uids:
            continue
        pg = cluster.pod_group_of(pod)
        if pg is not None and pg.full_name in eligible_gangs:
            keep.append(pod)
            kept_uids.add(pod.uid)
    for pod in pending:
        if pod.uid not in kept_uids:
            report.skipped.append(pod.uid)
    return keep


def _run_preemption(scheduler, cluster, pending, report, now):
    """PostFilter preemption: for each still-failed pod in queue order, dry
    run victim removal across all nodes, nominate the best candidate, mark
    victims terminating (the apiserver DELETE boundary in the reference)
    and record the nomination (SURVEY.md §3.3). Returns how many pods the
    search was run for: the failed pods less those of a gang rejected
    whole. With none left it returns before its snapshot, and the plugins
    stay bound to the cycle's own meta.

    Runs against a FRESH snapshot (this cycle's binds must count as node
    usage, or just-bound pods double as phantom victims) and threads the
    cycle's earlier nominations into each dry run so two preemptors cannot
    claim the same freed capacity (the upstream evaluator filters with
    nominated pods)."""
    engine = scheduler.profile.preemption
    if engine is None or not report.failed:
        return 0
    rejected = set(report.rejected_gangs)
    by_uid = {p.uid: p for p in pending}
    failed_pods = [by_uid[uid] for uid in report.failed if uid in by_uid]
    # whom the search is for, in queue order: a gang rejected whole gains
    # nothing from a victim. Settled before anything O(cluster): a cycle
    # whose failed pods all belong to such gangs builds no snapshot,
    # re-prepares no plugin and scans no hold.
    candidates = [
        pod for pod in failed_pods
        if (pg := cluster.pod_group_of(pod)) is None
        or pg.full_name not in rejected
    ]
    if not candidates:
        return 0
    # post-bind state: assigned pods now include this cycle's placements
    # (the pending list stays every failed pod: the quota nominee tables
    # and the pod rows are built from it)
    snap, meta = cluster.snapshot(failed_pods, now_ms=now)
    # re-prepare: the preemption snapshot's resource-axis layout can differ
    # from the main cycle's (extended names are interned in first-seen
    # order), and plugin aux arrays must match THIS meta
    scheduler.prepare(meta, cluster)
    nominated_extra = np.zeros(
        (len(meta.node_names), len(meta.index)), np.int64
    )
    node_pos = {name: i for i, name in enumerate(meta.node_names)}
    # PRIOR cycles' live nominations (kept while gated) and nominations made
    # EARLIER IN THIS LOOP hold capacity in the dry runs, but only against
    # preemptors of lower-or-equal priority (upstream AddNominatedPods adds
    # nominees with priority >= the evaluated pod, same UID excluded); the
    # capacity in-flight terminations will free is credited to everyone.
    # Each preemptor's view is assembled fresh from the hold list — the
    # queue order of failed_pods is NOT priority-descending under every
    # QueueSort (TopologicalSort orders same-AppGroup pods by topology
    # index), so a one-way pointer sweep would fold low-priority holds in
    # against later higher-priority preemptors. A nomination that clears or
    # moves during this loop drops its old hold (same-UID dedup below).
    for pod in cluster.pods.values():
        if pod.terminating and pod.node_name in node_pos:
            nominated_extra[node_pos[pod.node_name]] -= encode_demand(
                meta.index, pod
            )
    holds = [
        (
            node_pos[pod.nominated_node_name],
            encode_demand(meta.index, pod),
            pod.priority,
            pod.uid,
        )
        for pod in cluster.pods.values()
        if pod.node_name is None
        and not pod.terminating
        and pod.nominated_node_name in node_pos
    ]
    for pod in candidates:
        obs.metrics.inc(obs.PREEMPTION_ATTEMPTS)
        # PodEligibleToPreemptOthers runs inside preempt(): while pods this
        # pod could benefit from are still terminating on its nominated
        # node, it must NOT preempt again — and the nomination is KEPT so
        # the gate can keep firing (capacity_scheduling.go:409-484).
        extra = nominated_extra.copy()
        for n_, demand_, prio_, uid_ in holds:
            if prio_ >= pod.priority and uid_ != pod.uid:
                extra[n_] += demand_
        result = engine.preempt(
            cluster, scheduler, pod, snap, meta, now,
            extra_reserved=extra,
        )
        if result is GATED:
            continue  # terminations in flight: nomination (hold) stays
        # past this point the pod's nomination either clears or moves —
        # either way its previous hold is dead (same-UID dedup also keeps a
        # re-preempting nominee from holding double)
        holds = [h for h in holds if h[3] != pod.uid]
        if result is None:
            # nomination did not help and nothing is terminating: clear it
            # so the pod re-enters PostFilter fresh (upstream clears
            # NominatedNodeName when unschedulable again)
            pod.nominated_node_name = None
            if cluster.delta_sink is not None:
                # in-place clear never passes through a Cluster mutator —
                # untrack it or the serving engine's compatibility gate
                # stays pinned False for this pod's lifetime
                cluster.delta_sink.note_nomination(pod)
            if podledger.LEDGER.enabled:
                podledger.LEDGER.on_nomination(pod.uid, None)
            continue
        obs.metrics.inc(obs.PREEMPTION_VICTIMS, len(result.victims))
        # setting the nomination NOW makes this pod visible to later
        # preemptors' live nominated aggregates (quota feedback) exactly once
        pod.nominated_node_name = result.nominated_node
        if cluster.delta_sink is not None:
            cluster.delta_sink.note_nomination(pod)
        if podledger.LEDGER.enabled:
            podledger.LEDGER.on_nomination(pod.uid, result.nominated_node)
        n = node_pos[result.nominated_node]
        demand = encode_demand(meta.index, pod)
        victim_freed = np.zeros(len(meta.index), np.int64)
        for victim_uid in result.victims:
            victim = cluster.pods.get(victim_uid)
            if victim is not None:
                # DELETE issued; kubelet terminates (keeps the native
                # mirror's terminating counts in sync too)
                cluster.mark_terminating(victim_uid, now)
                victim_freed += encode_demand(meta.index, victim)
        # the new nominee holds its demand against later lower-or-equal-
        # priority preemptors; the capacity its victims free is credited
        # to everyone
        holds.append((n, demand, pod.priority, pod.uid))
        nominated_extra[n] -= victim_freed
        report.preempted[pod.uid] = (result.nominated_node, result.victims)
    return len(candidates)


def _refresh_metrics(scheduler, cluster: Cluster, now: int):
    """The collector pull loop: every distinct metrics source configured by
    a trimaran plugin — a WatcherAddress service or a MetricProvider library
    client (collector.go:60-73) — gets an async collector (cached on the
    scheduler) ticked once per cycle; see
    state.collector.AsyncLoadWatcherCollector for cadence/threading."""
    from scheduler_plugins_tpu.state.collector import (
        AsyncLoadWatcherCollector,
        make_metrics_client,
    )

    collectors = getattr(scheduler, "_collectors", None)
    for plugin in scheduler.profile.plugins:
        address = getattr(plugin, "watcher_address", None)
        provider = getattr(plugin, "metric_provider", None)
        if not address and not provider:
            continue
        key = address or tuple(sorted((provider or {}).items()))
        if collectors is None:
            collectors = scheduler._collectors = {}
        if key not in collectors:
            try:
                collectors[key] = AsyncLoadWatcherCollector(
                    make_metrics_client(address, provider)
                )
            except ValueError:
                # unusable source config: degrade to no metrics for this
                # source instead of failing every cycle (None sentinel stops
                # re-construction attempts)
                collectors[key] = None
        if collectors[key] is not None:
            collectors[key].tick(cluster, now)


def _resync_nrt_cache(cluster: Cluster, now: int = 0):
    """Drive the over-reserve cache's resync loop (the reference's background
    `wait.Forever(Resync, period)` goroutine, pluginhelpers.go:73): reconcile
    dirty nodes against their latest agent reports, on the configured
    CacheResyncPeriodSeconds cadence when the cache carries one."""
    cache = cluster.nrt_cache
    if cache is None or not hasattr(cache, "resync"):
        return
    period_ms = getattr(cache, "resync_period_ms", 0)
    if period_ms:
        last = getattr(cache, "_last_resync_ms", None)
        if last is not None and now - last < period_ms:
            return
        cache._last_resync_ms = now
    if not cache.desynced_nodes():
        return
    node_pods: dict[str, list] = {}
    relevant = getattr(cache, "pod_relevant", lambda pod: True)
    for pod in cluster.pods.values():
        # the cache's pod view goes through the informer-mode relevance
        # predicate (podprovider.go:37-93): fingerprints must be computed
        # over exactly the pods that provider would have listed
        if pod.node_name is not None and relevant(pod):
            node_pods.setdefault(pod.node_name, []).append(pod)
    cache.resync(node_pods)


def _mark_overreserved_on_failures(cluster: Cluster, report: CycleReport):
    """Filter failures on cached views may mean the deduction is stale
    (filter.go:219-223 NodeMaybeOverReserved): mark every node carrying
    assumed pods dirty so the next resync reconciles it."""
    cache = cluster.nrt_cache
    if not report.failed or cache is None:
        return
    if not hasattr(cache, "mark_maybe_overreserved") or not hasattr(cache, "assumed"):
        return
    for node, assumed in cache.assumed.items():
        if assumed:
            cache.mark_maybe_overreserved(node)


def _maybe_release_gang(cluster: Cluster, pg, report: CycleReport, now: int = 0):
    reserved = cluster.gang_reservations(pg)
    if not reserved:
        return
    bound = sum(
        1
        for p in cluster.gang_members(pg)
        if p.node_name is not None
    )
    if bound + len(reserved) >= pg.min_member:
        for uid in reserved:
            node = cluster.reserved[uid]
            cluster.bind(uid, node, now)  # clears the pod's permit timer
            report.bound[uid] = node
            report.reserved.pop(uid, None)


def _reject_gang(cluster: Cluster, pg, now: int, report: CycleReport, cosched, member_count: int):
    """Reject every waiting sibling, record failure time, back off the group
    (coscheduling.go:188-209, core.go:174-192). Backoff applies only when the
    gang has at least MinMember sibling pods (coscheduling.go:196-204) —
    an incomplete gang must retry as soon as its members appear."""
    for uid in cluster.gang_reservations(pg):
        cluster.release_reservation(uid)  # clears the pod's permit timer
        report.reserved.pop(uid, None)
        # released siblings are parked too (upstream Permit-Reject moves
        # waiting pods to the unschedulable queue) — without this the
        # gang-activation rule would re-run the whole group every cycle
        cluster.mark_unschedulable(uid, now)
    cluster.gang_last_failure_ms[pg.full_name] = now
    backoff_s = cosched.pod_group_backoff_seconds if cosched else 0
    if backoff_s > 0 and member_count >= pg.min_member:
        cluster.gang_backoff_until_ms[pg.full_name] = now + 1000 * backoff_s
    report.rejected_gangs.append(pg.full_name)


def _expire_gangs(cluster: Cluster, now: int, report: CycleReport):
    """Permit timeout: ANY waiting pod past its own deadline fires Reject
    (the upstream per-pod waitingPods timer, coscheduling.go:227-251), which
    unreserves every sibling — the earliest sibling deadline rejects the
    whole gang; staggered reservations carry staggered deadlines."""
    for uid, deadline in list(cluster.pod_deadline_ms.items()):
        if now < deadline or uid not in cluster.pod_deadline_ms:
            continue  # not due, or already cleared by a sibling's expiry
        pod = cluster.pods.get(uid)
        pg = cluster.pod_group_of(pod) if pod is not None else None
        if pg is None:
            cluster.release_reservation(uid)  # clears the timer too
            continue
        for sibling_uid in cluster.gang_reservations(pg):
            cluster.release_reservation(sibling_uid)
        cluster.gang_last_failure_ms[pg.full_name] = now
        report.expired_gangs.append(pg.full_name)
