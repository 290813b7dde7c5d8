"""Solve watchdog + degraded-mode failover state machine.

Every device solve the cycle loop dispatches completes through a
host-transfer fence (`np.asarray`) **in a worker thread** with a
deadline: a hung backend (blocking forever at 0% CPU) times out instead
of stalling the cycle loop. On timeout, device error, or garbage output the
watchdog retries with seeded-jitter exponential backoff; when the budget
is exhausted it raises `BackendUnavailable`, and `Resilience` fails over
to the host-side numpy parity solve (`resilience.hostsolve` —
bit-faithful by construction) and marks the process degraded
(`scheduler_degraded` gauge, `CycleReport.degraded`, daemon `/healthz`).
While degraded, periodic probation probes re-try the device path and
restore it the moment the backend answers again.

Threading note: a thread stuck in a hung backend call cannot be killed —
on timeout the watchdog ABANDONS its worker (daemon thread; the eventual
result is discarded, jitted solves are side-effect free) and builds a
fresh one for the next attempt. Abandoned workers are counted
(`scheduler_solve_workers_abandoned_total`) so a flapping backend is
visible, and bounded in practice by the backoff schedule.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Optional

import numpy as np

from scheduler_plugins_tpu.framework.runtime import solve_output_anomaly
from scheduler_plugins_tpu.resilience import faults, hostsolve
from scheduler_plugins_tpu.utils import observability as obs


class BackendUnavailable(RuntimeError):
    """The device backend failed past the watchdog's retry budget (or no
    host fallback exists for the profile). `reason` is the structured
    classification ("timeout (2.0s)", "device-error: XlaRuntimeError",
    "garbage-output: ...")."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class GarbageOutput(RuntimeError):
    """A solve returned, but its outputs fail the contract (out-of-range
    node indices, NaN, shape mismatch) — treated exactly like a device
    error: a desynced backend produces answers shaped like this."""




def call_with_deadline(fn, deadline_s: float, label: str = "call"):
    """Run `fn()` in a fresh daemon worker with a deadline. Raises
    `BackendUnavailable` on timeout (the worker is abandoned — it cannot
    be killed while stuck inside a backend call). The standalone helper
    behind `parallel.pipeline.run_chunk_pipeline(fetch_deadline_s=...)`;
    the cycle loop's stateful retry/failover logic lives in
    `SolveWatchdog`/`Resilience` below."""
    box: dict = {}
    done = threading.Event()

    def worker():
        try:
            box["value"] = fn()
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            box["error"] = exc
        finally:
            done.set()

    t = threading.Thread(target=worker, daemon=True, name=f"wd-{label}")
    t.start()
    if not done.wait(deadline_s):
        obs.metrics.inc(obs.SOLVE_WORKERS_ABANDONED)
        raise BackendUnavailable(f"timeout ({deadline_s}s) in {label}")
    if "error" in box:
        raise box["error"]
    return box["value"]


class _Worker:
    """Persistent single DAEMON worker thread with a job queue.

    Deliberately NOT a `ThreadPoolExecutor`: its workers are non-daemon
    and joined at interpreter exit (`concurrent.futures.thread`'s atexit
    hook), so a worker stuck inside a hung backend call would block
    process shutdown forever — defeating the SIGTERM-exits-0 guarantee
    this subsystem exists to protect. A daemon thread dies with the
    process; an abandoned one idles harmlessly on its own queue."""

    def __init__(self):
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="solve-watchdog"
        )
        self._thread.start()

    def _loop(self):
        while True:
            fn, box, done = self._jobs.get()
            try:
                box["value"] = fn()
            except BaseException as exc:  # noqa: BLE001 - re-raised by caller
                box["error"] = exc
            finally:
                done.set()

    def submit(self, fn):
        box: dict = {}
        done = threading.Event()
        self._jobs.put((fn, box, done))
        return box, done


#: default solve deadline, seconds. The longest single solve call of
#: `chip_smoke.py` phase A on a TPU v5e with an empty compile cache
#: (5,000 nodes x 2,500 pods, scan unroll 8) spent 21.0-21.5 s in trace +
#: compile (my chip runs, PR 21) — past two thirds of the 30 s this used to
#: be, with larger node buckets still to come. 120 s leaves more than 5x
#: over the measured cold compile and still diagnoses a hung backend
#: within two minutes; a warm compile cache makes it irrelevant.
DEFAULT_SOLVE_TIMEOUT_S = 120.0


class SolveWatchdog:
    """Deadline + seeded-jitter retry policy around one callable.

    `timeout_s` defaults from SPT_SOLVE_TIMEOUT_S, else
    `DEFAULT_SOLVE_TIMEOUT_S`: the deadline covers the solve call's first
    compile, so it has to outlast a cold one — "compiling" must not read
    as "hung". Backoff mirrors the
    requeue schedule: base * 2^(attempt-1), capped, with a
    deterministic-per-seed jitter multiplier in [0.5, 1.0] so colliding
    retries from many processes spread out while a given seed replays
    exactly."""

    def __init__(self, timeout_s: Optional[float] = None,
                 max_attempts: int = 3, backoff_base_s: float = 0.05,
                 backoff_cap_s: float = 2.0, seed: int = 0):
        if timeout_s is None:
            timeout_s = float(
                os.environ.get("SPT_SOLVE_TIMEOUT_S", DEFAULT_SOLVE_TIMEOUT_S)
            )
        self.timeout_s = timeout_s
        self.max_attempts = max(1, int(max_attempts))
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self._rng = np.random.default_rng(seed)
        self._worker: Optional[_Worker] = None
        self.abandoned = 0
        self.last_reason: Optional[str] = None

    def backoff_s(self, attempt: int) -> float:
        base = min(
            self.backoff_base_s * (2 ** (attempt - 1)), self.backoff_cap_s
        )
        return base * (0.5 + 0.5 * float(self._rng.random()))

    def _abandon(self) -> None:
        # the worker is stuck inside a backend call: it cannot be
        # interrupted, only orphaned (daemon thread, result discarded;
        # it can never block process exit)
        self._worker = None
        self.abandoned += 1
        obs.metrics.inc(obs.SOLVE_WORKERS_ABANDONED)

    def call_once(self, fn, label: str = "solve"):
        """One deadlined attempt; classifies failures into
        `BackendUnavailable` (timeout) or re-raises the device error."""
        if self._worker is None:
            self._worker = _Worker()
        box, done = self._worker.submit(fn)
        if not done.wait(self.timeout_s):
            self._abandon()
            raise BackendUnavailable(
                f"timeout ({self.timeout_s}s) in {label}"
            ) from None
        if "error" in box:
            raise box["error"]
        return box["value"]

    def run(self, fn, label: str = "solve", attempts: Optional[int] = None,
            on_fault=None):
        """Retry loop: deadline + backoff, then `BackendUnavailable` with
        the LAST failure's classification. `on_fault(reason)` fires on
        every failed attempt (the anti-entropy force-verify hook)."""
        attempts = attempts or self.max_attempts
        reason = "unknown"
        for attempt in range(1, attempts + 1):
            try:
                return self.call_once(fn, label=label)
            except BackendUnavailable as exc:
                reason = exc.reason
            except GarbageOutput as exc:
                reason = f"garbage-output: {exc}"
            except Exception as exc:  # device/runtime error from the solve
                reason = f"device-error: {type(exc).__name__}: {exc}"
            self.last_reason = reason
            obs.metrics.inc(obs.SOLVE_RETRIES, label=label)
            if on_fault is not None:
                on_fault(reason)
            if attempt < attempts:
                time.sleep(self.backoff_s(attempt))
        raise BackendUnavailable(reason)


class Resilience:
    """The cycle loop's degraded-mode state machine (one per scheduler
    process). `framework.cycle.run_cycle(resilience=...)` routes every
    solve through `solve_cycle`:

    - **fast** mode: device solve under the watchdog; on exhausted
      retries fail over to the host parity solve and go degraded.
    - **degraded** mode: host solve immediately (no device dispatch);
      every `probe_every` cycles a probation probe re-tries the device
      path (single attempt) and restores fast mode on success — the
      probe IS that cycle's solve, so recovery wastes no work.

    The optional `engine` (a `serving.engine.ServeEngine`) is notified
    on every fault (`note_fault`), forcing an anti-entropy verify at the
    next refresh — any fault is treated as potential state corruption.
    """

    def __init__(self, watchdog: Optional[SolveWatchdog] = None,
                 probe_every: int = 2, engine=None):
        self.watchdog = watchdog or SolveWatchdog()
        self.probe_every = max(1, int(probe_every))
        self.engine = engine
        self.mode = "fast"
        self.degraded_reason: Optional[str] = None
        self.cycle = 0
        self.degraded_cycles = 0
        self.failovers = 0
        #: (degraded_at_cycle, restored_at_cycle) pairs — recovery time
        #: in cycles is the difference, the chaos gate's bound
        self.recoveries: list = []
        self._degraded_at: Optional[int] = None
        obs.metrics.set_gauge(obs.DEGRADED, 0.0)

    @property
    def degraded(self) -> bool:
        return self.mode == "degraded"

    @property
    def degraded_at(self):
        """Cycle index of the active degradation (None while fast) —
        the chaos harness closes the recovery window from this."""
        return self._degraded_at

    # -- transitions ----------------------------------------------------
    def _on_fault(self, reason: str) -> None:
        if self.engine is not None:
            self.engine.note_fault(reason)

    def _enter_degraded(self, reason: str) -> None:
        self.mode = "degraded"
        self.degraded_reason = reason
        self._degraded_at = self.cycle
        self.failovers += 1
        obs.metrics.inc(obs.SOLVE_FAILOVERS)
        obs.metrics.set_gauge(obs.DEGRADED, 1.0)
        obs.logger.warning(
            "solve backend degraded (%s): failing over to the host "
            "sequential parity path", reason,
        )

    def _restore_fast(self) -> None:
        self.mode = "fast"
        self.recoveries.append((self._degraded_at, self.cycle))
        self._degraded_at = None
        self.degraded_reason = None
        obs.metrics.set_gauge(obs.DEGRADED, 0.0)
        obs.logger.info("solve backend recovered: fast path restored")

    # -- the per-cycle entry --------------------------------------------
    def solve_cycle(self, scheduler, snap, stream_chunk=None):
        """(assignment, admitted, wait, failed_plugin, path) — host numpy
        arrays, completion already forced. `path` is "device" or "host"."""
        self.cycle += 1
        if self.mode == "degraded":
            # anchored on the degradation cycle, not absolute parity: the
            # first probe fires exactly probe_every cycles after failover
            probe_due = (
                (self.cycle - self._degraded_at) % self.probe_every == 0
            )
            if probe_due:
                obs.metrics.inc(obs.PROBATION_PROBES)
                try:
                    out = self.watchdog.run(
                        lambda: self._device_call(
                            scheduler, snap, stream_chunk, probe=True
                        ),
                        label="probe", attempts=1, on_fault=self._on_fault,
                    )
                    self._restore_fast()
                    return out + ("device",)
                except BackendUnavailable:
                    pass  # still sick: stay degraded, serve from host
            self.degraded_cycles += 1
            return self._host_call(scheduler, snap) + ("host",)
        try:
            out = self.watchdog.run(
                lambda: self._device_call(scheduler, snap, stream_chunk),
                label="solve", on_fault=self._on_fault,
            )
            return out + ("device",)
        except BackendUnavailable as exc:
            self._enter_degraded(exc.reason)
            if not hostsolve.supports(scheduler, snap):
                # no bit-faithful fallback for this profile: surface the
                # outage to the caller (the daemon parks the cycle and
                # stays degraded) rather than inventing placements
                raise
            self.degraded_cycles += 1
            return self._host_call(scheduler, snap) + ("host",)

    # -- the two solve bodies -------------------------------------------
    def _device_call(self, scheduler, snap, stream_chunk, probe=False):
        """Runs IN THE WORKER THREAD: dispatch + host-transfer completion
        fence + output validation, with the SOLVE_DISPATCH/PROBE fault
        sites applied around it."""
        spec = None
        if faults.ACTIVE is not None:
            # a probation probe IS a solve dispatch (SOLVE_DISPATCH faults
            # hit it too); the PROBE site exists on top so tests can keep
            # the backend sick across probes specifically
            spec = faults.ACTIVE.fire(faults.SOLVE_DISPATCH)
            if spec is None and probe:
                spec = faults.ACTIVE.fire(faults.PROBE)
            if spec is not None and spec.kind == "hang":
                time.sleep(spec.seconds)
            elif spec is not None and spec.kind == "device-error":
                raise RuntimeError("injected device error")
        failed_np = None
        result = None
        if stream_chunk:
            from scheduler_plugins_tpu.parallel.pipeline import (
                streamed_profile_solve,
            )

            result = streamed_profile_solve(
                scheduler, snap, chunk=stream_chunk,
                # finer-grained hang detection INSIDE the chunk loop: the
                # whole-solve deadline above still bounds the worst case
                fetch_deadline_s=self.watchdog.timeout_s,
            )
        if result is not None:
            assignment, admitted, wait = result
        else:
            solved = scheduler.solve(snap)
            assignment, admitted, wait = (
                solved.assignment, solved.admitted, solved.wait
            )
            if solved.failed_plugin is not None:
                failed_np = np.asarray(solved.failed_plugin)
        # host transfers force completion inside the deadline window
        assignment = np.asarray(assignment)
        admitted = np.asarray(admitted)
        wait = np.asarray(wait)
        if spec is not None and spec.kind == "garbage":
            # a desynced backend answers with plausible-length junk
            assignment = assignment.copy()
            rng = faults.ACTIVE.rng
            assignment[: max(1, assignment.size // 8)] = rng.integers(
                snap.num_nodes, snap.num_nodes + 1000,
                size=max(1, assignment.size // 8),
            )
        anomaly = solve_output_anomaly(
            assignment, admitted, wait, snap.num_nodes
        )
        if anomaly is not None:
            raise GarbageOutput(anomaly)
        return assignment, admitted, wait, failed_np

    def _host_call(self, scheduler, snap):
        if not hostsolve.supports(scheduler, snap):
            raise BackendUnavailable(
                f"degraded ({self.degraded_reason}) and no host fallback "
                f"for profile {scheduler.profile.name!r}"
            )
        with obs.tracer.span("HostSolve", tid="cycle",
                             pending=snap.num_pods):
            return hostsolve.host_sequential_solve(scheduler, snap)
