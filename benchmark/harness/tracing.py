"""What a traced run switches on, and how its clocks are brought together.

Three recorders, all stopped before the correctness checks:

- `obs.tracer`, the program's host spans (Snapshot, Bind, ServeRefresh/*,
  Solve/...), for the whole window. Its stamps are `perf_counter_ns` since
  its start; the harness notes the origin, so spans come out on
  CLOCK_MONOTONIC (on Linux both clocks are that one; the harness checks).
- the harness's own span around every `Daemon.tick`, the layer boundary
  the program has no span for: what lies between a tick's start and its
  first inner span is the wait for the feed lock.
- the JAX profiler, for a few seconds in the middle of the window. Python
  call tracing is off: it would record every function of a host path that
  is all Python, and measure itself.
"""

from __future__ import annotations

import shutil
import time


def clocks_agree(tolerance_ns: int = 1_000_000) -> bool:
    a = time.perf_counter_ns()
    b = time.monotonic_ns()
    return abs(b - a) < tolerance_ns


class HostSpans:
    def __init__(self, daemon):
        from scheduler_plugins_tpu.utils import observability as obs

        self._obs = obs
        self._daemon = daemon
        self._origin_ns = 0
        self.ticks: list = []

    def start(self) -> None:
        daemon, ticks = self._daemon, self.ticks
        inner = daemon.tick

        def tick():
            t0 = time.monotonic_ns()
            try:
                return inner()
            finally:
                ticks.append((t0, time.monotonic_ns()))

        daemon.tick = tick  # `Daemon.run` looks `tick` up on the instance
        self._obs.tracer.start()
        self._origin_ns = time.perf_counter_ns() - self._obs.tracer.now_ns()

    def stop(self) -> list:
        """[(name, start ns, end ns, args), ...] on CLOCK_MONOTONIC: the
        program's spans and one `Tick` per tick."""
        self._obs.tracer.stop()
        del self._daemon.tick
        spans = []
        for event in self._obs.tracer.export()["traceEvents"]:
            if event.get("ph") != "X":
                continue
            start = self._origin_ns + int(event["ts"] * 1000)
            spans.append((event["name"], start,
                          start + int(event["dur"] * 1000),
                          event.get("args") or {}))
        spans.extend(("Tick", t0, t1, {}) for t0, t1 in self.ticks)
        return sorted(spans, key=lambda s: (s[1], -s[2]))


def profile(trace_dir: str, seconds: float) -> tuple:
    """Run the JAX profiler for `seconds` from now; returns the traced
    stretch as (start, end) on CLOCK_MONOTONIC. Blocks until the trace is
    written."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(f"bench_sync mono={time.monotonic_ns()}"):
        pass
    t0 = time.monotonic_ns()
    time.sleep(seconds)
    t1 = time.monotonic_ns()
    jax.profiler.stop_trace()
    return t0, t1
