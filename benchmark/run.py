"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip and IS the daemon: it builds
`Daemon(parse_args([...the configuration's flags...]))` as `python -m
scheduler_plugins_tpu` does and runs it on the main thread. A controller
thread beside it starts the client (`client.py`, a child process that never
imports JAX), waits for set-up and warm-up, opens the measured window, and
after it runs the correctness checks and ends the daemon with SIGTERM.

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` in a
traced run). Everything else is printed on earlier lines, each a JSON
object with an `info` key. Without a TPU the command exits non-zero before
any work; `--rehearse-cpu` runs the same code on the CPU backend at the size
of the files' `rehearsal` blocks and stamps `platform: cpu`.
"""

from __future__ import annotations

import time

T_PROCESS_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

from harness import spec, stats  # noqa: E402

#: how long the mix may run before every shape has compiled (a cold cache
#: compiles tens of seconds per pod bucket), and how long the client may
#: take to fence after the window
WARMUP_LIMIT_S = 900.0
WARM_CYCLES = 3
#: the generator runs on for this many cycle intervals after the window
COOLDOWN_INTERVALS = 2
CLIENT_REPLY_S = 120.0
#: how long the daemon may take to bind what the generator left behind (its
#: last, small batch can be of a pod bucket that was never compiled)
DRAIN_LIMIT_S = 300.0
#: the profiled stretch in the middle of a traced window
PROFILE_S = 5.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same code on the CPU backend, at the size of "
                         "the rehearsal blocks of the cell's files")
    ap.add_argument("--index", default=None,
                    help="another index than the repo's BENCHMARK.json (a "
                         "test fixture's); files are looked for beside it "
                         "first")
    return ap.parse_args(argv)


class Failure(Exception):
    """The run cannot produce a result."""


class Run:
    def __init__(self, args, cell, daemon, ledger, out_dir, out):
        from scheduler_plugins_tpu.utils import observability as obs

        self.obs = obs
        self.args = args
        self.cell = cell
        self.daemon = daemon
        self.ledger = ledger
        self.out_dir = out_dir
        self.out = out
        self.error = None
        self.child = None
        self.plan = None
        self.warmed = (0, 0)
        self.result = None
        # what the readers see
        self.t_process_start_ns = T_PROCESS_START_NS
        self.window = None
        self.client = None
        self.registry = {}
        self.spans = None
        self.profiled = None  # (start, end) on CLOCK_MONOTONIC
        self.device_trace = None
        self.trace_offset_ns = None
        self.memory_peak_bytes = None
        self.peaks = None

    # -- plumbing ----------------------------------------------------------
    def info(self, name: str, **fields) -> None:
        print(json.dumps({"info": name, **fields}), file=self.out, flush=True)

    @property
    def binds(self) -> list:
        return self.ledger.bind_stamps

    @functools.cached_property
    def bound_at(self) -> dict:
        """{pod uid: bind stamp}; first read after the window's pods bound."""
        return dict(self.binds)

    @functools.cached_property
    def cycles(self) -> list:
        """[(opened ns, pods bound, last bind ns or None), ...] for every
        scheduling cycle so far; first read after the window."""
        opened = self.ledger.cycle_stamps
        rows = [[at, 0, None] for at in opened]
        for _uid, t in self.binds:
            row = rows[bisect.bisect_right(opened, t) - 1]
            row[1] += 1
            row[2] = t
        return [tuple(row) for row in rows]

    def registry_now(self) -> dict:
        return {
            "counters": self.obs.metrics.snapshot(),
            "histograms": {
                key: (h["sum"], h["count"])
                for key, h in self.obs.metrics.histograms().items()
            },
        }

    def compile_events(self) -> int:
        """Watched jit-cache misses plus every backend compile request: a
        program loaded from the persistent cache counts too."""
        obs = self.obs
        return sum(
            value for key, value in obs.metrics.snapshot().items()
            if key.startswith((obs.JIT_CACHE_MISS, obs.COMPILE_CACHE_REQUESTS))
        )

    def _say(self, **message) -> None:
        self.child.stdin.write(json.dumps(message) + "\n")
        self.child.stdin.flush()

    def _hear(self, event: str, timeout_s: float) -> dict:
        try:
            message = self._inbox.get(timeout=timeout_s)
        except queue.Empty:
            raise Failure(f"the client sent no {event!r} in {timeout_s} s")
        if message.get("event") != event:
            raise Failure(f"the client sent {message} while {event!r} was due")
        return message

    def _read_child(self) -> None:
        for raw in self.child.stdout:
            self._inbox.put(json.loads(raw))
        self._inbox.put({"event": "eof", "rc": self.child.wait()})

    # -- the controller thread ---------------------------------------------
    def control(self) -> None:
        try:
            self._control()
        except BaseException as exc:  # reported by the main thread
            self.error = exc
            traceback.print_exc()
        finally:
            if self.child is not None:
                if self.child.poll() is None:
                    self.child.kill()
                self.child.wait()
            os.kill(os.getpid(), signal.SIGTERM)

    def start_client(self) -> int:
        """Start the client, check the clocks, wait for the set-up traffic;
        returns when the generator started (CLOCK_MONOTONIC ns)."""
        cell, daemon = self.cell, self.daemon
        interval_s = daemon.args.cycle_interval_s
        self.plan = {
            "index": str(spec.INDEX_PATH),
            "feed": list(daemon.feed.address),
            "health": "http://%s:%d/healthz" % daemon.health.address,
            "seed": self.args.seed, "cell": cell.params, "mix": cell.mix,
            "config": cell.config, "cycle_interval_s": interval_s,
            "cooldown_s": COOLDOWN_INTERVALS * interval_s,
            "drain_limit_s": DRAIN_LIMIT_S,
            "report_path": os.path.join(self.out_dir, "client_report.json"),
        }
        self._inbox: queue.Queue = queue.Queue()
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        # unnamed on purpose, like the controller: /healthz counts every
        # thread whose name the program's audit does not know as drift, on
        # every poll; "Thread-N" is a name it knows
        threading.Thread(target=self._read_child, daemon=True).start()
        self._say(**self.plan)

        # one clock: this process's readings must bracket the child's
        self._hear("hello", CLIENT_REPLY_S)
        before = time.monotonic_ns()
        self._say(clock=before)
        theirs = self._hear("clock", CLIENT_REPLY_S)["ns"]
        if not before <= theirs <= time.monotonic_ns():
            raise Failure("parent and client disagree on CLOCK_MONOTONIC")

        loaded = self._hear("loaded", WARMUP_LIMIT_S)
        self.info("loaded", **{k: v for k, v in loaded.items() if k != "event"})
        # the pod buckets the cell's file lists, one whole batch each: the
        # mix then warms what it uses most, and these what it uses rarely
        from harness import checks

        t0 = time.monotonic_ns()
        for size in cell.params.get("warm_pod_counts", []):
            checks.warm_pod_bucket(daemon, cell, self.args.seed, size)
        self.info("warmed", pod_counts=cell.params.get("warm_pod_counts", []),
                  seconds=(time.monotonic_ns() - t0) / 1e9,
                  compile_events=self.compile_events())
        self.warmed = (self.ledger.pods_bound, self.ledger.pods_deleted)
        self._say(go=True)
        return self._hear("generating", CLIENT_REPLY_S)["ns"]

    def wait_warm(self, since_ns: int) -> int:
        """Warm-up is the mix itself: block until it has run the cell's
        `warmup_s` since `since_ns`, and for as long, and through
        `WARM_CYCLES` cycles that bound pods, since anything last compiled
        or loaded from the cache. (A compile is counted when it ends: the
        cycles are what shows that none is under way.) Returns the compile
        events so far."""
        warmup_ns = int(self.cell.params["warmup_s"] * 1e9)
        opened = self.ledger.cycle_stamps
        seen, last_change = self.compile_events(), since_ns
        cursor, bound_cycles = len(self.binds), set()
        while True:
            time.sleep(0.05)
            now = time.monotonic_ns()
            if self.child.poll() is not None:
                raise Failure("the client ended during warm-up")
            count = self.compile_events()
            if count != seen:
                seen, last_change = count, now
                bound_cycles.clear()
            fresh = self.binds[cursor:]
            cursor += len(fresh)
            bound_cycles.update(
                bisect.bisect_right(opened, t) for _uid, t in fresh
                if t >= last_change
            )
            if (now - max(since_ns, last_change) >= warmup_ns
                    and len(bound_cycles) >= WARM_CYCLES):
                return seen
            if now - since_ns > WARMUP_LIMIT_S * 1e9:
                raise Failure("warm-up did not settle")

    def _control(self) -> None:
        daemon = self.daemon
        generating_ns = self.start_client()
        seen = self.wait_warm(generating_ns)
        window_ns = int(self.args.seconds * 1e9)
        t0 = time.monotonic_ns() + 100_000_000
        t1 = t0 + window_ns
        self._say(window_start_ns=t0, window_ns=window_ns)
        self.window = (t0, t1)

        traced = bool(self.args.trace)
        host_spans = None
        if traced:
            from harness import tracing

            if not tracing.clocks_agree():
                raise Failure("perf_counter and monotonic are two clocks here")
            host_spans = tracing.HostSpans(daemon)
            host_spans.start()
        _sleep_until(t0)
        self.registry["setup"] = self.registry_now()
        counters = self.registry["setup"]["counters"]
        self.info("window_open", setup_s=(t0 - T_PROCESS_START_NS) / 1e9,
                  warmup_s=(t0 - generating_ns) / 1e9,
                  compile_events=seen, bound=len(self.binds),
                  cache_requests=counters.get(self.obs.COMPILE_CACHE_REQUESTS, 0),
                  cache_hits=counters.get(self.obs.COMPILE_CACHE_HITS, 0))
        if traced:
            profile_s = min(PROFILE_S, self.args.seconds / 2)
            _sleep_until(t0 + (window_ns - int(profile_s * 1e9)) // 2)
            self.profiled = tracing.profile(
                os.path.join(self.out_dir, "trace"), profile_s
            )
        _sleep_until(t1)
        self.registry["window"] = self.registry_now()
        self.memory_peak_bytes = _memory_peak_bytes()
        if traced:
            self.spans = host_spans.stop()

        done = self._hear("done", CLIENT_REPLY_S + DRAIN_LIMIT_S)
        self.client = spec.load_json(done["report"])
        self._check_and_reduce()

    # -- after the window --------------------------------------------------
    def _check_and_reduce(self) -> None:
        from harness import checks
        from scheduler_plugins_tpu.utils.intmath import bucket_size

        cell, daemon, report = self.cell, self.daemon, self.client
        t0, t1 = self.window
        problems = checks.client_counts(report, self.ledger, self.warmed)
        with daemon.feed.locked():
            problems += checks.audits(cell, daemon.cluster)
        resident = bool(cell.config["resident_state"])
        problems += checks.resident_state(daemon, resident)

        # a pod due inside the window has the cool-down and the mix's grace
        # to bind; one that took longer, or never bound, has failed
        from readers import decision_latency

        interval_ns = int(daemon.args.cycle_interval_s * 1e9)
        limit = t1 + (COOLDOWN_INTERVALS + cell.mix["grace_intervals"]) * interval_ns
        pods = decision_latency.window_pods(self)
        unbound = sum(1 for _due, at in pods if at is None or at > limit)
        batches = [n for at, n, _ in self.cycles if n and t0 <= at < t1]
        probe_size = sorted(batches)[(len(batches) - 1) // 2] if batches else 0
        result = {}
        if probe_size:
            result = checks.probe(daemon, cell, self.args.seed, probe_size)
            self.info("probe", **result)
            problems += checks.probe_problems(result, resident)
        else:
            problems.append("no pod was bound inside the window")
        for problem in problems:
            self.info("problem", what=problem)
        # every number compared, beside its limit; the comparisons are exact
        compared = {
            name: {"value": value, "limit": limit}
            for name, value, limit in (
                ("pending_after_drain", report["sync"].get("pending"),
                 report["held"]),
                ("bound_of_arrivals",
                 report["healthz"]["bound_total"] - report["bound_base"],
                 report["arrivals"]),
                ("events_refused", report["refused"], 0),
                ("probe_cycles_min", result.get("cycles", 0), 1),
                ("probe_slots_differing", result.get("mismatches"), 0),
                ("probe_hard_violations", result.get("hard_violations"), 0),
                ("probe_reference_binds_unbound",
                 result.get("reference_unbound"), 0),
                ("problems", len(problems), 0),
            )
        }

        if self.args.trace:
            self._reduce_trace()
        kind = "per_layer" if self.args.trace else "end_to_end"
        other = "end_to_end" if self.args.trace else "per_layer"
        metrics = spec.evaluate(cell, kind, self)
        self.info("other_metrics", kind=other,
                  metrics=spec.evaluate(cell, other, self))
        delays = decision_latency.samples_ms(self)
        self.info("decision_ms", n=len(delays), **{
            f"p{q}": stats.percentile(delays, q) for q in (50, 90, 99, 100)
        })
        self.info("cycles", around_window=self._cycle_log())
        self.info("window", seconds=(t1 - t0) / 1e9, attempted=len(pods),
                  cycles_with_binds=len(batches), probe_size=probe_size,
                  batch_sizes=_histogram(batches),
                  pod_buckets=_histogram(bucket_size(n) for n in batches),
                  compiled_in_window=self._compiled_in_window(),
                  forgiven_s=report["forgiven_s"],
                  polls=len(report["polls"]))
        device = {
            "platform": daemon.device["platform"],
            "kind": daemon.device["device_kind"],
            "count": daemon.device["count"],
            "memory_peak_bytes": self.memory_peak_bytes,
        }
        self.result = {
            "correct": not problems, "attempted": len(pods),
            "failed": unbound + report["refused"],
            "metrics": metrics, "device": device,
        }
        self._attach_trace(device)
        self.result["compared"] = compared  # the line's last key

    def _attach_trace(self, device: dict) -> None:
        if self.device_trace is None:
            return
        from harness import trace_reduce

        w0, w1 = self.device_trace["window_ns"]
        device["busy_s"] = self.device_trace["busy_ns"] / 1e9
        device["window_s"] = (w1 - w0) / 1e9
        spans = [
            (name, s - self.trace_offset_ns, e - self.trace_offset_ns)
            for name, s, e, _ in self.spans
        ]
        self.result["breakdown"] = {
            "device_ops": trace_reduce.top(self.device_trace["op_ns"], 10),
            "idle_gaps": trace_reduce.top(
                trace_reduce.idle_by_span(self.device_trace["gaps"], spans),
                10,
            ),
        }
        self.info("device_modules", modules={
            name: {"device_s": ns / 1e9, "runs": runs}
            for name, (ns, runs) in self.device_trace["modules"].items()
        })

    def _compiled_in_window(self) -> dict:
        """{counter: delta} of the compile counters that moved inside the
        window: which program it was, when `compiles_in_window` is not 0."""
        obs = self.obs
        before = self.registry["setup"]["counters"]
        return {
            key: value - before.get(key, 0)
            for key, value in self.registry["window"]["counters"].items()
            if key.startswith((obs.JIT_CACHE_MISS, obs.COMPILE_CACHE_REQUESTS))
            and value != before.get(key, 0)
        }

    def _cycle_log(self) -> list:
        """[[opened, pods bound, last bind], ...], seconds from the window's
        start, for the cycles from two intervals before the window to the
        end of the run: the line to read when a tail looks wrong."""
        t0 = self.window[0]
        margin = 2 * int(self.daemon.args.cycle_interval_s * 1e9)
        return [
            [round((at - t0) / 1e9, 3), n,
             None if last is None else round((last - t0) / 1e9, 3)]
            for at, n, last in self.cycles if at >= t0 - margin
        ]

    def _reduce_trace(self) -> None:
        from harness import trace_reduce

        path = trace_reduce.newest_xplane(os.path.join(self.out_dir, "trace"))
        if path is None:
            self.info("trace", found=False)
            return
        profile = trace_reduce.load(path)
        offset = trace_reduce.sync_offset_ns(profile)
        if offset is None:
            raise Failure("the trace holds no bench_sync annotation")
        self.trace_offset_ns = offset
        p0, p1 = self.profiled
        self.device_trace = trace_reduce.reduce_trace(
            profile, window=(p0 - offset, p1 - offset)
        )
        self.info("trace", found=True, bytes=os.path.getsize(path),
                  device_planes=0 if self.device_trace is None
                  else self.device_trace["devices"])


def _sleep_until(t_ns: int) -> None:
    wait = t_ns - time.monotonic_ns()
    if wait > 0:
        time.sleep(wait / 1e9)


def _histogram(values) -> dict:
    out: dict = {}
    for v in values:
        out[v] = out.get(v, 0) + 1
    return dict(sorted(out.items()))


def _memory_peak_bytes():
    """The peak on the fullest device; None where the backend keeps no
    allocator statistics (the CPU backend)."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    return execute(parse_args(argv), Run)


def execute(args, run_class) -> int:
    """Build the daemon, run it with `run_class`'s controller beside it,
    print the controller's result as the last line."""
    if args.index:
        spec.use_index(args.index)
    cell = spec.Cell(args.workload, rehearse=args.rehearse_cpu)
    if args.seconds is None:
        args.seconds = float(spec.index()["run_seconds"])
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    wanted = "cpu" if args.rehearse_cpu else "tpu"
    if platform != wanted:
        print(f"benchmark: this run needs the {wanted} backend, JAX found "
              f"{platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} chips, "
              f"{len(devices)} present", file=sys.stderr)
        return 2

    from harness.bind_ledger import BindStampLedger
    from harness.peaks import peaks_for
    from scheduler_plugins_tpu.__main__ import Daemon
    from scheduler_plugins_tpu.__main__ import parse_args as daemon_args
    from scheduler_plugins_tpu.obs import ledger as podledger
    from scheduler_plugins_tpu.utils import compile_cache

    peaks = None if args.rehearse_cpu else peaks_for(devices[0].device_kind)
    cache_dir = compile_cache.configure()
    out_dir = os.path.join(BENCH_DIR, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    profile_path = os.path.join(out_dir, "profile.json")
    with open(profile_path, "w") as f:
        json.dump(cell.config["profile"], f)

    ledger = BindStampLedger()
    podledger.use(ledger)
    daemon = Daemon(daemon_args(
        ["--profile", profile_path] + cell.config["daemon_flags"]
    ))
    out = sys.stdout
    run = run_class(args, cell, daemon, ledger, out_dir, out)
    run.peaks = peaks
    run.info("start", workload=args.workload, seed=args.seed,
             seconds=args.seconds, trace=args.trace,
             rehearsal=args.rehearse_cpu, device=daemon.device,
             compile_cache_dir=cache_dir,
             compile_cache_entries=(
                 len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
             ),
             import_s=(time.monotonic_ns() - T_PROCESS_START_NS) / 1e9)

    controller = threading.Thread(target=run.control, daemon=True)
    # `Daemon.run` installs the same handler; this one covers a controller
    # that fails before the loop is up
    signal.signal(signal.SIGTERM, lambda *_: daemon.stop_event.set())
    daemon_lines = io.StringIO()
    controller.start()
    try:
        with contextlib.redirect_stdout(daemon_lines):
            daemon.run()  # until the controller's SIGTERM
    finally:
        ledger.stop()
    controller.join(timeout=60)
    if controller.is_alive():
        print("benchmark: the controller did not finish", file=sys.stderr)
        return 1
    exit_line = None
    for line in daemon_lines.getvalue().splitlines():
        if line.startswith("{"):
            exit_line = json.loads(line)
    run.info("daemon_exit", **(exit_line or {}))
    if run.error is not None or run.result is None:
        print(f"benchmark: no result: {run.error!r}", file=sys.stderr)
        return 1
    if not (exit_line and exit_line.get("daemon_exit")
            and not exit_line["parked_cycles"] and not exit_line["degraded"]):
        run.info("problem", what=f"daemon exit line not clean: {exit_line}")
        run.result["correct"] = False
    for name, numbers in run.result.get("compared", {}).items():
        print(f"compared {name}: {numbers['value']} (limit {numbers['limit']})",
              file=sys.stderr)
    print(f"correct: {run.result.get('correct')}", file=sys.stderr, flush=True)
    print(json.dumps(run.result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
