"""`python -m scheduler_plugins_tpu` — the long-lived scheduler daemon.

The analog of the reference's two binaries in one process, the way the
library composes them (VERDICT r4 item 2):

- the scheduler binary (/root/reference/cmd/scheduler/main.go:46-71):
  decode a profile, register plugins, run scheduling cycles against a live
  cluster store;
- the controller binary (/root/reference/cmd/controller/app/server.go:43-97):
  PodGroup/ElasticQuota reconcilers driven on the same cadence, plus a
  health/metrics surface.

Wiring per tick:

    apiserver (LIST+WATCH, bearer auth/ca)     [--apiserver URL]
        -> ClusterAgent reflector threads (one per watch path)
        -> FeedServer (rv-fenced event protocol over TCP; --grpc-port
           serves the same events over real gRPC/HTTP2; --native-store
           mirrors hot node columns into the C++ columnar store)
        -> Cluster store  (--scheduler-name gates the queue per profile)
    cycle loop:  [--leader-elect: only while holding the Lease]
                 a tick when a pod becomes schedulable, as early as
                 DEMAND_TICK_SPACING allows or sooner where the feed has
                 gone quiet and left the lock unused (QUIET_TICK_SHARE),
                 and every --cycle-interval-s otherwise (`Daemon.run`)
                 run_cycle (QueueSort..Bind, collector ticks, NRT resync)
                 reconcile_pod_groups / reconcile_elastic_quotas
                 bindings POSTed back to the apiserver [--bind-back]
    health:      GET /healthz      -> liveness + cycle/bound/leader status
                 GET /metrics      -> prometheus text format (counters incl.
                                      per-plugin unschedulable attribution +
                                      cycle/plugin latency histograms)
                 GET /metrics.json -> the flat JSON counter snapshot

Without --apiserver the daemon is feed-driven: external agents (the Go/C++
sidecar shape, bridge/feed.py clients) push events to --feed-port and the
cycle loop schedules whatever arrives.

`--max-cycles N` exits after N ticks (e2e tests; leader-election standby
ticks count, so bounded runs terminate either way); default runs until
SIGTERM/SIGINT, which stops cleanly (agents are daemon threads; the lease
is released, the feed/health servers shut down, a summary line prints).
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import signal
import sys
import threading
import time
import urllib.request
from pathlib import Path

from scheduler_plugins_tpu.api.config import load_profile
from scheduler_plugins_tpu.bridge.agent import DEFAULT_WATCH_PATHS, ClusterAgent
from scheduler_plugins_tpu.bridge.feed import (
    FEED_QUIET_NS,
    FeedClient,
    FeedServer,
)
from scheduler_plugins_tpu.controllers.elasticquota import (
    reconcile_elastic_quotas,
)
from scheduler_plugins_tpu.controllers.podgroup import reconcile_pod_groups
from scheduler_plugins_tpu.framework import Scheduler
from scheduler_plugins_tpu.framework.cycle import cycle_report_stages
from scheduler_plugins_tpu.obs import costmodel, ledger as podledger
from scheduler_plugins_tpu.state.cluster import Cluster
from scheduler_plugins_tpu.utils import compile_cache, observability as obs


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m scheduler_plugins_tpu",
        description="TPU-native scheduler daemon (feed server + reflector "
                    "agents + cycle loop + CRD controllers + health).",
    )
    ap.add_argument("--profile", required=True,
                    help="profile file (YAML or JSON): {plugins: [...], "
                         "pluginConfig: [{name, args}...]}")
    ap.add_argument("--feed-host", default="127.0.0.1")
    ap.add_argument("--feed-port", type=int, default=0,
                    help="TCP port for the event feed (0 = ephemeral)")
    ap.add_argument("--grpc-port", type=int, default=None,
                    help="also serve the event feed over real gRPC/HTTP2 "
                         "on this port (requires grpcio; shares the store "
                         "lock and rv fence with the TCP feed)")
    ap.add_argument("--apiserver", default=None,
                    help="kube-apiserver base URL to LIST+WATCH (optional; "
                         "without it the daemon is feed-driven only)")
    ap.add_argument("--token-file", default=None,
                    help="bearer token file for --apiserver")
    ap.add_argument("--ca-file", default=None,
                    help="CA bundle to trust for --apiserver TLS "
                         "(in-cluster: the serviceaccount ca.crt)")
    ap.add_argument("--insecure-skip-verify", action="store_true")
    ap.add_argument("--watch-paths", default=None,
                    help="comma-separated resource paths to watch "
                         "(default: the full reference informer surface)")
    ap.add_argument("--bind-back", action="store_true",
                    help="POST bindings back to --apiserver "
                         "(pods/<name>/binding, the upstream bind shape)")
    ap.add_argument("--native-store", action="store_true",
                    help="mirror hot node columns into the C++ columnar "
                         "store (bridge/snapshot_store.cc) — snapshots "
                         "read memcpy exports instead of per-cycle Python "
                         "accumulation (requires the compiled .so, "
                         "`make native`)")
    ap.add_argument("--scheduler-name", action="append", default=None,
                    help="profile name(s) this scheduler owns (repeatable; "
                         "default tpu-scheduler): only pods whose "
                         "spec.schedulerName matches are scheduled")
    ap.add_argument("--leader-elect", action="store_true",
                    help="coordination.k8s.io Lease leader election via "
                         "--apiserver: schedule only while holding the "
                         "lease (reflectors keep syncing on standby)")
    ap.add_argument("--lease-name", default="scheduler-plugins-tpu")
    ap.add_argument("--lease-namespace", default="kube-system")
    ap.add_argument("--lease-duration-s", type=float, default=15.0)
    ap.add_argument("--identity", default=None,
                    help="leader-election holder identity "
                         "(default hostname_pid)")
    ap.add_argument("--cycle-interval-s", type=float, default=1.0,
                    help="the longest a schedulable pod waits for a tick "
                         "to start: the loop ticks this often when nothing "
                         "arrives (backoff expiry, PodGroup time-outs, "
                         "reconcilers, a leader-election standby), and "
                         "sooner when a pod becomes schedulable and the "
                         "last tick took under a sixth of it")
    ap.add_argument("--health-port", type=int, default=0,
                    help="HTTP health/metrics port (0 = ephemeral; "
                         "-1 disables)")
    ap.add_argument("--max-cycles", type=int, default=0,
                    help="exit after N cycles (0 = run until SIGTERM)")
    ap.add_argument("--record", type=int, default=0, metavar="N",
                    help="flight recorder: keep the last N scheduling "
                         "cycles' full solver inputs+outputs in a ring "
                         "buffer (utils.flightrec; 0 = off). Enables "
                         "GET /explain?uid=<pod-uid> on the health port "
                         "(per-plugin score table for any recorded pod)")
    ap.add_argument("--record-dir", default=None, metavar="DIR",
                    help="with --record: persist the ring as a replayable "
                         "bundle under DIR on shutdown (crash-safe "
                         "temp+rename writes; replay offline with "
                         "tools/replay.py). NOTE: bundles carry full pod "
                         "specs — handle like an apiserver dump")
    ap.add_argument("--serve", action="store_true",
                    help="resident-state serving: keep node tensors "
                         "device-resident across cycles and ingest "
                         "O(changed) deltas (serving.engine.ServeEngine) "
                         "with periodic anti-entropy verification; falls "
                         "back to full snapshots transparently when the "
                         "profile surface needs them")
    ap.add_argument("--pipeline", action="store_true",
                    help="concurrent cycle pipeline "
                         "(framework.pipeline_cycle.PipelinedCycle): "
                         "dispatch the device solve asynchronously and "
                         "run the previous cycle's finalize in the "
                         "overlap window, with binds conflict-fenced at "
                         "the next ingest boundary; with --serve the "
                         "engine upgrades to the O(changed) "
                         "StreamingServeEngine (node-delete compaction, "
                         "memoized ingest, O(assigned) anti-entropy)")
    ap.add_argument("--tune", action="store_true",
                    help="online self-tuning shadow lane "
                         "(tuning.shadow.ShadowTuner): continuously "
                         "replay the recorded flight-recorder ring under "
                         "candidate plugin-weight vectors on a background "
                         "worker (deadlined — a hung sweep degrades to "
                         "'no tuning'), promote a winner only through "
                         "the tuning.promotion gates, roll it out live "
                         "via the aux channel (zero recompiles) and "
                         "auto-roll-back on quality-gauge regression "
                         "during probation. Implies --record 8 when "
                         "--record is not set (the ring IS the sweep "
                         "corpus). With --checkpoint, the promoted "
                         "weights + probation state persist to "
                         "<checkpoint>.tuner.json on shutdown and "
                         "restart resumes with them")
    ap.add_argument("--tune-candidates", type=int, default=24,
                    help="candidate weight vectors per shadow sweep")
    ap.add_argument("--tune-sweep-every", type=int, default=8,
                    help="cycles between shadow sweep dispatches")
    ap.add_argument("--resilient", action="store_true",
                    help="solve watchdog + degraded-mode failover "
                         "(resilience.watchdog): device solves complete "
                         "through a deadlined worker thread, retry with "
                         "seeded-jitter backoff, then fail over to the "
                         "host sequential parity path and probe for "
                         "recovery (SPT_SOLVE_TIMEOUT_S tunes the "
                         "deadline)")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="with --serve: restore the resident state from "
                         "PATH at startup (if present; anti-entropy "
                         "verifies it before trusting it) and write a "
                         "final crash-safe checkpoint there on shutdown")
    ap.add_argument("--no-ledger", action="store_true",
                    help="disable the pod-lifecycle SLO ledger "
                         "(obs.ledger; on by default in the daemon). The "
                         "ledger follows each pod across cycles — queue "
                         "wait, backoff, gang wait, solve/fence/bind — "
                         "feeding the scheduler_e2e_scheduling_duration_ms "
                         "/ scheduler_pod_scheduling_sli_duration_ms "
                         "families, the /healthz sli block and "
                         "GET /pods/<uid>/timeline on the health port")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the cycle tracer for the daemon's "
                         "lifetime and flush a Perfetto-loadable JSON to "
                         "OUT.json on shutdown (SIGTERM included)")
    return ap.parse_args(argv)


#: How far ahead of its interval the loop may run. A tick that kept the
#: feed lock for `d` (`Daemon.tick_locked_s`: from asking for the lock to
#: giving it up, for the cycle and again for the tail; what the tick does
#: with no lock held, the cycle's report-only epilogue above all, is not in
#: it) is followed by a demand tick no sooner than this many `d` after it
#: started, so whenever the loop runs early the synchronous feed is shut
#: out at most one sixth of the time. Why a sixth: a tick has a fixed cost
#: whatever its batch, so ticks closer together raise the lock's share,
#: and a closed backlog's throughput is what the lock leaves of the second
#: (PERF.md finding 20: pods/s ∝ 1 − share). The parent held the lock
#: (161.3 + 6.6) ms of every 1,000 in `basic-5000n.backlog` (ledger, PR
#: 24), 16.8 %: the cell where share is throughput keeps the share it had,
#: and a tick that keeps the lock a sixth of the interval or more keeps the
#: interval's cadence exactly. An anti-entropy check inside the tick (every
#: `verify_every`-th refresh, `Daemon.tick_check_s`) is counted once, not
#: this many times: it pauses the loop once, and multiplied it sent the
#: tick after it to the interval whatever was waiting (PERF.md finding 42):
#: a demand tick comes no sooner than `check + this × (d − check)` after
#: that tick started. The rule has a quiet exit (`QUIET_TICK_SHARE`): it
#: keeps the feed a share of the lock, and a feed that has stopped using
#: it gives the loop nothing to wait for.
DEMAND_TICK_SPACING = 6

#: The quiet exit from the spacing rule. While a pod waits, the loop
#: starts the next tick before `DEMAND_TICK_SPACING` allows at the first
#: moment at which the feed has had no line in hand for
#: `bridge.feed.FEED_QUIET_NS` and has been quiet for at least this share
#: of the time since the last tick gave the feed lock up, its gaps counted
#: as `bridge.feed.FeedTally` counts them (`FeedPulse`). Why a half: of the
#: loop's sleep the feed left quiet 0.824 in `trimaran-5000n.steady` (120
#: pods/s open), and 0.30 / 0.255 in `spread-` / `nodeaffinity-5000n.
#: steady`, 0.072 in `basic-5000n.steady` and 0.027–0.194 in the five
#: closed cells (PERF.md finding 45): a feed that uses its
#: share of the lock reads under 0.2 and keeps the rule, the light open
#: cell's feed reads four times that and does not. One gap does not pass
#: the gate unless it is as long as everything else since the lock came
#: free. Where the loop cannot see the store's writers go quiet (agents of
#: `--apiserver`, a `--grpc-port` front end, no feed connection open) or
#: the engine paces itself (`--pipeline`), the rule alone decides.
QUIET_TICK_SHARE = 0.5


class _Doorbell:
    """What the loop waits on between ticks: rung when a pod arrives and
    when the daemon is told to stop. A bare lock used as a binary
    semaphore (held = silent), not a `threading.Event`: `Event.set()` takes
    a Python-level lock, and the SIGTERM handler runs in the loop's own
    thread, which may hold that lock at that moment (a signal landing as
    the loop entered `Event.wait()` deadlocked the daemon about once in
    forty shutdowns). A lock's acquire and release are single C calls, so
    nothing here is ever half done when a handler runs. A ring is kept
    until a wait takes it."""

    def __init__(self):
        self._silent = threading.Lock()
        self._silent.acquire()

    def ring(self) -> None:
        try:
            self._silent.release()
        except RuntimeError:
            pass  # rung already

    def wait(self, timeout: float) -> None:
        """Until rung, `timeout` seconds at most; takes the ring."""
        self._silent.acquire(timeout=max(timeout, 0.0))

    def reset(self) -> None:
        self._silent.acquire(blocking=False)


class _StopEvent(threading.Event):
    """The daemon's stop flag. Setting it also rings the loop's doorbell,
    so a stop ends the loop's wait at once. The loop itself only reads
    the flag (`is_set()` takes no lock): see `_Doorbell`."""

    def __init__(self, doorbell: _Doorbell):
        super().__init__()
        self._doorbell = doorbell

    def set(self):
        super().set()
        self._doorbell.ring()


def decode_profile_file(path: str) -> dict:
    """YAML/JSON profile file -> the flat {plugins, pluginConfig} mapping
    `api.config.load_profile` takes. Accepts a KubeSchedulerConfiguration
    -style {profiles: [first]} wrapper. Shared by startup profile loading
    and the flight recorder's exact-config capture, so the recorded
    config can never diverge from the profile the daemon actually runs."""
    import yaml

    with open(path) as f:
        config = yaml.safe_load(f) or {}
    if "profiles" in config:
        config = (config.get("profiles") or [{}])[0]
    return config


def load_profile_file(path: str):
    """YAML/JSON profile file -> Profile."""
    return load_profile(decode_profile_file(path))


#: fnmatch patterns for live thread names the concurrency model covers,
#: resolved lazily from the committed auditor manifest
_THREAD_PATTERNS: list | None = None


def _known_thread_patterns() -> list:
    global _THREAD_PATTERNS
    if _THREAD_PATTERNS is None:
        # interpreter main + ThreadingHTTPServer's per-request threads
        # (stdlib-named; our own threads carry explicit names — GL012)
        pats = ["MainThread", "Thread-*"]
        manifest = (
            Path(__file__).resolve().parents[1] / "docs" / "race_audit.json"
        )
        try:
            entries = json.loads(manifest.read_text())["entries"]
            pats += [
                name for name, spec in sorted(entries.items())
                if spec.get("kind") in ("thread", "pool", "server")
            ]
        except (OSError, ValueError, KeyError):
            # installed without the repo checkout: fall back to the
            # names the code itself assigns (kept in sync by the
            # manifest-coverage test in tests/test_race_audit.py)
            pats += [
                "agent-*", "feed-server", "health-server",
                "leader-elector", "load-watcher", "shadow-tuner",
                "solve-watchdog", "spt-bind-flusher*", "wd-*",
            ]
        _THREAD_PATTERNS = pats
    return _THREAD_PATTERNS


def thread_topology() -> dict:
    """Live thread names diffed against the static concurrency model
    (tools/race_audit.py's entry table). `unknown` names are topology
    drift: a running thread the lockset analysis never audited."""
    live = sorted(t.name for t in threading.enumerate())
    pats = _known_thread_patterns()
    unknown = [
        n for n in live if not any(fnmatch.fnmatch(n, p) for p in pats)
    ]
    return {"live": live, "unknown": unknown}


class HealthServer:
    """GET /healthz (liveness + loop counters), /metrics (prometheus text
    exposition 0.0.4: counters incl. per-plugin unschedulable attribution,
    plus real `_bucket{le=...}`/`_sum`/`_count` histograms for cycle and
    per-extension-point plugin latency) and /metrics.json (the flat debug
    snapshot) — the probe/metrics surface of cmd/controller/app/server.go
    :52-58, now speaking the prometheus wire format."""

    def __init__(self, daemon, host: str, port: int):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        outer = daemon

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _json_reply(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path.startswith("/healthz"):
                    started_ns = time.perf_counter_ns()
                    # lock-free: a probe must answer while a cycle (incl.
                    # first-compile) holds the feed lock; `last_pending`
                    # is the previous tick's cached count
                    payload = {
                        "ok": True,
                        "cycles": outer.cycles,
                        "bound_total": outer.bound_total,
                        "pending": outer.last_pending,
                        # latest cycle's placement-quality objectives
                        # (tuning.quality; None before the first solved
                        # cycle) — the gauge view lives on /metrics as
                        # scheduler_placement_quality{objective}
                        "quality": outer.last_quality,
                        "feed_address": list(outer.feed.address),
                        # what JAX runs the solves on: platform,
                        # device_kind and device count
                        "device": outer.device,
                        # degraded-mode serving state (resilience.watchdog
                        # / docs/ROBUSTNESS.md): degraded=True means the
                        # device backend failed past the watchdog budget
                        # and cycles serve from the host parity path
                        "degraded": (
                            outer.resilience is not None
                            and outer.resilience.degraded
                        ),
                        "degraded_reason": (
                            outer.resilience.degraded_reason
                            if outer.resilience is not None else None
                        ),
                        "parked_cycles": outer.parked_cycles,
                        # pod-lifecycle SLIs (obs.ledger): e2e scheduling
                        # latency percentiles, per-stage decomposition
                        # totals and per-priority breakdown over the
                        # retired ring; None with --no-ledger
                        "sli": (
                            podledger.LEDGER.sli_summary()
                            if podledger.LEDGER.enabled else None
                        ),
                        # live thread census vs the static concurrency
                        # model (tools/race_audit.py entry table):
                        # `unknown` = running threads the lockset
                        # analysis never modeled
                        "threads": thread_topology(),
                        # device-memory watermarks (obs.costmodel, ISSUE
                        # 20): allocator bytes-in-use/peak stamped by the
                        # last cycle; available=False on backends without
                        # allocator stats (the CPU backend), None before
                        # the first cycle — the static counterpart is
                        # docs/cost_model.json's per-program peak_bytes
                        "memory": outer.last_memory,
                    }
                    if payload["threads"]["unknown"]:
                        obs.metrics.inc(
                            obs.THREAD_TOPOLOGY_DRIFT,
                            len(payload["threads"]["unknown"]),
                        )
                    if outer.pipeline is not None:
                        # concurrent cycle pipeline introspection:
                        # configured depth + host stages still in
                        # flight (deferred finalize / unflushed binds)
                        payload["pipeline"] = {
                            "depth": outer.pipeline.depth,
                            "inflight": outer.pipeline.inflight,
                        }
                    if outer.engine is not None:
                        payload["serve"] = {
                            "generation": outer.engine.generation,
                            "rebases": outer.engine.rebases,
                            "antientropy_divergences":
                                outer.engine.antientropy_divergences,
                            # resident gang/quota serving health: >0
                            # means gang rosters are falling back to
                            # O(cluster) snapshots (ISSUE 12 — should
                            # stay 0 on a compatible roster)
                            "gang_fallbacks":
                                outer.engine.gang_fallbacks,
                        }
                    if outer.tuner is not None:
                        # online self-tuning controller state (guarded
                        # rollout, docs/ROBUSTNESS.md): active weights +
                        # digest, probation progress, promotion/rollback
                        # counters, self-disable reason
                        payload["tuner"] = outer.tuner.status()
                    if outer.elector is not None:
                        payload["leader"] = outer.elector.is_leader
                        payload["holder"] = outer.elector.observed_holder
                    self._json_reply(200, payload)
                    # entry to reply written: what a poll costs inside the
                    # handler, its waits for the interpreter lock included
                    # (registry only: this thread records no tracer span)
                    obs.metrics.observe_ms(
                        obs.HEALTHZ_HANDLER_MS,
                        (time.perf_counter_ns() - started_ns) / 1e6,
                    )
                    return
                elif self.path.startswith("/explain"):
                    # per-plugin score table for a recorded pod (flight
                    # recorder ring; 404 when off or uid not recorded)
                    from urllib.parse import parse_qs, urlparse

                    from scheduler_plugins_tpu.utils import flightrec

                    query = parse_qs(urlparse(self.path).query)
                    uid = (query.get("uid") or [""])[0]
                    cycle = query.get("cycle")
                    try:
                        top_k = int((query.get("top") or [5])[0])
                        cycle_n = int(cycle[0]) if cycle else None
                    except ValueError as exc:
                        self._json_reply(
                            400, {"error": f"bad query parameter: {exc}"}
                        )
                        return
                    rec = flightrec.recorder.find(uid, cycle=cycle_n)
                    if not uid or rec is None:
                        detail = (
                            "flight recorder off (--record N)"
                            if not flightrec.recorder.enabled
                            else f"uid {uid!r} not in the recorded ring"
                        )
                        self._json_reply(404, {"error": detail})
                        return
                    try:
                        body = json.dumps(
                            flightrec.explain_record(rec, uid, top_k=top_k)
                        ).encode()
                    except Exception as exc:
                        self._json_reply(
                            500,
                            {"error": f"{type(exc).__name__}: {exc}"},
                        )
                        return
                elif self.path.startswith("/pods/"):
                    # GET /pods/<uid>/timeline — one pod's full lifecycle
                    # story from the pod ledger: events with (cycle, lane,
                    # seq) coordinates, the per-stage latency
                    # decomposition (sums to e2e exactly) and the meta of
                    # every cycle that observed the pod
                    from urllib.parse import unquote, urlparse

                    parts = urlparse(self.path).path.strip("/").split("/")
                    if len(parts) != 3 or parts[2] != "timeline":
                        self._json_reply(
                            404,
                            {"error": "expected /pods/<uid>/timeline"},
                        )
                        return
                    if not podledger.LEDGER.enabled:
                        self._json_reply(
                            404,
                            {"error": "pod-lifecycle ledger disabled "
                                      "(--no-ledger)"},
                        )
                        return
                    timeline = podledger.LEDGER.timeline(unquote(parts[1]))
                    if timeline is None:
                        self._json_reply(
                            404,
                            {"error": f"uid {unquote(parts[1])!r} not in "
                                      "the ledger (never pending, or "
                                      "aged out of the retired ring)"},
                        )
                        return
                    body = json.dumps(timeline).encode()
                elif self.path.startswith("/metrics.json"):
                    body = json.dumps(obs.metrics.snapshot()).encode()
                elif self.path.startswith("/metrics"):
                    body = obs.metrics.prometheus_text().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.address = self._httpd.server_address
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="health-server",
        )
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


class Daemon:
    def __init__(self, args):
        self.args = args
        # initializes the backend: a daemon asked to run on a device that
        # is not there fails here, with the backend's own error
        self.device = costmodel.device_identity()
        self.profile = load_profile_file(args.profile)
        self.scheduler = Scheduler(self.profile)
        if not getattr(args, "no_ledger", False):
            # pod-lifecycle SLO ledger (obs.ledger): O(changed) per cycle,
            # bounded ring — on by default in the daemon, feeding the
            # upstream-parity e2e/attempts/SLI metric families and the
            # /pods/<uid>/timeline surface
            podledger.LEDGER.start()
        if args.tune and not args.record:
            # the flight-recorder ring IS the shadow lane's sweep corpus
            args.record = 8
        if args.record:
            from scheduler_plugins_tpu.utils import flightrec

            flightrec.recorder.start(capacity=args.record)
            # the daemon knows its EXACT profile config — record that
            # instead of the best-effort attribute export
            flightrec.recorder.profile_config = decode_profile_file(
                args.profile
            )
        self.cluster = Cluster()
        if args.scheduler_name:
            self.cluster.scheduler_names = set(args.scheduler_name)
        # every engine reads the maintained pending index (the pipelined
        # one switches it on itself too): the scan's list in
        # the scan's order, without a walk over every pod twice a tick
        self.cluster.enable_pending_index()
        self.engine = None
        if args.serve:
            from scheduler_plugins_tpu.serving import (
                ServeEngine,
                StreamingServeEngine,
            )

            engine_cls = (
                StreamingServeEngine if args.pipeline else ServeEngine
            )
            self.engine = engine_cls().attach(self.cluster)
            if args.checkpoint and os.path.exists(args.checkpoint):
                try:
                    self.engine.restore_checkpoint(args.checkpoint)
                    obs.logger.info(
                        "resident state restored from %s (generation %d; "
                        "anti-entropy verifies at the first refresh)",
                        args.checkpoint, self.engine.generation,
                    )
                except Exception as exc:
                    # a bad checkpoint must never block startup: the
                    # engine just rebuilds from the store (cold path)
                    obs.logger.warning(
                        "checkpoint restore failed (%s): rebuilding "
                        "resident state from the store", exc,
                    )
        self.resilience = None
        if args.resilient:
            from scheduler_plugins_tpu.resilience import Resilience

            self.resilience = Resilience(engine=self.engine)
        self.tuner = None
        if args.tune:
            from scheduler_plugins_tpu.tuning.shadow import ShadowTuner

            try:
                self.tuner = ShadowTuner(
                    self.scheduler,
                    candidates=args.tune_candidates,
                    sweep_every=args.tune_sweep_every,
                )
            except ValueError as exc:
                # e.g. a packing-mode profile: the rollout seam is the
                # sequential parity path — refuse at startup, clearly
                raise SystemExit(f"--tune: {exc}")
            if args.checkpoint and os.path.exists(
                self._tuner_state_path()
            ):
                try:
                    with open(self._tuner_state_path()) as f:
                        restored = self.tuner.restore_state(json.load(f))
                    if restored:
                        obs.logger.info(
                            "tuner state restored from %s: weights %s "
                            "(%s)", self._tuner_state_path(),
                            self.tuner.status()["active_weights"],
                            self.tuner.status()["state"],
                        )
                except Exception as exc:
                    # a bad state file must never block startup: the
                    # tuner just starts fresh on the profile weights
                    obs.logger.warning(
                        "tuner state restore failed (%s): starting from "
                        "the profile weights", exc,
                    )
        self.pipeline = None
        if args.pipeline:
            from scheduler_plugins_tpu.framework import PipelinedCycle

            # binds flush inline (async_bind=False): every store
            # mutation happens under the feed lock the tick holds, so
            # the flusher thread's mutations cannot race feed ingest;
            # the overlap (async solve dispatch + the previous cycle's
            # finalize in the in-flight window) is within-tick
            self.pipeline = PipelinedCycle(
                self.scheduler, self.cluster, serve=self.engine,
                resilience=self.resilience, async_bind=False,
            )
        #: `run` waits on the doorbell between ticks; the store's hook
        #: rings it for the first pod that enters the pending set after a
        #: tick started, a stop rings it too
        self._doorbell = _Doorbell()
        self._pod_waiting = False
        #: `time.monotonic()` of the ring that raised `_pod_waiting`
        self._pod_rang_at = 0.0
        self.cluster.on_pending_gain = self._pod_arrived
        if args.trace:
            obs.tracer.start()
        if args.native_store:
            try:
                self.cluster.attach_native_store()
            except Exception as exc:
                raise SystemExit(
                    f"--native-store: {exc} (build it with `make native`)"
                )
        self.feed = FeedServer(
            self.cluster, host=args.feed_host, port=args.feed_port
        ).start()
        self.grpc_feed = None
        if args.grpc_port is not None:
            from scheduler_plugins_tpu.bridge.grpc_feed import GrpcFeedServer

            # same lock + rv fence: redundant TCP/gRPC agents stay coherent
            self.grpc_feed = GrpcFeedServer(
                self.cluster, host=args.feed_host, port=args.grpc_port,
                lock=self.feed.lock, rv_table=self.feed.rv_table,
            ).start()
            if not self.grpc_feed.port:
                # grpc's add_insecure_port reports a bind failure as port
                # 0 instead of raising — fail fast like any bad config
                raise SystemExit(
                    f"--grpc-port {args.grpc_port}: bind failed "
                    "(port in use?)"
                )
        self.cycles = 0
        self.ticks = 0
        #: seconds the last tick kept the feed lock, its waits for it
        #: included: what `DEMAND_TICK_SPACING` multiplies, less
        #: `tick_check_s`
        self.tick_locked_s = 0.0
        #: seconds of `tick_locked_s` the engine's anti-entropy checks took
        #: (`ServeEngine.refresh_check_s`; 0.0 on a tick that ran none):
        #: the spacing rule counts them once
        self.tick_check_s = 0.0
        #: whether the loop sees every writer of the store go quiet: the
        #: TCP feed's connections alone, and the serial engine
        self._sees_feed = (
            not args.apiserver and self.grpc_feed is None
            and self.pipeline is None
        )
        #: (`perf_counter_ns`, the feed's `quiet_total` then) where the
        #: last tick gave the feed lock up, `None` where the quiet exit
        #: does not apply to the wait after it
        self._released = None
        self.last_pending = 0
        self.last_quality = None
        self.last_memory = None  # /healthz device-memory block (ISSUE 20)
        self.parked_cycles = 0
        self._unposted: dict[str, str] = {}
        self.elector = None  # before HealthServer: /healthz reads it
        self.stop_event = _StopEvent(self._doorbell)
        self.health = None
        if args.health_port >= 0:
            self.health = HealthServer(self, args.feed_host, args.health_port)
        self.token = ""
        if args.token_file:
            with open(args.token_file) as f:
                self.token = f.read().strip()
        if args.leader_elect:
            if not args.apiserver:
                raise SystemExit("--leader-elect requires --apiserver")
            import socket as _socket

            from scheduler_plugins_tpu.bridge.leader import LeaseElector

            identity = args.identity or (
                f"{_socket.gethostname()}_{os.getpid()}"
            )
            self.elector = LeaseElector(
                args.apiserver, identity,
                name=args.lease_name, namespace=args.lease_namespace,
                lease_duration_s=args.lease_duration_s,
                renew_period_s=max(args.lease_duration_s / 3.0, 0.05),
                token=self.token, ca_file=args.ca_file,
                insecure_skip_verify=args.insecure_skip_verify,
            )
            threading.Thread(
                target=self.elector.run, args=(self.stop_event,),
                daemon=True, name="leader-elector",
            ).start()
        self._agent_threads = []
        if args.apiserver:
            paths = (
                [p.strip() for p in args.watch_paths.split(",") if p.strip()]
                if args.watch_paths else list(DEFAULT_WATCH_PATHS)
            )
            for path in paths:
                t = threading.Thread(
                    target=self._agent_loop, args=(path,), daemon=True,
                    name=f"agent-{path}",
                )
                t.start()
                self._agent_threads.append(t)

    @property
    def bound_total(self) -> int:
        """Pods this daemon has bound: the store's own count of its binds
        (`Cluster.binds_total`), which moves with the bind itself. A sum of
        `len(report.bound)` kept by the tick moved in the tick's tail,
        after the cycle gave the feed lock up: a client that saw the last
        pod bound on the feed and then read `/healthz` could read a count
        a whole batch behind the store (PERF.md, PR 28)."""
        return self.cluster.binds_total

    def _tuner_state_path(self) -> str:
        """The tuner's persisted controller state rides NEXT TO the
        resilience checkpoint (same crash-safe write discipline): the
        promoted weights + probation window survive a SIGTERM restart."""
        return f"{self.args.checkpoint}.tuner.json"

    def _agent_loop(self, path: str):
        """One reflector per watch path, feeding events through the real
        TCP wire to our own feed server (the exact path an external Go/C++
        agent would use)."""
        host, port = self.feed.address
        client = FeedClient(host, port)
        agent = ClusterAgent(client.send)
        agent.list_then_watch(
            self.args.apiserver, path,
            token=self.token,
            insecure_skip_verify=self.args.insecure_skip_verify,
            ca_file=self.args.ca_file,
            max_failures=None,  # the daemon retries for its lifetime
        )

    def _ssl_context(self):
        from scheduler_plugins_tpu.utils.httptls import ssl_context

        return ssl_context(self.args.apiserver, self.args.ca_file,
                           self.args.insecure_skip_verify)

    def _post_binding(self, uid: str, node: str) -> bool:
        """POST the upstream Binding shape back to the apiserver
        (the bind goroutine's process boundary, SURVEY.md §3.2). Returns
        True when the retry-queue entry should be dropped — success, or a
        pod that no longer exists in the store (deleted since binding:
        nothing left to bind)."""
        with self.feed.locked():
            pod = self.cluster.pods.get(uid)
            if pod is None:
                return True
            ns, name = pod.namespace, pod.name
        url = (f"{self.args.apiserver.rstrip('/')}"
               f"/api/v1/namespaces/{ns}/pods/{name}/binding")
        body = json.dumps({
            "apiVersion": "v1", "kind": "Binding",
            "metadata": {"name": name, "namespace": ns},
            "target": {"apiVersion": "v1", "kind": "Node", "name": node},
        }).encode()
        req = urllib.request.Request(
            url, data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        if self.token:
            req.add_header("Authorization", f"Bearer {self.token}")
        try:
            urllib.request.urlopen(
                req, timeout=3, context=self._ssl_context()
            ).close()
        except Exception as exc:
            obs.logger.warning("binding POST failed for %s: %s", uid, exc)
            return False
        return True

    def _pod_arrived(self) -> None:
        """The store's `on_pending_gain` hook: once per pod that enters
        the pending set, under the feed lock, by whichever thread applied
        the event. Only the first arrival after a tick started rings: the
        others find the flag up, which costs a tenth of a ring. No arrival
        is lost to that: `run` lowers the flag before its tick asks for the
        lock this caller holds, so a pod that found it up is in that tick's
        batch."""
        if not self._pod_waiting:
            # stamped before the flag goes up: the loop reads the flag
            # first, so the stamp it then reads is this ring's
            self._pod_rang_at = time.monotonic()
            self._pod_waiting = True
            self._doorbell.ring()

    def _count_pending(self) -> int:
        """The pending count `/healthz` serves; called under the feed
        lock. The size of the store's pending index."""
        with obs.tracer.span("PendingScan", tid="cycle",
                             pods=len(self.cluster.pods)):
            return self.cluster.pending_count()

    def tick(self):
        entered = time.monotonic()
        self.tick_check_s = 0.0
        self._released = None
        if self.elector is not None and not self.elector.is_leader:
            # standby: reflectors keep the store warm, scheduling waits
            # (client-go leaderelection semantics — informers run, the
            # scheduling/reconcile loops gate on leadership)
            with self.feed.locked():
                self.last_pending = self._count_pending()
            self.tick_locked_s = time.monotonic() - entered
            return None
        now_ms = int(time.time() * 1000)
        ctx = None
        try:
            if self.pipeline is not None:
                # the pipelined engine composes its own stage
                # functions; the tuner's two seams wrap the whole tick
                # (weights may only change between ticks — the conflict
                # fence keeps any in-flight solve on the weights it
                # dispatched with)
                if self.tuner is not None:
                    self.tuner.begin_cycle(now_ms=now_ms)
                with self.feed.locked():
                    report = self.pipeline.tick(now_ms)
                if self.tuner is not None and report is not None:
                    self.tuner.observe_report(report)
            else:
                # the stages that touch the store, under the feed lock;
                # the report-only rest comes after the tail, lock given up
                ctx = self.feed.cycle_store_stages(
                    self.scheduler, now=now_ms, serve=self.engine,
                    resilience=self.resilience, tuner=self.tuner,
                )
                if ctx.served:
                    self.tick_check_s = self.engine.refresh_check_s
        except Exception as exc:
            from scheduler_plugins_tpu.resilience import BackendUnavailable

            if not isinstance(exc, BackendUnavailable):
                raise
            # backend gone AND no host fallback for this profile: park
            # the cycle (pods stay pending, requeue backoff paces them)
            # and keep ticking — the probation probe restores the fast
            # path when the backend answers again
            obs.logger.warning("cycle parked: %s", exc.reason)
            self.parked_cycles += 1
            with self.feed.locked():
                self.last_pending = self._count_pending()
            self.tick_locked_s = time.monotonic() - entered
            return None
        tail_from = time.monotonic()
        cycle_s = tail_from - entered
        obs.metrics.observe_ms("scheduler_cycle", cycle_s * 1000)
        # the tick's tail, on the tracer's "daemon" row. The cycle gave
        # the lock up, and a feed thread that waited all cycle long has
        # it now: taking it again is a wait worth a span of its own
        lock = self.feed.locked()
        with obs.tracer.span("TickTail/relock", tid="daemon"):
            lock.acquire()
        try:
            with obs.tracer.span("TickTail/reconcile", tid="daemon"):
                events = reconcile_pod_groups(self.cluster, now_ms=now_ms)
                events += reconcile_elastic_quotas(self.cluster)
                self.last_pending = self._count_pending()
        finally:
            lock.release()
        self.tick_locked_s = cycle_s + (time.monotonic() - tail_from)
        if self._sees_feed:
            released = time.perf_counter_ns()
            self._released = (
                released, self.feed.pulse.quiet_total(released)[0]
            )
        obs.metrics.observe_ms(obs.TICK_LOCKED, self.tick_locked_s * 1000)
        if ctx is not None:
            # `Finalize`, with no lock held and outside `tick_locked_s`:
            # it reads the resident node columns the engine's next
            # refresh donates, and this thread, the only one that
            # refreshes, does that in its next tick
            report = cycle_report_stages(ctx, self.tuner)
        for line in events:
            obs.logger.info("controller: %s", line)
        if report.bound or report.failed:
            obs.logger.info(
                "cycle %d: bound %d, unschedulable %d",
                self.cycles + 1, len(report.bound), len(report.failed),
            )
        if self.args.apiserver and self.args.bind_back:
            # the local store binds immediately; the apiserver POST is the
            # process boundary and can fail transiently — keep unacked
            # bindings in a retry queue until the POST lands (the local
            # pod is no longer pending, so no re-schedule would re-emit
            # it). Retries are capped per tick: during an apiserver
            # outage each attempt burns its connect timeout, and the
            # scheduling loop must keep its cadence
            self._unposted.update(report.bound)
            failures = 0
            with obs.tracer.span("TickTail/bind_back", tid="daemon",
                                 unposted=len(self._unposted)):
                for uid, node in list(self._unposted.items()):
                    if failures >= 2:  # outage: stop burning timeouts
                        break
                    if self._post_binding(uid, node):
                        del self._unposted[uid]
                    else:
                        failures += 1
        self.cycles += 1
        if report.quality is not None:
            self.last_quality = report.quality
        # device-memory watermark gauges: one allocator-stats read per
        # cycle (no device sync, no transfer:
        # tests/test_cost_observatory.py); the span is what it costs
        with obs.tracer.span("TickTail/memory", tid="daemon"):
            self.last_memory = costmodel.stamp_device_memory(obs.metrics)
        return report

    def _quiet_wake_in(self) -> float | None:
        """Seconds until the spacing rule's quiet exit may end the wait,
        0.0 where it does now, `None` where it does not apply (see
        `QUIET_TICK_SHARE`; `_released` is where the last tick gave the
        feed lock up). It does when the feed has had no line in hand for
        `FEED_QUIET_NS` and has been quiet for `QUIET_TICK_SHARE` of the
        time since `_released`. Otherwise the answer is the first moment
        both could hold were the feed to stay quiet from now on: no wait
        ends at it sooner than it could, and a busy feed's wakes come
        ever further apart (where the feed is never quiet, each doubles
        the time since the lock came free)."""
        if self._released is None or not self.feed.pulse.connections:
            return None
        released, quiet_then = self._released
        now = time.perf_counter_ns()
        quiet, since = self.feed.pulse.quiet_total(now)
        # the gap open now, as it will count once it is FEED_QUIET_NS
        # long; a line in hand has its flush still to come
        quiet_at = now + FEED_QUIET_NS if since is None else (
            since + FEED_QUIET_NS
        )
        if since is not None and now < quiet_at:
            quiet += now - since
        short = QUIET_TICK_SHARE * (now - released) - (quiet - quiet_then)
        at = max(quiet_at, now + short / (1.0 - QUIET_TICK_SHARE))
        return max(at - now, 0) / 1e9

    def _wait_for_tick(self, started: float, duration: float,
                       check: float = 0.0) -> str:
        """The loop's wait between two ticks, in its own thread; returns
        what ended it. The last tick started at `started` and kept the
        feed lock for `duration`, `check` of it in anti-entropy checks
        (all `time.monotonic()` seconds: `tick_locked_s`, `tick_check_s`).
        The next one starts one interval after `started` at the latest
        ("interval"), and otherwise at the first moment from
        `check + DEMAND_TICK_SPACING × (duration − check)` after `started`
        at which a pod has entered the pending set ("demand"): a check is
        counted once. Before that moment a waiting pod starts it where
        the feed has gone quiet and left the lock unused ("quiet",
        `_quiet_wake_in`). A leader-election standby ticks on the interval
        only. `stop_event` ends the wait at once.

        The decision is recorded where it is made: the span's args carry
        `woke`, `locked_ms` (the `duration`), `check_ms` (the `check`),
        `since_start_ms` (the wait's end less `started`: on a demand wake
        at least the spacing above) and `held_ms` (the wait's end less the
        first ring since `started`, 0 where no pod waited), and
        `scheduler_tick_hold_ms{woke}` takes the last. After a tick with a
        check, `scheduler_tick_after_check_ms` takes `since_start_ms`."""
        interval = self.args.cycle_interval_s
        heartbeat = started + interval
        earliest = started + min(
            interval, check + DEMAND_TICK_SPACING * (duration - check)
        )
        standby = self.elector is not None and not self.elector.is_leader
        # with every tick's spans this tiles the thread's wall clock:
        # what is in neither is unaccounted
        with obs.tracer.span("Loop/sleep", tid="daemon",
                             locked_ms=duration * 1000,
                             check_ms=check * 1000) as said:
            woke = "interval"
            while not self.stop_event.is_set():
                now = time.monotonic()
                if now >= heartbeat:
                    break
                # to the next of the two moments, or a ring: a pod's
                # first arrival, a stop (a ring before `earliest` only
                # brings the loop round once more)
                until = earliest if now < earliest else heartbeat
                if self._pod_waiting and not standby:
                    if now >= earliest:
                        woke = "demand"
                        break
                    quiet_in = self._quiet_wake_in()
                    if quiet_in == 0.0:
                        woke = "quiet"
                        break
                    if quiet_in is not None:
                        until = min(until, now + quiet_in)
                self._doorbell.wait(until - now)
            woke_at = time.monotonic()
            held_ms = (
                (woke_at - self._pod_rang_at) * 1000
                if self._pod_waiting else 0.0
            )
            since_start_ms = (woke_at - started) * 1000
            said["woke"] = woke
            said["since_start_ms"] = since_start_ms
            said["held_ms"] = held_ms
        obs.metrics.observe_ms(obs.TICK_HOLD, held_ms, woke=woke)
        if check:
            obs.metrics.observe_ms(obs.TICK_AFTER_CHECK, since_start_ms)
        return woke

    def run(self):
        args = self.args

        def handle_sig(signum, frame):
            self.stop_event.set()

        signal.signal(signal.SIGTERM, handle_sig)
        signal.signal(signal.SIGINT, handle_sig)

        host, port = self.feed.address
        status = {"feed": f"{host}:{port}", "device": self.device}
        if self.grpc_feed is not None:
            status["grpc"] = f"{self.grpc_feed.host}:{self.grpc_feed.port}"
        if self.health:
            status["health"] = "http://%s:%d/healthz" % self.health.address
        print("daemon ready " + json.dumps(status), flush=True)

        try:
            woke = "interval"  # the first tick waits for nothing
            while not self.stop_event.is_set():
                started = time.monotonic()
                # lowered as the tick starts, not as it ends: a pod that
                # arrives while the tick runs is not in its batch
                self._doorbell.reset()
                self._pod_waiting = False
                obs.metrics.inc(obs.TICKS)
                obs.metrics.inc(obs.TICK_WAKEUPS, reason=woke)
                self.tick()
                self.ticks += 1
                # ticks, not scheduling cycles: a bounded run must also
                # terminate when leader-election standby skips every cycle
                if args.max_cycles and self.ticks >= args.max_cycles:
                    break
                woke = self._wait_for_tick(
                    started, self.tick_locked_s, self.tick_check_s
                )
        finally:
            # graceful shutdown (SIGTERM/SIGINT path): every artifact the
            # process owns is flushed through the crash-safe
            # `obs.atomic_write` discipline BEFORE the servers come down,
            # then the exit path returns rc 0 — a drained, checkpointed
            # daemon is indistinguishable from one that never ran
            if self.pipeline is not None:
                try:
                    # conflict-fence + deferred finalize of the last
                    # in-flight cycle: a drained pipeline leaves the
                    # store and the recorder exactly as the serial
                    # engine would
                    with self.feed.locked():
                        self.pipeline.close()
                except Exception as exc:
                    obs.logger.warning("pipeline flush failed: %s", exc)
            if self.args.record and self.args.record_dir:
                from scheduler_plugins_tpu.utils import flightrec

                try:
                    summary = flightrec.recorder.save(self.args.record_dir)
                    obs.logger.info("flight recorder bundle: %s", summary)
                except Exception as exc:
                    obs.logger.warning("flight recorder save failed: %s", exc)
            if self.args.trace and obs.tracer.enabled:
                try:
                    obs.tracer.stop()
                    obs.tracer.write(self.args.trace)  # atomic_write inside
                except Exception as exc:
                    obs.logger.warning("tracer flush failed: %s", exc)
            if self.engine is not None and self.args.checkpoint:
                try:
                    if self.engine.save_checkpoint(self.args.checkpoint):
                        obs.logger.info(
                            "resilience checkpoint written: %s",
                            self.args.checkpoint,
                        )
                except Exception as exc:
                    obs.logger.warning("checkpoint write failed: %s", exc)
            if self.tuner is not None and self.args.checkpoint:
                # currently-promoted weights + probation state persist
                # with the resilience checkpoint; restart resumes them
                try:
                    obs.atomic_write(
                        self._tuner_state_path(),
                        json.dumps(self.tuner.state_dict(), sort_keys=True)
                        + "\n",
                    )
                    obs.logger.info(
                        "tuner state written: %s", self._tuner_state_path()
                    )
                except Exception as exc:
                    obs.logger.warning("tuner state write failed: %s", exc)
            if self.elector is not None:
                self.elector.release()  # ReleaseOnCancel (idempotent)
            if self.health:
                self.health.stop()
            if self.grpc_feed is not None:
                self.grpc_feed.stop()
            self.feed.stop()
            print(json.dumps({
                "daemon_exit": True,
                "cycles": self.cycles,
                "bound_total": self.bound_total,
                "parked_cycles": self.parked_cycles,
                "degraded": (
                    self.resilience is not None and self.resilience.degraded
                ),
            }), flush=True)


def main(argv=None):
    args = parse_args(argv)
    obs.logger.info("compile cache: %s", compile_cache.configure())
    daemon = Daemon(args)
    daemon.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
