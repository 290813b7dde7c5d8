"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                 one TPU chip, phases A, B, C
    python chip_smoke.py --devices 4     four chips, adds phase D
    python chip_smoke.py --rehearse-cpu  the same code at a tiny size on
                                         the CPU backend (no chip check;
                                         every line says platform: cpu)

One process drives the system's main paths through the entry points a user
calls, at the size an operator would call real, and checks every result by
the repo's own means. It is the only process that touches the device and
starts no child.

- Phase A, the served path: the daemon, built as `python -m
  scheduler_plugins_tpu --serve` builds it, fed over the TCP feed with
  5,000 nodes (Kubernetes' documented per-cluster limit; upstream
  scheduler_perf SchedulingBasic 5000Nodes), 1,000 pods, then 10,000 more
  in four fenced waves. Every pod must bind; every solved cycle must come
  from resident state and be bit-equal to the numpy twin
  `resilience.host_sequential_solve` on the recorded cycle inputs.
- Phase B, the throughput path: the north-star chunk pipeline
  (`parallel.pipeline.north_star_chunk_solver` on
  `models.problems.north_star_problem`), 10,240 nodes x 102,400 pods.
- Phase C, every plugin profile once at its BASELINE shape:
  `models.problems.config_problem` configs 2-5 through `Scheduler.solve`,
  on the chip and on the host CPU backend in this same process, bit-equal.
- Phase D (`--devices 4` only): the sharded wave solve on a real four-chip
  node mesh, lax collectives and compiled Pallas ring kernels, bit-equal to
  phase B's one-device placements.

Without an accelerator the script exits non-zero before doing any work. A
failed check raises; nothing turns a failure into a field of a line that
still ends in exit code 0. The last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import signal
import sys
import tempfile
import threading
import time
import urllib.request

#: problem sizes: what an operator would call real, and the rehearsal.
#: `interval_s` is the daemon's `--cycle-interval-s` in phase A. A wave is
#: sent after an idle tick; its first pod starts a tick (the loop is paced
#: by demand), so a wave is solved in a few cycles, whatever pod buckets
#: they fall in (2,500 acknowledged events take a few hundred ms over
#: loopback). Every recorded cycle is checked; `shapes_as_planned` says
#: whether each wave came out as one cycle.
SIZES = {
    "real": {
        "serve": dict(n_nodes=5000, init_pods=1000, waves=4, wave_pods=2500,
                      interval_s=2.0),
        "north_star": None,  # problems.NORTH_STAR_SHAPE
        "profiles": {2: None, 3: None, 4: None, 5: None},  # BASELINE shapes
    },
    "rehearsal": {
        "serve": dict(n_nodes=48, init_pods=12, waves=4, wave_pods=24,
                      interval_s=0.25),
        "north_star": dict(n_nodes=64, n_pods=512, chunk=128),
        "profiles": {
            2: dict(n_nodes=64, n_pods=32),
            3: dict(n_nodes=32, n_pods=16, zones=4),
            4: dict(n_gangs=2, gang_size=4, n_nodes=16),
            5: dict(n_nodes=32, n_pods=16),
        },
    },
}

ALLOCATABLE_PROFILE = (
    "plugins:\n"
    "  - NodeResourcesAllocatable\n"
    "pluginConfig:\n"
    "  - name: NodeResourcesAllocatable\n"
    "    args:\n"
    "      mode: Least\n"
)


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4 adds phase D on a four-chip node mesh and "
                         "fails when fewer than four devices are present")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same phases at a tiny size on the CPU "
                         "backend; refuses to run on anything else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the phase-A client's node and pod sizes")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# per-phase accounting
# ---------------------------------------------------------------------------


class Phase:
    """Wall, compile and memory accounting of one phase, printed as ONE
    JSON line when the phase's body returned without raising."""

    def __init__(self, name: str, stamp: dict):
        self.name = name
        self.stamp = stamp
        self.detail: dict = {}

    def __enter__(self):
        from scheduler_plugins_tpu.utils import observability as obs

        self._obs = obs
        self._scope = obs.metrics.scoped()
        self._compile_ms0 = self._compile_ms()
        self._t0 = time.perf_counter()
        return self

    def _compile_ms(self) -> dict:
        """Watched program -> compile ms so far (obs.compile_watch)."""
        prefix = self._obs.JIT_COMPILE + '{program="'
        return {
            key[len(prefix):-2]: h["sum"]
            for key, h in self._obs.metrics.histograms().items()
            if key.startswith(prefix)
        }

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False  # the failure propagates: no line, non-zero exit
        from scheduler_plugins_tpu.obs import costmodel

        obs = self._obs
        delta = self._scope.delta()
        compile_s = {
            program: round((ms - self._compile_ms0.get(program, 0.0)) / 1e3, 3)
            for program, ms in self._compile_ms().items()
            if ms > self._compile_ms0.get(program, 0.0)
        }
        print(json.dumps({
            "phase": self.name,
            "ok": True,
            **self.stamp,
            "wall_s": round(time.perf_counter() - self._t0, 3),
            # trace + lower + backend compile of the watched programs
            # (obs.compile_watch): seconds, per program, and the calls
            # that compiled
            "compile_s": round(sum(compile_s.values()), 3),
            "compile_s_by_program": compile_s,
            "compile_count": sum(
                v for k, v in delta.items()
                if k.startswith(obs.JIT_CACHE_MISS)
            ),
            # every backend compile of the phase, watched or not, and how
            # many of them the persistent cache answered
            "cache_requests": delta.get(obs.COMPILE_CACHE_REQUESTS, 0),
            "cache_hits": delta.get(obs.COMPILE_CACHE_HITS, 0),
            "peak_bytes_in_use":
                costmodel.device_memory_block()["peak_bytes_in_use"],
            **self.detail,
        }), flush=True)
        return False


# ---------------------------------------------------------------------------
# phase A: the served daemon path
# ---------------------------------------------------------------------------


def _serve_events(shape: dict, seed: int):
    """(node events, [pod-event waves]) of the seeded client: three node
    SKUs, pod requests drawn from the seed."""
    import numpy as np

    gib = 1 << 30
    rng = np.random.default_rng(seed)
    skus = [(4000, 16 * gib), (8000, 32 * gib), (16000, 64 * gib)]
    picks = rng.integers(0, len(skus), size=shape["n_nodes"])
    nodes = [
        {
            "op": "upsert_node", "name": f"node-{i:05d}",
            "allocatable": {
                "cpu": skus[k][0], "memory": skus[k][1], "pods": 110,
            },
        }
        for i, k in enumerate(picks)
    ]
    sizes = [shape["init_pods"]] + [shape["wave_pods"]] * shape["waves"]
    waves, serial = [], 0
    for size in sizes:
        cpus = rng.integers(100, 1000, size=size)
        mems = rng.integers(128 << 20, 2 * gib, size=size)
        wave = []
        for c, m in zip(cpus, mems):
            wave.append({
                "op": "upsert_pod", "name": f"pod-{serial:06d}",
                "creation_ms": serial,
                "requests": {"cpu": int(c), "memory": int(m)},
            })
            serial += 1
        waves.append(wave)
    return nodes, waves


def _serve_client(status: dict, nodes, waves, box: dict) -> None:
    """The client's side of phase A, on its own thread: events over the
    real TCP wire, binds observed on /healthz, SIGTERM at the end — what
    an agent and an operator would do from outside the process."""
    from scheduler_plugins_tpu.bridge.feed import FeedClient

    def healthz() -> dict:
        with urllib.request.urlopen(status["health"], timeout=10) as resp:
            return json.loads(resp.read())

    def wait_for(predicate, what: str, timeout_s: float = 900.0) -> dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            health = healthz()
            if predicate(health):
                return health
            time.sleep(0.05)
        raise SmokeFailure(f"phase A: timed out waiting for {what}")

    try:
        host, port = status["feed"].rsplit(":", 1)
        client = FeedClient(host, int(port))
        try:
            def send(events, expect_pods: int):
                for event in events:
                    ack = client.send(event)
                    check(ack.get("ok"), f"feed refused {event}: {ack}")
                ack = client.send({"op": "sync"})
                check(
                    ack.get("ok") and ack["nodes"] == len(nodes)
                    and ack["pods"] == expect_pods,
                    f"sync fence disagrees: {ack}",
                )

            send(nodes, 0)
            sent = 0
            box["send_s"] = []
            for wave in waves:
                # start a wave from an idle daemon: nothing of the wave
                # before it is still pending
                seen = healthz()["cycles"]
                wait_for(lambda h: h["cycles"] > seen, "an idle tick")
                t0 = time.perf_counter()
                sent += len(wave)
                send(wave, sent)
                box["send_s"].append(round(time.perf_counter() - t0, 3))
                wait_for(
                    lambda h: h["bound_total"] >= sent,
                    f"{sent} binds",
                )
            box["healthz"] = healthz()
        finally:
            client.close()
    except BaseException as exc:  # re-raised by the main thread
        box["error"] = exc
    finally:
        os.kill(os.getpid(), signal.SIGTERM)


def phase_serve(phase: Phase, shape: dict, seed: int) -> None:
    from scheduler_plugins_tpu.__main__ import Daemon
    from scheduler_plugins_tpu.__main__ import parse_args as daemon_args
    from scheduler_plugins_tpu.obs import ledger as podledger
    from scheduler_plugins_tpu.resilience import hostsolve
    from scheduler_plugins_tpu.tuning import gates
    from scheduler_plugins_tpu.utils import flightrec, observability as obs
    from scheduler_plugins_tpu.utils.intmath import bucket_size

    nodes, waves = _serve_events(shape, seed)
    total = sum(len(w) for w in waves)
    with tempfile.TemporaryDirectory() as tmp:
        profile = os.path.join(tmp, "profile.yaml")
        with open(profile, "w") as f:
            f.write(ALLOCATABLE_PROFILE)
        daemon = Daemon(daemon_args([
            "--profile", profile, "--serve", "--record", "64",
            "--cycle-interval-s", str(shape["interval_s"]),
        ]))
    host, port = daemon.feed.address
    status = {
        "feed": f"{host}:{port}",
        "health": "http://%s:%d/healthz" % daemon.health.address,
    }
    box: dict = {}
    client = threading.Thread(
        target=_serve_client, args=(status, nodes, waves, box),
        name="smoke-client", daemon=True,
    )
    out = io.StringIO()
    previous = {
        sig: signal.getsignal(sig) for sig in (signal.SIGTERM, signal.SIGINT)
    }
    try:
        # `run` installs the same handler; this one covers a client that
        # fails before the loop is up
        signal.signal(signal.SIGTERM, lambda *_: daemon.stop_event.set())
        client.start()
        with contextlib.redirect_stdout(out):
            daemon.run()  # until the client's SIGTERM
    finally:
        for sig, handler in previous.items():
            signal.signal(sig, handler)
        podledger.LEDGER.stop()
    client.join(timeout=30)
    check(not client.is_alive(), "phase A: client thread did not finish")
    if "error" in box:
        raise box["error"]

    ready, exit_line = None, None
    for line in out.getvalue().splitlines():
        if line.startswith("daemon ready "):
            ready = json.loads(line[len("daemon ready "):])
        elif line.startswith("{"):
            exit_line = json.loads(line)
    check(ready is not None and ready["device"] == daemon.device,
          f"daemon ready line lacks the device: {ready}")
    check(
        exit_line is not None and exit_line.get("daemon_exit") is True
        and exit_line["bound_total"] == total
        and exit_line["parked_cycles"] == 0
        and exit_line["degraded"] is False,
        f"daemon exit line not clean: {exit_line}",
    )
    health = box["healthz"]
    check(health["device"] == daemon.device, "/healthz lacks the device")
    check(health["bound_total"] == total, f"bound {health['bound_total']}")
    check(health["parked_cycles"] == 0 and health["degraded"] is False,
          f"parked/degraded: {health}")
    bound = sum(
        1 for p in daemon.cluster.pods.values() if p.node_name is not None
    )
    check(bound == total, f"store holds {bound} bound pods of {total}")

    # every solved cycle: served from resident state, bit-equal to the
    # numpy twin on the recorded inputs, no hard constraint violated
    records = [
        r for r in flightrec.recorder.records() if "outputs" in r.manifest
    ]
    flightrec.recorder.stop()
    check(records and all(r.complete for r in records),
          "flight recorder holds no complete cycle")
    placed, modes, shapes, generations = 0, [], set(), []
    for rec in records:
        serve = rec.manifest.get("serve")
        check(serve is not None,
              f"cycle {rec.seq} was not served from resident state")
        modes.append(serve["mode"])
        generations.append(serve["generation"])
        snap = flightrec.unpack_pytree(rec.manifest["snapshot"], rec.blobs)
        check(hostsolve.supports(daemon.scheduler, snap),
              "the numpy twin does not cover this snapshot")
        got = {
            name: flightrec.unpack_pytree(spec, rec.blobs)
            for name, spec in rec.manifest["outputs"].items()
            if name != "mode"
        }
        want = dict(zip(
            ("assignment", "admitted", "wait", "failed_plugin"),
            hostsolve.host_sequential_solve(daemon.scheduler, snap),
        ))
        for name, ref in want.items():
            check(
                got[name].shape == ref.shape and (got[name] == ref).all(),
                f"cycle {rec.seq}: {name} differs from the numpy twin in "
                f"{int((got[name] != ref).sum())} of {ref.size} slots",
            )
        violations = gates.hard_violations(
            snap, got["assignment"], got["wait"]
        )
        check(not any(violations.values()),
              f"cycle {rec.seq}: hard violations {violations}")
        placed += int((got["assignment"] >= 0).sum())
        shapes.add((snap.num_nodes, snap.num_pods))
    check(placed == total, f"recorded cycles placed {placed} of {total}")
    check(generations == sorted(set(generations)),
          f"serve generation did not advance: {generations}")
    engine = daemon.engine
    check(engine.gang_fallbacks == 0, "gang fallbacks")
    # the one rebase is the cold build of the resident base at the first
    # served cycle; every later cycle applies O(changed) deltas
    check(engine.rebases == modes.count("rebase") == 1,
          f"rebases {engine.rebases}, cycle modes {modes}")
    check(engine.antientropy_divergences == 0, "anti-entropy divergence")
    # the last cycle's binds are still in the delta sink (an idle tick
    # drains nothing): absorb them, then digest resident against store
    engine.refresh(daemon.cluster, [], now_ms=int(time.time() * 1000))
    divergence = engine.verify(daemon.cluster)
    check(divergence is None, f"engine.verify: {divergence}")

    planned = {
        (bucket_size(len(nodes)), bucket_size(len(w))) for w in waves
    }
    # the longest single solve call's trace + compile: what the solve
    # watchdog's deadline has to outlast on a cold cache
    solve_hist = obs.metrics.histograms().get(
        obs.JIT_COMPILE + '{program="solve"}'
    )
    phase.detail = {
        "nodes": len(nodes), "pods_bound": total,
        "solved_cycles": len(records), "ticks": daemon.ticks,
        "cycle_modes": {m: modes.count(m) for m in sorted(set(modes))},
        "serve_generation": engine.generation, "rebases": engine.rebases,
        "bit_equal_to_host_twin": True, "hard_violations": 0,
        "engine_verify": None,
        "distinct_shapes": sorted(shapes),
        "shapes_as_planned": shapes == planned,
        "solve_compile_max_s": (
            round(solve_hist["max"] / 1e3, 3) if solve_hist else 0.0
        ),
        "wave_send_s": box["send_s"],
        "daemon_exit": exit_line,
    }


# ---------------------------------------------------------------------------
# phase B: the north-star chunk pipeline
# ---------------------------------------------------------------------------


def phase_north_star(phase: Phase, shape: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import (
        north_star_chunk_solver,
        run_chunk_pipeline,
    )
    from scheduler_plugins_tpu.tuning import gates
    from scheduler_plugins_tpu.utils import observability as obs

    n_pods, chunk = shape["n_pods"], shape["chunk"]
    _cluster, snap, _meta, weights, raw, padded = problems.north_star_problem(
        shape["n_nodes"], n_pods, chunk
    )
    solve_chunk = north_star_chunk_solver()
    req_np = np.asarray(snap.pods.req)
    chunk_inputs = problems.pod_chunks(snap, chunk)
    free0 = np.asarray(free_capacity(snap.nodes.alloc, snap.nodes.requested))

    # warm-up chunk: compiles the one chunk shape, donates its own carry.
    # Inputs are staged as the pipeline stages them: a committed array and
    # a host buffer are different jit cache keys
    t0 = time.perf_counter()
    (a, _), _ = solve_chunk(
        raw, snap.nodes.mask,
        *(jax.device_put(x) for x in chunk_inputs[0]), jnp.asarray(free0),
    )
    np.asarray(a)
    warmup_s = time.perf_counter() - t0

    window = obs.metrics.scoped()
    t0 = time.perf_counter()
    results, free, _done_s, _timeline = run_chunk_pipeline(
        solve_chunk, (raw, snap.nodes.mask), chunk_inputs, jnp.asarray(free0)
    )
    pipeline_s = time.perf_counter() - t0
    in_window = {
        k: v for k, v in window.delta().items()
        if k.startswith((obs.JIT_CACHE_MISS, obs.COMPILE_CACHE_REQUESTS))
    }
    check(not in_window, f"compiles after the warm-up chunk: {in_window}")

    assignment = np.concatenate([np.asarray(a) for a, _ in results])
    placed = int((assignment[:n_pods] >= 0).sum())
    check(placed == n_pods, f"placed {placed} of {n_pods}")
    check((assignment[n_pods:] == -1).all(), "a padding pod was placed")
    violations = gates.hard_violations(
        snap, assignment, np.zeros(assignment.shape[0], bool)
    )
    check(not any(violations.values()), f"capacity audit: {violations}")
    # the donated carry threaded all chunks: what came out of the last one
    # equals an independent replay of every placement against free0
    node_mask = np.asarray(snap.nodes.mask)
    expect = np.where(node_mask[:, None], free0, 0)
    ok = assignment >= 0
    np.subtract.at(
        expect, assignment[ok], gates.pod_fit_demand_np(req_np)[ok]
    )
    check((np.asarray(free) == expect).all(),
          "the donated free carry does not match the replayed placements")

    phase.detail = {
        "nodes": shape["n_nodes"], "pods": n_pods, "chunk": chunk,
        "chunks": len(chunk_inputs), "placed": placed,
        "hard_violations": 0, "in_window_compiles": 0,
        "carry_matches_replay": True,
        "waves": int(sum(int(s["waves"]) for _, s in results)),
        "warmup_chunk_s": round(warmup_s, 3),
        "pipeline_s": round(pipeline_s, 3),
    }
    return {
        "snap": snap, "weights": weights, "raw": raw,
        "assignment": assignment,
    }


# ---------------------------------------------------------------------------
# phase C: every plugin profile, chip == host CPU
# ---------------------------------------------------------------------------


def phase_profiles(phase: Phase, shapes: dict) -> None:
    import jax
    import numpy as np

    from scheduler_plugins_tpu.framework import Profile, Scheduler
    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.tuning import gates

    fields = ("assignment", "admitted", "wait", "failed_plugin")
    cpu = jax.devices("cpu")[0]
    configs = {}
    for config, shape in shapes.items():
        cluster, plugins, detail = problems.config_problem(config, shape=shape)
        scheduler = Scheduler(Profile(plugins=plugins))
        pending = scheduler.sort_pending(cluster.pending_pods(), cluster)

        def solve(device):
            snap, meta = cluster.snapshot(pending, now_ms=0)
            scheduler.prepare(meta, cluster)
            t0 = time.perf_counter()
            result = scheduler.solve(snap)
            out = {f: np.asarray(getattr(result, f)) for f in fields}
            elapsed = time.perf_counter() - t0
            check(result.assignment.devices() == {device},
                  f"config {config} solved on "
                  f"{result.assignment.devices()}, not {device}")
            return snap, out, elapsed

        snap, on_device, device_s = solve(jax.devices()[0])
        with jax.default_device(cpu):
            _, on_host, host_s = solve(cpu)
        for f in fields:
            check(
                on_device[f].shape == on_host[f].shape
                and (on_device[f] == on_host[f]).all(),
                f"config {config}: {f} on the device differs from the "
                f"host CPU backend in "
                f"{int((on_device[f] != on_host[f]).sum())} slots",
            )
        violations = gates.hard_violations(
            snap, on_device["assignment"], on_device["wait"]
        )
        check(not any(violations.values()),
              f"config {config}: hard violations {violations}")
        configs[str(config)] = {
            "detail": detail,
            "placed": int((on_device["assignment"] >= 0).sum()),
            "pods": len(pending),
            "device_equals_host_cpu": True, "hard_violations": 0,
            "first_solve_s": round(device_s, 3),
            "host_cpu_first_solve_s": round(host_s, 3),
        }
    phase.detail = {"configs": configs}


# ---------------------------------------------------------------------------
# phase D: four chips
# ---------------------------------------------------------------------------


def phase_four_chips(phase: Phase, shape: dict, north_star: dict,
                     interpret: bool) -> None:
    import jax
    import numpy as np

    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.mesh import make_node_mesh
    from scheduler_plugins_tpu.parallel.solver import (
        rank_order_inputs,
        sharded_wave_chunk_solver,
        sharded_wave_solve,
    )

    snap, weights = north_star["snap"], north_star["weights"]
    reference = north_star["assignment"]
    chunk, devices = shape["chunk"], 4
    mesh = make_node_mesh(devices)
    check(len(set(mesh.devices.flat)) == devices, "mesh has repeated devices")

    # the lax-collectives build, through the solver's own entry point
    assignment, _admitted, _wait, stats = sharded_wave_solve(
        snap, mesh, weights, chunk=chunk, rescue_window=256,
        collect_stats=True,
    )
    lax = np.asarray(assignment)
    check((lax == reference).all(),
          f"sharded wave placements differ from the one-device path in "
          f"{int((lax != reference).sum())} slots")
    check(not np.asarray(stats["pallas_sites"]).any(),
          "the lax build reports Pallas election sites")

    # the Pallas build of the same chunk program: compiled ring kernels on
    # a TPU, never interpreted there and never replaced by lax
    solver = sharded_wave_chunk_solver(
        mesh, snap.num_nodes, rescue_window=256,
        use_pallas=True, pallas_interpret=interpret,
    )
    node_ids, rank_free = rank_order_inputs(
        north_star["raw"],
        free_capacity(snap.nodes.alloc, snap.nodes.requested),
        snap.nodes.mask, devices,
    )
    parts, sites = [], None
    with jax.set_mesh(mesh):
        for lo in range(0, snap.num_pods, chunk):
            (a, chunk_stats), rank_free = solver(
                node_ids, snap.pods.req[lo:lo + chunk],
                snap.pods.mask[lo:lo + chunk], rank_free,
            )
            parts.append(np.asarray(a))
            sites = np.asarray(chunk_stats["pallas_sites"])
    pallas = np.concatenate(parts)
    check((pallas == reference).all(),
          f"Pallas-election placements differ from the one-device path in "
          f"{int((pallas != reference).sum())} slots")
    check(sites.all(), f"an election site gave way to lax: {sites}")
    # every device must hold a shard of the resident carry
    holders = {s.device for s in rank_free.addressable_shards}
    check(len(holders) == devices,
          f"the carry lives on {len(holders)} of {devices} devices")
    rows = {s.data.shape[0] for s in rank_free.addressable_shards}
    check(rows == {rank_free.shape[0] // devices},
          f"uneven carry shards: {rows}")

    phase.detail = {
        "mesh": {"nodes": devices},
        "lax_equals_one_device": True,
        "pallas_equals_one_device": True,
        "pallas_interpret": interpret,
        "pallas_sites": [bool(x) for x in sites],
        "carry_sharding": str(rank_free.sharding),
        "carry_shard_devices": sorted(str(d) for d in holders),
    }


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse_cpu:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse-cpu runs on the CPU backend "
                  f"only, found {platform}", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.devices:
        print(f"chip_smoke: --devices {args.devices} asked for, "
              f"{len(devices)} present", file=sys.stderr)
        return 2

    from scheduler_plugins_tpu.models import problems
    from scheduler_plugins_tpu.obs import costmodel
    from scheduler_plugins_tpu.parallel import vmem
    from scheduler_plugins_tpu.utils import compile_cache

    device = costmodel.device_identity()
    row = (
        vmem.target_for_device_kind(device["device_kind"])
        if platform == "tpu" else None
    )
    stamp = {
        "platform": device["platform"], "device_kind": device["device_kind"],
        "device_count": device["count"], "hardware_row": row,
    }
    sizes = SIZES["rehearsal" if args.rehearse_cpu else "real"]
    cache_dir = compile_cache.configure()
    print(json.dumps({
        **stamp, "compile_cache_dir": cache_dir,
        "compile_cache_entries": (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        ),
        "size": "rehearsal" if args.rehearse_cpu else "real",
        "seed": args.seed,
    }), flush=True)

    with Phase("A_served_daemon", stamp) as phase:
        phase_serve(phase, sizes["serve"], args.seed)
    north_star_shape = sizes["north_star"] or problems.NORTH_STAR_SHAPE
    with Phase("B_north_star_pipeline", stamp) as phase:
        north_star = phase_north_star(phase, north_star_shape)
    with Phase("C_plugin_profiles", stamp) as phase:
        phase_profiles(phase, sizes["profiles"])
    if args.devices == 4:
        with Phase("D_four_chip_wave", stamp) as phase:
            phase_four_chips(
                phase, north_star_shape, north_star,
                interpret=platform != "tpu",
            )

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"], "kind": device["device_kind"],
            "count": device["count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
