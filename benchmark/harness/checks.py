"""What decides `correct`. All of it runs after the window, outside every
timing, in the daemon's process (it reads the store under the feed lock).

- the client's counts agree with the daemon's;
- a capacity audit of the whole store, from the pod objects: no node over
  its allocatable CPU, memory or pod count; every bound pod on a known
  node; no pod bound twice; no pending pod was ever deleted;
- where the configuration keeps resident state: it equals the store;
- a probe: with the flight recorder on, one seeded wave of pods is given to
  the daemon whole, and every cycle the recorder then holds is compared bit
  for bit with the configuration's plain reference (`references/`), and
  checked for hard-constraint violations.
"""

from __future__ import annotations

import importlib
import json
import time

from harness import cluster_gen as gen


def client_counts(report: dict, ledger, warmed: int) -> list:
    """`warmed`: pods the harness itself had bound during set-up."""
    problems = []
    arrivals = report["arrivals"]
    expect_pods = report["prefilled"] + arrivals - report["deletes"]
    sync = report["sync"]
    if sync.get("pending") != 0:
        problems.append(f"{sync.get('pending')} pods pending after the drain")
    if sync.get("pods") != expect_pods:
        problems.append(
            f"store holds {sync.get('pods')} pods, client expects {expect_pods}"
        )
    bound = report["healthz"]["bound_total"] - report["bound_base"]
    if bound != arrivals:
        problems.append(f"/healthz bound {bound} of {arrivals} arrivals")
    if ledger.pods_bound - warmed != arrivals:
        problems.append(
            f"ledger bound {ledger.pods_bound - warmed} of {arrivals}"
        )
    if ledger.pods_deleted:
        problems.append(f"{ledger.pods_deleted} pending pods were deleted")
    if report["refused"]:
        problems.append(f"{report['refused']} events refused")
    health = report["healthz"]
    if health["parked_cycles"] or health["degraded"]:
        problems.append(
            f"parked_cycles {health['parked_cycles']}, "
            f"degraded {health['degraded']}"
        )
    stamps = ledger.bind_stamps
    if len({uid for uid, _ in stamps}) != len(stamps):
        problems.append("a pod was bound twice")
    return problems


def capacity_audit(cluster) -> list:
    """Walks every pod object of the store (call under the feed lock)."""
    used: dict = {}
    problems = []
    for pod in cluster.pods.values():
        if pod.node_name is None:
            continue
        if pod.node_name not in cluster.nodes:
            problems.append(f"{pod.uid} bound to unknown node {pod.node_name}")
            continue
        row = used.setdefault(pod.node_name, [0, 0, 0])
        for container in pod.containers:
            row[0] += container.requests.get("cpu", 0)
            row[1] += container.requests.get("memory", 0)
        row[2] += 1
    over = 0
    for name, (cpu, mem, pods) in used.items():
        alloc = cluster.nodes[name].allocatable
        if cpu > alloc["cpu"] or mem > alloc["memory"] or pods > alloc["pods"]:
            over += 1
    if over:
        problems.append(f"{over} nodes hold more than their allocatable")
    return problems


def resident_state(daemon) -> list:
    """The resident node tensors against the store, and how they got
    there: one rebase (the cold build), no anti-entropy divergence."""
    engine = daemon.engine
    problems = []
    with daemon.feed.locked():
        # the last cycle's binds are still in the delta sink
        engine.refresh(daemon.cluster, [], now_ms=int(time.time() * 1000))
        divergence = engine.verify(daemon.cluster)
    if divergence is not None:
        problems.append(f"resident state differs from the store: {divergence}")
    if engine.rebases != 1:
        problems.append(f"{engine.rebases} rebases, expected the cold build only")
    if engine.antientropy_divergences:
        problems.append(
            f"{engine.antientropy_divergences} anti-entropy divergences"
        )
    return problems


def reference_inputs(snap) -> dict:
    """The recorded snapshot as the plain arrays a reference takes."""
    import numpy as np

    x = {
        "alloc": snap.nodes.alloc, "requested": snap.nodes.requested,
        "capacity": snap.nodes.capacity, "node_mask": snap.nodes.mask,
        "req": snap.pods.req, "pod_mask": snap.pods.mask,
        "gated": snap.pods.gated,
        "predicted_cpu_millis": snap.pods.predicted_cpu_millis,
    }
    if snap.metrics is not None:
        for name in ("cpu_tlp", "cpu_avg", "cpu_std", "mem_avg", "mem_std",
                     "cpu_tlp_valid", "cpu_valid", "mem_valid",
                     "missing_cpu_millis"):
            x[name] = getattr(snap.metrics, name)
    return {name: np.array(value) for name, value in x.items()}


def whole_wave(daemon, cell, seed: int, size: int, prefix: str,
               timeout_s: float = 600.0) -> tuple:
    """Give the daemon `size` seeded pods at once, under the feed lock, so
    that one cycle solves them together (in the pod bucket of `size`), and
    wait until they are bound. Returns (their names, how many are not)."""
    from scheduler_plugins_tpu.bridge.feed import apply_event

    rng = gen.stream(seed, f"{prefix}/{size}")
    names = [f"{prefix}-{size}-{i:06d}" for i in range(size)]
    with daemon.feed.locked():
        for i, name in enumerate(names):
            cpu, mem = gen.draw_request(
                rng, cell.config["cluster"]["pod_requests"]
            )
            apply_event(
                daemon.cluster,
                json.loads(gen.pod_line(name, 1_000_000_000 + i, cpu, mem)),
                rv_table=daemon.feed.rv_table,
            )
    deadline = time.monotonic() + timeout_s
    while True:
        with daemon.feed.locked():
            left = sum(
                1 for name in names
                if daemon.cluster.pods[f"default/{name}"].node_name is None
            )
        if not left or time.monotonic() > deadline:
            return names, left
        time.sleep(0.05)


def warm_pod_bucket(daemon, cell, seed: int, size: int) -> None:
    """Set-up: make the daemon solve one batch of `size` pods, so that the
    program for that pod bucket is compiled, or loaded from the cache,
    before the window can need it; then take the pods away again."""
    from scheduler_plugins_tpu.bridge.feed import apply_event

    names, left = whole_wave(daemon, cell, seed, size, "warm")
    if left:
        raise RuntimeError(f"{left} of {size} warm-up pods never bound")
    with daemon.feed.locked():
        for name in names:
            apply_event(daemon.cluster, json.loads(gen.delete_line(name)),
                        rv_table=daemon.feed.rv_table)


def probe(daemon, cell, seed: int, size: int) -> dict:
    """One seeded wave of `size` pods, recorded and compared."""
    import numpy as np

    from scheduler_plugins_tpu.tuning import gates
    from scheduler_plugins_tpu.utils import flightrec

    reference = importlib.import_module(
        f"references.{cell.config['reference']}"
    )
    flightrec.recorder.start(capacity=8)
    flightrec.recorder.profile_config = cell.config["profile"]
    try:
        _names, left = whole_wave(daemon, cell, seed, size, "probe")
        records = [
            r for r in flightrec.recorder.records() if "outputs" in r.manifest
        ]
    finally:
        flightrec.recorder.stop()
    out = {"size": size, "cycles": len(records), "unbound": left,
           "mismatches": 0, "hard_violations": 0, "placed": 0,
           "unserved_cycles": 0, "reference_s": 0.0}
    for rec in records:
        snap = flightrec.unpack_pytree(rec.manifest["snapshot"], rec.blobs)
        got = {
            name: flightrec.unpack_pytree(spec, rec.blobs)
            for name, spec in rec.manifest["outputs"].items()
            if name != "mode"
        }
        t0 = time.perf_counter()
        want = reference.solve(reference_inputs(snap), cell.config["profile"])
        out["reference_s"] += time.perf_counter() - t0
        for name, ref in want.items():
            if got[name].shape != ref.shape:
                out["mismatches"] += ref.size
            else:
                out["mismatches"] += int((np.asarray(got[name]) != ref).sum())
        violations = gates.hard_violations(
            snap, got["assignment"], got["wait"]
        )
        out["hard_violations"] += int(sum(violations.values()))
        out["placed"] += int((got["assignment"] >= 0).sum())
        if rec.manifest.get("serve") is None:
            out["unserved_cycles"] += 1
    return out


def probe_problems(result: dict, resident: bool) -> list:
    problems = []
    if result["unbound"]:
        problems.append(f"{result['unbound']} probe pods never bound")
    if not result["cycles"]:
        problems.append("the flight recorder holds no probe cycle")
    if result["placed"] != result["size"]:
        problems.append(
            f"recorded cycles placed {result['placed']} of {result['size']}"
        )
    if result["mismatches"]:
        problems.append(
            f"{result['mismatches']} slots differ from the plain reference"
        )
    if result["hard_violations"]:
        problems.append(f"{result['hard_violations']} hard violations")
    if resident and result["unserved_cycles"]:
        problems.append(
            f"{result['unserved_cycles']} probe cycles were not served from "
            "resident state"
        )
    return problems
