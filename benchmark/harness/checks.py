"""What decides `correct`. All of it runs after the window, outside every
timing, in the daemon's process (it reads the store under the feed lock).

- the client's counts agree with the daemon's: what it expected bound is
  bound, what it expected to stay pending is all that is pending; no pod
  bound twice; no pending pod was ever deleted;
- the configuration's audits (`audits/<name>.py`, its `"audits"` key, else
  `capacity`), each over the whole store, from its objects;
- wherever the engine has owned a cycle: resident state equals the store;
- a probe: with the flight recorder on, one seeded wave of the
  population's units is given to the daemon whole, and every cycle the
  recorder then holds is compared bit for bit with the configuration's plain
  reference (`references/`), and checked for hard-constraint violations.
"""

from __future__ import annotations

import dataclasses
import json
import time

from harness import spec

#: the names the first references were written to, beside the dotted paths
ALIASES = {
    "alloc": "nodes.alloc", "requested": "nodes.requested",
    "capacity": "nodes.capacity", "node_mask": "nodes.mask",
    "req": "pods.req", "pod_mask": "pods.mask", "gated": "pods.gated",
    "predicted_cpu_millis": "pods.predicted_cpu_millis",
}
METRIC_ALIASES = (
    "cpu_tlp", "cpu_avg", "cpu_std", "mem_avg", "mem_std", "cpu_tlp_valid",
    "cpu_valid", "mem_valid", "missing_cpu_millis",
)


def client_counts(report: dict, ledger, warmed: tuple) -> list:
    """`warmed`: (pods bound, pending pods deleted) by the harness's own
    waves during set-up. `arrivals` are the pods the client sent to be
    bound, `held` those its population says the guarantees keep pending."""
    problems = []
    arrivals, held = report["arrivals"], report["held"]
    warm_bound, warm_deleted = warmed
    expect_pods = report["prefilled"] + arrivals + held - report["deletes"]
    sync = report["sync"]
    if sync.get("pending") != held:
        problems.append(
            f"{sync.get('pending')} pods pending after the drain, "
            f"{held} expected"
        )
    if sync.get("pods") != expect_pods:
        problems.append(
            f"store holds {sync.get('pods')} pods, client expects {expect_pods}"
        )
    bound = report["healthz"]["bound_total"] - report["bound_base"]
    if bound != arrivals:
        problems.append(f"/healthz bound {bound} of {arrivals} arrivals")
    if ledger.pods_bound - warm_bound != arrivals:
        problems.append(
            f"ledger bound {ledger.pods_bound - warm_bound} of {arrivals}"
        )
    if ledger.pods_deleted != warm_deleted:
        problems.append(
            f"{ledger.pods_deleted - warm_deleted} pending pods were deleted"
        )
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


def audits(cell, cluster) -> list:
    """The configuration's audits by name, each `audit(cluster) ->
    [problem, ...]` over the store's objects (call under the feed lock)."""
    problems = []
    for name in cell.config.get("audits", ["capacity"]):
        found = spec.load_module("audits", name).audit(cluster)
        problems += [f"{name}: {problem}" for problem in found]
    return problems


def resident_state(daemon, declared: bool) -> list:
    """Whenever the engine has owned a cycle: no anti-entropy divergence on
    the way, and, where it still owns the store's state (it refreshes to a
    snapshot; an engine that has fallen back keeps columns it does not
    serve from), the resident tensors against the store. Where the
    configuration declares resident state, they are compared in any case,
    and how they got there counts too: one rebase, the cold build."""
    engine = daemon.engine
    if engine is None or not engine.rebases:
        if declared:
            return ["the configuration keeps resident state; the engine "
                    "never owned a cycle"]
        return []
    problems = []
    with daemon.feed.locked():
        # the last cycle's binds are still in the delta sink
        owns = engine.refresh(
            daemon.cluster, [], now_ms=int(time.time() * 1000)
        ) is not None
        divergence = (
            engine.verify(daemon.cluster) if owns or declared else None
        )
    if divergence is not None:
        problems.append(f"resident state differs from the store: {divergence}")
    if declared and engine.rebases != 1:
        problems.append(f"{engine.rebases} rebases, expected the cold build only")
    if engine.antientropy_divergences:
        problems.append(
            f"{engine.antientropy_divergences} anti-entropy divergences"
        )
    return problems


def reference_inputs(snap) -> dict:
    """The recorded cycle as the plain arrays a reference takes: every
    array of the snapshot under its dotted path (`nodes.alloc`,
    `pods.gang`, `quota.max`, ...; a sub-state the cycle has not is absent),
    and the first references' short names for some of them."""
    import numpy as np

    x = {}
    for group in dataclasses.fields(snap):
        state = getattr(snap, group.name)
        if state is None:
            continue
        for leaf in dataclasses.fields(state):
            value = getattr(state, leaf.name)
            if hasattr(value, "shape") and hasattr(value, "dtype"):
                x[f"{group.name}.{leaf.name}"] = np.array(value)
    for alias, path in ALIASES.items():
        x[alias] = x[path]
    if snap.metrics is not None:
        for name in METRIC_ALIASES:
            x[name] = x[f"metrics.{name}"]
    return x


def _apply(daemon, lines) -> None:
    from scheduler_plugins_tpu.bridge.feed import apply_event

    for line in lines:
        apply_event(daemon.cluster, json.loads(line),
                    rv_table=daemon.feed.rv_table)


def _unbound(daemon, uids) -> int:
    pods = daemon.cluster.pods
    return sum(1 for uid in uids if pods[uid].node_name is None)


def whole_wave(daemon, cell, seed: int, size: int, prefix: str,
               timeout_s: float = 600.0) -> tuple:
    """Give the daemon `size` pods' worth of the population's units at once
    (whole units of the stream `<prefix>/<size>`, as many as fit), under
    the feed lock, so that one cycle solves them together (in the pod
    bucket of `size`), and wait until those that are to bind are bound.
    Returns (the units, how many pods that were to bind are not)."""
    population = spec.population(cell.config, seed)
    units, pods = [], 0
    while pods < size:
        unit = population.unit(f"{prefix}/{size}", len(units))
        if units and pods + len(unit.pods) > size:
            break  # whole units only, and one at the least
        units.append(unit)
        pods += len(unit.pods)
    with daemon.feed.locked():
        for unit in units:
            _apply(daemon, unit.head + unit.pods)
    to_bind = [uid for unit in units if unit.binds for uid in unit.uids]
    deadline = time.monotonic() + timeout_s
    while True:
        with daemon.feed.locked():
            left = _unbound(daemon, to_bind)
        if not left or time.monotonic() > deadline:
            return units, left
        time.sleep(0.05)


def warm_pod_bucket(daemon, cell, seed: int, size: int) -> None:
    """Set-up: make the daemon solve one batch of `size` pods, so that the
    program for that pod bucket is compiled, or loaded from the cache,
    before the window can need it; then take the units away again."""
    units, left = whole_wave(daemon, cell, seed, size, "warm")
    if left:
        raise RuntimeError(f"{left} of {size} warm-up pods never bound")
    with daemon.feed.locked():
        for unit in units:
            _apply(daemon, unit.removal)


def probe(daemon, cell, seed: int, size: int) -> dict:
    """One seeded wave of `size` pods, recorded and compared."""
    import numpy as np

    from scheduler_plugins_tpu.tuning import gates
    from scheduler_plugins_tpu.utils import flightrec

    reference = spec.load_module("references", cell.config["reference"])
    flightrec.recorder.start(capacity=8)
    flightrec.recorder.profile_config = cell.config["profile"]
    try:
        units, left = whole_wave(daemon, cell, seed, size, "probe")
        records = [
            r for r in flightrec.recorder.records() if "outputs" in r.manifest
        ]
    finally:
        flightrec.recorder.stop()
    uids = [uid for unit in units for uid in unit.uids]
    out = {"size": len(uids), "cycles": len(records), "unbound": left,
           "mismatches": 0, "hard_violations": 0, "placed": 0,
           "reference_placed": 0, "reference_unbound": 0,
           "unserved_cycles": 0, "reference_s": 0.0}
    owed = set()  # pods the reference bound in a recorded cycle
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
        binds = (want["assignment"] >= 0) & ~want["wait"]
        out["reference_placed"] += int(binds.sum())
        names = rec.manifest["meta"]["pod_names"]
        owed.update(names[i] for i in np.flatnonzero(binds[:len(names)]))
        if rec.manifest.get("serve") is None:
            out["unserved_cycles"] += 1
    with daemon.feed.locked():
        out["reference_unbound"] = _unbound(daemon, owed & set(uids))
    return out


def probe_problems(result: dict, resident: bool) -> list:
    problems = []
    if result["unbound"]:
        problems.append(f"{result['unbound']} probe pods never bound")
    if not result["cycles"]:
        problems.append("the flight recorder holds no probe cycle")
    if result["reference_unbound"]:
        problems.append(
            f"{result['reference_unbound']} probe pods the reference binds "
            "are not bound"
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
