"""Cycle flight recorder: deterministic record/replay bundles + explain.

The upstream scheduler leaves two postmortem trails this rebuild lacked:
`Scheduled`/`FailedScheduling` events with per-pod reasons, and the
`--v=10` per-plugin score dump (SURVEY.md §5). This module is the data
substrate for both — and for any score-tuning loop (PAPERS.md "Learning
to Score"): you cannot tune or audit placement quality without
per-decision score breakdowns tied to **reproducible inputs**.

Three layers:

- **FlightRecorder** (`recorder`, process-global, OFF by default): a
  bounded ring buffer of `CycleRecord`s. When enabled, `framework.cycle
  .run_cycle` captures each cycle's FULL solver inputs at the Snapshot
  boundary — every snapshot tensor (content-addressed by digest), the
  queue order (`SnapshotMeta.pod_names`), each plugin's traced `aux()`
  config arrays, `static_key`, weight and cluster-derived `host_state`
  (specializations like the NRT uniform scope that a replay rebuild
  without a Cluster could not recompute), the profile + solve mode and an
  optional scenario seed — and its outputs at the Solve/Bind boundaries
  (assignment / admitted / wait / failed_plugin, then the report's
  bound/failed_by maps). Records enter the ring at capture time, so a
  crash mid-solve still leaves the inputs that provoked it.
- **Bundles**: `recorder.save(dir)` persists the ring as a self-contained
  `cycles.jsonl` manifest + `blobs/<digest>.npy` array store. Every file
  lands via temp-file + `os.replace` (`observability.atomic_write`), blobs
  before the manifest, so a kill mid-save never leaves a manifest naming
  missing or truncated blobs. `load_bundle(dir)` rebuilds the exact
  `ClusterSnapshot` / `SnapshotMeta` / aux pytrees; `tools/replay.py`
  re-runs them through the bit-identical sequential parity path
  (`Scheduler.solve`) and diffs placements.
- **Explain**: `explain_solver(...)` formats the per-(pod, cycle) score
  table — top-k candidate nodes with per-plugin weighted normalized score
  columns, the built-in fit margin and the winner gap (the upstream
  `--v=10` score dump) — from `Scheduler.explain_rows` (sequential) or
  `parallel.solver.batch_explain_rows` (batched); both share the
  framework's attribution/score helpers so they cannot drift. Exposed as
  `tools/replay.py explain`, the daemon's `/explain?uid=`, and
  `CycleReport.explain(uid)`.

Digest scheme: `blake2b-128(dtype ":" shape ":" C-order bytes)` per
array; a cycle's digest is `blake2b-128` over its canonical (sorted-key,
compact) manifest JSON with the digest field blanked — stable across
save/load round-trips, so "same digest" means "bit-identical record".

Privacy note: bundles carry FULL solver inputs — pod names/uids, node
names, namespaces, requests, the entire snapshot. Treat a recorded bundle
like an apiserver dump, not like a metrics scrape (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from scheduler_plugins_tpu.utils import observability as obs

#: manifest format version (bump on incompatible schema changes)
FORMAT = 1

#: fit-margin sentinel for masked-out (unschedulable/padded) nodes
MARGIN_MASKED = -(2 ** 62)


# ---------------------------------------------------------------------------
# array digests + pytree (de)serialization
# ---------------------------------------------------------------------------


def array_digest(arr: np.ndarray) -> str:
    """Content address of one array: blake2b-128 over dtype, shape and
    C-order bytes (dtype/shape prefixed so a reshape or cast can never
    collide with the original)."""
    arr = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(arr.dtype).encode())
    h.update(b":")
    h.update(",".join(map(str, arr.shape)).encode())
    h.update(b":")
    h.update(arr.tobytes())
    return h.hexdigest()


def _struct_registry() -> dict:
    """Class-name -> struct dataclass for every snapshot pytree node type
    (state.snapshot + state.scheduling)."""
    import dataclasses

    from scheduler_plugins_tpu.state import scheduling as _scheduling
    from scheduler_plugins_tpu.state import snapshot as _snapshot

    registry = {}
    for mod in (_snapshot, _scheduling):
        for name in dir(mod):
            obj = getattr(mod, name)
            if isinstance(obj, type) and dataclasses.is_dataclass(obj):
                registry[obj.__name__] = obj
    return registry


def pack_pytree(value, blobs: dict) -> object:
    """Lower a snapshot/aux pytree into a JSON-able spec, depositing every
    array into `blobs` keyed by content digest. Handles struct dataclasses
    (incl. non-pytree static fields like `NumaState.pack_scales`), plain
    containers, arrays and scalars."""
    import dataclasses

    if value is None:
        return None
    if isinstance(value, (bool, int, float, str)):
        return {"v": value}
    if isinstance(value, np.generic):
        return {"v": value.item()}
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return {
            "s": type(value).__name__,
            "f": {
                f.name: pack_pytree(getattr(value, f.name), blobs)
                for f in dataclasses.fields(value)
            },
        }
    if isinstance(value, (tuple, list)):
        return {
            "t": [pack_pytree(v, blobs) for v in value],
            "k": "tuple" if isinstance(value, tuple) else "list",
        }
    if isinstance(value, dict):
        return {"d": {str(k): pack_pytree(v, blobs) for k, v in value.items()}}
    arr = np.asarray(value)  # np.ndarray or jax.Array
    if arr.dtype == object:
        raise TypeError(f"unrecordable value of type {type(value).__name__}")
    digest = array_digest(arr)
    blobs[digest] = arr
    return {"a": digest, "dtype": str(arr.dtype), "shape": list(arr.shape)}


def unpack_pytree(spec, blobs: dict, registry: Optional[dict] = None):
    """Inverse of `pack_pytree` (arrays come back as host numpy)."""
    if spec is None:
        return None
    if registry is None:
        registry = _struct_registry()
    if "v" in spec:
        return spec["v"]
    if "a" in spec:
        arr = blobs[spec["a"]]
        expect = (spec["dtype"], tuple(spec["shape"]))
        if (str(arr.dtype), arr.shape) != expect:
            raise ValueError(
                f"blob {spec['a']}: dtype/shape {arr.dtype}/{arr.shape} "
                f"does not match manifest {expect}"
            )
        return arr
    if "t" in spec:
        items = [unpack_pytree(v, blobs, registry) for v in spec["t"]]
        return tuple(items) if spec.get("k") == "tuple" else items
    if "d" in spec:
        return {k: unpack_pytree(v, blobs, registry) for k, v in spec["d"].items()}
    cls = registry.get(spec["s"])
    if cls is None:
        raise ValueError(f"unknown struct {spec['s']!r} in bundle")
    return cls(**{
        name: unpack_pytree(v, blobs, registry)
        for name, v in spec["f"].items()
    })


def pack_meta(meta) -> dict:
    """`SnapshotMeta` -> JSON (host-only name<->code tables; the resource
    axis is recorded as the full ordered name list)."""
    from scheduler_plugins_tpu.api.resources import CANONICAL

    names = list(meta.index.names)
    if tuple(names[: len(CANONICAL)]) != CANONICAL:
        raise ValueError("resource index does not start with CANONICAL")
    return {
        "resources": names,
        "node_names": list(meta.node_names),
        "pod_names": list(meta.pod_names),
        "namespaces": list(meta.namespaces),
        "gang_names": list(meta.gang_names),
        "regions": list(meta.regions),
        "zones": list(meta.zones),
        "workloads": list(meta.workloads),
    }


def unpack_meta(spec: dict):
    from scheduler_plugins_tpu.api.resources import CANONICAL, ResourceIndex
    from scheduler_plugins_tpu.state.snapshot import SnapshotMeta

    index = ResourceIndex(spec["resources"][len(CANONICAL):])
    if tuple(index.names) != tuple(spec["resources"]):
        raise ValueError("resource axis did not round-trip")
    return SnapshotMeta(
        index=index,
        node_names=list(spec["node_names"]),
        pod_names=list(spec["pod_names"]),
        namespaces=list(spec["namespaces"]),
        gang_names=list(spec["gang_names"]),
        regions=list(spec["regions"]),
        zones=list(spec["zones"]),
        workloads=list(spec["workloads"]),
    )


def _canonical_json(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


# ---------------------------------------------------------------------------
# cycle records + the ring-buffer recorder
# ---------------------------------------------------------------------------


@dataclass
class CycleRecord:
    """One recorded scheduling cycle: inputs captured at the Snapshot
    boundary, outputs at Solve/Bind. `manifest` is the JSON-able view
    (arrays as digest references); `blobs` holds the arrays."""

    seq: int
    now_ms: int
    profile: str
    seed: object = None
    manifest: dict = field(default_factory=dict)
    blobs: dict = field(default_factory=dict)
    complete: bool = False

    def capture_inputs(self, snap, meta, scheduler, stream_chunk=None,
                       profile_config=None) -> None:
        """Record the full solver input surface for this cycle. Must run
        AFTER `scheduler.prepare(meta, ...)` so the captured `aux()`
        pytrees are exactly what the solve would bind."""
        self.manifest["snapshot"] = pack_pytree(snap, self.blobs)
        self.manifest["meta"] = pack_meta(meta)
        self.manifest["stream_chunk"] = stream_chunk
        if profile_config is not None:
            self.manifest["profile_config"] = profile_config
        else:
            from scheduler_plugins_tpu.api.config import profile_spec

            self.manifest["profile_config"] = profile_spec(scheduler.profile)
        self.manifest["plugins"] = [
            {
                "name": p.name,
                "class": type(p).__name__,
                "weight": int(p.weight),
                "static_key": repr(p.static_key()),
                "aux": pack_pytree(p.aux(), self.blobs),
                # cluster-derived trace specialization (e.g. NRT uniform
                # scope, NetworkOverhead cost matrices) that a rebuild
                # without a Cluster cannot recompute — restored on replay
                "host_state": pack_pytree(p.host_state(), self.blobs),
            }
            for p in scheduler.profile.plugins
        ]

    def capture_outputs(self, mode: str, assignment, admitted, wait,
                        failed_plugin=None) -> None:
        out = {
            "mode": mode,
            "assignment": pack_pytree(np.asarray(assignment), self.blobs),
            "admitted": pack_pytree(np.asarray(admitted), self.blobs),
            "wait": pack_pytree(np.asarray(wait), self.blobs),
            "failed_plugin": (
                None if failed_plugin is None
                else pack_pytree(np.asarray(failed_plugin), self.blobs)
            ),
        }
        self.manifest["outputs"] = out

    def commit(self, report=None, drift=None) -> None:
        if report is not None:
            self.manifest["report"] = {
                "bound": dict(report.bound),
                "reserved": dict(report.reserved),
                "failed": list(report.failed),
                "failed_by": dict(report.failed_by),
            }
            # per-cycle placement-quality objectives (tuning.quality) —
            # `tools/replay.py quality` diffs its recomputation against
            # this recorded stamp
            if getattr(report, "quality", None) is not None:
                self.manifest["report"]["quality"] = dict(report.quality)
        self.manifest["drift"] = drift
        self.complete = True

    def to_manifest(self) -> dict:
        line = {
            "format": FORMAT,
            "cycle": self.seq,
            "now_ms": self.now_ms,
            "profile": self.profile,
            "seed": self.seed,
            "complete": self.complete,
            **self.manifest,
        }
        line["digest"] = record_digest(line)
        return line

    @property
    def pod_names(self) -> list:
        return self.manifest.get("meta", {}).get("pod_names", [])


def record_digest(manifest: dict) -> str:
    """Cycle digest: blake2b-128 over the canonical manifest JSON with the
    digest field blanked. Arrays contribute through their content
    digests, so equal digest == bit-identical inputs AND outputs."""
    scrubbed = {k: v for k, v in manifest.items() if k != "digest"}
    return hashlib.blake2b(
        _canonical_json(scrubbed).encode(), digest_size=16
    ).hexdigest()


class FlightRecorder:
    """Bounded ring buffer of `CycleRecord`s. OFF by default; when off,
    `begin()` returns None and the cycle hooks cost one attribute read.
    `start(capacity)` arms it; records enter the ring as soon as `begin`
    returns (partial records are visible — the point of a flight recorder
    is surviving the crash that would have prevented a tidy commit)."""

    def __init__(self):
        self._enabled = False
        self._ring: deque = deque(maxlen=8)
        self._seq = 0
        self._lock = threading.Lock()
        #: optional exact profile config (the daemon sets its decoded
        #: profile file here); falls back to `api.config.profile_spec`
        self.profile_config: Optional[dict] = None
        #: optional scenario seed stamped into every record (bench sets it)
        self.seed = None

    @property
    def enabled(self) -> bool:
        return self._enabled

    def start(self, capacity: int = 8) -> None:
        with self._lock:
            self._ring = deque(maxlen=max(int(capacity), 1))
            self._seq = 0
            self._enabled = True

    def stop(self) -> None:
        self._enabled = False

    def resume(self) -> None:
        """Re-arm WITHOUT resetting the ring (`start` resets; `stop` is
        the pause) — the interleaved-pairs overhead benches toggle the
        recorder per cycle and must not lose the accumulated corpus."""
        with self._lock:
            self._enabled = True

    def begin(self, now_ms: int, profile: str) -> Optional[CycleRecord]:
        if not self._enabled:
            return None
        with self._lock:
            self._seq += 1
            rec = CycleRecord(
                seq=self._seq, now_ms=now_ms, profile=profile, seed=self.seed
            )
            self._ring.append(rec)
        return rec

    def records(self) -> list:
        with self._lock:
            return list(self._ring)

    def find(self, uid: str, cycle: Optional[int] = None):
        """Newest COMPLETE record whose pending batch contains `uid` (or
        the exact `cycle` number when given); when no complete record has
        it, the newest in-flight record with captured inputs (outputs
        missing — crash postmortems live here). Records still inside
        `capture_inputs` (the current cycle, seen from another thread)
        are never returned — a half-built manifest would crash the
        caller."""
        recs = self.records()
        for want_complete in (True, False):
            for rec in reversed(recs):
                if cycle is not None and rec.seq != cycle:
                    continue
                if rec.complete is not want_complete:
                    continue
                if "plugins" not in rec.manifest:  # capture in flight
                    continue
                if uid in rec.pod_names:
                    return rec
        return None

    def save(self, directory: str) -> dict:
        """Persist the ring as a bundle: `blobs/<digest>.npy` (each written
        atomically) then the `cycles.jsonl` manifest LAST — a reader only
        trusts arrays the manifest names, so a crash mid-save leaves at
        worst orphan blobs, never a manifest with missing data. An
        existing manifest in `directory` is appended to, not replaced
        (blobs are content-addressed, so successive runs accumulate into
        one bundle);
        records already present verbatim are not duplicated. Returns a
        small summary dict."""
        records = [r for r in self.records() if r.manifest.get("snapshot")]
        os.makedirs(os.path.join(directory, "blobs"), exist_ok=True)
        written = 0
        seen: set = set()
        for rec in records:
            for digest, arr in rec.blobs.items():
                if digest in seen:
                    continue
                seen.add(digest)
                path = os.path.join(directory, "blobs", f"{digest}.npy")
                if os.path.exists(path):
                    continue
                buf = io.BytesIO()
                np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
                obs.atomic_write(path, buf.getvalue())
                written += 1
        # sidecars land with the blobs, BEFORE the manifest: cycles.jsonl
        # stays the last write so a crash mid-save never leaves a
        # manifest naming missing data (gated by test_flightrec
        # TestAtomicWrites)
        self._save_cost_stamp(directory)
        manifest_path = os.path.join(directory, "cycles.jsonl")
        lines: list = []
        if os.path.exists(manifest_path):
            with open(manifest_path) as f:
                lines = [ln.strip() for ln in f if ln.strip()]
        have = set(lines)
        lines += [
            line for rec in records
            if (line := _canonical_json(rec.to_manifest())) not in have
        ]
        obs.atomic_write(
            manifest_path,
            "\n".join(lines) + ("\n" if lines else ""),
        )
        ledger_pods = self._save_ledger_segment(directory)
        return {
            "cycles": len(lines),
            "blobs_written": written,
            "ledger_pods": ledger_pods,
            "path": directory,
        }

    @staticmethod
    def _save_cost_stamp(directory: str) -> None:
        """Stamp the committed static-cost provenance (docs/cost_model.json,
        ISSUE 20) beside the cycle manifest: `cost.json` records the
        manifest digest + per-program cost digests in force when the
        bundle was written, so `tools/replay.py info` can flag "recorded
        under a program with a different cost shape" instead of silently
        replaying across an algorithmic change. A sidecar like
        ledger.json — NOT a manifest field — because record digests
        (`record_digest`) cover the cycle manifest, and provenance about
        the surrounding tree must not churn the integrity digest of the
        recorded data itself. Best-effort: no cost manifest, no stamp."""
        from scheduler_plugins_tpu.obs import costmodel

        manifest = costmodel.load_manifest()
        if not manifest:
            return
        stamp = {
            "manifest_digest": costmodel.manifest_digest(manifest),
            "jax": manifest.get("jax"),
            "programs": {
                name: row.get("cost_digest")
                for name, row in sorted(manifest.get("programs", {}).items())
            },
        }
        obs.atomic_write(
            os.path.join(directory, "cost.json"),
            json.dumps(stamp, sort_keys=True),
        )

    @staticmethod
    def _save_ledger_segment(directory: str) -> int:
        """Persist the pod-lifecycle ledger (obs.ledger) alongside the
        cycle manifest when it is live: `ledger.json` lets
        `tools/replay.py timeline <bundle> <uid>` reconstruct a pod's
        cross-cycle story next to the cycle-level replay evidence. Lazy
        import — flightrec must not pull the ledger in for the many
        callers that never record. Returns the number of pod records
        persisted (0 when the ledger is off or empty)."""
        from scheduler_plugins_tpu.obs import ledger as podledger

        led = podledger.LEDGER
        if not led.enabled:
            return 0
        export = led.export()
        n = len(export["retired"]) + len(export["live"])
        if n == 0:
            return 0
        obs.atomic_write(
            os.path.join(directory, "ledger.json"),
            json.dumps(export, sort_keys=True),
        )
        return n


#: global recorder, off by default (`run_cycle` hooks, daemon `--record`,
#: `tools/replay.py smoke` turn it on)
recorder = FlightRecorder()


# ---------------------------------------------------------------------------
# bundle loading + replay reconstruction
# ---------------------------------------------------------------------------


def rebuild_scheduler(manifest: dict, blob_resolver, profile_name=None):
    """(Scheduler, meta, faithful): THE one profile-rebuild recipe, shared
    by the bundle replay path (`LoadedCycle.scheduler`) and the live
    daemon `/explain` path (`explain_record` on a ring `CycleRecord`):
    `load_profile` on the recorded config, recorded per-plugin weights,
    `prepare(meta, None)` (no Cluster exists at replay), then each
    plugin's recorded `host_state` re-baked — so the rebuilt plugins trace
    the same specialized program the recorded solve ran. `faithful` is
    False when, after all that, a rebuilt plugin's class/static_key still
    disagrees with the record (lossy config export). `blob_resolver`
    lowers a packed pytree spec back to arrays (bundle blob dir or the
    in-memory record's blobs)."""
    from scheduler_plugins_tpu.api.config import load_profile
    from scheduler_plugins_tpu.framework.runtime import Scheduler

    profile = load_profile(manifest["profile_config"])
    profile.name = (
        profile_name if profile_name is not None
        else manifest.get("profile", profile.name)
    )
    recorded = manifest["plugins"]
    faithful = len(profile.plugins) == len(recorded)
    if faithful:
        for plugin, rec in zip(profile.plugins, recorded):
            plugin.weight = int(rec.get("weight", plugin.weight))
    scheduler = Scheduler(profile)
    meta = unpack_meta(manifest["meta"])
    scheduler.prepare(meta, None)
    if faithful:
        for plugin, rec in zip(profile.plugins, recorded):
            hs = rec.get("host_state")
            if hs is not None:
                plugin.restore_host_state(blob_resolver(hs))
            if type(plugin).__name__ != rec["class"] or repr(
                plugin.static_key()
            ) != rec["static_key"]:
                faithful = False
    return scheduler, meta, faithful


class LoadedCycle:
    """One manifest line + lazy blob access from a bundle directory."""

    def __init__(self, manifest: dict, blob_dir: str):
        self.manifest = manifest
        self._blob_dir = blob_dir
        self._cache: dict = {}
        self._registry = None

    def blob(self, digest: str) -> np.ndarray:
        arr = self._cache.get(digest)
        if arr is None:
            arr = np.load(
                os.path.join(self._blob_dir, f"{digest}.npy"),
                allow_pickle=False,
            )
            if array_digest(arr) != digest:
                raise ValueError(f"blob {digest} content does not match name")
            self._cache[digest] = arr
        return arr

    def _blobs_for(self, spec) -> dict:
        digests: set = set()

        def walk(node):
            if node is None:
                return
            if "a" in node:
                digests.add(node["a"])
            for child in node.get("f", {}).values():
                walk(child)
            for child in node.get("t", []):
                walk(child)
            for child in node.get("d", {}).values():
                walk(child)

        walk(spec)
        return {d: self.blob(d) for d in digests}

    def snapshot(self):
        spec = self.manifest["snapshot"]
        return unpack_pytree(spec, self._blobs_for(spec))

    def meta(self):
        return unpack_meta(self.manifest["meta"])

    def auxes(self) -> tuple:
        return tuple(
            unpack_pytree(p["aux"], self._blobs_for(p["aux"]))
            for p in self.manifest["plugins"]
        )

    def output(self, name: str):
        out = self.manifest.get("outputs") or {}
        spec = out.get(name)
        if spec is None:
            return None
        return unpack_pytree(spec, self._blobs_for(spec))

    def scheduler(self):
        """Rebuild (Scheduler, faithful: bool) from the recorded profile
        config — prepared and host-state-restored (`rebuild_scheduler`).
        Even when `faithful` is False (lossy config export) replay still
        runs, with the recorded aux arrays force-bound so the traced
        config inputs are exact either way."""
        scheduler, _meta, faithful = rebuild_scheduler(
            self.manifest,
            lambda spec: unpack_pytree(spec, self._blobs_for(spec)),
        )
        return scheduler, faithful

    def digest_ok(self) -> bool:
        return record_digest(self.manifest) == self.manifest.get("digest")


def load_bundle(directory: str) -> list:
    """Parse a bundle directory into `LoadedCycle`s (manifest order)."""
    path = os.path.join(directory, "cycles.jsonl")
    blob_dir = os.path.join(directory, "blobs")
    cycles = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            manifest = json.loads(line)
            if manifest.get("format") != FORMAT:
                raise ValueError(
                    f"bundle format {manifest.get('format')!r} != {FORMAT}"
                )
            cycles.append(LoadedCycle(manifest, blob_dir))
    return cycles


def replay_cycle(loaded: LoadedCycle) -> dict:
    """Re-run one recorded cycle through the bit-identical sequential
    parity path (`Scheduler.solve`) with the RECORDED aux arrays bound,
    and diff placements against the recorded outputs. The diff must be
    empty for cycles recorded in sequential mode; wave-mode recordings
    (batch/streamed) may legitimately differ on soft tie-breaking, so the
    mismatch list is evidence, not an error, there."""
    scheduler, faithful = loaded.scheduler()  # prepared + host-state restored
    snap = loaded.snapshot()
    meta = loaded.meta()
    auxes = loaded.auxes()
    aux_match = all(
        _pack_digest(plugin.aux()) == _pack_digest(aux)
        for plugin, aux in zip(scheduler.profile.plugins, auxes)
    )
    # mode pinned: replay certifies the sequential parity semantics even
    # when the recorded profile selects another solve mode (packing)
    result = scheduler.solve(snap, auxes=auxes, mode="sequential")
    assignment = np.asarray(result.assignment)
    recorded = loaded.output("assignment")
    mode = (loaded.manifest.get("outputs") or {}).get("mode")
    mismatches = []
    if recorded is not None:
        diff = np.nonzero(assignment != np.asarray(recorded))[0]
        pod_names = loaded.manifest["meta"]["pod_names"]
        node_names = loaded.manifest["meta"]["node_names"]

        def node(ix):
            return node_names[ix] if 0 <= ix < len(node_names) else None

        for i in diff[:64]:
            i = int(i)
            mismatches.append({
                "pod": pod_names[i] if i < len(pod_names) else f"<pad {i}>",
                "recorded": node(int(np.asarray(recorded)[i])),
                "replayed": node(int(assignment[i])),
            })
    return {
        "cycle": loaded.manifest["cycle"],
        "mode": mode,
        "digest_ok": loaded.digest_ok(),
        "profile_faithful": faithful,
        "aux_match": bool(aux_match),
        "placed_recorded": (
            None if recorded is None else int((np.asarray(recorded) >= 0).sum())
        ),
        "placed_replayed": int((assignment >= 0).sum()),
        "placements_match": recorded is not None and not mismatches,
        "mismatches": mismatches,
        "_assignment": assignment,
        "_scheduler": scheduler,
        "_snap": snap,
        "_meta": meta,
        "_auxes": auxes,
    }


def _pack_digest(pytree) -> str:
    blobs: dict = {}
    spec = pack_pytree(pytree, blobs)
    return hashlib.blake2b(
        _canonical_json(spec).encode(), digest_size=16
    ).hexdigest()


# ---------------------------------------------------------------------------
# explain: the per-(pod, cycle) score table
# ---------------------------------------------------------------------------


def explain_solver(scheduler, snap, meta, uid: str, top_k: int = 5,
                   assignment=None, auxes=None, batched: bool = False,
                   cycle=None) -> dict:
    """The "why this node" table for one pod of one solved cycle: top-k
    candidate nodes with per-plugin weighted normalized score columns, the
    built-in fit margin (min over resources of free - demand; most
    negative binding), and each candidate's gap to the winner — the
    upstream `--v=10` score dump as JSON. Scores are evaluated against the
    CYCLE-INITIAL state (the objective both solve modes rank by,
    `parallel.solver.profile_initial_scores`); `batched=True` derives the
    same columns through the batched solver's class-collapsed row hooks
    instead of the per-pod tensor methods (gated for agreement by
    tests/test_explain.py)."""
    try:
        pod_index = meta.pod_names.index(uid)
    except ValueError:
        raise KeyError(f"pod {uid!r} is not in this cycle's pending batch")
    if batched:
        from scheduler_plugins_tpu.parallel.solver import batch_explain_rows

        rows = batch_explain_rows(scheduler, snap, [pod_index], auxes=auxes)
    else:
        rows = scheduler.explain_rows(snap, [pod_index], auxes=auxes)
    plugins = scheduler.profile.plugins
    fail_names = scheduler.fail_plugin_names()
    n_real = len(meta.node_names)

    total = rows["total"][0][:n_real]
    feasible = rows["feasible"][0][:n_real]
    margin = rows["fit_margin"][0][:n_real]
    columns = rows["columns"][0][:, :n_real]
    admitted = bool(rows["admitted"][0])
    fail_code = int(rows["fail_code"][0])

    # infeasible nodes keep their relative score order but rank after
    # every feasible node (scores are bounded far below 2^61, so the
    # shift cannot overflow or let an infeasible node catch a feasible one)
    masked = np.where(feasible, total, total + MARGIN_MASKED)
    # score desc, lowest node index tie-break — the solver's own argmax rule
    order = np.lexsort((np.arange(n_real), -masked))
    any_feasible = bool(feasible.any())
    winner = int(order[0]) if any_feasible else None
    winner_total = int(total[winner]) if winner is not None else None
    runner_up_gap = None
    if any_feasible and int(feasible.sum()) >= 2:
        runner_up_gap = int(winner_total - masked[order[1]])

    assigned_node = None
    placed = None
    if assignment is not None:
        a = int(np.asarray(assignment)[pod_index])
        placed = a >= 0
        if placed and a < n_real:
            assigned_node = meta.node_names[a]
    failed_plugin = None
    if placed is not True and (not admitted or not any_feasible or
                               placed is False):
        failed_plugin = fail_names[fail_code] if fail_code > 0 else fail_names[0]

    candidates = []
    # feasible nodes first, then the best-scoring near-misses — an
    # unschedulable pod's table shows its closest candidates with the fit
    # margins telling why each missed
    for n in order[: max(int(top_k), 1)]:
        n = int(n)
        candidates.append({
            "node": meta.node_names[n],
            "total": int(total[n]),
            "gap_to_winner": (
                None if winner_total is None else int(winner_total - total[n])
            ),
            "feasible": bool(feasible[n]),
            "fit_margin": (
                None if int(margin[n]) == MARGIN_MASKED else int(margin[n])
            ),
            "scores": {
                p.name: int(columns[l][n]) for l, p in enumerate(plugins)
            },
        })
    return {
        "uid": uid,
        "cycle": cycle,
        "pod_index": pod_index,
        "profile": scheduler.profile.name,
        "path": "batched" if batched else "sequential",
        "admitted": admitted,
        "placed": placed,
        "assigned": assigned_node,
        "failed_plugin": failed_plugin,
        "winner": meta.node_names[winner] if winner is not None else None,
        "winner_total": winner_total,
        "runner_up_gap": runner_up_gap,
        "weights": {p.name: int(p.weight) for p in plugins},
        "candidates": candidates,
    }


#: rebuilt-scheduler cache for `explain_record`, keyed by record IDENTITY
#: (a polling `/explain` client hits the same ring `CycleRecord` object
#: repeatedly — without this every request would re-trace+compile the
#: explain program on the HTTP thread, contending with the cycle loop).
#: Identity keying is exact: the ring holds records by reference, and a
#: rotated-out record simply ages out of this deque with it.
_REBUILD_CACHE: deque = deque(maxlen=4)

#: serializes `explain_record`: the daemon serves `/explain` from
#: ThreadingHTTPServer worker threads, and two concurrent requests would
#: otherwise race on the rebuild cache AND trace jit programs against the
#: same rebuilt plugin objects mid-bind (UnexpectedTracerError at best)
_EXPLAIN_LOCK = threading.Lock()


def _cached_rebuild(rec, build):
    for key, value in _REBUILD_CACHE:
        if key is rec:
            return value
    value = build()
    _REBUILD_CACHE.append((rec, value))
    return value


def explain_record(rec, uid: str, top_k: int = 5,
                   batched: bool = False) -> dict:
    """Explain one pod of a ring-buffer `CycleRecord` (the daemon's live
    `/explain` path) or a bundle `LoadedCycle` (the offline replay path).
    Rebuilds both the snapshot and a FRESH scheduler from the record's own
    arrays and profile config — the daemon's live scheduler is never
    touched (re-preparing it for an older record's layout from an HTTP
    thread would corrupt the cycle loop's prepared plugin state), and the
    recorded aux arrays are force-bound so the traced config inputs are
    exactly what the recorded solve saw. The rebuilt scheduler (and its
    compiled explain program) is cached per record, so repeat requests
    for the same recorded cycle pay host unpacking only. Thread-safe:
    concurrent callers (the daemon's HTTP worker threads) serialize on a
    module lock."""
    with _EXPLAIN_LOCK:
        return _explain_record(rec, uid, top_k=top_k, batched=batched)


def _explain_record(rec, uid: str, top_k: int, batched: bool) -> dict:
    if isinstance(rec, CycleRecord):
        spec = rec.manifest["snapshot"]
        snap = unpack_pytree(spec, rec.blobs)
        out = rec.manifest.get("outputs") or {}
        a_spec = out.get("assignment")
        assignment = (
            unpack_pytree(a_spec, rec.blobs) if a_spec is not None else None
        )
        auxes = tuple(
            unpack_pytree(p["aux"], rec.blobs)
            for p in rec.manifest["plugins"]
        )
        cycle = rec.seq
        scheduler, meta = _cached_rebuild(
            rec,
            lambda: rebuild_scheduler(
                rec.manifest, lambda s: unpack_pytree(s, rec.blobs),
                profile_name=rec.profile,
            )[:2],
        )
    else:
        snap = rec.snapshot()
        meta = rec.meta()
        assignment = rec.output("assignment")
        auxes = rec.auxes()
        cycle = rec.manifest["cycle"]
        # prepared + host-state restored (faithfulness flag dropped here —
        # `replay_cycle` is the surface that reports it)
        scheduler = _cached_rebuild(rec, lambda: rec.scheduler()[0])
    return explain_solver(
        scheduler, snap, meta, uid, top_k=top_k, assignment=assignment,
        auxes=auxes, batched=batched, cycle=cycle,
    )
