#!/usr/bin/env python
"""Jaxpr-level invariant auditor: carry provenance, donation discipline,
i64 dataflow and effect ordering on the COMPILED programs.

`tools/graft_lint.py` enforces the CLAUDE.md invariants at the source-AST
level; this tool proves them on the traced programs themselves, where
helper indirection, vmap/scan batching and cross-function dataflow are
fully resolved. It traces the same program registry `tools/tpu_lower.py`
AOT-lowers (bench cfgs 0-6 including the north-star chunk, both sharded
solves, `entry()`) to closed jaxprs and walks them with a provenance
lattice: every input leaf is tagged with its pytree path (snapshot family,
SolverState carry, aux channel), and tags propagate forward through every
equation — including jit/scan/while/cond sub-jaxprs, with a fixpoint over
loop carries.

Rules:

- **JA001 stale-snapshot read** — a program output depends on a static
  snapshot tensor whose SolverState carry counterpart
  (`state.snapshot.CARRY_COUNTERPARTS` /
  `state.scheduling.TRACK_CARRY_COUNTERPARTS`) is also a program input but
  is DEAD in the jaxpr (eliminated by DCE): the solve consumed the static
  base where the live carry exists, i.e. a plugin bypassed the carry.
  Cycle-initial snapshot reads are sanctioned by design (scores are
  documented cycle-initial) — the rule fires only on a dead carry.
- **JA002 post-donation read** — a var passed in a DONATED position of an
  inner jitted call (`donated_invars` on the jit equation) is consumed by
  any LATER equation, or returned, in the enclosing jaxpr. The
  compiled-level complement of graft-lint GL006: catches reuse routed
  through helpers or unrolled loop iterations that the lexical AST sweep
  cannot see.
- **JA003 i64 landmine through indirection** — an i64 `dot_general`/
  `conv_general_dilated`, a rank>=2 i64 cumulative-scan primitive, or a
  rank>=2 i64 `reduce_window` anywhere in the traced program, however it
  was reached (vmap batching, scan bodies, helper chains invisible to the
  source AST). Pre-lowering twin of the StableHLO landmine scan, with
  operand provenance attached as evidence.
- **JA004 nondeterminism** — unordered-effect callbacks inside solve
  programs: `io_callback(ordered=False)` and debug-print callbacks. Solve
  programs must be replayable; unordered host effects are not.

`pallas_call` equations (the ISSUE 13 ring kernels) are first-class:
input taints flow onto the kernel body's input refs (output/scratch refs
enter untainted), the body jaxpr is walked under every JA rule like any
other sub-jaxpr, and a per-program KERNEL-BODY OP CENSUS (dma_start/
dma_wait/semaphore ops and the body arithmetic) is recorded in the
manifest — the jaxpr-level twin of the StableHLO manifest, whose
`tpu_custom_call` payload is opaque to the text scan.

A manifest (`docs/jaxpr_audit.json`: per-program rule verdicts +
provenance-tagged equation counts) is committed so program drift shows up
as a diff; `--check` is the read-only fail-closed CI gate (missing manifest
fails, rule violations always fail, count equality is enforced only under
the manifest's jax version — jaxprs are version-dependent).

Usage:
    python tools/jaxpr_audit.py             # audit all, write manifest
    python tools/jaxpr_audit.py --check     # read-only verify vs manifest
    python tools/jaxpr_audit.py --programs entry bench_cfg3_numa_sequential
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
MANIFEST = REPO / "docs" / "jaxpr_audit.json"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.tpu_lower import PROGRAMS, bootstrap  # noqa: E402  (registry reuse)

RULES = ("JA001", "JA002", "JA003", "JA004")

#: call-like primitives whose sub-jaxpr invars align 1:1 with the equation
#: operands (param name -> where the jaxpr lives)
_CALL_PRIMS = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "remat": "jaxpr",
    "checkpoint": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vmap_call": "call_jaxpr",
    # shard_map's body jaxpr takes the PER-SHARD blocks of the same
    # operands, 1:1 with the equation invars — provenance flows through
    # unchanged (the sharded wave solver program)
    "shard_map": "jaxpr",
}

#: cumulative-scan primitives whose rank>=2 i64 form lowers to the
#: vmem-pathological multi-dim reduce_window on TPU (CLAUDE.md)
_CUM_PRIMS = frozenset({"cumsum", "cumprod", "cummax", "cummin"})


# ---------------------------------------------------------------------------
# input labeling (pytree-path provenance)
# ---------------------------------------------------------------------------


#: per-program role names for positional (non-dataclass) arguments; programs
#: absent here get type-derived roles (ClusterSnapshot -> "snap",
#: SolverState -> "state", tuple -> "aux", else "argN")
ROLE_OVERRIDES = {
    # north_star_solve_chunk(raw, node_mask, req_chunk, mask_chunk, free0):
    # the free carry is the SolverState.free thread of the chunk pipeline
    "bench_cfg6_north_star_chunk": (
        "score_raw", "snap.nodes.mask", "snap.pods.req", "snap.pods.mask",
        "state.free",
    ),
    # apply_node_deltas(nodes, <7 packed upsert cols>, <6 usage cols>):
    # the NodeState argument is the donated RESIDENT carry (the serving
    # engine's cycle-to-cycle thread), not a static snapshot — label it
    # state.* so JA001's stale-snapshot rule doesn't treat the resident
    # columns as a bypassed snapshot read
    "serving_delta_apply": (
        "state.nodes",
        "up.idx", "up.valid", "up.alloc", "up.capacity", "up.mask",
        "up.region", "up.zone",
        "d.idx", "d.requested", "d.nonzero", "d.limits", "d.pod_count",
        "d.terminating",
    ),
    # compact_node_rows(nodes, gather_idx, valid): the NodeState arg is
    # the donated RESIDENT carry being row-compacted in place (the
    # serving engine's cycle-to-cycle thread), same labeling rationale
    # as serving_delta_apply
    "serving_node_compact": ("state.nodes", "gather_idx", "valid"),
    # sharded_wave_chunk(node_ids, req_chunk, mask_chunk, rank_free): the
    # rank-ordered free block is the donated RESIDENT carry threading
    # chunk to chunk on device (the sharded analog of cfg6's state.free)
    "sharded_wave_chunk": (
        "node_ids", "snap.pods.req", "snap.pods.mask", "state.free",
    ),
    # same program with the SPT_PALLAS election path: identical calling
    # convention, the collectives are pallas_call ring kernels
    "sharded_wave_chunk_pallas": (
        "node_ids", "snap.pods.req", "snap.pods.mask", "state.free",
    ),
    # packing_solve(snap, weights, pack_aux): the flagship packing-mode
    # program — `weights` is the static allocatable score config and
    # `pack_aux` the traced packing-knob vector (iterations/price/
    # temperature/decay), both aux-channel inputs, not snapshot state
    "packing_solve": ("snap", "aux.weights", "aux.packing"),
    # sweep(snap, state0, auxes, W): the (K, L) candidate weight matrix
    # IS an aux-channel input — per-lane weight scalars bound through
    # Plugin.bind_weight, the traced twin of the profile's static weight
    # (labeling it aux keeps JA001's snapshot-bypass lattice honest about
    # where candidate config enters the program)
    "sweep_solve": ("snap", "state", "aux", "aux.weights"),
    # gang_solve_body(gangs, state0, node_mask): the RankGangState arg is
    # the gang phase's snapshot family — labeling it snap.ranks makes its
    # `prev_assigned` leaf the CARRY_COUNTERPARTS twin of the
    # SolverState.rank_nodes carry, so JA001 proves the solve never
    # bypasses the rank-assignment carry (the state arg keeps its
    # type-derived "state" role)
    "rank_gang_solve": ("snap.ranks", "state", "snap.nodes.mask"),
    # wave_solve_body(gangs, free, eq_used, node_mask, ids): ONE wave of
    # the wave-batched gang solve — the per-gang body vmapped over a
    # lane of gang ids against the wave-start state. There is no
    # SolverState arg BY DESIGN: the free/eq/rank carries live host-side
    # between waves (the validator commits accepted lanes exactly), so
    # the wave-start state is labeled state.* (it IS the live carry, not
    # a static snapshot) and the gang tensors snap.ranks
    "wave_gang_solve": (
        "snap.ranks", "state.free", "state.eq_used", "snap.nodes.mask",
        "wave.ids",
    ),
    # apply_side_deltas(tables, <4 gang cols>, <3 ns cols>): the
    # SideTables argument is the donated RESIDENT gang/quota aggregate
    # carry (the serving engine's cycle-to-cycle thread), same labeling
    # rationale as serving_delta_apply
    "serving_side_apply": (
        "state.side",
        "sd.g_idx", "sd.g_assigned", "sd.g_gated", "sd.g_slack",
        "sd.q_idx", "sd.q_used", "sd.q_count",
    ),
    # apply_selector_deltas((track_base, anti_count, sym_base), track,
    # domain, delta): the tables are the donated RESIDENT selector and
    # carrier counts, same labeling rationale as serving_delta_apply
    "serving_selector_apply": (
        "state.sel", "sel.track", "sel.domain", "sel.delta",
    ),
    # shrink_select(rank_nodes, live, node_block, block_cost, n_release):
    # rank_nodes is the RESIDENT rank-assignment carry (the elastic delta
    # program mutates resident state, not a snapshot); the release count
    # is elastic config
    "elastic_shrink": (
        "state.rank_nodes", "snap.ranks.rank_mask", "snap.ranks.node_block",
        "snap.ranks.block_cost", "elastic.release",
    ),
}


def default_roles(args):
    """Role name per top-level argument, derived from the repo's calling
    conventions: snapshots and solver states are recognized by type, a
    tuple argument is the aux channel, everything else is positional."""
    from scheduler_plugins_tpu.framework.plugin import SolverState
    from scheduler_plugins_tpu.state.snapshot import ClusterSnapshot

    roles = []
    for i, a in enumerate(args):
        if isinstance(a, ClusterSnapshot):
            roles.append("snap")
        elif isinstance(a, SolverState):
            roles.append("state")
        elif isinstance(a, tuple):
            roles.append("aux")
        else:
            roles.append(f"arg{i}")
    return tuple(roles)


def label_leaves(args, roles=None):
    """One provenance label per flattened leaf of `args`, in jax flatten
    order (so labels align with the closed jaxpr's invars): role of the
    top-level argument + the leaf's pytree key path within it."""
    from jax import tree_util as jtu

    roles = tuple(roles) if roles is not None else default_roles(args)
    labels = []
    for path, _leaf in jtu.tree_flatten_with_path(tuple(args))[0]:
        idx = path[0].idx
        labels.append(f"{roles[idx]}{jtu.keystr(path[1:])}")
    return labels


def classify(labels) -> str:
    """Lattice point name for a taint set: which provenance families feed a
    value. Stable strings — they key the committed manifest's op counts."""
    kinds = set()
    for label in labels:
        if label.startswith("snap."):
            kinds.add("snapshot")
        elif label.startswith("state."):
            kinds.add("carry")
        elif label.startswith("aux"):
            kinds.add("aux")
        else:
            kinds.add("other")
    if not kinds:
        return "const"
    return "+".join(sorted(kinds))


# ---------------------------------------------------------------------------
# taint propagation + per-equation rule checks
# ---------------------------------------------------------------------------

_EMPTY = frozenset()


def _is_i64(v) -> bool:
    aval = getattr(v, "aval", None)
    return aval is not None and str(getattr(aval, "dtype", "")) == "int64"


def _rank(v) -> int:
    aval = getattr(v, "aval", None)
    return len(getattr(aval, "shape", ()))


class Auditor:
    """Forward taint walk over a closed jaxpr with recursive sub-jaxpr
    handling. Collects JA002/JA003/JA004 findings and the provenance-tagged
    equation census during the walk; JA001 is decided afterwards from the
    output taints plus a DCE liveness pass."""

    def __init__(self):
        self.violations: list[dict] = []
        self.op_counts: Counter = Counter()
        #: primitive census over pallas_call KERNEL BODIES only (the
        #: manifest's jaxpr-level evidence for the opaque Mosaic payloads)
        self.pallas_ops: Counter = Counter()
        self.eqn_count = 0
        self._scanned: set[int] = set()  # eqn ids already rule-checked
        self._seen_sites: set = set()    # violation dedup across revisits

    # -- violation plumbing -------------------------------------------------

    def _add(self, rule, detail, **extra):
        key = (rule, detail)
        if key in self._seen_sites:
            return
        self._seen_sites.add(key)
        self.violations.append({"rule": rule, "detail": detail, **extra})

    # -- the walk -----------------------------------------------------------

    def propagate(self, jaxpr, in_taints):
        """Per-output taint sets for one `core.Jaxpr` given per-invar taint
        sets. Mutates the census/violation state; revisits (loop fixpoints)
        re-propagate taints but never double-count equations."""
        from jax.extend.core import Literal

        env: dict = {}

        def read(v):
            if isinstance(v, Literal):
                return _EMPTY
            return env.get(v, _EMPTY)

        def write(v, t):
            env[v] = env.get(v, _EMPTY) | t

        for var, taint in zip(jaxpr.invars, in_taints):
            write(var, taint)
        donated: dict = {}  # var -> donating call name
        for eqn in jaxpr.eqns:
            first_visit = id(eqn) not in self._scanned
            ts = [read(v) for v in eqn.invars]
            # JA002: consuming (or re-donating) an already-donated var
            for v in eqn.invars:
                if not isinstance(v, Literal) and v in donated:
                    self._add(
                        "JA002",
                        f"var donated to {donated[v]!r} consumed later by "
                        f"{eqn.primitive.name}",
                        primitive=eqn.primitive.name,
                    )
            out_ts = self._eqn(eqn, ts)
            if first_visit:
                self._scanned.add(id(eqn))
                self.eqn_count += 1
                self.op_counts[
                    f"{classify(frozenset().union(*out_ts) if out_ts else _EMPTY)}"
                ] += 1
                self._check_primitive(eqn, ts)
            di = eqn.params.get("donated_invars")
            if di and eqn.primitive.name in _CALL_PRIMS:
                name = eqn.params.get("name", eqn.primitive.name)
                for flag, v in zip(di, eqn.invars):
                    if flag and not isinstance(v, Literal):
                        donated[v] = name
            for v, t in zip(eqn.outvars, out_ts):
                if type(v).__name__ != "DropVar":
                    write(v, t)
        for v in jaxpr.outvars:
            if not isinstance(v, Literal) and v in donated:
                self._add(
                    "JA002",
                    f"var donated to {donated[v]!r} returned from the "
                    "enclosing jaxpr",
                )
        return [read(v) for v in jaxpr.outvars]

    def _eqn(self, eqn, ts):
        """Output taints for one equation, recursing into sub-jaxprs."""
        name = eqn.primitive.name
        params = eqn.params
        if name in _CALL_PRIMS and _CALL_PRIMS[name] in params:
            sub = params[_CALL_PRIMS[name]]
            sub_jaxpr = getattr(sub, "jaxpr", sub)
            if len(sub_jaxpr.invars) == len(ts):
                return self.propagate(sub_jaxpr, ts)
            return self._fallback(eqn, ts)
        if name == "scan":
            return self._scan(eqn, ts)
        if name == "while":
            return self._while(eqn, ts)
        if name == "cond":
            return self._cond(eqn, ts)
        if name == "pallas_call":
            return self._pallas(eqn, ts)
        # generic primitive (or unknown higher-order op): every output
        # carries the union of input taints; unknown sub-jaxprs are still
        # rule-scanned with that coarse union
        return self._fallback(eqn, ts)

    def _fallback(self, eqn, ts):
        union = frozenset().union(*ts) if ts else _EMPTY
        from jax import core

        for sub in core.jaxprs_in_params(eqn.params):
            sub_jaxpr = getattr(sub, "jaxpr", sub)
            self.propagate(sub_jaxpr, [union] * len(sub_jaxpr.invars))
        return [union for _ in eqn.outvars]

    def _scan(self, eqn, ts):
        params = eqn.params
        sub = params["jaxpr"].jaxpr
        n_consts = params["num_consts"]
        n_carry = params["num_carry"]
        consts, carry, xs = (
            ts[:n_consts], ts[n_consts:n_consts + n_carry], ts[n_consts + n_carry:]
        )
        carry = list(carry)
        for _ in range(32):  # fixpoint over the loop back-edge
            outs = self.propagate(sub, consts + carry + xs)
            new_carry = [c | o for c, o in zip(carry, outs[:n_carry])]
            if new_carry == carry:
                break
            carry = new_carry
        outs = self.propagate(sub, consts + carry + xs)
        return outs[:n_carry] + outs[n_carry:]

    def _while(self, eqn, ts):
        params = eqn.params
        cond_sub = params["cond_jaxpr"].jaxpr
        body_sub = params["body_jaxpr"].jaxpr
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts, body_consts, carry = ts[:cn], ts[cn:cn + bn], list(ts[cn + bn:])
        pred = _EMPTY
        for _ in range(32):
            pred = self.propagate(cond_sub, cond_consts + carry)[0]
            outs = self.propagate(body_sub, body_consts + carry)
            new_carry = [c | o for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        # trip count is control-dependence: outputs inherit the predicate
        return [c | pred for c in carry]

    def _pallas(self, eqn, ts):
        """pallas_call: the body jaxpr's invars are [input refs..., output
        refs..., scratch refs...] — input taints map 1:1 onto the leading
        refs (provenance "through the grid"), outputs/scratch enter
        untainted. The body is rule-walked like any sub-jaxpr, its
        primitive names censused into `pallas_ops`, and the equation's
        outputs carry the union of input taints (the kernel writes its
        output refs from the inputs; finer ref-dataflow is deliberately
        coarse-but-sound, like `_fallback`)."""
        from jax import core

        sub = eqn.params.get("jaxpr")
        if sub is None:
            return self._fallback(eqn, ts)
        sub_jaxpr = getattr(sub, "jaxpr", sub)
        if id(eqn) not in self._scanned:

            def census(j):
                for e in j.eqns:
                    self.pallas_ops[e.primitive.name] += 1
                    for s in core.jaxprs_in_params(e.params):
                        census(getattr(s, "jaxpr", s))

            census(sub_jaxpr)
        taints = list(ts) + [_EMPTY] * (len(sub_jaxpr.invars) - len(ts))
        self.propagate(sub_jaxpr, taints[: len(sub_jaxpr.invars)])
        union = frozenset().union(*ts) if ts else _EMPTY
        return [union for _ in eqn.outvars]

    def _cond(self, eqn, ts):
        pred, oper = ts[0], ts[1:]
        outs = None
        for branch in eqn.params["branches"]:
            b_outs = self.propagate(branch.jaxpr, oper)
            outs = b_outs if outs is None else [
                a | b for a, b in zip(outs, b_outs)
            ]
        return [o | pred for o in (outs or [])]

    # -- per-primitive rules (JA003 / JA004) --------------------------------

    def _check_primitive(self, eqn, ts):
        name = eqn.primitive.name
        if name in ("dot_general", "conv_general_dilated"):
            if any(_is_i64(v) for v in eqn.invars[:2]):
                self._add(
                    "JA003",
                    f"i64 {name} "
                    f"(provenance: {sorted(frozenset().union(*ts) or {'const'})})",
                    primitive=name,
                )
        elif name in _CUM_PRIMS:
            v = eqn.invars[0]
            if _is_i64(v) and _rank(v) >= 2:
                self._add(
                    "JA003",
                    f"rank-{_rank(v)} i64 {name}: lowers to multi-dim "
                    f"reduce_window on TPU "
                    f"(provenance: {sorted(frozenset().union(*ts) or {'const'})})",
                    primitive=name,
                )
        elif name.startswith("reduce_window"):
            v = eqn.invars[0]
            if _is_i64(v) and _rank(v) >= 2:
                self._add(
                    "JA003",
                    f"rank-{_rank(v)} i64 {name}",
                    primitive=name,
                )
        elif name == "io_callback":
            if not eqn.params.get("ordered", False):
                self._add(
                    "JA004",
                    "io_callback(ordered=False) inside a solve program",
                    primitive=name,
                )
        elif name in ("debug_callback", "debug_print"):
            self._add(
                "JA004",
                f"{name} (debug print) inside a solve program",
                primitive=name,
            )


# ---------------------------------------------------------------------------
# liveness (dead-carry detection for JA001)
# ---------------------------------------------------------------------------


def used_inputs(closed_jaxpr) -> list[bool]:
    """Per-invar liveness: does the input contribute to any output? Uses
    jax's own DCE (handles jit/scan/while/cond sub-jaxpr recursion
    precisely); falls back to a coarse any-equation-reads-it sweep if the
    private API moves."""
    jaxpr = closed_jaxpr.jaxpr
    try:
        from jax._src.interpreters import partial_eval as pe

        _, used = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        return list(used)
    except Exception as exc:
        # the degradation must be VISIBLE: the coarse sweep cannot see a
        # carry that is read but discarded, so JA001 is weaker here
        print(
            f"[jaxpr-audit] note: DCE liveness unavailable ({exc!r}); "
            "falling back to coarse any-read liveness — JA001 may miss "
            "dead-after-read carries",
            file=sys.stderr,
        )
        from jax import core
        from jax.extend.core import Literal

        read: set = set()

        def sweep(j):
            for eqn in j.eqns:
                for v in eqn.invars:
                    if not isinstance(v, Literal):
                        read.add(v)
                for sub in core.jaxprs_in_params(eqn.params):
                    sweep(getattr(sub, "jaxpr", sub))
            for v in j.outvars:
                if not isinstance(v, Literal):
                    read.add(v)

        sweep(jaxpr)
        return [v in read for v in jaxpr.invars]


def carry_pairs():
    """(snapshot label, carry label) counterpart pairs, as input labels."""
    from scheduler_plugins_tpu.state.scheduling import TRACK_CARRY_COUNTERPARTS
    from scheduler_plugins_tpu.state.snapshot import CARRY_COUNTERPARTS

    pairs = []
    for suffix, field in {**CARRY_COUNTERPARTS,
                          **TRACK_CARRY_COUNTERPARTS}.items():
        pairs.append((f"snap{suffix}", f"state.{field}"))
    return pairs


# ---------------------------------------------------------------------------
# program audit
# ---------------------------------------------------------------------------


def audit_fn(fn, args, roles=None, mesh=None) -> dict:
    """Trace `fn(*args)` to a closed jaxpr and run every JA rule. `roles`
    optionally names the top-level arguments (see `label_leaves`); `mesh`
    wraps the trace in the ambient mesh (sharded programs)."""
    import jax

    if mesh is not None:
        with jax.set_mesh(mesh):
            closed = jax.make_jaxpr(fn)(*args)
    else:
        closed = jax.make_jaxpr(fn)(*args)
    labels = label_leaves(args, roles)
    if len(labels) != len(closed.jaxpr.invars):
        raise RuntimeError(
            f"label/invar mismatch: {len(labels)} leaves vs "
            f"{len(closed.jaxpr.invars)} invars (kwargs or non-leaf "
            "arguments are not supported by the auditor)"
        )
    auditor = Auditor()
    out_taints = auditor.propagate(
        closed.jaxpr, [frozenset([label]) for label in labels]
    )
    out_union = frozenset().union(*out_taints) if out_taints else _EMPTY

    live = used_inputs(closed)
    live_labels = {lab for lab, u in zip(labels, live) if u}
    label_set = set(labels)
    for snap_label, carry_label in carry_pairs():
        if snap_label not in label_set or carry_label not in label_set:
            continue  # the pair must exist in THIS program's inputs
        if snap_label in out_union and carry_label not in live_labels:
            auditor._add(
                "JA001",
                f"outputs depend on static {snap_label!r} while its carry "
                f"counterpart {carry_label!r} is dead in the jaxpr — the "
                "solve bypassed the SolverState carry",
                snapshot=snap_label,
                carry=carry_label,
            )

    rule_counts = {r: 0 for r in RULES}
    for v in auditor.violations:
        rule_counts[v["rule"]] += 1
    return {
        "rules": rule_counts,
        "violations": auditor.violations,
        "eqns": auditor.eqn_count,
        "provenance_ops": {
            k: auditor.op_counts[k] for k in sorted(auditor.op_counts)
        },
        # kernel-body primitive census over pallas_call equations ({} for
        # programs without kernels): the committed jaxpr-level evidence
        # for what lives inside the opaque tpu_custom_call payloads
        "pallas_kernels": {
            k: auditor.pallas_ops[k] for k in sorted(auditor.pallas_ops)
        },
        "output_provenance": classify(out_union),
    }


def audit_program(name: str) -> dict:
    fn, args, mesh = PROGRAMS[name]()
    return audit_fn(fn, args, roles=ROLE_OVERRIDES.get(name), mesh=mesh)


# ---------------------------------------------------------------------------
# driver (mirrors tools/tpu_lower.py: fail-closed --check, committed digest)
# ---------------------------------------------------------------------------


def run(names, check: bool) -> int:
    import jax

    prior = {}
    if MANIFEST.exists():
        prior = json.loads(MANIFEST.read_text())
    results, failures = {}, []
    for name in names:
        print(f"[jaxpr-audit] {name} ...", flush=True)
        try:
            results[name] = audit_program(name)
        except Exception as exc:  # a program that cannot trace IS a failure
            failures.append(f"{name}: trace failed: {exc!r}")
            continue
        res = results[name]
        for v in res["violations"]:
            failures.append(f"{name}: {v['rule']} {v['detail']}")
        print(
            f"[jaxpr-audit] {name}: {res['eqns']} eqns, "
            f"{sum(res['rules'].values())} violations, "
            f"output provenance {res['output_provenance']}",
            flush=True,
        )

    manifest = {
        "jax": jax.__version__,
        "programs": {
            n: {
                "rules": r["rules"],
                "eqns": r["eqns"],
                "provenance_ops": r["provenance_ops"],
                "pallas_kernels": r["pallas_kernels"],
                "output_provenance": r["output_provenance"],
            }
            for n, r in sorted(results.items())
        },
    }

    if check and not prior:
        failures.append(
            "docs/jaxpr_audit.json missing: run `python tools/jaxpr_audit.py`"
            " and commit it"
        )
    if check and prior:
        prior_programs = prior.get("programs", {})
        missing = [n for n in names if n in PROGRAMS and n not in prior_programs]
        if missing:
            failures.append(
                f"manifest missing programs {missing}: run "
                "`python tools/jaxpr_audit.py` and commit docs/jaxpr_audit.json"
            )
        for n, p in prior_programs.items():
            dirty = {r: c for r, c in p.get("rules", {}).items() if c}
            if dirty:
                failures.append(f"manifest records violations for {n}: {dirty}")
        if prior.get("jax") == jax.__version__:
            for n, r in results.items():
                want = prior_programs.get(n, {})
                if want and (
                    want.get("eqns") != r["eqns"]
                    or want.get("provenance_ops") != r["provenance_ops"]
                    or want.get("pallas_kernels", {})
                    != r["pallas_kernels"]
                ):
                    failures.append(
                        f"{n}: jaxpr census drift vs manifest — intended? "
                        "re-run `python tools/jaxpr_audit.py` and commit the "
                        "manifest diff"
                    )
        else:
            print(
                f"[jaxpr-audit] note: manifest written under jax "
                f"{prior.get('jax')}, running {jax.__version__}; census "
                "equality not enforced, rule/coverage gates still apply"
            )

    if not check and set(names) == set(PROGRAMS) and not failures:
        MANIFEST.write_text(json.dumps(manifest, indent=1, sort_keys=True) + "\n")
        print(f"[jaxpr-audit] wrote {MANIFEST.relative_to(REPO)}")
    elif not check:
        reason = "failures" if failures else "partial program set"
        print(f"[jaxpr-audit] {reason}: manifest NOT rewritten")

    for f in failures:
        print(f"[jaxpr-audit] FAIL: {f}", file=sys.stderr)
    if not failures:
        print(
            f"[jaxpr-audit] OK: {len(results)}/{len(names)} programs audit "
            "clean (JA001-JA004)"
        )
    return 1 if failures else 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--check",
        action="store_true",
        help="read-only: verify against the committed manifest (census "
        "equality enforced only under the manifest's jax version)",
    )
    parser.add_argument(
        "--programs",
        nargs="+",
        choices=sorted(PROGRAMS),
        default=sorted(PROGRAMS),
        help="subset of programs (default: all)",
    )
    args = parser.parse_args(argv)
    bootstrap()
    return run(args.programs, check=args.check)


if __name__ == "__main__":
    sys.exit(main())
