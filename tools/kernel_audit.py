#!/usr/bin/env python
"""Kernel-resource & numeric-exactness auditor: static VMEM envelopes,
DMA discipline, and the 2^53 exactness lattice (ISSUE 18).

Third static-analysis prong. `tools/graft_lint.py` enforces the CLAUDE.md
invariants on the source AST; `tools/jaxpr_audit.py` proves carry/
donation/i64/effect invariants on the traced programs; this tool audits
the ON-CHIP and NUMERIC surface of the same program registry
(`tools/tpu_lower.PROGRAMS`): what the Pallas kernels resident-allocate,
whether their DMA protocol is balanced on every control path, and whether
the float64/int32 arithmetic the solver calls "exact" actually stays
inside the representable range.

Rules:

- **KA001 VMEM envelope** — every `pallas_call` body's worst-case VMEM
  footprint, computed statically from its block-mapped ref shapes x
  dtypes x double-buffer copies (grid-pipelined operands count twice) +
  VMEM scratch, must fit the per-target budget table
  (`parallel.vmem.VMEM_BUDGET_BYTES`); semaphores live in semaphore
  memory and are counted separately. The per-kernel envelopes are
  committed to docs/kernel_audit.json, and the solver's
  `PALLAS_MAX_ELECTION_ELEMS` gate must equal the envelope-derived
  threshold (`parallel.vmem.derive_max_election_elems`) with the traced
  worst-case payload-copy count no worse than the family table the
  derivation uses — the gate is machine-checked, not hand-picked.
- **KA002 DMA discipline** — inside every kernel body: each
  `make_async_remote_copy` start must have a matching wait on ALL
  control paths (cond branches must leave the same in-flight set, loop
  bodies must be balanced), no wait before the corresponding start, and
  no (semaphore, slot) pair re-armed while its copy is still in flight.
- **KA003 exactness lattice** — declared static bounds on the input
  families (`api.bounds.LABEL_BOUNDS`, int64 reference units) propagate
  through casts, sums, cumsums, dot_generals, scatters and scan/while
  carries as a max-abs interval lattice with provenance taint. Flagged,
  with the provenance chain: any float64 accumulation of exact integer
  quantity operands whose result cannot be proven < 2^53, any int64 ->
  float64 cast of a quantity not provably < 2^53 (outside the blessed
  helpers `api.bounds.EXACT_FN_BOUNDS`), and any int32 demotion of a
  quantity not provably < 2^31. Where the naive interval overflows on a
  QUANTITY aggregation, the declared cluster-total invariant
  (`QUANTITY_SUM_MAX`) is substituted and the assumption is RECORDED in
  the manifest — every scattered "exact < 2^53" comment becomes either
  an arithmetic fact or a named, committed assumption.

A manifest (`docs/kernel_audit.json`: per-program rule verdicts, per-
kernel envelopes, DMA censuses, recorded assumptions, the derived
election threshold) is committed so drift shows up as a diff; `--check`
is the read-only fail-closed CI gate (missing manifest fails, rule
violations always fail, census equality enforced only under the
manifest's jax version). The manifest is never rewritten while
`SPT_PALLAS_MAX_ELECTION_ELEMS` overrides the derived gate.

Usage:
    python tools/kernel_audit.py             # audit all, write manifest
    python tools/kernel_audit.py --check     # read-only verify vs manifest
    python tools/kernel_audit.py --programs entry pallas_ring_offsets
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections import Counter
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
MANIFEST = REPO / "docs" / "kernel_audit.json"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.jaxpr_audit import (  # noqa: E402  (registry + labeling reuse)
    _CALL_PRIMS,
    ROLE_OVERRIDES,
    label_leaves,
)
from tools.tpu_lower import PROGRAMS, bootstrap  # noqa: E402

RULES = ("KA001", "KA002", "KA003")

#: the pallas kernel programs' positional args are election payloads —
#: declared-quantity roles the generic type-derived labeling can't see
KA_ROLE_OVERRIDES = {
    **ROLE_OVERRIDES,
    "pallas_ring_offsets": ("elect.payload",),
    "pallas_fused_election": ("elect.keys", "elect.payload"),
    # flagship_solve_stats(snap, weights): the int64 allocatable-weight
    # vector is aux-channel plugin config, declared <= 2^20 in
    # api.bounds (the reference's resource_allocation.go weight range)
    "bench_cfg0_tpu_smoke": ("snap", "aux.weights"),
    "bench_cfg1_flagship": ("snap", "aux.weights"),
}

#: f64 ops that CLAIM integer exactness when fed exact integer operands
#: (an f64 div/exp/etc. is score math — approximate by design, no claim)
_ACCUM_PRIMS = frozenset(
    {"add", "sub", "mul", "dot_general", "reduce_sum", "cumsum"}
)

#: aggregation primitives eligible for the declared cluster-total cap
_EMPTY = frozenset()


def _aval(v):
    return getattr(v, "aval", None)


def _dtype_str(v) -> str:
    aval = _aval(v)
    return str(getattr(aval, "dtype", ""))


def _shape(v):
    aval = _aval(v)
    return tuple(getattr(aval, "shape", ()))


def _is_sem_ref(v) -> bool:
    s = str(_aval(v))
    return "semaphore" in s or "dma_sem" in s


class Val:
    """One lattice point: provenance taint, max-abs bound (None =
    unknown), exactness (the value is an integer held exactly in its
    dtype), and quantity kind ("elem" = declared per-element resource
    quantity, "sum" = aggregation of quantities under the declared
    cluster-total invariant, "plain" = no quantity semantics)."""

    __slots__ = ("taint", "bound", "exact", "kind")

    def __init__(self, taint=_EMPTY, bound=None, exact=False, kind="plain"):
        self.taint = taint
        self.bound = bound
        self.exact = exact
        self.kind = kind

    def key(self):
        return (self.taint, self.bound, self.exact, self.kind)

    def quantity(self) -> bool:
        return self.kind in ("elem", "sum")


def _neutral(v: Val) -> bool:
    """A side proven |x| <= 1 (the literal arm of `where(mask, q, 0)`,
    `maximum(q, 0)`, a reset-to-1 segment sentinel) is kind-NEUTRAL in a
    join: masking or seeding a quantity stream with 0/±1 constants does
    not change what the aggregation invariant bounds (QUANTITY_SUM_MAX
    has cluster-scale headroom over per-lane ±1 sentinels)."""
    return v.bound is not None and v.bound <= 1


def _kind_join(a: Val, b: Val) -> str:
    """Kind of a two-way join/merge, with 0/±1 sides kind-neutral."""
    if _neutral(b):
        return a.kind
    if _neutral(a):
        return b.kind
    if a.kind == b.kind:
        return a.kind
    return "sum" if a.quantity() and b.quantity() else "plain"


def _join(a: Val, b: Val) -> Val:
    """Control-flow join: union taint, weakest bound/exactness/kind."""
    bound = None if (a.bound is None or b.bound is None) else max(a.bound, b.bound)
    return Val(a.taint | b.taint, bound, a.exact and b.exact,
               _kind_join(a, b))


def _badd(a, b):
    return None if (a is None or b is None) else a + b


def _bmul(a, b):
    return None if (a is None or b is None) else a * b


def _bmax(*bs):
    if any(b is None for b in bs):
        return None
    return max(bs) if bs else None


class KernelAuditor:
    """Forward interval/taint walk over a closed jaxpr with recursive
    sub-jaxpr handling (KA003), plus per-`pallas_call` VMEM envelope
    accounting (KA001) and DMA-protocol simulation (KA002)."""

    def __init__(self, axis_sizes=None):
        from scheduler_plugins_tpu.api import bounds as B

        self.B = B
        self.axis_sizes = dict(axis_sizes or {})
        self.violations: list[dict] = []
        self.assumptions: set[str] = set()
        self.kernels: list[dict] = []
        self.dma_census: Counter = Counter()
        self.eqn_count = 0
        self._scanned: set[int] = set()
        self._seen_sites: set = set()

    # -- violation/assumption plumbing --------------------------------

    def _add(self, rule, detail, **extra):
        key = (rule, detail)
        if key in self._seen_sites:
            return
        self._seen_sites.add(key)
        self.violations.append({"rule": rule, "detail": detail, **extra})

    def _assume(self, text):
        self.assumptions.add(text)

    def _prov(self, vals) -> str:
        labels = sorted(frozenset().union(*[v.taint for v in vals]) or {"const"})
        return ",".join(labels)

    @staticmethod
    def _kernel_name(eqn) -> str:
        """Stable kernel name of a pallas_call eqn: the explicit `name=`
        (kernels._ring_call passes the vmem.RING_FAMILIES family)."""
        return str(eqn.params.get("name") or "pallas_kernel")

    @staticmethod
    def _site(eqn) -> str:
        """Best-effort `file:line(function)` of the traced call site —
        diagnostic text for the console report, NOT keyed into the
        manifest (line drift must not dirty the committed digest)."""
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return ""
        fname = frame.file_name.rsplit("/", 1)[-1]
        return f" at {fname}:{frame.start_line}({frame.function_name})"

    # -- the walk -----------------------------------------------------

    def propagate(self, jaxpr, in_vals):
        from jax.extend.core import Literal

        env: dict = {}

        def read(v):
            if isinstance(v, Literal):
                return self._literal(v)
            return env.get(v, Val())

        def write(var, val):
            if type(var).__name__ == "DropVar":
                return
            prev = env.get(var)
            env[var] = val if prev is None else _join(prev, val)

        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        for var in jaxpr.constvars:
            env[var] = Val(exact="int" in _dtype_str(var) or
                           _dtype_str(var) == "bool")
        for eqn in jaxpr.eqns:
            first = id(eqn) not in self._scanned
            vals = [read(v) for v in eqn.invars]
            outs = self._eqn(eqn, vals, first)
            if first:
                self._scanned.add(id(eqn))
                self.eqn_count += 1
            for var, val in zip(eqn.outvars, outs):
                write(var, val)
        return [read(v) for v in jaxpr.outvars]

    @staticmethod
    def _literal_value(var):
        """The concrete value of a jaxpr Literal operand, else None —
        sign-checkable constants (bit masks, clamp limits) support
        transfer rules that max-abs bounds alone cannot justify."""
        from jax.extend.core import Literal

        if isinstance(var, Literal):
            try:
                import numpy as np

                return np.asarray(var.val)
            except Exception:
                return None
        return None

    def _literal(self, lit) -> Val:
        import numpy as np

        try:
            arr = np.asarray(lit.val)
            bound = float(np.max(np.abs(arr))) if arr.size else 0.0
            if bound == int(bound):
                bound = int(bound)
            exact = bool(
                np.issubdtype(arr.dtype, np.integer)
                or arr.dtype == np.bool_
                or (np.issubdtype(arr.dtype, np.floating)
                    and np.all(arr == np.floor(arr)))
            )
            return Val(_EMPTY, bound, exact, "plain")
        except Exception:
            return Val()

    def _eqn(self, eqn, vals, first):
        name = eqn.primitive.name
        params = eqn.params
        if name == "jit":
            blessed = self.B.EXACT_FN_BOUNDS.get(params.get("name"))
            if blessed is not None:
                union = frozenset().union(*[v.taint for v in vals]) if vals else _EMPTY
                self._assume(
                    f"blessed exactness helper {params.get('name')!r}: result "
                    f"bound declared {blessed} (api.bounds.EXACT_FN_BOUNDS)"
                )
                return [
                    Val(union, blessed, True,
                        "sum" if any(v.quantity() for v in vals) else "plain")
                    for _ in eqn.outvars
                ]
        if name in _CALL_PRIMS and _CALL_PRIMS[name] in params:
            sub = params[_CALL_PRIMS[name]]
            sub_jaxpr = getattr(sub, "jaxpr", sub)
            if len(sub_jaxpr.invars) == len(vals):
                return self.propagate(sub_jaxpr, vals)
            return self._fallback(eqn, vals)
        if name == "scan":
            return self._scan(eqn, vals)
        if name == "while":
            return self._while(eqn, vals)
        if name == "cond":
            return self._cond(eqn, vals)
        if name == "pallas_call":
            return self._pallas(eqn, vals, first)
        return self._apply(eqn, vals, first)

    def _fallback(self, eqn, vals):
        from jax import core

        union = frozenset().union(*[v.taint for v in vals]) if vals else _EMPTY
        coarse = Val(union)
        for sub in core.jaxprs_in_params(eqn.params):
            sub_jaxpr = getattr(sub, "jaxpr", sub)
            self.propagate(sub_jaxpr, [coarse] * len(sub_jaxpr.invars))
        return [Val(union) for _ in eqn.outvars]

    # -- control flow -------------------------------------------------

    def _scan(self, eqn, vals):
        params = eqn.params
        sub = params["jaxpr"].jaxpr
        n_consts, n_carry = params["num_consts"], params["num_carry"]
        consts = vals[:n_consts]
        entry = vals[n_consts:n_consts + n_carry]
        xs = vals[n_consts + n_carry:]
        carry = list(entry)
        outs = None
        for _ in range(32):
            outs = self.propagate(sub, consts + carry + xs)
            new_carry = []
            changed = False
            for ent, cur, out in zip(entry, carry, outs[:n_carry]):
                nxt = self._carry_invariant(ent, cur, out, "scan")
                changed = changed or nxt.key() != cur.key()
                new_carry.append(nxt)
            if not changed:
                break
            carry = new_carry
        return carry + outs[n_carry:]

    def _while(self, eqn, vals):
        params = eqn.params
        cond_sub = params["cond_jaxpr"].jaxpr
        body_sub = params["body_jaxpr"].jaxpr
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts = vals[:cn]
        body_consts = vals[cn:cn + bn]
        entry = vals[cn + bn:]
        carry = list(entry)
        pred = Val()
        for _ in range(32):
            pred = self.propagate(cond_sub, cond_consts + carry)[0]
            outs = self.propagate(body_sub, body_consts + carry)
            new_carry = []
            changed = False
            for ent, cur, out in zip(entry, carry, outs):
                nxt = self._carry_invariant(ent, cur, out, "while")
                changed = changed or nxt.key() != cur.key()
                new_carry.append(nxt)
            if not changed:
                break
            carry = new_carry
        return [Val(c.taint | pred.taint, c.bound, c.exact, c.kind)
                for c in carry]

    def _carry_invariant(self, ent: Val, cur: Val, out: Val, what: str) -> Val:
        """Loop-carry bound policy: a carry whose body-out bound stays
        within the entry bound keeps it (proven inductive). A QUANTITY
        carry that grows takes the declared cluster-total cap (a loop
        accumulating quantities is a sum of quantities — assumption
        recorded). Anything else that grows degrades to UNKNOWN — the
        lattice never invents a bound it can't justify."""
        taint = cur.taint | out.taint
        exact = cur.exact and out.exact
        if ent.bound is not None and out.bound is not None \
                and out.bound <= ent.bound:
            return Val(taint, ent.bound, exact, cur.kind)
        if cur.quantity() or out.quantity():
            self._assume(
                f"{what} carry ({','.join(sorted(taint)) or 'const'}) grows "
                f"past its entry bound: held at QUANTITY_SUM_MAX by the "
                f"declared aggregation invariant"
            )
            return Val(taint, self.B.QUANTITY_SUM_MAX, exact, "sum")
        return Val(taint, None, exact, "plain")

    def _cond(self, eqn, vals):
        pred, oper = vals[0], vals[1:]
        outs = None
        for branch in eqn.params["branches"]:
            b_outs = self.propagate(branch.jaxpr, oper)
            outs = b_outs if outs is None else [
                _join(a, b) for a, b in zip(outs, b_outs)
            ]
        return [Val(o.taint | pred.taint, o.bound, o.exact, o.kind)
                for o in (outs or [])]

    # -- pallas_call: KA001 + KA002 + body walk -----------------------

    def _pallas(self, eqn, vals, first):
        sub = eqn.params.get("jaxpr")
        if sub is None:
            return self._fallback(eqn, vals)
        body = getattr(sub, "jaxpr", sub)
        if first:
            self.kernels.append(self._envelope(eqn, body))
            self._check_dma(eqn, body)
        in_vals = list(vals) + [
            Val(exact="int" in _dtype_str(v) or _dtype_str(v) == "bool")
            for v in body.invars[len(vals):]
        ]
        self.propagate(body, in_vals[: len(body.invars)])
        union = frozenset().union(*[v.taint for v in vals]) if vals else _EMPTY
        # kernel outputs: the ref->output mapping is opaque here, so the
        # bound is UNKNOWN and exactness is not claimed — but a kernel
        # fed quantities emits quantities (the ring programs sum/elect
        # them), so kind survives and the downstream aggregation
        # invariant can still apply.
        kind = "sum" if any(v.quantity() for v in vals) else "plain"
        return [Val(union, None, False, kind) for _ in eqn.outvars]

    def _envelope(self, eqn, body) -> dict:
        """KA001: static worst-case VMEM footprint of one kernel body."""
        import numpy as np

        from scheduler_plugins_tpu.parallel import vmem

        params = eqn.params
        gm = params.get("grid_mapping")
        grid = tuple(getattr(gm, "grid", ()) or ())
        grid_steps = int(np.prod(grid)) if grid else 1
        num_scratch = int(getattr(gm, "num_scratch_operands", 0))
        n_inv = len(body.invars)
        name = self._kernel_name(eqn)

        vmem_bytes = 0
        sem_slots = 0
        shapes: Counter = Counter()
        refs = []
        for i, v in enumerate(body.invars):
            if _is_sem_ref(v):
                sem_slots += int(np.prod(_shape(v))) if _shape(v) else 1
                continue
            shape = _shape(v)
            try:
                itemsize = np.dtype(str(_aval(v).dtype)).itemsize
            except Exception:
                itemsize = 4
            copies = 2 if (grid_steps > 1 and i < n_inv - num_scratch) else 1
            nbytes = int(np.prod(shape)) * itemsize * copies if shape else itemsize
            vmem_bytes += nbytes
            shapes[(shape, itemsize)] += copies
            refs.append({
                "shape": list(shape),
                "itemsize": itemsize,
                "copies": copies,
                "bytes": nbytes,
            })
        # whole-payload buffer equivalents: total VMEM over the bytes of
        # the modal (payload-shaped) buffer — the (3, Hp, Lp) comm
        # scratch counts as its 3 slots, matching how
        # vmem.ring_buffer_copies sizes the envelope (ceil: partial
        # buffers still occupy a copy's worth of budget headroom)
        budget = vmem.VMEM_BUDGET_BYTES[vmem.VMEM_TARGET]
        if shapes:
            (pshape, pitem), _ = shapes.most_common(1)[0]
            pbytes = (int(np.prod(pshape)) or 1) * pitem if pshape else pitem
            payload_copies = -(-vmem_bytes // pbytes)
        else:
            payload_copies = 0
        if vmem_bytes > budget:
            self._add(
                "KA001",
                f"kernel {name!r}: worst-case VMEM footprint {vmem_bytes} B "
                f"exceeds the {vmem.VMEM_TARGET} budget {budget} B",
                kernel=name,
            )
        # the budget table and the traced body must agree per family:
        # a new output or scratch buffer added to a ring kernel without
        # updating vmem.RING_FAMILIES would silently shrink the derived
        # election threshold's safety margin
        expect = vmem.RING_FAMILIES.get(name)
        if expect is not None \
                and payload_copies != vmem.ring_buffer_copies(expect):
            self._add(
                "KA001",
                f"kernel {name!r}: traced body holds {payload_copies} "
                f"same-shape payload buffers but vmem.RING_FAMILIES "
                f"declares {vmem.ring_buffer_copies(expect)} — the "
                f"envelope table is stale",
                kernel=name,
            )
        return {
            "name": name,
            "grid": list(grid),
            "vmem_bytes": vmem_bytes,
            "budget_bytes": budget,
            "double_buffered": grid_steps > 1,
            "payload_copies": payload_copies,
            "sem_slots": sem_slots,
            "refs": refs,
        }

    # -- KA002: DMA protocol simulation -------------------------------

    def _dma_tokens(self, eqn):
        """(sem var, slot) tokens named by one dma_start/dma_wait: each
        semaphore-ref operand pairs with its immediately following index
        operand (a Literal slot in the unrolled ring; a traced index
        degrades to the wildcard slot '?')."""
        from jax.extend.core import Literal

        toks = []
        invars = list(eqn.invars)
        for i, v in enumerate(invars):
            if isinstance(v, Literal) or not _is_sem_ref(v):
                continue
            slot = "?"
            if i + 1 < len(invars) and isinstance(invars[i + 1], Literal):
                try:
                    slot = int(invars[i + 1].val)
                except Exception:
                    slot = str(invars[i + 1].val)
            toks.append((v, slot))
        return toks

    def _token_name(self, tok, names):
        var, slot = tok
        return f"sem{names.setdefault(var, len(names))}[{slot}]"

    def _check_dma(self, eqn, body):
        """Simulate the start/wait protocol over the kernel body. `armed`
        maps (sem, slot) -> True while a copy is in flight; cond branches
        must agree on the resulting state, loop bodies must be balanced,
        and the body must end drained."""
        name = self._kernel_name(eqn)
        names: dict = {}
        starts = waits = 0

        def walk(jaxpr, armed: set) -> set:
            nonlocal starts, waits
            from jax import core

            for e in jaxpr.eqns:
                prim = e.primitive.name
                if prim == "dma_start":
                    starts += 1
                    self.dma_census[f"{name}.dma_start"] += 1
                    for tok in self._dma_tokens(e):
                        if tok in armed:
                            self._add(
                                "KA002",
                                f"kernel {name!r}: semaphore "
                                f"{self._token_name(tok, names)} re-armed "
                                "while its copy is still in flight",
                                kernel=name,
                            )
                        armed.add(tok)
                elif prim == "dma_wait":
                    waits += 1
                    self.dma_census[f"{name}.dma_wait"] += 1
                    toks = self._dma_tokens(e)
                    cleared = False
                    for tok in toks:  # first-listed semaphore preferred
                        if tok in armed:
                            armed.discard(tok)
                            cleared = True
                            break
                    if not cleared:
                        self._add(
                            "KA002",
                            f"kernel {name!r}: dma_wait on "
                            f"{[self._token_name(t, names) for t in toks]} "
                            "with no matching in-flight start "
                            "(wait-before-start)",
                            kernel=name,
                        )
                elif prim == "cond":
                    ends = []
                    for branch in e.params["branches"]:
                        ends.append(walk(branch.jaxpr, set(armed)))
                    if any(end != ends[0] for end in ends[1:]):
                        self._add(
                            "KA002",
                            f"kernel {name!r}: in-flight DMA set diverges "
                            "across cond branches",
                            kernel=name,
                        )
                    armed = set().union(*ends) if ends else armed
                elif prim in ("scan", "while"):
                    subs = []
                    if prim == "scan":
                        subs = [e.params["jaxpr"].jaxpr]
                    else:
                        subs = [e.params["body_jaxpr"].jaxpr]
                    for sub in subs:
                        end = walk(sub, set(armed))
                        if end != armed:
                            self._add(
                                "KA002",
                                f"kernel {name!r}: {prim} body leaves the "
                                "in-flight DMA set unbalanced",
                                kernel=name,
                            )
                else:
                    for sub in core.jaxprs_in_params(e.params):
                        armed = walk(getattr(sub, "jaxpr", sub), armed)
            return armed

        leftover = walk(body, set())
        for tok in sorted(
            leftover, key=lambda t: self._token_name(t, names)
        ):
            self._add(
                "KA002",
                f"kernel {name!r}: dma_start on "
                f"{self._token_name(tok, names)} never waited on "
                "(missing wait on some control path)",
                kernel=name,
            )
        if self.kernels:
            self.kernels[-1]["dma_starts"] = starts
            self.kernels[-1]["dma_waits"] = waits

    # -- KA003: per-primitive interval transfer + exactness checks ----

    def _agg(self, v: Val, n, what: str) -> Val:
        """Aggregate `n` elements of `v` (sum/cumsum/psum/scatter-add):
        naive interval when provable, the declared cluster-total cap for
        quantity operands otherwise (assumption recorded), UNKNOWN else."""
        naive = _bmul(v.bound, n)
        if naive is not None and naive < self.B.F64_EXACT_MAX:
            return Val(v.taint, naive,
                       v.exact, "sum" if v.quantity() else "plain")
        if v.quantity():
            self._assume(
                f"{what} over quantity family "
                f"({','.join(sorted(v.taint)) or 'const'}) bounded by "
                f"QUANTITY_SUM_MAX (declared aggregation invariant)"
            )
            return Val(v.taint, self.B.QUANTITY_SUM_MAX, v.exact, "sum")
        # non-quantity overflow of the 2^53 line: the naive interval is
        # still a SOUND max-abs (int64 holds it) — keep it so downstream
        # demotions/casts are judged against a number, not UNKNOWN
        return Val(v.taint, naive, v.exact, "plain")

    def _apply(self, eqn, vals, first):
        import numpy as np

        B = self.B
        name = eqn.primitive.name
        params = eqn.params
        union = frozenset().union(*[v.taint for v in vals]) if vals else _EMPTY
        out_dt = _dtype_str(eqn.outvars[0]) if eqn.outvars else ""

        def mk(bound=None, exact=False, kind="plain", taint=union):
            return Val(taint, bound, exact, kind)

        out = None
        if name in ("add", "sub"):
            a, b = vals
            if b.bound == 0:
                out = mk(a.bound, a.exact and b.exact, a.kind)
            elif a.bound == 0:
                out = mk(b.bound, a.exact and b.exact, b.kind)
            elif a.quantity() and b.quantity():
                naive = _badd(a.bound, b.bound)
                if naive is not None and naive < B.F64_EXACT_MAX:
                    out = mk(naive, a.exact and b.exact, "sum"
                             if "sum" in (a.kind, b.kind) else "elem")
                else:
                    self._assume(
                        f"{name} of quantity families "
                        f"({','.join(sorted(union)) or 'const'}) bounded by "
                        f"QUANTITY_SUM_MAX (declared aggregation invariant)"
                    )
                    out = mk(B.QUANTITY_SUM_MAX, a.exact and b.exact, "sum")
            else:
                out = mk(_badd(a.bound, b.bound), a.exact and b.exact)
        elif name == "mul":
            a, b = vals
            # multiplying by a proven 0/±1 factor (bool masks, sign
            # flips) preserves quantity kind — it's masking, not scaling
            kind = "plain"
            if b.bound is not None and b.bound <= 1 and b.exact:
                kind = a.kind
            elif a.bound is not None and a.bound <= 1 and a.exact:
                kind = b.kind
            out = mk(_bmul(a.bound, b.bound), a.exact and b.exact, kind)
        elif name in ("neg", "abs", "stop_gradient", "copy", "real"):
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        elif name in ("max", "min"):
            a, b = vals
            out = mk(_bmax(a.bound, b.bound), a.exact and b.exact,
                     _kind_join(a, b))
        elif name == "select_n":
            branches = vals[1:]
            bound = _bmax(*[v.bound for v in branches])
            exact = all(v.exact for v in branches)
            # 0/±1 arms (the `where(mask, q, 0)` masking idiom) are
            # kind-neutral; the live arms decide
            live = [v for v in branches if not _neutral(v)]
            kinds = {v.kind for v in live}
            kind = kinds.pop() if len(kinds) == 1 else (
                "sum" if live and all(v.quantity() for v in live)
                else "plain")
            out = mk(bound, exact, kind)
        elif name == "clamp":
            lo, x, hi = vals
            if lo.bound is not None and hi.bound is not None:
                out = mk(max(lo.bound, hi.bound), x.exact and lo.exact
                         and hi.exact, x.kind)
            else:
                out = mk(x.bound, x.exact and lo.exact and hi.exact, x.kind)
        elif name == "convert_element_type":
            out = self._convert(eqn, vals[0], union)
        elif name in ("broadcast_in_dim", "reshape", "transpose", "squeeze",
                      "expand_dims", "rev", "reduce_precision"):
            v = vals[0]
            exact = v.exact and name != "reduce_precision"
            out = mk(v.bound, exact, v.kind)
        elif name in ("slice", "dynamic_slice", "gather"):
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        elif name in ("dynamic_update_slice",):
            a, b = vals[0], vals[1]
            out = mk(_bmax(a.bound, b.bound), a.exact and b.exact,
                     _kind_join(a, b))
        elif name == "concatenate":
            # fold the pairwise kind join (zero-segment seeds stay
            # neutral — the exclusive-prefix idiom concatenates [0, ...])
            acc = vals[0]
            for v in vals[1:]:
                acc = Val(acc.taint | v.taint,
                          _bmax(acc.bound, v.bound),
                          acc.exact and v.exact, _kind_join(acc, v))
            out = mk(acc.bound, acc.exact, acc.kind)
        elif name == "pad":
            x, padv = vals[0], vals[1]
            out = mk(_bmax(x.bound, padv.bound), x.exact and padv.exact,
                     x.kind)
        elif name == "iota":
            dim = params.get("dimension", 0)
            shape = params.get("shape") or _shape(eqn.outvars[0])
            n = shape[dim] if shape else 0
            out = Val(_EMPTY, max(int(n) - 1, 0), True, "plain")
        elif name in ("argmin", "argmax"):
            axes = params.get("axes", ())
            shape = _shape(eqn.invars[0])
            n = int(np.prod([shape[a] for a in axes])) if shape else 1
            out = mk(max(n - 1, 0), True)
        elif name == "reduce_sum":
            axes = params.get("axes", ())
            shape = _shape(eqn.invars[0])
            n = int(np.prod([shape[a] for a in axes])) if axes else 1
            out = self._agg(vals[0], max(n, 1), "reduce_sum")
        elif name == "cumsum":
            axis = params.get("axis", 0)
            shape = _shape(eqn.invars[0])
            n = shape[axis] if shape else 1
            out = self._agg(vals[0], max(int(n), 1), "cumsum")
        elif name in ("reduce_max", "reduce_min", "cummax", "cummin"):
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        elif name in ("reduce_and", "reduce_or", "reduce_xor"):
            out = mk(1, True)
        elif name == "reduce_prod":
            out = mk(None, vals[0].exact)
        elif name == "dot_general":
            a, b = vals[0], vals[1]
            dims = params.get("dimension_numbers")
            k = 1
            try:
                (lc, _rc), _ = dims
                shape = _shape(eqn.invars[0])
                k = int(np.prod([shape[d] for d in lc])) if lc else 1
            except Exception:
                k = None
            out = mk(_bmul(_bmul(a.bound, b.bound), k),
                     a.exact and b.exact)
        elif name == "sort":
            out_vals = [mk(v.bound, v.exact, v.kind, taint=union)
                        for v in vals]
            return out_vals
        elif name == "rem":
            a, b = vals
            out = mk(b.bound if b.bound is not None else a.bound,
                     a.exact and b.exact, a.kind)
        elif name == "div":
            a, b = vals
            if "int" in out_dt:
                out = mk(a.bound, a.exact and b.exact, a.kind)
            else:
                out = mk(a.bound, False)
        elif name == "sign":
            out = mk(1, True)
        elif name == "floor" or name == "ceil" or name.startswith("round"):
            v = vals[0]
            exact = v.bound is not None and v.bound < B.F64_EXACT_MAX
            out = mk(_badd(v.bound, 1), exact, v.kind)
        elif name == "integer_pow":
            v = vals[0]
            y = params.get("y", 1)
            b = None
            if v.bound is not None and abs(y) < 16:
                try:
                    b = v.bound ** y if y >= 0 else None
                except OverflowError:
                    b = None
            out = mk(b, v.exact and y >= 0)
        elif name == "shift_left":
            a, s = vals
            b = _bmul(a.bound, None if s.bound is None else 2 ** min(
                int(s.bound), 63))
            out = mk(b, a.exact and s.exact, a.kind)
        elif name in ("shift_right_logical", "shift_right_arithmetic"):
            out = mk(vals[0].bound, vals[0].exact, vals[0].kind)
        elif name in ("and", "or", "xor"):
            a, b = vals
            known = [x for x in (a.bound, b.bound) if x is not None]
            bound = max(known) if known else None
            kind = _kind_join(a, b)
            if name == "and":
                # x & m with a literal NONNEGATIVE mask m lands in
                # [0, m] (two's complement) — the limb-split idiom
                # (`row >> s & (2^18 - 1)`) becomes provably int32-safe.
                # min-of-bounds alone would be UNSOUND (m = -1 is all
                # ones), so the mask side must be a literal we can sign-
                # check.
                for i, other in ((0, b), (1, a)):
                    lit = self._literal_value(eqn.invars[i])
                    if lit is not None and np.all(np.asarray(lit) >= 0):
                        m = int(np.max(np.asarray(lit))) if np.size(lit) \
                            else 0
                        bound = m if bound is None else min(bound, m)
                        kind = other.kind
            out = mk(bound, a.exact and b.exact, kind)
        elif name == "not":
            out = mk(1, True)
        elif name in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite"):
            out = mk(1, True)
        elif name == "psum":
            axes = params.get("axes", ())
            n = 1
            for ax in axes:
                size = self.axis_sizes.get(ax)
                if size is None:
                    n = None
                    break
                n *= size
            if n is None:
                out = self._agg(vals[0], None, "psum")
            else:
                out = self._agg(vals[0], n, "psum")
            if len(vals) > 1:  # multi-operand psum: coarse per-output
                return [self._agg(v, n, "psum") for v in vals]
        elif name in ("pmin", "pmax", "all_gather", "ppermute",
                      "pbroadcast"):
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        elif name == "axis_index":
            ax = params.get("axis_name")
            size = self.axis_sizes.get(ax)
            out = Val(_EMPTY, (size - 1) if size else None, True, "plain")
        elif name.startswith("scatter"):
            oper, upd = vals[0], vals[-1]
            if name in ("scatter-add", "scatter_add"):
                upd_n = int(np.prod(_shape(eqn.invars[-1]))) or 1
                grown = self._agg(upd, upd_n, "scatter-add")
                if oper.bound == 0:
                    # segment-sum idiom: scatter quantity updates into a
                    # zeros accumulator — the result IS the aggregation
                    out = mk(grown.bound, oper.exact and upd.exact,
                             grown.kind, taint=oper.taint | grown.taint)
                elif oper.quantity() and grown.quantity():
                    out = self._agg(_join(oper, grown), 2, "scatter-add")
                else:
                    out = mk(_badd(oper.bound, grown.bound),
                             oper.exact and upd.exact,
                             _kind_join(oper, grown))
            else:
                out = mk(_bmax(oper.bound, upd.bound),
                         oper.exact and upd.exact, _kind_join(oper, upd))
        elif name in ("exp", "log", "log1p", "tanh", "logistic", "sqrt",
                      "rsqrt", "pow", "erf", "sin", "cos", "expm1",
                      "cbrt", "atan2"):
            out = mk(None, False)
        elif name == "get":
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        elif name in ("swap", "addupdate", "masked_swap", "masked_load",
                      "masked_store"):
            v = vals[0]
            out = mk(v.bound, v.exact, v.kind)
        else:
            return self._fallback(eqn, vals)

        if out is None:
            out = mk()
        # the KA003 f64-accumulation flag: an op that CLAIMS exactness
        # (integer operands carried in f64) must prove its result < 2^53
        if (first and name in _ACCUM_PRIMS and out_dt == "float64"
                and vals and all(v.exact for v in vals)
                and any(v.quantity() for v in vals)
                and (out.bound is None or out.bound >= B.F64_EXACT_MAX)):
            self._add(
                "KA003",
                f"float64 {name} of exact integer quantity operands not "
                f"provably < 2^53 (bound="
                f"{'unknown' if out.bound is None else int(out.bound)}; "
                f"provenance: {self._prov(vals)}){self._site(eqn)}",
                primitive=name,
            )
            out = Val(out.taint, out.bound, False, out.kind)
        return [out] + [Val(union) for _ in eqn.outvars[1:]]

    def _convert(self, eqn, v: Val, union) -> Val:
        B = self.B
        new = str(eqn.params.get("new_dtype", ""))
        first = id(eqn) not in self._scanned
        src = _dtype_str(eqn.invars[0])
        # scope: the KIND lattice decides what is a quantity — the
        # transfer rules carry kind through masking/selection/aggregation,
        # so taint (reported as provenance) does not widen the net to
        # score/index values that merely DEPEND on quantities
        quantity = v.quantity()
        if new == "float64":
            exact = v.exact and v.bound is not None \
                and v.bound < B.F64_EXACT_MAX
            if (first and quantity and v.exact and not exact
                    and src.startswith("int")):
                self._add(
                    "KA003",
                    f"int64 -> float64 cast of quantity not provably "
                    f"< 2^53 (bound="
                    f"{'unknown' if v.bound is None else int(v.bound)}; "
                    f"provenance: {self._prov([v])}){self._site(eqn)} — "
                    "route through a blessed helper "
                    "(utils.intmath.exact_f64) or declare the bound in "
                    "api.bounds",
                    primitive="convert_element_type",
                )
            return Val(union, v.bound, exact, v.kind)
        if new in ("int32", "uint32"):
            if (first and quantity and src in ("int64", "float64",
                                               "float32")
                    and (v.bound is None or v.bound >= B.I32_MAX)):
                self._add(
                    "KA003",
                    f"{src} -> {new} demotion of quantity not provably "
                    f"< 2^31 (bound="
                    f"{'unknown' if v.bound is None else int(v.bound)}; "
                    f"provenance: {self._prov([v])}){self._site(eqn)}",
                    primitive="convert_element_type",
                )
            bound = v.bound if v.bound is not None else None
            if bound is not None:
                bound = min(bound, B.I32_MAX - 1)
            return Val(union, bound, "int" in src or src == "bool", v.kind)
        if new == "float32":
            exact = v.exact and v.bound is not None and v.bound < (1 << 24)
            return Val(union, v.bound, exact, v.kind)
        if new in ("int64", "uint64"):
            return Val(union, v.bound, v.exact or "int" in src
                       or src == "bool", v.kind)
        if new == "bool":
            return Val(union, 1, True, "plain")
        return Val(union, v.bound, False, v.kind)


# ---------------------------------------------------------------------------
# program audit
# ---------------------------------------------------------------------------


def audit_fn(fn, args, roles=None, mesh=None) -> dict:
    """Trace `fn(*args)` to a closed jaxpr and run every KA rule."""
    import jax

    from scheduler_plugins_tpu.api import bounds as B
    if mesh is not None:
        with jax.set_mesh(mesh):
            closed = jax.make_jaxpr(fn)(*args)
        axis_sizes = dict(mesh.shape)
    else:
        closed = jax.make_jaxpr(fn)(*args)
        axis_sizes = {}
    labels = label_leaves(args, roles)
    if len(labels) != len(closed.jaxpr.invars):
        raise RuntimeError(
            f"label/invar mismatch: {len(labels)} leaves vs "
            f"{len(closed.jaxpr.invars)} invars"
        )
    auditor = KernelAuditor(axis_sizes)
    in_vals = []
    for label, var in zip(labels, closed.jaxpr.invars):
        dt = _dtype_str(var)
        bound, kind = B.leaf_bound(label, dt)
        exact = ("int" in dt or dt == "bool"
                 or (dt == "float64" and kind == "elem"))
        in_vals.append(Val(frozenset([label]), bound, exact, kind))
    auditor.propagate(closed.jaxpr, in_vals)

    rule_counts = {r: 0 for r in RULES}
    for v in auditor.violations:
        rule_counts[v["rule"]] += 1
    return {
        "rules": rule_counts,
        "violations": auditor.violations,
        "eqns": auditor.eqn_count,
        "kernels": auditor.kernels,
        "dma_census": {
            k: auditor.dma_census[k] for k in sorted(auditor.dma_census)
        },
        "assumptions": sorted(auditor.assumptions),
    }


def audit_program(name: str) -> dict:
    fn, args, mesh = PROGRAMS[name]()
    return audit_fn(fn, args, roles=KA_ROLE_OVERRIDES.get(name), mesh=mesh)


def envelope_summary() -> dict:
    """The shared VMEM envelope section of the manifest: budget table
    target, the envelope-derived election threshold, and the solver
    gate actually in force (KA001 fails when they drift apart)."""
    from scheduler_plugins_tpu.parallel import kernels, vmem

    derived = vmem.derive_max_election_elems()
    return {
        "target": vmem.VMEM_TARGET,
        "budget_bytes": vmem.VMEM_BUDGET_BYTES[vmem.VMEM_TARGET],
        "worst_ring_copies": vmem.WORST_RING_COPIES,
        "derived_max_election_elems": derived,
        "solver_gate": kernels.PALLAS_MAX_ELECTION_ELEMS,
        # PR 13 hand-picked 1 << 19; the derivation lands on the same
        # number, so replacing the guess changed its provenance, not the
        # fallback behavior (delta 0)
        "previous_hand_picked": 1 << 19,
    }


# ---------------------------------------------------------------------------
# driver (mirrors tools/jaxpr_audit.py: fail-closed --check, committed
# manifest)
# ---------------------------------------------------------------------------


def run(names, check: bool) -> int:
    import jax

    from scheduler_plugins_tpu.parallel import vmem

    prior = {}
    if MANIFEST.exists():
        prior = json.loads(MANIFEST.read_text())

    env = envelope_summary()
    failures = []
    if env["solver_gate"] != env["derived_max_election_elems"]:
        if os.environ.get("SPT_PALLAS_MAX_ELECTION_ELEMS"):
            print(
                "[kernel-audit] note: SPT_PALLAS_MAX_ELECTION_ELEMS "
                f"override in force (gate {env['solver_gate']}, derived "
                f"{env['derived_max_election_elems']})"
            )
        else:
            failures.append(
                "KA001 PALLAS_MAX_ELECTION_ELEMS "
                f"({env['solver_gate']}) != envelope-derived threshold "
                f"({env['derived_max_election_elems']}): the solver gate "
                "drifted from parallel/vmem.py"
            )

    results = {}
    worst_payload_copies = 0
    for name in names:
        print(f"[kernel-audit] {name} ...", flush=True)
        try:
            results[name] = audit_program(name)
        except Exception as exc:  # a program that cannot trace IS a failure
            failures.append(f"{name}: trace failed: {exc!r}")
            continue
        res = results[name]
        for v in res["violations"]:
            failures.append(f"{name}: {v['rule']} {v['detail']}")
        for k in res["kernels"]:
            worst_payload_copies = max(
                worst_payload_copies, k["payload_copies"]
            )
        print(
            f"[kernel-audit] {name}: {res['eqns']} eqns, "
            f"{len(res['kernels'])} kernels, "
            f"{sum(res['rules'].values())} violations, "
            f"{len(res['assumptions'])} assumptions",
            flush=True,
        )

    # the family table the threshold derivation uses must be no tighter
    # than what the traced kernels actually allocate
    if worst_payload_copies > vmem.WORST_RING_COPIES:
        failures.append(
            "KA001 traced worst-case payload copies "
            f"({worst_payload_copies}) exceed parallel/vmem.py "
            f"WORST_RING_COPIES ({vmem.WORST_RING_COPIES}): the ring "
            "family table is stale — fix RING_FAMILIES and re-derive"
        )

    manifest = {
        "jax": jax.__version__,
        "vmem": env,
        "programs": {
            n: {
                "rules": r["rules"],
                "eqns": r["eqns"],
                "kernels": [
                    {k: v for k, v in kern.items() if k != "refs"}
                    for kern in r["kernels"]
                ],
                "dma_census": r["dma_census"],
                "assumptions": r["assumptions"],
            }
            for n, r in sorted(results.items())
        },
    }

    if check and not prior:
        failures.append(
            "docs/kernel_audit.json missing: run "
            "`python tools/kernel_audit.py` and commit it"
        )
    if check and prior:
        prior_programs = prior.get("programs", {})
        missing = [n for n in names if n in PROGRAMS
                   and n not in prior_programs]
        if missing:
            failures.append(
                f"manifest missing programs {missing}: run "
                "`python tools/kernel_audit.py` and commit "
                "docs/kernel_audit.json"
            )
        for n, p in prior_programs.items():
            dirty = {r: c for r, c in p.get("rules", {}).items() if c}
            if dirty:
                failures.append(
                    f"manifest records violations for {n}: {dirty}"
                )
        if prior.get("vmem", {}).get("solver_gate") != env["solver_gate"] \
                or prior.get("vmem", {}).get("derived_max_election_elems") \
                != env["derived_max_election_elems"]:
            failures.append(
                "vmem envelope drift vs manifest "
                f"(manifest {prior.get('vmem')}, computed {env}): "
                "intended? re-run `python tools/kernel_audit.py` and "
                "commit the diff"
            )
        if prior.get("jax") == jax.__version__:
            for n, r in results.items():
                want = prior_programs.get(n, {})
                got = manifest["programs"][n]
                if want and want != got:
                    failures.append(
                        f"{n}: kernel-audit census drift vs manifest — "
                        "intended? re-run `python tools/kernel_audit.py` "
                        "and commit the manifest diff"
                    )
        else:
            print(
                f"[kernel-audit] note: manifest written under jax "
                f"{prior.get('jax')}, running {jax.__version__}; census "
                "equality not enforced, rule/coverage gates still apply"
            )

    overridden = bool(os.environ.get("SPT_PALLAS_MAX_ELECTION_ELEMS"))
    if not check and set(names) == set(PROGRAMS) and not failures \
            and not overridden:
        MANIFEST.write_text(
            json.dumps(manifest, indent=1, sort_keys=True) + "\n"
        )
        print(f"[kernel-audit] wrote {MANIFEST.relative_to(REPO)}")
    elif not check:
        reason = (
            "failures" if failures else
            "SPT_PALLAS_MAX_ELECTION_ELEMS override in force"
            if overridden else "partial program set"
        )
        print(f"[kernel-audit] {reason}: manifest NOT rewritten")

    for f in failures:
        print(f"[kernel-audit] FAIL: {f}", file=sys.stderr)
    if not failures:
        n_kernels = sum(len(r["kernels"]) for r in results.values())
        n_assume = sum(len(r["assumptions"]) for r in results.values())
        print(
            f"[kernel-audit] OK: {len(results)}/{len(names)} programs "
            f"audit clean (KA001-KA003), {n_kernels} kernel envelopes, "
            f"{n_assume} recorded assumptions, election gate "
            f"{env['solver_gate']} (derived)"
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
