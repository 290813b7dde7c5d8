#!/usr/bin/env python
"""AST lint enforcing the CLAUDE.md invariants that only bite at
compile/runtime today (pure stdlib — no jax import, no tracing):

- **GL001 aux-closure-capture** — plugin config arrays must flow through the
  `aux()` channel (read back as `self._aux` after `bind_aux`), never read
  directly inside jitted tensor methods: jit caches the traced program by
  shape, so a closure-captured array is constant-folded and silently goes
  stale when config or name<->code layouts change between cycles.
- **GL002 i64-2d-cumsum** — no `jnp.cumsum` on int64 arrays with an `axis=`
  argument (the 2-D form): it lowers to vmem-hungry reduce-windows on TPU
  and can hang compiles. Use 1-D scans over sorted segments, float64
  (exact < 2^53), or an explicit int32 dtype.
- **GL003 i64-matmul** — no `@` / `jnp.dot` / `jnp.matmul` /
  `lax.dot_general` on int64 operands: int64 `dot_general` is unsupported
  on TPU.
- **GL004 block-until-ready-timing** — no `block_until_ready()` in a
  function that also reads a wall clock: the cycle consumes every result
  on the HOST, so the device-to-host copy belongs inside the measurement;
  force completion with a host transfer (`np.asarray(x)`).
- **GL005 resource-slot-literal** — resource-axis positions must come from
  `api.resources.CANONICAL` / `meta.index.position(...)`, never hardcoded
  slot integers: the C++ bridge (`bridge/snapshot_store.cc`) hardcodes the
  same slots, so silent drift is silent data corruption.
- **GL006 donated-buffer-reuse** — a buffer passed in a DONATED position of
  a jitted call (`jax.jit(..., donate_argnums=...)` or
  `parallel.pipeline.donated_chunk_solver`) is dead after the call: XLA may
  have reused its memory for the outputs, and reading it raises (on the
  CPU backend donation is a no-op, so the bug first shows on a chip).
  Rebind the name from the call's
  results (`a, free = solve(..., free)`) before any further read. The check
  is lexical and conservative: only Name operands at literal donated
  positions are tracked, reassignment revives, and loop back-edges are not
  followed.
- **GL007 library-config-update** — no `jax.config.update(...)` outside the
  sanctioned owner files (`config-update-owners` in the pyproject config):
  platform/precision/cache config is owned by the entrypoints and the
  compile-cache helper they call; a library-level update fights them and
  its effect depends on import order.
- **GL008 jit-walltime** — no wall-clock reads (`time.perf_counter`,
  `time.perf_counter_ns`, `time.time`, `time.monotonic`, ...) inside
  jit-traced functions: trace-time Python runs ONCE per compile, so the
  "timestamp" is a baked constant that measures nothing.
  Device work is timed by bracketing HOST-SYNC transfers
  (`np.asarray(result)`); see `utils/observability.py` Tracer. Functions
  count as jit-traced when decorated with / passed to `jax.jit`,
  `parallel.pipeline.donated_chunk_solver`, `utils.sanitize.checkified`,
  or when they are Plugin tensor methods (which run under the fused
  solve's trace).

- **GL009 node-axis-all-gather** — no `lax.all_gather` /
  `all_gather_invariant` over the NODE shard axis (`"nodes"` /
  `parallel.mesh.NODES_AXIS`): the sharded wave solver's per-wave
  elections reduce per-shard CHAMPIONS (ring `ppermute` scans, psum/pmin
  slot-scatter reductions — `ops.assign.block_exclusive_offsets`); an
  all_gather of the node axis reassembles the full (N, ...) tensor on
  every shard, silently degrading the O(shards)-collective election back
  to a full gather. The shard-smoke gate's jaxpr collective census is the
  compiled-level twin.

- **GL011 pallas-kernel-purity** — inside a `pallas_call` kernel body: no
  host callbacks (`io_callback` / `pure_callback` / `debug_callback`), no
  wall-clock reads (`time.*`), and no Python `if`/`while` branching on the
  kernel's ref/traced parameters. A Pallas body is staged ONCE by Mosaic:
  host calls cannot cross the kernel boundary at all, a clock read is a
  baked constant (GL008's rule, one level deeper), and a Python branch on
  a ref value either fails to trace or silently bakes one path. Branch on
  STATIC closure config (shard counts, interpret flags) instead and mask
  traced conditions with `jnp.where`/`pl.when`. Detection is lexical and
  conservative: a function counts as a kernel body when its name is the
  first argument of a `pallas_call(...)` call (directly or through
  `functools.partial`); helpers it delegates to are trusted, like GL006's
  helper blindness.

- **GL010 swallowed-exception** — no broad exception handler (bare
  ``except:``, ``Exception``, ``BaseException``) whose body is only
  ``pass``/``...``: around solve/ingest sites that is how a backend
  fault, a poisoned delta batch, or a checkpoint failure vanishes
  silently. Fault paths must record + re-route (retry/failover/park/
  re-base — `resilience.watchdog` is the pattern); sanctioned
  best-effort paths (GC finalizers, shutdown cleanup, optional-dep
  probes) carry an inline ignore with their reason.

- **GL013 unaudited-f64-quantity-cast** — no new `.astype(jnp.float64)`
  (or array construction with `dtype=float64`) of a provably-int64
  quantity tensor outside the audited exactness owners
  (`exact-cast-owners` in the pyproject config). int64 quantities are
  exact in float64 only below 2^53; the owner modules' casts are walked
  and PROVEN by `tools/kernel_audit.py` KA003 (interval lattice over the
  declared `api.bounds` families, assumptions recorded in
  docs/kernel_audit.json), but a cast in un-traced new code silently
  assumes the invariant with no audit trail. Route new casts through the
  blessed helpers (`utils.intmath.exact_f64` — the sanctioned asserted-
  bound cast — or `parallel.kernels.join_limbs`), or add the module to
  the owner list, which is a reviewed declaration that its programs are
  in the kernel auditor's trace scope.

- **GL012 anonymous-thread** — every `threading.Thread(...)` must pass
  explicit `name=` and `daemon=`. The concurrency auditor
  (`tools/race_audit.py`) and the daemon's `/healthz` thread census key
  thread ENTRY POINTS by thread name — an anonymous thread is
  unauditable (it shows up as `Thread-7` in the live census and as an
  `anon@file:line` entry in the manifest, so topology drift cannot be
  attributed). Implicit `daemon` is a shutdown hazard: a forgotten
  non-daemon thread blocks interpreter exit.

Dtype inference is deliberately conservative: a rule fires only when an
operand PROVABLY carries int64 (explicit `.astype(jnp.int64)`, an int64
array constructor, a local name assigned from one, or a known int64
snapshot field like `.req`/`.alloc`). Unknown dtypes never fire.

Suppress a finding with a trailing `# graft-lint: ignore[GLxxx]` comment.

Config (`pyproject.toml [tool.graft-lint]`, parsed with a tiny stdlib
TOML subset — flat string / string-list keys only):
- `exclude`: repo-relative path prefixes skipped when EXPANDING directory
  arguments (the known-bad fixture corpora); a file named explicitly on
  the command line is always linted.
- `config-update-owners`: repo-relative path prefixes where GL007 is
  sanctioned.

Usage: python tools/graft_lint.py [paths...]   (default: the source tree
plus tests/ and tools/)
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: default lint scope: the package, the driver entry files, and the test +
#: tool trees (known-bad fixture corpora are excluded via the pyproject
#: config, not path hacks)
DEFAULT_PATHS = (
    "scheduler_plugins_tpu", "chip_smoke.py", "__graft_entry__.py",
    "tests", "tools",
)


def load_config() -> dict:
    """`[tool.graft-lint]` from pyproject.toml. Deliberately tiny TOML
    subset (the repo stays stdlib-only on py3.10, no tomllib): flat
    `key = "str"` / `key = ["str", ...]` entries inside the one section,
    values parsed as Python literals (valid for TOML strings/string
    lists)."""
    import ast as _ast

    cfg = {"exclude": [], "config-update-owners": [], "exact-cast-owners": []}
    path = REPO / "pyproject.toml"
    if not path.exists():
        return cfg
    def strip_comment(s: str) -> str:
        """Drop a trailing `# ...` TOML comment, respecting quoted strings
        (a `#` inside quotes is content, not a comment)."""
        quote = None
        for i, ch in enumerate(s):
            if quote:
                if ch == quote:
                    quote = None
            elif ch in "\"'":
                quote = ch
            elif ch == "#":
                return s[:i].rstrip()
        return s

    section, key, buf = None, None, None
    for raw in path.read_text().splitlines():
        line = strip_comment(raw.strip())
        if buf is not None:
            if not line:
                continue  # blank/comment-only lines inside a list
            buf += " " + line
            if line.endswith("]"):
                try:
                    cfg[key] = list(_ast.literal_eval(buf))
                except (ValueError, SyntaxError):
                    # a malformed list must fail LOUDLY: silently dropping
                    # `exclude` would sweep the known-bad fixture corpora
                    # into make lint with findings that look real
                    raise SystemExit(
                        f"graft-lint: unparseable [tool.graft-lint] value "
                        f"for {key!r} in pyproject.toml: {buf!r}"
                    )
                buf = None
            continue
        if line.startswith("["):
            section = line.strip("[]").strip()
            continue
        if section != "tool.graft-lint" or not line or line.startswith("#"):
            continue
        if "=" in line:
            key, _, val = line.partition("=")
            key, val = key.strip(), val.strip()
            if val.startswith("[") and not val.endswith("]"):
                buf = val
                continue
            try:
                parsed = _ast.literal_eval(val)
            except (ValueError, SyntaxError):
                continue
            cfg[key] = (
                list(parsed) if isinstance(parsed, (list, tuple)) else parsed
            )
    return cfg


def _rel_to_repo(path: Path):
    """Repo-relative POSIX path of `path`, or None when outside the repo
    (tmp-dir test files: never excluded, never GL007-sanctioned)."""
    try:
        return Path(path).resolve().relative_to(REPO).as_posix()
    except ValueError:
        return None

INT64, INT32, FLOAT, BOOL, UNKNOWN = "int64", "int32", "float", "bool", None

#: jitted tensor methods of the Plugin trait (framework/plugin.py) — code in
#: these runs under trace, so host-built jnp arrays read here are closure
#: captures. aux()/bind_aux and prepare_solve()/bind_presolve are the
#: sanctioned channels.
TENSOR_METHODS = frozenset({
    "admit", "filter", "score", "normalize", "commit", "static_node_scores",
    "filter_batch", "score_batch", "filter_rows", "batch_rows", "wave_guard",
    "wave_guard_demand", "wave_capacity", "validate_at", "commit_batch",
    "prepare_solve",
})
#: host-side methods where building jnp arrays is fine (they run BEFORE the
#: trace; arrays built here must then travel via aux()).
HOST_BUILD_METHODS = frozenset({
    "__init__", "prepare", "prepare_cluster", "configure_cluster",
})
#: attribute reads sanctioned inside tensor methods
SANCTIONED_ATTRS = frozenset({"_aux", "_presolve"})

#: jnp array constructors
ARRAY_CTORS = frozenset({
    "asarray", "array", "zeros", "ones", "full", "arange", "stack",
    "concatenate", "eye", "linspace",
})

#: snapshot fields that are int64 by construction (state/snapshot.py lowers
#: quantities as int64 in reference units)
INT64_ATTRS = frozenset({"req", "alloc", "requested", "nom_req"})

#: names that denote a (R,)-shaped resource vector — a literal-int subscript
#: on these is a hardcoded resource slot
RESOURCE_VECTOR_NAMES = re.compile(r"^(weights|w_res|resource_weights)$")
#: names/attrs denoting (..., R)-shaped resource tensors — a literal int in
#: the LAST position of a multi-axis subscript is a hardcoded resource slot
RESOURCE_TENSOR_NAMES = re.compile(
    r"^(req|reqs|quota_req|alloc|allocatable|free|free0|requested|capacity"
    r"|demand|dem|usage|used|eq_used|q_min|q_max)$"
)
RESOURCE_TENSOR_ATTRS = frozenset({"req", "alloc", "requested", "nom_req"})

MAX_CANONICAL_SLOT = 3  # cpu, memory, ephemeral-storage, pods


class Finding:
    def __init__(self, path, node, rule, message):
        self.path = path
        self.line = getattr(node, "lineno", 0)
        self.col = getattr(node, "col_offset", 0)
        self.rule = rule
        self.message = message

    def __str__(self):
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# dtype inference
# ---------------------------------------------------------------------------


def _dtype_from_dtype_expr(node):
    """jnp.int64 / np.float64 / "int64" -> lattice tag."""
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value
    if name is None:
        return UNKNOWN
    if name in ("int64", "uint64"):
        return INT64
    if name in ("int32", "int16", "int8", "uint32", "uint16", "uint8"):
        return INT32
    if name.startswith("float") or name.startswith("bfloat"):
        return FLOAT
    if name.startswith("bool"):
        return BOOL
    return UNKNOWN


def _call_dtype(node, env):
    func = node.func
    if isinstance(func, ast.Attribute):
        if func.attr == "astype" and node.args:
            return _dtype_from_dtype_expr(node.args[0])
        if func.attr in ARRAY_CTORS:
            for kw in node.keywords:
                if kw.arg == "dtype":
                    return _dtype_from_dtype_expr(kw.value)
            # positional dtype: asarray/array(x, D), full(shape, v, D),
            # zeros/ones/arange(shape, D)
            pos = {"asarray": 1, "array": 1, "zeros": 1, "ones": 1,
                   "full": 2, "arange": None, "eye": None}.get(func.attr, None)
            if pos is not None and len(node.args) > pos:
                return _dtype_from_dtype_expr(node.args[pos])
            if func.attr in ("asarray", "array") and len(node.args) >= 1:
                return infer_dtype(node.args[0], env)
            return UNKNOWN
        if func.attr in ("cumsum", "cumprod", "where", "sum", "prod",
                         "maximum", "minimum", "clip"):
            for kw in node.keywords:
                if kw.arg == "dtype":
                    return _dtype_from_dtype_expr(kw.value)
            if func.attr == "where" and len(node.args) == 3:
                return _combine(
                    infer_dtype(node.args[1], env),
                    infer_dtype(node.args[2], env),
                )
            if node.args:
                return infer_dtype(node.args[0], env)
        if func.attr in ("transpose", "reshape", "ravel", "squeeze", "copy"):
            return infer_dtype(func.value, env)
    return UNKNOWN


def _combine(a, b):
    if a == b:
        return a
    if FLOAT in (a, b):
        # int64 + float -> float; but unknown + float stays unknown-float?
        # conservative: float wins only when both sides are known
        return FLOAT if UNKNOWN not in (a, b) else UNKNOWN
    if UNKNOWN in (a, b):
        return UNKNOWN
    if INT64 in (a, b):
        return INT64
    return UNKNOWN


def infer_dtype(node, env):
    """Conservative dtype lattice walk; UNKNOWN when not provable."""
    if isinstance(node, ast.Call):
        return _call_dtype(node, env)
    if isinstance(node, ast.Name):
        return env.get(node.id, UNKNOWN)
    if isinstance(node, ast.Attribute):
        if node.attr == "T":
            return infer_dtype(node.value, env)
        if node.attr in INT64_ATTRS:
            return INT64
        return UNKNOWN
    if isinstance(node, ast.Subscript):
        return infer_dtype(node.value, env)
    if isinstance(node, ast.UnaryOp):
        return infer_dtype(node.operand, env)
    if isinstance(node, ast.BinOp):
        if isinstance(node.op, ast.MatMult):
            return UNKNOWN
        return _combine(
            infer_dtype(node.left, env), infer_dtype(node.right, env)
        )
    if isinstance(node, ast.Constant):
        if isinstance(node.value, bool):
            return BOOL
        if isinstance(node.value, float):
            return FLOAT
        return UNKNOWN  # python ints adopt the other operand's dtype
    return UNKNOWN


def build_env(fn_node):
    """name -> dtype for single-dtype local assignments in one function."""
    seen: dict[str, set] = {}
    for node in _walk_scope(fn_node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                seen.setdefault(t.id, set()).add(
                    infer_dtype(node.value, {})
                )
    env = {}
    for name, dts in seen.items():
        dts.discard(UNKNOWN)
        if len(dts) == 1:
            env[name] = next(iter(dts))
    # second pass so names defined from other names resolve one level deep
    for node in _walk_scope(fn_node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and t.id not in env:
                dt = infer_dtype(node.value, env)
                if dt is not UNKNOWN:
                    env[t.id] = dt
    return env


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _is_jnp_call(node, names):
    """Call like jnp.X / np.X / lax.X / jax.lax.X with X in names."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return isinstance(f, ast.Attribute) and f.attr in names


def _functions(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            yield node


def _walk_scope(fn):
    """Walk one function's nodes WITHOUT descending into nested
    function/lambda scopes: each nested scope is visited by its own
    `_functions` pass with its own env, so an enclosing `a = x.astype(
    jnp.int64)` cannot taint a nested function's shadowing parameter `a`
    (and findings inside nested scopes aren't reported twice)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def check_matmul(path, tree, findings):
    """GL003: int64 @ / dot / matmul / dot_general."""
    for fn in _functions(tree):
        env = build_env(fn)
        for node in _walk_scope(fn):
            operands = None
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
                operands = (node.left, node.right)
            elif _is_jnp_call(node, {"dot", "matmul", "dot_general", "vdot",
                                     "tensordot", "einsum"}):
                operands = tuple(node.args[:3])
            if operands is None:
                continue
            for op in operands:
                if infer_dtype(op, env) == INT64:
                    findings.append(Finding(
                        path, node, "GL003",
                        "int64 matmul/dot_general: unsupported on TPU — "
                        "cast to float64 (exact < 2^53) or float32",
                    ))
                    break


def check_cumsum(path, tree, findings):
    """GL002: jnp.cumsum on int64 with axis= (the 2-D form)."""
    for fn in _functions(tree):
        env = build_env(fn)
        for node in _walk_scope(fn):
            if not _is_jnp_call(node, {"cumsum"}):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            # cumsum(a, axis, dtype): axis/dtype may be positional
            axis = kw.get("axis") or (node.args[1] if len(node.args) > 1 else None)
            dtype = kw.get("dtype") or (node.args[2] if len(node.args) > 2 else None)
            if isinstance(axis, ast.Constant) and axis.value is None:
                axis = None  # explicit axis=None flattens: the 1-D form
            if axis is None:
                continue  # 1-D cumsum: fine on TPU
            if dtype is not None:
                if _dtype_from_dtype_expr(dtype) != INT64:
                    continue
                dt = INT64
            else:
                dt = infer_dtype(node.args[0], env) if node.args else UNKNOWN
            if dt == INT64:
                findings.append(Finding(
                    path, node, "GL002",
                    "multi-axis int64 cumsum: lowers to vmem-hungry "
                    "reduce_window on TPU — use 1-D sorted-segment scans, "
                    "float64, or int32",
                ))


def check_block_until_ready(path, tree, findings):
    """GL004: block_until_ready in a wall-clock-reading function."""
    for fn in _functions(tree):
        if isinstance(fn, ast.Lambda):
            continue
        reads_clock = False
        for node in _walk_scope(fn):
            if isinstance(node, ast.Attribute) and node.attr in (
                "perf_counter", "monotonic", "time", "perf_counter_ns"
            ):
                base = node.value
                if isinstance(base, ast.Name) and base.id == "time":
                    reads_clock = True
        if not reads_clock:
            continue
        for node in _walk_scope(fn):
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ) and node.func.attr == "block_until_ready":
                findings.append(Finding(
                    path, node, "GL004",
                    "block_until_ready() in a timing function: the "
                    "result is consumed on the host — force completion "
                    "with a host transfer (np.asarray) so the copy is "
                    "inside the measurement",
                ))


def _plugin_classes(trees):
    """Transitive Plugin subclasses across all parsed files."""
    bases = {}
    for _, tree in trees:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                names = []
                for b in node.bases:
                    if isinstance(b, ast.Name):
                        names.append(b.id)
                    elif isinstance(b, ast.Attribute):
                        names.append(b.attr)
                bases[node.name] = names
    plugins = {"Plugin"}
    changed = True
    while changed:
        changed = False
        for cls, bs in bases.items():
            if cls not in plugins and any(b in plugins for b in bs):
                plugins.add(cls)
                changed = True
    return plugins


def check_aux_capture(path, tree, plugin_classes, findings):
    """GL001: tensor methods reading host-built jnp array attributes."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef) or node.name not in plugin_classes:
            continue
        captured = set()
        for meth in node.body:
            if not isinstance(meth, ast.FunctionDef):
                continue
            if meth.name not in HOST_BUILD_METHODS:
                continue
            for sub in ast.walk(meth):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    t = sub.targets[0]
                    if (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        # RHS builds or contains a jnp array?
                        for c in ast.walk(sub.value):
                            if _is_jnp_call(c, ARRAY_CTORS) and isinstance(
                                c.func.value, ast.Name
                            ) and c.func.value.id in ("jnp", "jax"):
                                captured.add(t.attr)
                                break
        if not captured:
            continue
        for meth in node.body:
            if not isinstance(meth, ast.FunctionDef):
                continue
            if meth.name not in TENSOR_METHODS:
                continue
            # `self.X is None` presence checks are trace-time CONFIG
            # branches, not value captures: flipping presence changes the
            # aux() pytree structure, which retraces — sanctioned idiom
            presence_checks = set()
            for sub in ast.walk(meth):
                if isinstance(sub, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in sub.ops
                ):
                    for side in (sub.left, *sub.comparators):
                        presence_checks.add(id(side))
            for sub in ast.walk(meth):
                if (
                    isinstance(sub, ast.Attribute)
                    and isinstance(sub.value, ast.Name)
                    and sub.value.id == "self"
                    and sub.attr in captured
                    and sub.attr not in SANCTIONED_ATTRS
                    and id(sub) not in presence_checks
                    and not isinstance(getattr(sub, "ctx", None), ast.Store)
                ):
                    findings.append(Finding(
                        path, sub, "GL001",
                        f"jitted {meth.name}() reads host-built array "
                        f"self.{sub.attr}: a jit closure capture is "
                        "constant-folded per shape and goes stale — route "
                        "it through aux()/bind_aux (read self._aux)",
                    ))


def check_resource_slots(path, tree, findings):
    """GL005: hardcoded resource-axis slot integers."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        base = node.value
        # unwrap .at[...] indexing
        if isinstance(base, ast.Attribute) and base.attr == "at":
            base = base.value
        idx = node.slice
        is_vector = (
            isinstance(base, ast.Name)
            and RESOURCE_VECTOR_NAMES.match(base.id) is not None
        )
        resourceish = is_vector or (
            isinstance(base, ast.Name)
            and RESOURCE_TENSOR_NAMES.match(base.id) is not None
        ) or (
            isinstance(base, ast.Attribute)
            and base.attr in RESOURCE_TENSOR_ATTRS
        )
        if not resourceish:
            continue
        slot = None
        if is_vector and isinstance(idx, ast.Constant) and isinstance(
            idx.value, int
        ) and not isinstance(idx.value, bool):
            slot = idx.value
        elif isinstance(idx, ast.Tuple) and idx.elts:
            last = idx.elts[-1]
            leading_sliced = any(
                isinstance(e, ast.Slice)
                or (isinstance(e, ast.Constant) and e.value is Ellipsis)
                for e in idx.elts[:-1]
            )
            if leading_sliced and isinstance(last, ast.Constant) and isinstance(
                last.value, int
            ) and not isinstance(last.value, bool):
                slot = last.value
        if slot is not None and 0 <= slot <= MAX_CANONICAL_SLOT:
            findings.append(Finding(
                path, node, "GL005",
                f"hardcoded resource slot [{slot}]: the axis order is "
                "owned by api.resources.CANONICAL (and mirrored by the "
                "C++ bridge) — use CANONICAL.index(...) / "
                "meta.index.position(...)",
            ))


def check_config_update(path, tree, findings):
    """GL007: `jax.config.update(...)` (or `config.update` from
    `from jax import config`) outside the sanctioned owner files. The
    bare-name form only fires when the module actually binds `config`
    FROM jax — a local dict named `config` being .update()d is not a
    finding."""
    jax_config_imported = any(
        isinstance(node, ast.ImportFrom)
        and node.module == "jax"
        and any((alias.asname or alias.name) == "config"
                and alias.name == "config" for alias in node.names)
        for node in ast.walk(tree)
    )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "update"):
            continue
        base = f.value
        is_jax_config = (
            isinstance(base, ast.Attribute)
            and base.attr == "config"
            and isinstance(base.value, ast.Name)
            and base.value.id == "jax"
        ) or (
            isinstance(base, ast.Name)
            and base.id == "config"
            and jax_config_imported
        )
        if not is_jax_config:
            continue
        findings.append(Finding(
            path, node, "GL007",
            "jax.config.update in library code: platform/precision config "
            "is owned by the entrypoints and tests/conftest.py "
            "(config-update-owners in pyproject [tool.graft-lint]) — a "
            "library-level update fights their platform pinning",
        ))


#: wall-clock reads that are meaningless (trace-time constants) inside a
#: jit-traced function
WALL_CLOCK_ATTRS = frozenset({
    "perf_counter", "perf_counter_ns", "time", "time_ns", "monotonic",
    "monotonic_ns", "process_time", "process_time_ns",
})

#: callables whose function argument gets jit-traced
JIT_WRAPPERS = frozenset({"jit", "donated_chunk_solver", "checkified"})


def check_thread_names(path, tree, findings):
    """GL012: `threading.Thread(...)` without explicit `name=` and
    `daemon=`. The bare-name `Thread(...)` form fires only when the
    module binds `Thread` from threading — another class that happens
    to be called Thread is not a finding."""
    thread_imported = any(
        isinstance(node, ast.ImportFrom)
        and node.module == "threading"
        and any((alias.asname or alias.name) == "Thread"
                and alias.name == "Thread" for alias in node.names)
        for node in ast.walk(tree)
    )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_thread = (
            isinstance(f, ast.Attribute)
            and f.attr == "Thread"
            and isinstance(f.value, ast.Name)
            and f.value.id == "threading"
        ) or (
            isinstance(f, ast.Name) and f.id == "Thread" and thread_imported
        )
        if not is_thread:
            continue
        kwargs = {k.arg for k in node.keywords if k.arg}
        missing = [k for k in ("name", "daemon") if k not in kwargs]
        if not missing:
            continue
        findings.append(Finding(
            path, node, "GL012",
            f"threading.Thread without explicit {' and '.join(missing)}: "
            "the concurrency auditor (tools/race_audit.py) and the "
            "/healthz thread census key entry points by thread name — "
            "anonymous threads are unauditable, and implicit daemon is a "
            "shutdown hazard",
        ))


def _callee_name(func):
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _jitted_function_nodes(tree):
    """Function/lambda nodes in `tree` that get jit-traced: decorated with
    jit (bare, `jax.jit`, or `partial(jax.jit, ...)`), or passed (by name
    or inline lambda) as the first argument of `jax.jit` /
    `donated_chunk_solver` / `checkified`. Name references resolve to
    every same-named def in the file — conservative in the right
    direction for a lint that flags wall clocks."""
    defs_by_name: dict[str, list] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
    jitted = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = _callee_name(target)
                if name == "jit":
                    jitted.append(node)
                elif name == "partial" and isinstance(dec, ast.Call) and any(
                    _callee_name(a) == "jit"
                    for a in dec.args
                    if isinstance(a, (ast.Name, ast.Attribute))
                ):
                    jitted.append(node)
        elif isinstance(node, ast.Call):
            if _callee_name(node.func) not in JIT_WRAPPERS or not node.args:
                continue
            fn_arg = node.args[0]
            if isinstance(fn_arg, ast.Lambda):
                jitted.append(fn_arg)
            elif isinstance(fn_arg, ast.Name):
                jitted.extend(defs_by_name.get(fn_arg.id, ()))
    return jitted


def check_jit_walltime(path, tree, plugin_classes, findings):
    """GL008: wall-clock reads inside jit-traced functions (including
    Plugin tensor methods and functions nested inside a traced scope)."""
    traced = list(_jitted_function_nodes(tree))
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name in plugin_classes:
            traced.extend(
                meth for meth in node.body
                if isinstance(meth, ast.FunctionDef)
                and meth.name in TENSOR_METHODS
            )
    seen = set()
    for fn in traced:
        # descend into NESTED defs too: code defined inside a traced
        # function traces with it
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in WALL_CLOCK_ATTRS
                    and isinstance(sub.func.value, ast.Name)
                    and sub.func.value.id == "time"):
                continue
            key = (sub.lineno, sub.col_offset)
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                path, sub, "GL008",
                f"time.{sub.func.attr}() inside a jit-traced function: "
                "trace-time Python runs once per compile, so this is a "
                "baked constant, not a measurement — time device work by "
                "bracketing host-sync transfers (np.asarray) outside the "
                "jit (GL004's rule; see utils/observability.py)",
            ))


def _donate_positions(node):
    """Literal int positions from a donate_argnums/carry_argnum value."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = {
            e.value
            for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, int)
        }
        return vals or None
    return None


def _donating_jits(tree):
    """name -> donated arg positions, from `x = jax.jit(f, donate_argnums=
    ...)` and `x = donated_chunk_solver(f, carry_argnum=k)` assignments
    (module- or function-level). Only literal positions are tracked."""
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        t = node.targets[0]
        if not isinstance(t, ast.Name) or not isinstance(node.value, ast.Call):
            continue
        call = node.value
        fname = (
            call.func.attr if isinstance(call.func, ast.Attribute)
            else getattr(call.func, "id", None)
        )
        pos = None
        if fname == "jit":
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    pos = _donate_positions(kw.value)
        elif fname == "donated_chunk_solver":
            for kw in call.keywords:
                if kw.arg == "carry_argnum":
                    pos = _donate_positions(kw.value)
            if pos is None and len(call.args) > 1:
                pos = _donate_positions(call.args[1])
        if pos:
            out[t.id] = pos
    return out


def _sweep_unit(unit, extra_stores, donating, poisoned, report):
    """One statement unit: check loads against the poisoned set FIRST
    (passing an already-donated buffer anywhere is a read), then the
    unit's donating calls poison their donated Name operands, then the
    unit's assignment targets revive — so the chunk-carry idiom
    `a, free = solve(..., free)` is clean."""
    loads, stores, calls = [], list(extra_stores or ()), []
    for node in ast.walk(unit):
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                loads.append(node)
            elif isinstance(node.ctx, ast.Store):
                stores.append(node.id)
        elif isinstance(node, ast.Call) and isinstance(
            node.func, ast.Name
        ) and node.func.id in donating:
            calls.append(node)
    for name_node in loads:
        if name_node.id in poisoned:
            report(name_node, poisoned[name_node.id])
    for call in calls:
        for k in donating[call.func.id]:
            if k < len(call.args) and isinstance(call.args[k], ast.Name):
                poisoned[call.args[k].id] = call.func.id
    for name in stores:
        poisoned.pop(name, None)


def _sweep_body(body, donating, poisoned, report):
    """Sweep a statement list in source order, mutating `poisoned`.
    Loop bodies are swept TWICE — the second pass carries the poison from
    the end of the first, so a carry donated in iteration k and read (not
    rebound) at the top of iteration k+1 is caught. If/try branches sweep
    on copies and union their surviving poison (either branch may have
    run); nested function/class definitions are their own scope."""
    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            targets = [
                n.id for n in ast.walk(stmt.target)
                if isinstance(n, ast.Name)
            ]
            _sweep_unit(stmt.iter, targets, donating, poisoned, report)
            for _ in range(2):  # second pass: loop back-edge
                # the loop TARGET rebinds at the top of every iteration —
                # revive it before each pass, or a donated per-iteration
                # input (`for x in xs: step(a, x)`) false-positives on the
                # back-edge sweep
                for name in targets:
                    poisoned.pop(name, None)
                _sweep_body(stmt.body, donating, poisoned, report)
            _sweep_body(stmt.orelse, donating, poisoned, report)
        elif isinstance(stmt, ast.While):
            _sweep_unit(stmt.test, [], donating, poisoned, report)
            for _ in range(2):
                _sweep_body(stmt.body, donating, poisoned, report)
            _sweep_body(stmt.orelse, donating, poisoned, report)
        elif isinstance(stmt, ast.If):
            _sweep_unit(stmt.test, [], donating, poisoned, report)
            then_p, else_p = dict(poisoned), dict(poisoned)
            _sweep_body(stmt.body, donating, then_p, report)
            _sweep_body(stmt.orelse, donating, else_p, report)
            poisoned.clear()
            poisoned.update(then_p)
            poisoned.update(else_p)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                names = []
                if item.optional_vars is not None:
                    names = [
                        n.id for n in ast.walk(item.optional_vars)
                        if isinstance(n, ast.Name)
                    ]
                _sweep_unit(item.context_expr, names, donating, poisoned,
                            report)
            _sweep_body(stmt.body, donating, poisoned, report)
        elif isinstance(stmt, ast.Try):
            _sweep_body(stmt.body, donating, poisoned, report)
            for handler in stmt.handlers:
                _sweep_body(handler.body, donating, poisoned, report)
            _sweep_body(stmt.orelse, donating, poisoned, report)
            _sweep_body(stmt.finalbody, donating, poisoned, report)
        else:
            _sweep_unit(stmt, None, donating, poisoned, report)


def check_donated_reuse(path, tree, findings):
    """GL006: a Name read after being passed in a donated position of a
    jitted call, without an intervening rebind — including across loop
    iterations (the chunk-loop bug class: `for ...: a = solve(raw, free)`
    without rebinding `free`). Findings are deduplicated per site so the
    loop double-sweep reports each read once."""
    donating = _donating_jits(tree)
    if not donating:
        return
    for fn in _functions(tree):
        if isinstance(fn, ast.Lambda):
            continue
        seen = set()

        def report(name_node, callee):
            key = (name_node.lineno, name_node.col_offset, name_node.id)
            if key in seen:
                return
            seen.add(key)
            findings.append(Finding(
                path, name_node, "GL006",
                f"read of {name_node.id!r} after it was donated to "
                f"{callee!r}(): the donated buffer may have been reused "
                "for outputs — rebind it from the call's results first",
            ))

        _sweep_body(fn.body, donating, {}, report)


#: the node shard axis name (mirrors parallel.mesh.NODES_AXIS — the lint is
#: stdlib-only and cannot import jax-adjacent modules)
_NODE_AXIS_LITERAL = "nodes"
_NODE_AXIS_NAMES = frozenset({"NODES_AXIS"})


def _is_node_axis_expr(node) -> bool:
    """Does this AST expression denote the node shard axis? Literal
    "nodes", the NODES_AXIS constant (bare or attribute), or a tuple/list
    containing one of those (multi-axis gathers over the node axis are
    just as much a full-axis gather)."""
    if isinstance(node, ast.Constant):
        return node.value == _NODE_AXIS_LITERAL
    if isinstance(node, ast.Name):
        return node.id in _NODE_AXIS_NAMES
    if isinstance(node, ast.Attribute):
        return node.attr in _NODE_AXIS_NAMES
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_is_node_axis_expr(e) for e in node.elts)
    return False


def check_node_axis_all_gather(path, tree, findings):
    """GL009: `all_gather`/`all_gather_invariant` over the node shard
    axis. The axis is read from the second positional argument or the
    `axis_name` keyword; gathers over other axes (pod-axis prefix
    exchanges, side-table sweeps) are not findings."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _callee_name(node.func)
        if name not in ("all_gather", "all_gather_invariant"):
            continue
        axis = node.args[1] if len(node.args) >= 2 else None
        for kw in node.keywords:
            if kw.arg == "axis_name":
                axis = kw.value
        if axis is None or not _is_node_axis_expr(axis):
            continue
        findings.append(Finding(
            path, node, "GL009",
            f"{name} over the node shard axis reassembles the full node "
            "tensor on every shard — the ring election degrades back to a "
            "full gather. Reduce per-shard champions instead "
            "(ops.assign.block_exclusive_offsets / ring_exclusive_scan, "
            "lax.pmin/psum key reductions)",
        ))


#: host-callback callables that can never appear inside a Pallas kernel
#: body (the kernel is staged by Mosaic; there is no host to call back to)
_HOST_CALLBACK_NAMES = frozenset({
    "io_callback", "pure_callback", "debug_callback", "callback",
})


def _pallas_kernel_fns(tree):
    """(FunctionDef, n_bound, kw_bound) triples for defs whose NAME is
    passed as the first argument of a `pallas_call(...)` call — directly
    or through `functools.partial(name, ...)`. `n_bound`/`kw_bound` are
    the leading positional count and keyword names `partial` statically
    binds (minimum / intersection across references when a name is used
    more than once): those parameters hold compile-time Python config,
    not traced refs, so GL011's branch check must not fire on them. Name
    resolution is module-wide and conservative: every def sharing a
    referenced name is treated as a kernel body (nested `def kernel(...)`
    closures are the repo idiom, `parallel/kernels.py`)."""
    refs: dict = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _callee_name(node.func) == "pallas_call"
                and node.args):
            continue
        first = node.args[0]
        n_bound, kw_bound = 0, frozenset()
        if isinstance(first, ast.Call) and _callee_name(
            first.func
        ) == "partial" and first.args:
            n_bound = len(first.args) - 1
            kw_bound = frozenset(
                kw.arg for kw in first.keywords if kw.arg
            )
            first = first.args[0]
        if isinstance(first, ast.Name):
            prev = refs.get(first.id)
            refs[first.id] = (
                (n_bound, kw_bound) if prev is None
                else (min(prev[0], n_bound), prev[1] & kw_bound)
            )
    if not refs:
        return []
    return [
        (fn,) + refs[fn.name] for fn in ast.walk(tree)
        if isinstance(fn, ast.FunctionDef) and fn.name in refs
    ]


def check_pallas_kernel_purity(path, tree, findings):
    """GL011: host callbacks, wall-clock reads, and Python branching on
    traced ref parameters inside `pallas_call` kernel bodies."""
    for fn, n_bound, kw_bound in _pallas_kernel_fns(tree):
        positional = [
            a.arg for a in fn.args.posonlyargs + fn.args.args
        ]
        # partial-bound leading positionals / keywords are static Python
        # config (the sanctioned "branch on static closure config" shape)
        params = set(positional[n_bound:]) - kw_bound
        params.update(
            a.arg for a in fn.args.kwonlyargs if a.arg not in kw_bound
        )
        if fn.args.vararg:
            params.add(fn.args.vararg.arg)

        def reads_param(expr):
            return any(
                isinstance(n, ast.Name) and n.id in params
                for n in ast.walk(expr)
            )

        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                name = _callee_name(sub.func)
                if name in _HOST_CALLBACK_NAMES:
                    findings.append(Finding(
                        path, sub, "GL011",
                        f"host callback {name}() inside a pallas_call "
                        "kernel body: the kernel is staged by Mosaic — "
                        "there is no host to call back to; move the "
                        "callback outside the kernel",
                    ))
                elif (isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in WALL_CLOCK_ATTRS
                        and isinstance(sub.func.value, ast.Name)
                        and sub.func.value.id == "time"):
                    findings.append(Finding(
                        path, sub, "GL011",
                        f"time.{sub.func.attr}() inside a pallas_call "
                        "kernel body: the body is staged once, so this is "
                        "a baked constant (GL008 one level deeper) — time "
                        "kernels by bracketing host-sync transfers "
                        "outside the program",
                    ))
            elif isinstance(sub, (ast.If, ast.While)) and reads_param(
                sub.test
            ):
                findings.append(Finding(
                    path, sub, "GL011",
                    "Python branching on a kernel ref/traced parameter "
                    "inside a pallas_call body: the branch is resolved at "
                    "staging time (wrong or untraceable) — branch on "
                    "static closure config, or mask with jnp.where / "
                    "pl.when",
                ))


def _is_float64_expr(node) -> bool:
    """jnp.float64 / np.float64 / "float64" — float64 SPECIFICALLY (the
    exactness contract is about the 2^53 mantissa line; float32 casts of
    int64 are a different, visibly lossy decision)."""
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value
    return name == "float64"


def check_exact_f64_cast(path, tree, findings):
    """GL013: int64 -> float64 casts of quantity tensors outside the
    audited exactness owners. Fires on `X.astype(jnp.float64)` and on
    array constructors with an explicit float64 dtype whose operand is
    provably int64 (the same conservative dtype lattice as GL002/GL003:
    unknown dtypes never fire)."""
    scopes = [tree]
    scopes.extend(_functions(tree))
    for fn in scopes:
        env = build_env(fn)
        for node in _walk_scope(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            operand = None
            if isinstance(f, ast.Attribute) and f.attr == "astype" \
                    and node.args and _is_float64_expr(node.args[0]):
                operand = f.value
            elif isinstance(f, ast.Attribute) and f.attr in ARRAY_CTORS:
                dtype = None
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        dtype = kw.value
                if dtype is not None and _is_float64_expr(dtype) \
                        and node.args:
                    operand = node.args[0]
            if operand is None:
                continue
            if infer_dtype(operand, env) != INT64:
                continue
            findings.append(Finding(
                path, node, "GL013",
                "float64 cast of an int64 quantity outside the audited "
                "exactness owners: exact only below 2^53, and this call "
                "site is outside tools/kernel_audit.py's proven trace "
                "scope — use utils.intmath.exact_f64 (asserted-bound "
                "cast) / parallel.kernels.join_limbs, or add the module "
                "to exact-cast-owners in pyproject [tool.graft-lint] to "
                "bring it under the audit",
            ))


def check_swallowed_exception(path, tree, findings):
    """GL010: a broad exception handler (bare ``except:``, ``except
    Exception``, ``except BaseException``) whose body is only
    ``pass``/``...``. Around solve/ingest sites this is how a backend
    fault, a poisoned delta batch, or a checkpoint failure disappears
    without a trace — fault paths must RECORD (log/metric) and RE-ROUTE
    (retry, failover, park, re-base; `resilience.watchdog` is the
    pattern), never swallow. Narrow handlers for specific exceptions are
    fine; genuinely-sanctioned best-effort paths (GC finalizers,
    shutdown cleanup, optional-dependency probes) carry an inline
    ``# graft-lint: ignore[GL010]`` with their reason."""

    def is_broad(t) -> bool:
        if t is None:
            return True  # bare except
        if isinstance(t, ast.Name):
            return t.id in ("Exception", "BaseException")
        if isinstance(t, ast.Tuple):
            return any(is_broad(e) for e in t.elts)
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not is_broad(node.type):
            continue
        body_swallows = all(
            isinstance(stmt, ast.Pass)
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant))
            for stmt in node.body
        )
        if not body_swallows:
            continue
        findings.append(Finding(
            path, node, "GL010",
            "broad exception handler swallows the fault (body is only "
            "pass) — record + re-route instead: log/count it and retry, "
            "fail over, park, or re-base (resilience.watchdog is the "
            "pattern); a sanctioned best-effort path needs an inline "
            "ignore with its reason",
        ))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_IGNORE_RE = re.compile(r"#\s*graft-lint:\s*ignore(?:\[([A-Z0-9, ]+)\])?")


def _suppressed(finding, source_lines):
    if 0 < finding.line <= len(source_lines):
        m = _IGNORE_RE.search(source_lines[finding.line - 1])
        if m:
            rules = m.group(1)
            return rules is None or finding.rule in re.split(r"[,\s]+", rules)
    return False


def lint_file(
    path: Path,
    config_owner: bool = False,
    exact_cast_owner: bool = False,
) -> tuple[list, object, str]:
    """(findings, ast tree, source) for one file — the tree/source feed the
    cross-file plugin-hierarchy pass and suppression filter in lint_paths.
    `config_owner` marks a sanctioned GL007 owner file (platform/precision
    config allowed); `exact_cast_owner` marks a GL013 exactness-owner file
    (its int64 -> float64 casts are walked by the kernel auditor's jaxpr
    lattice, so the source-level rule stands down). Direct callers default
    to NOT owned."""
    source = path.read_text()
    tree = ast.parse(source, filename=str(path))
    findings: list[Finding] = []
    rel = path
    check_matmul(rel, tree, findings)
    check_cumsum(rel, tree, findings)
    check_block_until_ready(rel, tree, findings)
    check_resource_slots(rel, tree, findings)
    check_donated_reuse(rel, tree, findings)
    check_node_axis_all_gather(rel, tree, findings)
    check_swallowed_exception(rel, tree, findings)
    check_pallas_kernel_purity(rel, tree, findings)
    check_thread_names(rel, tree, findings)
    if not config_owner:
        check_config_update(rel, tree, findings)
    if not exact_cast_owner:
        check_exact_f64_cast(rel, tree, findings)
    return findings, tree, source


def lint_paths(paths) -> list[Finding]:
    cfg = load_config()
    exclude = tuple(cfg.get("exclude", ()))
    owners = tuple(cfg.get("config-update-owners", ()))
    cast_owners = tuple(cfg.get("exact-cast-owners", ()))

    def excluded(f):
        rel = _rel_to_repo(f)
        return rel is not None and any(rel.startswith(e) for e in exclude)

    def owned(f):
        rel = _rel_to_repo(f)
        return rel is not None and any(rel.startswith(o) for o in owners)

    def cast_owned(f):
        rel = _rel_to_repo(f)
        return rel is not None and any(rel.startswith(o) for o in cast_owners)

    files = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            # config exclusions apply when EXPANDING directories only —
            # a file named explicitly is always linted (the fixture tests
            # point the linter straight at the known-bad corpus)
            files.extend(f for f in sorted(p.rglob("*.py")) if not excluded(f))
        else:
            files.append(p)
    all_findings, trees, sources = [], [], {}
    for f in files:
        findings, tree, source = lint_file(
            f, config_owner=owned(f), exact_cast_owner=cast_owned(f))
        all_findings.extend(findings)
        trees.append((f, tree))
        sources[f] = source.splitlines()
    plugin_classes = _plugin_classes(trees)
    for f, tree in trees:
        extra: list[Finding] = []
        check_aux_capture(f, tree, plugin_classes, extra)
        check_jit_walltime(f, tree, plugin_classes, extra)
        all_findings.extend(extra)
    return [
        fi for fi in all_findings
        if not _suppressed(fi, sources.get(fi.path, []))
    ]


def main(argv=None):
    args = argv if argv is not None else sys.argv[1:]
    paths = args or [str(REPO / p) for p in DEFAULT_PATHS]
    findings = lint_paths(paths)
    for f in sorted(findings, key=lambda f: (str(f.path), f.line)):
        print(f)
    if findings:
        print(f"graft-lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("graft-lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
