"""SPT_SANITIZE=1 checkify sanitizer mode.

`jax.experimental.checkify` instruments the traced solve programs with
runtime checks — index out-of-bounds on the commit scatters, NaN
production, division by zero — that XLA otherwise silently clamps, drops
or propagates. The wrap points are the three program families the
compile-readiness gates certify: `parallel.solver.profile_batch_fn`,
`parallel.pipeline.donated_chunk_solver` and `__graft_entry__.entry()`.

Semantics under sanitize mode:

- **donation is dropped** — this is a debug mode; keeping every carry
  readable after the call beats the peak-memory win, and checkify threads
  an error value through the program that must not alias a donated buffer.
- errors surface as STRUCTURED JSON (one line per checked invocation on
  stderr when an error fired) and accumulate in an in-process report list;
  `drain()` hands them to drivers — tests/test_sanitize.py fails on any,
  `framework.cycle.run_cycle` attaches them to its CycleReport.
- the mode is decided when a solver is BUILT (solver caches key on it), so
  flipping the env var mid-process yields fresh, correctly-instrumented
  jits instead of stale cache hits.
"""

from __future__ import annotations

import json
import os
import sys

_REPORTS: list[dict] = []


def enabled() -> bool:
    return os.environ.get("SPT_SANITIZE", "") == "1"


def checks():
    """The check set: index OOB (commit scatters), NaN, div-by-zero."""
    from jax.experimental import checkify

    return checkify.index_checks | checkify.float_checks | checkify.div_checks


def checkified_fn(fn):
    """The jittable `(error, out)` form of `fn` — for callers that manage
    the error value themselves (e.g. `__graft_entry__.entry()`, whose
    contract is to stay jittable)."""
    from jax.experimental import checkify

    return checkify.checkify(fn, errors=checks())


def checkified(fn, program: str):
    """Host-callable sanitized build of `fn`: jits the checkified form,
    extracts the error after every call, records a structured report, and
    returns `fn`'s own outputs — a drop-in for the production jit (minus
    donation, see module docstring)."""
    import functools

    import jax

    checked = jax.jit(checkified_fn(fn))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        err, out = checked(*args, **kwargs)
        report(program, err)
        return out

    wrapped.__name__ = f"sanitized_{program}"
    return wrapped


def report(program: str, err) -> None:
    """Record one checked invocation. `err` is a checkify Error pytree;
    `err.get()` is None when every check passed."""
    msg = err.get()
    entry = {"sanitize": program, "ok": msg is None}
    if msg is not None:
        entry["error"] = " ".join(msg.split())[:400]
        print(json.dumps(entry), file=sys.stderr, flush=True)
    _REPORTS.append(entry)


def drain() -> list[dict]:
    """All reports since the last drain (clears the buffer)."""
    out = list(_REPORTS)
    _REPORTS.clear()
    return out
