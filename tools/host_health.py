"""Host-health probe: is this machine fit to produce trustworthy timings?

Benchmark numbers taken on a sick host (hung backend, load
spike from a co-tenant, thermal throttle) look exactly like code
regressions.  This probe produces one JSON line capturing the two
signals we have learned to distrust first (see CLAUDE.md "TPU
gotchas"):

  * a small timed matmul forced through a host transfer
    (``np.asarray``), run in a daemon thread under a hard timeout so
    a hung backend reports ``probe_timeout`` instead of hanging the
    caller; and
  * 1-minute loadavg normalised by CPU count.

``make verify`` prints this line before the suite so every archived
log is self-describing, and tools/perf_sentry.py uses the same
``probe()`` to downgrade "regression" verdicts to "degraded-host"
when the host itself cannot be trusted.  rc is always 0 — a sick
host is a finding, not a failure of the probe.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

# Matmul wall-time above this (ms) marks the host degraded: on a healthy
# CPU backend an 8x8 float32 matmul plus transfer is far under 1s even
# with cold jit; multi-second times mean a wedged backend or a host under
# severe load.  Kept deliberately loose — the probe must never flag a
# merely busy-but-fine machine.
MATMUL_DEGRADED_MS = 2000.0
# 1-minute loadavg per core above this marks the host loaded.
LOAD_DEGRADED_PER_CPU = 4.0
DEFAULT_TIMEOUT_S = 30.0


def _timed_matmul(out: dict) -> None:
    import numpy as np
    import jax.numpy as jnp

    t0 = time.monotonic()
    # Host transfer, not block_until_ready: see CLAUDE.md TPU gotchas.
    res = np.asarray(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    out["matmul_ms"] = (time.monotonic() - t0) * 1000.0
    out["matmul_ok"] = bool(abs(float(res[0][0]) - 8.0) < 1e-6)


def probe(timeout_s: float = DEFAULT_TIMEOUT_S) -> dict:
    """Return a host-health dict; never raises, never hangs past timeout_s."""
    out: dict = {
        "probe": "host_health",
        "matmul_ms": None,
        "matmul_ok": False,
        "timeout_s": timeout_s,
    }
    th = threading.Thread(
        target=_timed_matmul, args=(out,), daemon=True,
        name="host-health-probe",
    )
    t0 = time.monotonic()
    th.start()
    th.join(timeout_s)
    if th.is_alive():
        out["error"] = "probe_timeout"
        out["matmul_ms"] = (time.monotonic() - t0) * 1000.0
    try:
        la1, la5, la15 = os.getloadavg()
    except OSError:  # pragma: no cover - platform without getloadavg
        la1 = la5 = la15 = -1.0
    ncpu = os.cpu_count() or 1
    out["loadavg_1m"] = round(la1, 3)
    out["loadavg_5m"] = round(la5, 3)
    out["cpu_count"] = ncpu
    out["load_per_cpu"] = round(la1 / ncpu, 4) if la1 >= 0 else None

    reasons = []
    if not out["matmul_ok"]:
        reasons.append(out.get("error", "matmul_failed"))
    elif out["matmul_ms"] is not None and out["matmul_ms"] > MATMUL_DEGRADED_MS:
        reasons.append("matmul_slow")
    if out["load_per_cpu"] is not None and out["load_per_cpu"] > LOAD_DEGRADED_PER_CPU:
        reasons.append("load_high")
    out["healthy"] = not reasons
    out["reasons"] = reasons
    if out["matmul_ms"] is not None:
        out["matmul_ms"] = round(out["matmul_ms"], 3)
    return out


def cost_arm_summary() -> dict | None:
    """The deterministic companion to a sick-host verdict (ISSUE 20):
    a one-block summary of the committed static-cost manifest
    (docs/cost_model.json).  Wall-clock numbers from this machine may be
    garbage, but the cost manifest digest is a pure function of the
    committed tree — so a degraded host still has a trustworthy perf
    statement ("the cost shape is X") and an algorithmic regression
    cannot hide behind (or be faked by) host sickness.  None when no
    manifest is committed; never raises."""
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from scheduler_plugins_tpu.obs import costmodel

        manifest = costmodel.load_manifest()
        if not manifest:
            return None
        programs = manifest.get("programs", {})
        return {
            "arm": "cost",
            "manifest_digest": costmodel.manifest_digest(manifest),
            "programs": len(programs),
            "static_only": sum(
                1 for r in programs.values() if r.get("static_only")
            ),
            "jax": manifest.get("jax"),
            "note": ("static cost is backend-independent: verdict a "
                     "suspect change with `perf_sentry.py cost` even "
                     "while this host is degraded"),
        }
    except Exception:
        return None


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--timeout", type=float, default=DEFAULT_TIMEOUT_S,
        help="seconds to wait for the timed matmul before declaring the "
             "backend hung (default %(default)s)")
    ap.add_argument(
        "--cost-arm", action="store_true",
        help="attach the deterministic cost-arm summary "
             "(docs/cost_model.json digest) so a degraded-host line "
             "still carries a trustworthy perf statement")
    args = ap.parse_args(argv)
    out = probe(args.timeout)
    if args.cost_arm:
        out["cost_arm"] = cost_arm_summary()
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
