"""How `tpu_scan.xplane.pb` and `tpu_scan.spans.json` beside this file were
recorded: on one TPU v5e chip, `python3 benchmark/tests/data/record_trace.py
<out dir>`. Kept so that the recording can be made again on another chip or
another JAX.

Three runs of a small jitted scan (the shape of the sequential solve: a
carried (N, 4) int64 free matrix, one masked arg-max and one row update a
step), a host sleep after each, all inside the harness's own profiler call
(`harness.tracing.profile` switches Python call tracing off) and after its
`bench_sync` annotation. The host spans are written on CLOCK_MONOTONIC, as
`HostSpans` writes them.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness import trace_reduce, tracing  # noqa: E402

jax.config.update("jax_enable_x64", True)


@jax.jit
def small_scan(free, req):
    def step(free, r):
        fits = jnp.all(r[None, :] <= free, axis=-1)
        score = jnp.where(fits, -free[:, 0], -(2 ** 62))
        choice = jnp.argmax(score)
        return free.at[choice].add(-r), choice

    return jax.lax.scan(step, free, req)


def main(out_dir: str) -> None:
    free = jnp.full((5120, 4), 1 << 20, jnp.int64)
    req = jnp.ones((64, 4), jnp.int64)
    np.asarray(small_scan(free, req)[1])  # compile outside the trace

    spans = []

    def work() -> None:
        time.sleep(0.05)  # let the profiler start
        for name in ("run_a", "run_b", "run_c"):
            t0 = time.monotonic_ns()
            np.asarray(small_scan(free, req)[1])
            t1 = time.monotonic_ns()
            time.sleep(0.002)
            spans.append((name, t0, t1, {}))
            spans.append((f"sleep_after_{name}", t1, time.monotonic_ns(), {}))

    worker = threading.Thread(target=work)
    trace_dir = os.path.join(out_dir, "trace")
    worker.start()
    profiled = tracing.profile(trace_dir, 0.2)
    worker.join()

    path = trace_reduce.newest_xplane(trace_dir)
    shutil.copy(path, os.path.join(out_dir, "tpu_scan.xplane.pb"))
    with open(os.path.join(out_dir, "tpu_scan.spans.json"), "w") as f:
        json.dump({"profiled_ns": profiled, "spans": spans,
                   "device_kind": jax.devices()[0].device_kind,
                   "jax": jax.__version__}, f)
    profile = trace_reduce.load(path)
    print(json.dumps({"bytes": os.path.getsize(path),
                      "offset": trace_reduce.sync_offset_ns(profile)}))
    for plane in profile.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for event in events[:6]:
                print("     ", event.name[:100], event.start_ns,
                      event.duration_ns)
    reduced = trace_reduce.reduce_trace(profile)
    if reduced is not None:
        reduced.pop("gaps")
        reduced.pop("module_events")
        print(json.dumps(reduced))


if __name__ == "__main__":
    main(sys.argv[1])
