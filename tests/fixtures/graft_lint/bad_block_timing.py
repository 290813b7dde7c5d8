"""Golden-bad: block_until_ready() as the completion fence in a timing
loop — the device-to-host copy stays outside the measurement (GL004)."""

import time


def bench_step(solve, snap):
    start = time.perf_counter()
    out = solve(snap)
    # BAD: must force completion with a host transfer (np.asarray)
    out.block_until_ready()
    return time.perf_counter() - start
