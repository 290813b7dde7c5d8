"""SPT_SANITIZE=1 end to end (utils.sanitize): the checkify-instrumented
build of each of the three wrap points — the batched profile solve
(`parallel.solver.profile_batch_fn`, configs 2 and 3), the donated chunk
pipeline (the north-star loop body) and `__graft_entry__.entry()` — runs at
a micro shape with every checked call reporting and ZERO findings: no index
out-of-bounds on the commit scatters, no NaN, no division by zero that the
production jits would silently clamp or propagate. The wrap mechanics (a
planted OOB is reported, donation is dropped) are tests/test_pipeline.py's.
"""

import jax
import numpy as np
import pytest

from scheduler_plugins_tpu.framework import Profile, Scheduler
from scheduler_plugins_tpu.models import problems
from scheduler_plugins_tpu.utils import sanitize


def _profile_batch(config, shape):
    from scheduler_plugins_tpu.parallel.solver import profile_batch_solve

    cluster, plugins, _ = problems.config_problem(config, shape=shape)
    scheduler = Scheduler(Profile(plugins=plugins))
    pending = scheduler.sort_pending(cluster.pending_pods(), cluster)
    snap, meta = cluster.snapshot(pending, now_ms=0)
    scheduler.prepare(meta, cluster)
    return np.asarray(profile_batch_solve(scheduler, snap)[0])


def _cfg2():
    return _profile_batch(2, dict(n_nodes=64, n_pods=32))


def _cfg3():
    return _profile_batch(3, dict(n_nodes=32, n_pods=16, zones=4))


def _chunk_pipeline():
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import (
        north_star_chunk_solver,
        run_chunk_pipeline,
    )

    chunk = 128
    _, snap, _, _, raw, _ = problems.north_star_problem(64, 256, chunk)
    results, _, _, _ = run_chunk_pipeline(
        north_star_chunk_solver(),  # sanitized under SPT_SANITIZE
        (raw, snap.nodes.mask), problems.pod_chunks(snap, chunk),
        free_capacity(snap.nodes.alloc, snap.nodes.requested),
    )
    return np.concatenate([np.asarray(a) for a, _stats in results])


def _entry():
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    err, result = jax.jit(fn)(*args)  # the (error, result) contract
    sanitize.report("entry", err)
    return np.asarray(result.assignment)


@pytest.mark.parametrize(
    "run, min_calls",
    [(_cfg2, 1), (_cfg3, 1), (_chunk_pipeline, 2), (_entry, 1)],
    ids=["cfg2_batch", "cfg3_batch", "chunk_pipeline", "entry"],
)
def test_sanitized_program_runs_clean(run, min_calls, monkeypatch):
    monkeypatch.setenv("SPT_SANITIZE", "1")
    assert sanitize.enabled()
    sanitize.drain()
    assignment = run()
    reports = sanitize.drain()
    assert len(reports) >= min_calls, reports
    assert [r for r in reports if not r["ok"]] == []
    assert (assignment >= 0).any()  # the checked program really placed pods
