"""The seeded problem builders (`models/problems.py`) and the north-star
chunk program (`parallel/pipeline.py`): what the audit registry,
`chip_smoke.py` and the tests build on. The committed manifests under
`docs/` digest the programs these build, so a builder may not drift: same
arguments, same problem, bit for bit; the registry lowers exactly the
shapes the constants name; an unknown config is a `ValueError`."""

import hashlib

import jax
import numpy as np
import pytest

from scheduler_plugins_tpu import models
from scheduler_plugins_tpu.models import problems


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _snap_and_weights(built):
    _cluster, snap, _meta, weights = built
    return snap, weights


def _north_star(built):
    _cluster, snap, _meta, weights, raw, padded = built
    return snap, weights, raw, padded


def _config(built):
    cluster, plugins, detail = built
    pending = sorted(cluster.pending_pods(), key=lambda p: p.creation_ms)
    snap, _ = cluster.snapshot(pending, now_ms=0)
    return snap, [type(p).__name__ for p in plugins], detail


BUILDERS = {
    "alloc": (lambda: problems.alloc_problem(16, 32), _snap_and_weights),
    "north_star": (
        lambda: problems.north_star_problem(16, 40, 16), _north_star),
    "mega": (lambda: problems.mega_problem(20, 50, 16), lambda d: d),
    "packing": (
        lambda: problems.packing_problem(12, 0.8, 0.2), _snap_and_weights),
    "cfg2": (lambda: problems.config_problem(
        2, shape=dict(n_nodes=16, n_pods=8)), _config),
    "cfg3": (lambda: problems.config_problem(
        3, shape=dict(n_nodes=8, n_pods=4, zones=2)), _config),
    "cfg4": (lambda: problems.config_problem(
        4, shape=dict(n_gangs=2, gang_size=2, n_nodes=8)), _config),
    "cfg5": (lambda: problems.config_problem(
        5, shape=dict(n_nodes=8, n_pods=4)), _config),
}


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_same_arguments_same_problem(name):
    build, view = BUILDERS[name]
    assert _digest(view(build())) == _digest(view(build()))


def test_seed_changes_the_seeded_builders():
    a = problems.mega_problem(20, 50, 16, seed=0)
    b = problems.mega_problem(20, 50, 16, seed=1)
    assert _digest(a) != _digest(b)
    a = problems.packing_problem(12, 0.8, 0.2, seed=0)
    b = problems.packing_problem(12, 0.8, 0.2, seed=1)
    assert _digest(_snap_and_weights(a)) != _digest(_snap_and_weights(b))


def test_pods_pad_to_a_chunk_multiple():
    _c, snap, _m, _w, raw, padded = problems.north_star_problem(16, 40, 16)
    assert padded == 48 and snap.num_pods == 48
    assert int(np.asarray(snap.pods.mask).sum()) == 40
    assert raw.shape == (snap.nodes.alloc.shape[0],)
    mega = problems.mega_problem(20, 50, 16)
    assert mega["padded"] == 64 and mega["req"].shape[0] == 64
    assert int(mega["mask"].sum()) == mega["n_pods"] == 50


@pytest.mark.parametrize("config", [0, 1, 6, 7, 99])
def test_config_problem_rejects_an_unknown_config(config):
    with pytest.raises(ValueError, match="unknown config"):
        problems.config_problem(config)


def test_exported_beside_the_scenarios():
    for name in ("alloc_problem", "flagship_solve_stats",
                 "north_star_problem", "mega_problem", "packing_problem",
                 "config_problem", "pod_chunks", "NORTH_STAR_SHAPE", "FLAGSHIP_SHAPE",
                 "SMOKE_SHAPE", "SHARD_SMOKE_SHAPE", "PACK_SMOKE_SHAPE",
                 "SMOKE_COMPARE_SHAPES"):
        assert getattr(models, name) is getattr(problems, name)


@pytest.mark.parametrize("program, shape", [
    ("bench_cfg0_tpu_smoke", problems.SMOKE_SHAPE),
    ("packing_solve", problems.PACK_SMOKE_SHAPE),
])
def test_registry_builds_at_the_constants_shape(program, shape):
    """The cheap registry programs' arguments have the node count the
    shape constant names (the expensive ones are lowered by the `*-check`
    gates against the committed digests)."""
    from tools import tpu_lower

    _fn, args, _mesh = tpu_lower.PROGRAMS[program]()
    snap = args[0]
    assert int(np.asarray(snap.nodes.mask).sum()) == shape["n_nodes"]
    if "n_pods" in shape:
        assert int(np.asarray(snap.pods.mask).sum()) == shape["n_pods"]


def test_north_star_chunk_program_places_and_threads_its_carry():
    """`north_star_chunk_solver` through `run_chunk_pipeline`: every pod
    of a roomy problem places, no node is overcommitted, and the carry
    that comes back is the free capacity the placements leave."""
    from scheduler_plugins_tpu.api.resources import CANONICAL, PODS
    from scheduler_plugins_tpu.ops.fit import free_capacity
    from scheduler_plugins_tpu.parallel.pipeline import (
        north_star_chunk_solver,
        run_chunk_pipeline,
    )

    chunk = 16
    _c, snap, _m, _w, raw, padded = problems.north_star_problem(16, 40, chunk)
    req, mask = np.asarray(snap.pods.req), np.asarray(snap.pods.mask)
    free0 = np.asarray(free_capacity(snap.nodes.alloc, snap.nodes.requested))
    results, free, done_s, _tl = run_chunk_pipeline(
        north_star_chunk_solver(), (raw, snap.nodes.mask),
        problems.pod_chunks(snap, chunk), jax.numpy.asarray(free0),
    )
    assignment = np.concatenate([np.asarray(a) for a, _stats in results])
    assert len(done_s) == padded // chunk
    assert (assignment[mask] >= 0).all() and (assignment[~mask] == -1).all()
    used = np.zeros_like(free0)
    np.add.at(used, assignment[mask], req[mask])
    # every placed pod also takes one slot of the node's pod count
    np.add.at(used[:, CANONICAL.index(PODS)], assignment[mask], 1)
    assert (np.asarray(free) == free0 - used).all()
    assert (np.asarray(free) >= 0).all()
