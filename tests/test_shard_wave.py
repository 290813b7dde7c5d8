"""Sharded wave solver tests (ops.assign.waterfill_targeted_sharded +
parallel.solver.sharded_wave_chunk_solver): the shard_map ring-election
waterfill must be BIT-IDENTICAL to the single-device targeted waterfill at
every shard count (the test shapes sit far below the 2^53 cumulative-
capacity bound where parity is unconditional), padded rank rows must never
win an election, and the per-wave cross-shard traffic must stay O(shards)
champion reductions with no full-axis gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scheduler_plugins_tpu.api.resources import CANONICAL, CPU, MEMORY
from scheduler_plugins_tpu.ops.assign import waterfill_assign_targeted
from scheduler_plugins_tpu.parallel.mesh import make_node_mesh, pad_to_shards
from scheduler_plugins_tpu.parallel.solver import (
    collective_census,
    rank_order_inputs,
    sharded_wave_chunk_solver,
)

gib = 1 << 30


def random_problem(seed, n_nodes, n_pods, tight=False):
    """(raw, free0, node_mask, req, pod_mask) int64 tensors in CANONICAL
    axis order. `tight` shrinks capacity so rescue waves, hopeless
    retirements and admission rejections all fire."""
    rng = np.random.default_rng(seed)
    cpu_hi = 8_000 if tight else 64_000
    alloc = np.stack([
        rng.integers(2000, cpu_hi, n_nodes),
        rng.integers(4, 64 if tight else 256, n_nodes) * gib,
        np.zeros(n_nodes, np.int64),
        rng.integers(2 if tight else 4, 60, n_nodes),
    ], axis=1).astype(np.int64)
    req = np.stack([
        rng.integers(50, 8000, n_pods),
        rng.integers(1, 16, n_pods) * gib,
        np.zeros(n_pods, np.int64),
        np.zeros(n_pods, np.int64),
    ], axis=1).astype(np.int64)
    free0 = jnp.asarray(alloc)
    weights_cpu, weights_mem = 1 << 20, 1
    cpu_col = jnp.asarray(alloc[:, CANONICAL.index(CPU)])
    mem_col = jnp.asarray(alloc[:, CANONICAL.index(MEMORY)])
    raw = -(cpu_col * weights_cpu + mem_col * weights_mem) // (
        weights_cpu + weights_mem
    )
    node_mask = jnp.asarray(rng.random(n_nodes) > 0.1)  # some cordoned
    pod_mask = jnp.asarray(rng.random(n_pods) > 0.05)  # some gated
    return raw, free0, node_mask, jnp.asarray(req), pod_mask


def solve_single(raw, free0, node_mask, req, pod_mask, **kw):
    a, free = waterfill_assign_targeted(
        raw, req, pod_mask, jnp.where(node_mask[:, None], free0, 0),
        max_waves=8, rescue_window=64, lite_window=32, **kw,
    )
    return np.asarray(a), np.asarray(free)


#: solver memo keyed on everything that shapes the compiled program — tests
#: with equal shapes share ONE compile (the suite budget is real: every
#: distinct (mesh, shapes) pair costs a multi-device XLA compile)
_SOLVERS = {}


def solve_sharded(raw, free0, node_mask, req, pod_mask, n_shards,
                  chunk=None):
    node_ids, rank_free = rank_order_inputs(raw, free0, node_mask, n_shards)
    key = (n_shards, free0.shape, req.shape, chunk)
    if key not in _SOLVERS:
        _SOLVERS[key] = sharded_wave_chunk_solver(
            make_node_mesh(n_shards), free0.shape[0],
            max_waves=8, rescue_window=64, lite_window=32,
        )
    solver = _SOLVERS[key]
    P = req.shape[0]
    chunk = P if chunk is None else chunk
    parts = []
    for lo in range(0, P, chunk):
        (a, _stats), rank_free = solver(
            node_ids, req[lo:lo + chunk], pod_mask[lo:lo + chunk], rank_free
        )
        parts.append(np.asarray(a))
    return np.concatenate(parts), np.asarray(rank_free), np.asarray(node_ids)


class TestDegenerateOneShard:
    """The 1-shard shard_map program is the degenerate-mesh regression that
    catches election-key drift: no padding, no cross-shard traffic, and the
    placements AND the free carry must be bit-identical to the single-
    device targeted waterfill."""

    @pytest.mark.parametrize("seed", [0, 2])
    def test_bit_identical_to_single_device(self, seed):
        prob = random_problem(seed, n_nodes=24, n_pods=120, tight=(seed == 2))
        a_ref, free_ref = solve_single(*prob)
        a_sh, rank_free, node_ids = solve_sharded(*prob, n_shards=1)
        assert (a_sh == a_ref).all()
        # the rank-space carry maps back onto the reference free tensor
        assert (rank_free == free_ref[node_ids]).all()

    def test_chunked_carry_matches_unchunked(self):
        # the donated rank-free carry threads chunk to chunk exactly like
        # one whole-batch solve (queue order is preserved at boundaries,
        # and wave budgets apply per chunk in BOTH paths by construction)
        prob = random_problem(7, n_nodes=16, n_pods=96)
        raw, free0, node_mask, req, pod_mask = prob
        a_chunked, _, _ = solve_sharded(*prob, n_shards=1, chunk=32)
        # reference: single-device solve per chunk with the free carried
        free = jnp.where(node_mask[:, None], free0, 0)
        parts = []
        for lo in range(0, 96, 32):
            a, free = waterfill_assign_targeted(
                raw, req[lo:lo + 32], pod_mask[lo:lo + 32], free,
                max_waves=8, rescue_window=64, lite_window=32,
            )
            parts.append(np.asarray(a))
        assert (a_chunked == np.concatenate(parts)).all()


class TestShardedParity:
    """Multi-shard placements are bit-identical to the single-device wave
    path — including NON-power-of-two node counts, where the mesh-aligned
    padding rows (zero capacity, node id -1) enter the election and must
    never win."""

    # every distinct (shapes, mesh) pair is a multi-device XLA compile the
    # suite budget pays for — two cases cover the whole edge matrix: an
    # evenly-dividing count, and a tight-capacity count whose padding
    # exceeds a whole block (rescue + hopeless retirement cross shards
    # while most rank rows are padding)
    @pytest.mark.parametrize("seed,n_nodes,n_shards,tight", [
        (0, 24, 8, False),  # divides evenly
        (3, 9, 8, True),    # pads 9 -> 16: more padding than one block
    ])
    def test_matches_single_device(self, seed, n_nodes, n_shards, tight):
        prob = random_problem(
            seed, n_nodes=n_nodes, n_pods=160, tight=tight
        )
        a_ref, free_ref = solve_single(*prob)
        a_sh, rank_free, node_ids = solve_sharded(*prob, n_shards=n_shards)
        assert (a_sh == a_ref).all()
        # padded rank rows: id -1, zero capacity, untouched by commits
        pad = node_ids < 0
        assert int(pad.sum()) == pad_to_shards(n_nodes, n_shards) - n_nodes
        assert (rank_free[pad] == 0).all()
        # real rows map back onto the reference free tensor
        real = ~pad
        assert (rank_free[real] == free_ref[node_ids[real]]).all()

    def test_padded_rows_never_win_under_pressure(self):
        # every real node is FULL (zero free): nothing must place, and in
        # particular no pod may elect a padding row even though padding
        # rows are the only "nodes" with equal (zero) capacity everywhere
        # (shapes shared with the 9-node parity case: one compile)
        n_nodes, n_shards = 9, 8
        raw = jnp.zeros(n_nodes, jnp.int64)
        free0 = jnp.zeros((n_nodes, 4), jnp.int64)
        node_mask = jnp.ones(n_nodes, bool)
        req = jnp.ones((160, 4), jnp.int64) * jnp.asarray([100, gib, 0, 0])
        pod_mask = jnp.ones(160, bool)
        a_sh, rank_free, node_ids = solve_sharded(
            raw, free0, node_mask, req, pod_mask, n_shards=n_shards
        )
        assert (a_sh == -1).all()
        assert (rank_free == 0).all()

    def test_cordoned_nodes_unreachable(self):
        # masked nodes are zeroed before rank ordering, so they behave
        # exactly like padding: never elected at any shard count (shapes
        # shared with the 24-node parity case: one compile)
        prob = random_problem(5, n_nodes=24, n_pods=160)
        _, _, node_mask, _, _ = prob
        a_sh, _, _ = solve_sharded(*prob, n_shards=8)
        placed = a_sh[a_sh >= 0]
        assert np.asarray(node_mask)[placed].all()


class TestCollectiveShape:
    """The per-wave cross-shard traffic contract: champion reductions only
    (psum/pmin slot-scatter scans at small S, the ppermute ring above
    PSUM_SCAN_MAX_SHARDS), never a gather of the node axis."""

    def test_census_is_bounded_and_gather_free(self):
        prob = random_problem(0, n_nodes=24, n_pods=64)
        raw, free0, node_mask, req, pod_mask = prob
        S = 8
        mesh = make_node_mesh(S)
        node_ids, rank_free = rank_order_inputs(raw, free0, node_mask, S)
        census = collective_census(
            sharded_wave_chunk_solver(
                mesh, 24, max_waves=8, rescue_window=64, lite_window=32
            ),
            node_ids, req, pod_mask, rank_free,
        )
        assert census.get("all_gather", 0) == 0
        assert census.get("all_gather_invariant", 0) == 0
        assert census.get("all_to_all", 0) == 0
        # 3 wave bodies x a handful of psum/pmin elections
        assert 0 < sum(census.values()) <= 6 * S + 24

    def test_ring_scan_matches_slot_scatter_scan(self):
        # the ppermute ring (the large-S regime) and the one-psum slot
        # scatter must agree exactly — shard_map over the real 8-device
        # mesh, both dtypes the waves use
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from scheduler_plugins_tpu.ops.assign import (
            block_exclusive_offsets,
            ring_exclusive_scan,
        )

        mesh = make_node_mesh(8)

        def both(x):
            ring = ring_exclusive_scan(x, "nodes", 8)
            excl, total = block_exclusive_offsets(x, "nodes", 8)
            return ring, excl, total

        prog = jax.shard_map(
            both, mesh=mesh, in_specs=(P("nodes", None),),
            out_specs=(P("nodes", None), P("nodes", None), P(None, None)),
            check_vma=False,
        )
        for dtype, hi in ((jnp.float64, 1 << 40), (jnp.int32, 1 << 20)):
            x = jnp.asarray(
                np.random.default_rng(0).integers(0, hi, (8, 3)), dtype
            )
            ring, excl, total = jax.jit(prog)(x)
            expect = np.cumsum(np.asarray(x), axis=0) - np.asarray(x)
            assert (np.asarray(ring) == expect).all(), dtype
            assert (np.asarray(excl) == expect).all(), dtype
            assert (np.asarray(total) == np.asarray(x).sum(axis=0)).all()
