"""Pallas ring kernel tests (parallel.kernels, ISSUE 13): the interpret-
mode CPU twins must be BIT-IDENTICAL to the lax collective formulations
they replace (`ops.assign.block_exclusive_offsets` / `lax.pmin` / the
packed verdict psum), the limb packing must be lossless at the 2^53
quantity bound, and the ring engine must behave at the shard-count edges
(S=1 degenerate, non-power-of-two S over a partial device set).

Also home to the ISSUE 13 edge-coverage satellite for the EXISTING lax
election collectives: `ring_exclusive_scan`/`block_exclusive_offsets` at
S=1, non-power-of-two shard counts, and the `PSUM_SCAN_MAX_SHARDS`
formulation crossover (the slot-scatter psum and the ppermute ring must
agree bit-exactly on either side of the boundary).

All programs here are tiny shard_map lambdas over the 8-device host
platform — compile cost per case is a fraction of a second, and cases
share shapes wherever shard counts allow.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from scheduler_plugins_tpu.ops import assign
from scheduler_plugins_tpu.ops.assign import (
    block_exclusive_offsets,
    ring_exclusive_scan,
)
from scheduler_plugins_tpu.parallel import kernels as pk

AXIS = "nodes"


def node_mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), (AXIS,))


def shard_run(fn, mesh, x, out_specs):
    """Run a per-shard fn over the flattened-leading-axis input."""
    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P(AXIS), out_specs=out_specs,
        check_vma=False,
    ))
    return f(x)


class TestLimbPacking:
    def test_round_trip_at_quantity_bound(self):
        vals = jnp.asarray([0, 1, (1 << 53) - 1, 1 << 40, 123456789,
                            (1 << 30) * 3 + 7], dtype=jnp.int64)
        limbs = pk.split_limbs(vals)
        assert limbs.dtype == jnp.int32
        back = pk.join_limbs(limbs)
        assert (back == vals.astype(jnp.float64)).all()  # graft-lint: ignore[GL013] oracle, vals < 2^53

    def test_float64_exact_integers(self):
        vals = jnp.asarray([0.0, 2.0**52, 3.0 * 2**40], dtype=jnp.float64)
        assert (pk.join_limbs(pk.split_limbs(vals)) == vals).all()

    def test_summed_limbs_recombine_exactly(self):
        # limbs summed across shards (each < S * 2^18) still recombine to
        # the true sum — the property the ring relies on
        rng = np.random.default_rng(0)
        parts = rng.integers(0, 1 << 49, size=(32, 5))
        limb_sum = sum(np.asarray(pk.split_limbs(jnp.asarray(p)))
                       for p in parts)
        total = pk.join_limbs(jnp.asarray(limb_sum))
        assert (np.asarray(total) == parts.sum(axis=0).astype(np.float64)).all()


class TestRingOffsetsKernels:
    """Interpret-twin parity vs `block_exclusive_offsets` — S=2 and the
    non-power-of-two S=3 (mesh over a strict subset of the 8 devices:
    LOGICAL neighbor ids must stay mesh-relative)."""

    @pytest.mark.parametrize("S", [2, 3])
    def test_f64_bitident(self, S):
        mesh = node_mesh(S)
        rng = np.random.default_rng(S)
        x = jnp.asarray(
            rng.integers(0, 1 << 49, size=(S, 5)).astype(np.float64)
        ).reshape(-1)

        def lax_fn(xs):
            return block_exclusive_offsets(xs.reshape(5), AXIS, S)

        def pk_fn(xs):
            return pk.ring_offsets_f64(
                xs.reshape(5), AXIS, S, interpret=True
            )

        a = shard_run(lax_fn, mesh, x, (P(AXIS), P(AXIS)))
        b = shard_run(pk_fn, mesh, x, (P(AXIS), P(AXIS)))
        for u, v in zip(a, b):
            assert (np.asarray(u) == np.asarray(v)).all()

    @pytest.mark.parametrize("S", [2, 3])
    def test_i32_bitident(self, S):
        mesh = node_mesh(S)
        rng = np.random.default_rng(10 + S)
        x = jnp.asarray(
            rng.integers(0, 1000, size=(S, 7)).astype(np.int32)
        ).reshape(-1)

        def lax_fn(xs):
            return block_exclusive_offsets(xs.reshape(7), AXIS, S)

        def pk_fn(xs):
            return pk.ring_offsets_i32(
                xs.reshape(7), AXIS, S, interpret=True
            )

        a = shard_run(lax_fn, mesh, x, (P(AXIS), P(AXIS)))
        b = shard_run(pk_fn, mesh, x, (P(AXIS), P(AXIS)))
        for u, v in zip(a, b):
            assert (np.asarray(u) == np.asarray(v)).all()

    def test_one_shard_degenerate(self):
        # no ring steps, no pallas_call: (zeros, x) like the lax helper
        x = jnp.asarray([3.0, 5.0], dtype=jnp.float64)
        excl, tot = pk.ring_offsets_f64(x, AXIS, 1, interpret=True)
        assert (np.asarray(excl) == 0).all()
        assert (np.asarray(tot) == np.asarray(x)).all()
        xi = jnp.asarray([3, 5], dtype=jnp.int32)
        excl, tot = pk.ring_offsets_i32(xi, AXIS, 1, interpret=True)
        assert (np.asarray(excl) == 0).all()
        assert (np.asarray(tot) == np.asarray(xi)).all()


class TestElectionKernels:
    def test_elect_min_matches_pmin(self):
        S = 4
        mesh = node_mesh(S)
        rng = np.random.default_rng(1)
        m = jnp.asarray(
            rng.integers(0, 1 << 30, size=(S, 3, 11)).astype(np.int32)
        ).reshape(-1)

        def lax_fn(xs):
            return jax.lax.pmin(xs.reshape(3, 11), AXIS)

        def pk_fn(xs):
            return pk.elect_min(xs.reshape(3, 11), AXIS, S, interpret=True)

        a = shard_run(lax_fn, mesh, m, P(None, None))
        b = shard_run(pk_fn, mesh, m, P(None, None))
        assert (np.asarray(a) == np.asarray(b)).all()

    def test_fused_election_selects_winner_payload(self):
        # unique keys per shard block (the solver's invariant), shared
        # sentinel N with zero payload; the winner's payload must arrive
        # with the min key on EVERY shard
        S, W, N = 4, 13, 400
        mesh = node_mesh(S)
        rng = np.random.default_rng(2)
        keys = np.full((S, W), N, np.int32)
        payload = np.zeros((S, 4, W), np.int32)
        for s in range(S):
            propose = rng.random(W) > 0.3
            k = s * 100 + rng.integers(0, 100, W)
            keys[s, propose] = k[propose]
            payload[s][:, propose] = rng.integers(
                1, 1000, (4, int(propose.sum()))
            )

        def pk_fn(xs):
            kk = xs[:W].astype(jnp.int32)
            pp = xs[W:].reshape(4, W).astype(jnp.int32)
            mk, mp = pk.fused_election(kk, pp, AXIS, S, interpret=True)
            return jnp.concatenate([mk.reshape(1, W), mp], axis=0)

        flat = jnp.asarray(np.concatenate(
            [keys.reshape(S, W), payload.reshape(S, -1)], axis=1
        ).reshape(-1))
        out = np.asarray(shard_run(pk_fn, mesh, flat, P(None, None)))
        want_k = keys.min(axis=0)
        winner = keys.argmin(axis=0)
        want_p = payload[winner, :, np.arange(W)].T
        assert (out[0] == want_k).all()
        assert (out[1:] == np.where(want_k[None, :] < N, want_p, 0)).all()

    def test_one_shard_degenerate(self):
        keys = jnp.asarray([4, 2], jnp.int32)
        rows = jnp.asarray([[7, 8]], jnp.int32)
        k, p = pk.fused_election(keys, rows, AXIS, 1, interpret=True)
        assert (np.asarray(k) == np.asarray(keys)).all()
        assert (np.asarray(p) == np.asarray(rows)).all()
        assert (np.asarray(pk.elect_min(rows, AXIS, 1, interpret=True))
                == np.asarray(rows)).all()

    def test_election_budget_gate(self, monkeypatch):
        # the static VMEM-envelope gate the solver call sites branch on —
        # pinned: the constant is SPT_PALLAS_MAX_ELECTION_ELEMS-overridable
        # at import time, and an ambient override must not fail tier-1
        monkeypatch.setattr(pk, "PALLAS_MAX_ELECTION_ELEMS", 1 << 19)
        assert pk.fits_election_budget(16, 1024)
        assert not pk.fits_election_budget(
            16, pk.PALLAS_MAX_ELECTION_ELEMS
        )
        assert pk.election_elems(1, 1) == 8 * 128


class TestLaxElectionCollectiveEdges:
    """ISSUE 13 edge satellite for the EXISTING lax collectives: S=1,
    non-power-of-two shard counts, and the `PSUM_SCAN_MAX_SHARDS`
    formulation crossover."""

    def test_one_shard_identities(self):
        x = jnp.asarray([5.0, 7.0], jnp.float64)
        assert (np.asarray(ring_exclusive_scan(x, AXIS, 1)) == 0).all()
        excl, tot = block_exclusive_offsets(x, AXIS, 1)
        assert (np.asarray(excl) == 0).all()
        assert (np.asarray(tot) == np.asarray(x)).all()

    @pytest.mark.parametrize("S", [3, 5, 7])
    def test_non_power_of_two_shard_counts(self, S):
        # slot-psum formulation vs a host prefix on non-pow2 meshes over
        # a strict subset of the 8 devices
        mesh = node_mesh(S)
        rng = np.random.default_rng(S)
        vals = rng.integers(0, 1 << 49, size=(S, 3)).astype(np.float64)
        x = jnp.asarray(vals).reshape(-1)

        def fn(xs):
            return block_exclusive_offsets(xs.reshape(3), AXIS, S)

        excl, tot = shard_run(fn, mesh, x, (P(AXIS), P(AXIS)))
        excl = np.asarray(excl).reshape(S, 3)
        want = np.cumsum(vals, axis=0) - vals
        assert (excl == want).all()
        assert (np.asarray(tot).reshape(S, 3) == vals.sum(axis=0)).all()

    @pytest.mark.parametrize("S", [4, 8])
    def test_psum_scan_boundary_crossover(self, S, monkeypatch):
        """Force the ring formulation at CI shard counts by dropping the
        boundary BELOW S: ring and slot-psum paths must agree bit-exactly
        on the same inputs (both orderings sum blocks left-to-right)."""
        mesh = node_mesh(S)
        rng = np.random.default_rng(40 + S)
        vals = rng.integers(0, 1 << 49, size=(S, 3)).astype(np.float64)
        x = jnp.asarray(vals).reshape(-1)

        def fn(xs):
            return block_exclusive_offsets(xs.reshape(3), AXIS, S)

        a = shard_run(fn, mesh, x, (P(AXIS), P(AXIS)))
        monkeypatch.setattr(assign, "PSUM_SCAN_MAX_SHARDS", S - 1)

        def fn_ring(xs):
            return block_exclusive_offsets(xs.reshape(3), AXIS, S)

        b = shard_run(fn_ring, mesh, x, (P(AXIS), P(AXIS)))
        for u, v in zip(a, b):
            assert (np.asarray(u) == np.asarray(v)).all()

    def test_boundary_is_inclusive(self, monkeypatch):
        """S == PSUM_SCAN_MAX_SHARDS stays on the slot-psum side; S just
        above crosses to the ring — both exact, same outputs."""
        S = 4
        mesh = node_mesh(S)
        rng = np.random.default_rng(99)
        vals = rng.integers(0, 1000, size=(S, 3)).astype(np.int32)
        x = jnp.asarray(vals).reshape(-1)
        outs = []
        for bound in (S, S - 1):  # slot-psum side, then ring side
            monkeypatch.setattr(assign, "PSUM_SCAN_MAX_SHARDS", bound)

            def fn(xs):
                return block_exclusive_offsets(xs.reshape(3), AXIS, S)

            outs.append([
                np.asarray(v)
                for v in shard_run(fn, mesh, x, (P(AXIS), P(AXIS)))
            ])
        for u, v in zip(*outs):
            assert (u == v).all()
        want = np.cumsum(vals, axis=0) - vals
        assert (outs[0][0].reshape(S, 3) == want).all()


class TestCommittedPallasCensus:
    """What the committed manifests record of the Pallas build of the
    sharded wave chunk program (`make cost-audit-check` and
    `make tpu-lower-check` hold the manifests to the code): the ring
    kernels REPLACED every per-wave framework collective, no gather of the
    node axis appeared, and the kernel programs are in the lowering
    manifest beside it."""

    @staticmethod
    def _manifest(name):
        import json
        from pathlib import Path

        docs = Path(__file__).resolve().parent.parent / "docs"
        return json.loads((docs / name).read_text())["programs"]

    def test_ring_kernels_replaced_the_framework_collectives(self):
        cost = self._manifest("cost_model.json")
        pallas = cost["sharded_wave_chunk_pallas"]["collectives"]
        lax = cost["sharded_wave_chunk"]["collectives"]
        assert pallas.get("pallas_call", 0) > 0
        assert lax.get("pallas_call", 0) == 0 and sum(lax.values()) > 0
        for census in (pallas, lax):
            for gather in ("all_gather", "all_gather_invariant",
                           "all_to_all"):
                assert census.get(gather, 0) == 0, gather
        for collective in ("psum", "pmin", "pmax", "ppermute"):
            assert pallas.get(collective, 0) == 0, collective

    def test_kernel_programs_are_in_the_lowering_manifest(self):
        lowered = self._manifest("tpu_lowering.json")
        for name in ("pallas_ring_offsets", "pallas_fused_election",
                     "sharded_wave_chunk_pallas"):
            assert lowered[name]["landmines"] == 0
            assert lowered[name]["ops"].get("custom_call", 0) > 0, name
        assert "all_reduce" not in lowered["sharded_wave_chunk_pallas"]["ops"]
        assert lowered["sharded_wave_chunk"]["ops"]["all_reduce"] > 0
