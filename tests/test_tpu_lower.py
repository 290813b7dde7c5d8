"""Compile-readiness gate tests (tools/tpu_lower.py): golden known-bad
programs must be flagged by the StableHLO landmine scanner, the current
tree's hot programs must lower clean, and the committed digest manifest
must cover the full program registry."""

import json
from pathlib import Path

import jax
import jax.export
import jax.numpy as jnp
import pytest

import scheduler_plugins_tpu  # noqa: F401  (enables x64: quantities are int64)

from tools.tpu_lower import (
    MANIFEST,
    PROGRAMS,
    canonical_text,
    lower_program,
    op_histogram,
    scan_landmines,
    stablehlo_digest,
)


def _lower(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=("tpu",))(*args).mlir_module()


class TestLandmineScanner:
    """Golden-bad programs: each CLAUDE.md landmine must be flagged."""

    def test_i64_matmul_flagged(self):
        txt = _lower(
            lambda a, b: a @ b,
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.int64),
        )
        mines = scan_landmines(txt)
        assert any(m["op"] in ("dot_general", "dot") for m in mines), txt

    def test_i64_dot_general_via_jnp_dot_flagged(self):
        txt = _lower(
            lambda a, b: jnp.dot(a, b),
            jnp.ones((4, 4), jnp.int64),
            jnp.ones((4, 4), jnp.int64),
        )
        assert scan_landmines(txt)

    def test_2d_i64_cumsum_flagged_as_reduce_window(self):
        # on the TPU lowering path a multi-axis int64 cumsum becomes a
        # reduce_window over i64 — the vmem-hungry compile-hang pattern
        txt = _lower(
            lambda x: jnp.cumsum(x, axis=0), jnp.ones((64, 8), jnp.int64)
        )
        mines = scan_landmines(txt)
        assert any(m["op"] == "reduce_window" for m in mines), txt

    def test_i64_matmul_followed_by_region_op_still_flagged(self):
        # regression: the signature parser must read the dot's OWN line —
        # a following region op (sort) once shadowed it and hid the landmine
        txt = _lower(
            lambda a, b, c: (a @ b, jnp.sort(c, axis=0)),
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.float32),
        )
        mines = scan_landmines(txt)
        assert any(m["op"] == "dot_general" for m in mines), txt

    def test_f64_matmul_near_region_op_not_false_positive(self):
        txt = _lower(
            lambda a, b, c: (
                a.astype(jnp.float64) @ b.astype(jnp.float64),
                jnp.sort(c, axis=0),
            ),
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.float32),
        )
        assert scan_landmines(txt) == []

    def test_f64_matmul_clean(self):
        # the sanctioned idiom: float64 matmul, exact below 2^53
        txt = _lower(
            lambda a, b: (
                a.astype(jnp.float64) @ b.astype(jnp.float64)
            ).astype(jnp.int64),
            jnp.ones((8, 8), jnp.int64),
            jnp.ones((8, 8), jnp.int64),
        )
        assert scan_landmines(txt) == []

    def test_1d_i64_cumsum_clean(self):
        txt = _lower(lambda x: jnp.cumsum(x), jnp.ones(64, jnp.int64))
        assert scan_landmines(txt) == []

    def test_histogram_counts_ops(self):
        txt = _lower(lambda a, b: a + b, jnp.ones(4), jnp.ones(4))
        hist = op_histogram(txt)
        assert hist.get("add", 0) >= 1


class TestDigest:
    def test_digest_strips_loc_metadata(self):
        txt = _lower(lambda x: x * 2, jnp.ones(4))
        assert "loc(" in txt  # raw module carries source locations...
        assert "loc(" not in canonical_text(txt)  # ...the digest input not
        assert len(stablehlo_digest(txt)) == 64

    def test_digest_deterministic(self):
        a = _lower(lambda x: x * 2, jnp.ones(4))
        b = _lower(lambda x: x * 2, jnp.ones(4))
        assert stablehlo_digest(a) == stablehlo_digest(b)


class TestCurrentTree:
    """The shipped programs must lower to TPU StableHLO with no landmines.

    Only the cheap programs run in the unit suite (the full registry —
    north-star shapes, 5000-node scenarios — runs under `make tpu-lower`);
    program choice here still spans both solver families."""

    @pytest.mark.parametrize("name", ["entry", "bench_cfg0_tpu_smoke"])
    def test_program_lowers_clean(self, name):
        txt = lower_program(name)
        assert scan_landmines(txt) == []

    def test_manifest_covers_all_programs_clean(self):
        assert MANIFEST.exists(), (
            "docs/tpu_lowering.json missing: run `make tpu-lower` and "
            "commit it"
        )
        manifest = json.loads(MANIFEST.read_text())
        programs = manifest["programs"]
        missing = sorted(set(PROGRAMS) - set(programs))
        assert not missing, f"manifest missing programs: {missing}"
        dirty = {n: p["landmines"] for n, p in programs.items()
                 if p["landmines"]}
        assert not dirty, f"manifest records landmines: {dirty}"

    def test_check_fails_closed_without_manifest(self, monkeypatch, tmp_path):
        import tools.tpu_lower as T

        monkeypatch.setattr(T, "MANIFEST", tmp_path / "absent.json")
        assert T.run(["entry"], check=True) == 1

    def test_registry_covers_required_surface(self):
        # the ISSUE-1 coverage contract: bench configs 0-6 (incl. the
        # north-star chunk loop), both sharded solves, and entry()
        names = set(PROGRAMS)
        for cfg in range(7):
            assert any(f"cfg{cfg}" in n for n in names), names
        assert "sharded_batch_solve" in names
        assert "sharded_profile_batch_solve" in names
        # ISSUE-7: the shard_map ring-election wave program must stay
        # under the gate (its collectives must keep lowering for TPU)
        assert "sharded_wave_chunk" in names
        assert "entry" in names
        # ISSUE-13: the Pallas ring kernels and the full pallas-election
        # chunk solver must keep AOT-lowering (chip_smoke.py --devices 4
        # compiles the same builds for real on four chips)
        assert {
            "pallas_ring_offsets", "pallas_fused_election",
            "sharded_wave_chunk_pallas",
        } <= names
