"""Compiled-cost observatory gate tests (ISSUE 20).

Covers the five claims the cost layer makes:

- roofline projections match hand-computed oracles (pure arithmetic);
- the compiled cost census is deterministic (two independent compiles of
  the same program produce identical rows and digests) and the committed
  docs/cost_model.json is self-consistent: full 25-program coverage,
  zero budget violations, digests and rooflines re-derivable from the
  committed rows without compiling anything;
- the `--check` gate fails closed: missing manifest, coverage gap,
  budget breach, and cost-digest drift all exit non-zero;
- the golden-bad fixture (an O(N*P) dense blow-up) fires EXACTLY the
  cost-budget rule and is invisible to graft_lint / jaxpr_audit /
  kernel_audit, per the ANALYSIS.md division of labor;
- the sentry's two-arm split: an injected algorithmic cost regression
  stays `regression` under a simulated sick host where the timing arm
  downgrades to `degraded-host`, and a zero cost delta stays quiet.

Tier-1 budget discipline: everything here is pure host arithmetic or
committed-manifest reads except THREE tiny compiles (the 768x512 int32
toy program twice for determinism, `serving_side_apply` — the smallest
registered program, 151 flops — once per fail-closed table row).
"""

import importlib.util
import json
from pathlib import Path

import pytest

import scheduler_plugins_tpu  # noqa: F401  (enables x64: quantities are int64)

from scheduler_plugins_tpu.obs import costmodel
from scheduler_plugins_tpu.parallel.vmem import (
    HBM_BYTES_PER_S,
    PEAK_FLOPS_PER_S,
    ROOFLINE_TARGETS,
    VMEM_BUDGET_BYTES,
)

REPO = Path(__file__).parent.parent
FIXTURE = REPO / "tests" / "fixtures" / "cost_audit" / "bad_cost_budget.py"


def _load_fixture():
    spec = importlib.util.spec_from_file_location(
        "cost_audit_fixture_bad_cost_budget", FIXTURE
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fixture_cost():
    """One compiled-cost measurement of the golden-bad toy program,
    shared by every test that needs a real measured row."""
    mod = _load_fixture()
    fn, args, _roles = mod.build()
    return mod, costmodel.compiled_cost(fn, args)


# ---------------------------------------------------------------------------
# roofline arithmetic vs hand-computed oracles
# ---------------------------------------------------------------------------


class TestRooflineOracle:
    def test_memory_bound_oracle(self):
        # 1.2e6 flops over 1.2e6 bytes on v4: intensity 1.0 is far below
        # the ridge 275/1.2 ~ 229.2, so the HBM roof binds and the floor
        # is bytes/bw = 1.2e6/1.2e12 s = 1.0 us exactly
        r = costmodel.roofline(1_200_000, 1_200_000, "tpu_v4")
        assert r["bound"] == "memory"
        assert r["intensity_flops_per_byte"] == 1.0
        assert r["ridge_flops_per_byte"] == round(275e12 / 1.2e12, 6)
        assert r["memory_floor_us"] == 1.0
        assert r["step_floor_us"] == 1.0
        assert r["compute_floor_us"] == round(1_200_000 / 275e12 * 1e6, 6)

    def test_compute_bound_oracle(self):
        # 2.75e15 flops over 1e6 bytes: intensity 2.75e9 >> ridge, the
        # MXU roof binds, floor = flops/peak = 10 s
        r = costmodel.roofline(int(2.75e15), 1_000_000, "tpu_v4")
        assert r["bound"] == "compute"
        assert r["step_floor_us"] == pytest.approx(10e6)
        assert r["compute_floor_us"] == r["step_floor_us"]

    def test_exact_ridge_is_compute(self):
        # at EXACTLY the ridge intensity both roofs give the same floor;
        # the verdict tie-breaks to compute (>=)
        bytes_accessed = 1_200_000
        flops = int(bytes_accessed * (275e12 / 1.2e12))
        r = costmodel.roofline(flops, bytes_accessed, "tpu_v4")
        assert r["bound"] == "compute"
        assert r["compute_floor_us"] == pytest.approx(
            r["memory_floor_us"], rel=1e-9
        )

    def test_zero_bytes_is_compute_bound(self):
        r = costmodel.roofline(1000, 0, "tpu_v4")
        assert r["bound"] == "compute"
        assert r["intensity_flops_per_byte"] is None
        assert r["memory_floor_us"] == 0.0
        assert r["step_floor_us"] == r["compute_floor_us"]

    @pytest.mark.parametrize("target", sorted(PEAK_FLOPS_PER_S))
    def test_per_generation_oracle(self, target):
        flops, nbytes = 5_000_000, 3_000_000
        r = costmodel.roofline(flops, nbytes, target)
        assert r["target"] == target
        assert r["compute_floor_us"] == round(
            flops / PEAK_FLOPS_PER_S[target] * 1e6, 6
        )
        assert r["memory_floor_us"] == round(
            nbytes / HBM_BYTES_PER_S[target] * 1e6, 6
        )
        assert r["step_floor_us"] == max(
            r["compute_floor_us"], r["memory_floor_us"]
        )

    def test_one_module_owns_all_hardware_numbers(self):
        # every generation with a VMEM budget has both peaks, and the
        # roofline-target set is exactly that intersection
        assert set(ROOFLINE_TARGETS) == set(VMEM_BUDGET_BYTES)
        assert set(PEAK_FLOPS_PER_S) == set(HBM_BYTES_PER_S)


# ---------------------------------------------------------------------------
# digests + budgets (pure arithmetic)
# ---------------------------------------------------------------------------


class TestDigestsAndBudgets:
    ROW = {
        "flops": 1000, "transcendentals": 0, "bytes_accessed": 4000,
        "argument_bytes": 2000, "output_bytes": 100, "temp_bytes": 400,
        "peak_bytes": 2500,
    }

    def test_digest_deterministic_and_sensitive(self):
        d1 = costmodel.cost_digest(dict(self.ROW))
        d2 = costmodel.cost_digest(dict(reversed(list(self.ROW.items()))))
        assert d1 == d2  # canonical: field order cannot matter
        bumped = dict(self.ROW, flops=self.ROW["flops"] + 1)
        assert costmodel.cost_digest(bumped) != d1

    def test_static_only_digest_tracks_tpu_shape(self):
        row = {"flops": None, "tpu": {"sha256": "aa"},
               "collectives": {"psum": 2}}
        d1 = costmodel.cost_digest(row)
        assert costmodel.cost_digest(dict(row, tpu={"sha256": "bb"})) != d1
        assert costmodel.cost_digest(
            dict(row, collectives={"psum": 3})
        ) != d1

    def test_default_budgets_headroom(self):
        budgets = costmodel.default_budgets(self.ROW)
        assert budgets == {"flops": 1500, "bytes_accessed": 6000,
                           "peak_bytes": 3750}
        assert costmodel.default_budgets({"flops": None}) == {}

    def test_budget_violation_table(self):
        budgets = costmodel.default_budgets(self.ROW)
        assert costmodel.budget_violations(self.ROW, budgets) == []
        # breach: any budgeted axis over its cap
        hot = dict(self.ROW, bytes_accessed=6001)
        v = costmodel.budget_violations(hot, budgets)
        assert len(v) == 1 and "bytes_accessed" in v[0]
        # fail closed: a measured axis with NO committed budget is
        # itself a violation
        v = costmodel.budget_violations(self.ROW, {"flops": 1500})
        assert any("no committed budget" in s for s in v)
        # static-only rows (no budgets) never violate
        assert costmodel.budget_violations({"flops": None}, {}) == []


# ---------------------------------------------------------------------------
# the committed manifest: coverage, self-consistency, hardware agreement
# ---------------------------------------------------------------------------


class TestCommittedManifest:
    @pytest.fixture(scope="class")
    def manifest(self):
        m = costmodel.load_manifest()
        assert m is not None, "docs/cost_model.json missing: run `make cost-audit`"
        return m

    def test_full_registry_coverage(self, manifest):
        from tools.tpu_lower import PROGRAMS

        assert sorted(manifest["programs"]) == sorted(PROGRAMS)

    def test_zero_budget_violations(self, manifest):
        for name, row in manifest["programs"].items():
            assert costmodel.budget_violations(
                row, row.get("budgets")
            ) == [], name

    def test_digests_rederivable_without_compiling(self, manifest):
        # determinism evidence that costs nothing: the committed digest
        # of every row must equal the digest recomputed from the
        # committed fields — a hand-edited manifest cannot pass
        for name, row in manifest["programs"].items():
            assert row["cost_digest"] == costmodel.cost_digest(row), name

    def test_rooflines_rederivable(self, manifest):
        for name, row in manifest["programs"].items():
            if row["flops"] is None:
                assert row["roofline"] is None, name
                continue
            assert row["roofline"] == costmodel.roofline(
                row["flops"], row["bytes_accessed"],
                row["roofline"]["target"],
            ), name

    def test_static_only_rows_are_the_mosaic_kernels(self, manifest):
        static = {n for n, r in manifest["programs"].items()
                  if r.get("static_only")}
        assert static == {"sharded_wave_chunk_pallas", "pallas_ring_offsets",
                          "pallas_fused_election"}
        for name in static:
            row = manifest["programs"][name]
            # still joined: TPU digest + VMEM envelope + census all
            # present, so 25/25 coverage is real, not vacuous
            assert row["tpu"]["sha256"]
            assert row["kernels"], name
            assert row["collectives"], name

    def test_hardware_block_matches_vmem_module(self, manifest):
        hw = manifest["hardware"]
        t = hw["target"]
        assert hw["peak_flops_per_s"] == PEAK_FLOPS_PER_S[t]
        assert hw["hbm_bytes_per_s"] == HBM_BYTES_PER_S[t]
        assert hw["vmem_budget_bytes"] == VMEM_BUDGET_BYTES[t]


# ---------------------------------------------------------------------------
# measurement determinism (two independent compiles)
# ---------------------------------------------------------------------------


class TestDeterminism:
    def test_two_compiles_identical_cost(self, fixture_cost):
        mod, row1 = fixture_cost
        fn, args, _roles = mod.build()  # a FRESH jit: nothing shared
        row2 = costmodel.compiled_cost(fn, args)
        assert row1 == row2
        assert costmodel.cost_digest(row1) == costmodel.cost_digest(row2)


# ---------------------------------------------------------------------------
# fail-closed check tables (tools/cost_observatory.py --check)
# ---------------------------------------------------------------------------


class TestFailClosed:
    @pytest.fixture()
    def observatory(self):
        from tools import cost_observatory

        return cost_observatory

    def test_missing_manifest_fails(self, observatory, tmp_path, monkeypatch):
        monkeypatch.setattr(
            observatory, "MANIFEST", tmp_path / "absent.json"
        )
        assert observatory.run([], check=True) == 1

    def test_coverage_gap_fails(self, observatory, tmp_path, monkeypatch):
        import jax

        gap = tmp_path / "gap.json"
        gap.write_text(json.dumps({"jax": jax.__version__, "programs": {}}))
        monkeypatch.setattr(observatory, "MANIFEST", gap)
        assert observatory.run([], check=True) == 1

    def _tampered(self, tmp_path, mutate):
        committed = json.loads(
            (REPO / "docs" / "cost_model.json").read_text()
        )
        mutate(committed)
        path = tmp_path / "tampered.json"
        path.write_text(json.dumps(committed))
        return path

    def test_budget_breach_fails(self, observatory, tmp_path, monkeypatch):
        # squeeze the committed budget below the measured value: the
        # re-measure must breach it (one tiny compile: 151 flops)
        def mutate(m):
            m["programs"]["serving_side_apply"]["budgets"]["flops"] = 1

        monkeypatch.setattr(
            observatory, "MANIFEST", self._tampered(tmp_path, mutate)
        )
        assert observatory.run(["serving_side_apply"], check=True) == 1

    def test_cost_drift_fails(self, observatory, tmp_path, monkeypatch):
        def mutate(m):
            m["programs"]["serving_side_apply"]["cost_digest"] = "0" * 64

        monkeypatch.setattr(
            observatory, "MANIFEST", self._tampered(tmp_path, mutate)
        )
        assert observatory.run(["serving_side_apply"], check=True) == 1

    def test_green_on_committed_tree(self, observatory):
        assert observatory.run(["serving_side_apply"], check=True) == 0


# ---------------------------------------------------------------------------
# golden-bad fixture: the cost rule fires; the other prongs stay silent
# ---------------------------------------------------------------------------


class TestGoldenBad:
    def test_cost_budget_rule_fires(self, fixture_cost):
        mod, row = fixture_cost
        violations = costmodel.budget_violations(row, mod.BUDGETS)
        # every budgeted axis breached — the O(N*P) blow-up is visible
        # on flops AND bytes AND peak
        assert len(violations) == 3, (violations, row)

    def test_invisible_to_ast_lint(self):
        from tools.graft_lint import lint_file

        findings, _, _ = lint_file(FIXTURE)
        assert findings == [], [str(f) for f in findings]

    def test_invisible_to_jaxpr_audit(self):
        from tools import jaxpr_audit

        fn, args, roles = _load_fixture().build()
        res = jaxpr_audit.audit_fn(fn, args, roles=roles)
        assert res["rules"] == {r: 0 for r in jaxpr_audit.RULES}, (
            res["violations"]
        )

    def test_invisible_to_kernel_audit(self):
        from tools import kernel_audit

        fn, args, roles = _load_fixture().build()
        res = kernel_audit.audit_fn(fn, args, roles=roles)
        assert res["rules"] == {r: 0 for r in kernel_audit.RULES}, (
            res["violations"]
        )


# ---------------------------------------------------------------------------
# the sentry's two-arm split (pure arithmetic — no timings needed here;
# the really-measured version runs in `perf_sentry.py selftest`)
# ---------------------------------------------------------------------------


class TestSentryCostArm:
    @pytest.fixture(scope="class")
    def sentry(self):
        from tools import perf_sentry

        return perf_sentry

    @staticmethod
    def _row(flops, nbytes, peak):
        row = {"flops": flops, "bytes_accessed": nbytes, "peak_bytes": peak}
        row["cost_digest"] = costmodel.cost_digest(row)
        return row

    def test_cost_regression_survives_sick_host(self, sentry):
        base = self._row(1_000_000, 2_000_000, 500_000)
        bad = self._row(2_000_000, 4_000_000, 500_000)
        sick = {"healthy": False, "reasons": ["load_high"]}
        # timing arm on the same sick host: a real 2x slowdown must
        # downgrade (this host cannot be trusted to time anything)
        t = sentry.verdict([10.0, 10.1, 10.2], [20.0, 20.2, 20.4],
                           metric="selftest_ms", health=sick)
        assert t["verdict"] == "degraded-host"
        # cost arm: zero noise floor, health ignored BY DESIGN
        c = sentry.cost_verdict(base, bad, program="p", health=sick)
        assert c["verdict"] == "regression"
        assert c["noise_floor"] == 0.0
        assert c["max_rel_delta"] == 1.0
        # combined: the deterministic arm wins
        assert sentry.combine_arms(t["verdict"], c["verdict"]) == "regression"

    def test_zero_cost_delta_stays_quiet(self, sentry):
        base = self._row(1_000_000, 2_000_000, 500_000)
        c = sentry.cost_verdict(base, dict(base), program="p",
                                health={"healthy": False, "reasons": ["x"]})
        assert c["verdict"] == "ok"
        assert c["max_rel_delta"] == 0.0
        assert sentry.combine_arms("ok", c["verdict"]) == "ok"

    def test_cost_improvement_and_no_baseline(self, sentry):
        base = self._row(1_000_000, 2_000_000, 500_000)
        better = self._row(500_000, 1_000_000, 400_000)
        assert sentry.cost_verdict(base, better)["verdict"] == "improved"
        assert sentry.cost_verdict(None, base)["verdict"] == "no-baseline"
        assert sentry.cost_verdict(base, None)["verdict"] == "no-baseline"

    def test_static_only_shape_change_is_regression(self, sentry):
        a = {"flops": None, "tpu": {"sha256": "aa"}}
        b = {"flops": None, "tpu": {"sha256": "bb"}}
        a["cost_digest"] = costmodel.cost_digest(a)
        b["cost_digest"] = costmodel.cost_digest(b)
        assert sentry.cost_verdict(a, b)["verdict"] == "regression"
        assert sentry.cost_verdict(a, dict(a))["verdict"] == "ok"

    def test_cost_check_overall_is_worst(self, sentry):
        base = {"jax": "x", "programs": {
            "good": self._row(100, 200, 50),
            "bad": self._row(100, 200, 50),
        }}
        cand = {"jax": "x", "programs": {
            "good": dict(base["programs"]["good"]),
            "bad": self._row(300, 200, 50),
        }}
        rep = sentry.cost_check(base, cand)
        assert rep["overall"] == "regression"
        assert rep["verdicts"]["good"]["verdict"] == "ok"
        assert rep["comparable_jax"] is True

    def test_verdict_order_matches_timing_arm(self, sentry):
        # one severity scale across both arms: degraded-host sits below
        # regression, so combine_arms can never LOWER a timing verdict
        assert sentry.combine_arms("regression", "ok") == "regression"
        assert sentry.combine_arms("no-baseline", "improved") == "improved"


# ---------------------------------------------------------------------------
# flight-recorder cost stamp + replay drift flag
# ---------------------------------------------------------------------------


class TestBundleCostStamp:
    def test_stamp_and_drift_roundtrip(self, tmp_path):
        from scheduler_plugins_tpu.utils.flightrec import FlightRecorder
        from tools.replay import _cost_stamp_drift

        bundle = tmp_path / "bundle"
        bundle.mkdir()
        # no stamp -> None (old bundles stay loadable, no false flag)
        assert _cost_stamp_drift(str(bundle)) is None
        FlightRecorder._save_cost_stamp(str(bundle))
        fresh = _cost_stamp_drift(str(bundle))
        assert fresh is not None and fresh["drifted"] is False
        # tamper the recorded provenance: drift flagged with the changed
        # program set named
        stamp = json.loads((bundle / "cost.json").read_text())
        stamp["manifest_digest"] = "0" * 64
        stamp["programs"]["entry"] = "f" * 64
        (bundle / "cost.json").write_text(json.dumps(stamp))
        drifted = _cost_stamp_drift(str(bundle))
        assert drifted["drifted"] is True
        assert "entry" in drifted["changed_programs"]
        assert "different cost shape" in drifted["warning"]


# ---------------------------------------------------------------------------
# runtime watermark gauges
# ---------------------------------------------------------------------------


class _StubMetrics:
    def __init__(self):
        self.gauges = {}

    def set_gauge(self, name, value, **labels):
        self.gauges[name] = value


class TestWatermarkGauges:
    def test_block_is_null_safe_on_cpu(self):
        block = costmodel.device_memory_block()
        assert block["backend"] == "cpu"
        assert isinstance(block["available"], bool)
        if not block["available"]:
            assert block["bytes_in_use"] is None
            assert block["peak_bytes_in_use"] is None

    def test_stamp_sets_gauges_when_available(self, monkeypatch):
        from scheduler_plugins_tpu.utils import observability as obs

        fake = {
            "backend": "tpu", "available": True,
            "bytes_in_use": 12345, "peak_bytes_in_use": 67890,
            "devices": [{"id": 0, "bytes_in_use": 12345,
                         "peak_bytes_in_use": 67890}],
        }
        monkeypatch.setattr(
            costmodel, "device_memory_block", lambda: dict(fake)
        )
        stub = _StubMetrics()
        block = costmodel.stamp_device_memory(stub)
        assert block["bytes_in_use"] == 12345
        assert stub.gauges[obs.DEVICE_BYTES_IN_USE] == 12345
        assert stub.gauges[obs.DEVICE_PEAK_BYTES] == 67890

    def test_stamp_skips_gauges_when_unavailable(self):
        stub = _StubMetrics()
        block = costmodel.stamp_device_memory(stub)
        if not block["available"]:  # the CPU/tier-1 case
            assert stub.gauges == {}

    def test_stamp_overhead_within_bound(self, monkeypatch):
        """What bounds the stamp's cost, checked by stub: one
        `memory_stats()` read per local device and nothing else of JAX —
        no transfer, no sync. What it costs a tick in time is read on the
        chip, from the daemon's `TickTail/memory` span (PERF.md); the
        paired timing of a 300 us loop this test used to make failed
        under six workers and said nothing about the chip."""
        from scheduler_plugins_tpu.utils import observability as obs

        class Device:
            __slots__ = ("id", "reads")  # any other attribute raises

            def __init__(self, device_id):
                self.id, self.reads = device_id, 0

            def memory_stats(self):
                self.reads += 1
                return {"bytes_in_use": 10 * (self.id + 1),
                        "peak_bytes_in_use": 100}

        devices = [Device(0), Device(1)]

        class OnlyTheAllocatorStats:
            """Stands in for the `jax` module: `device_get`,
            `block_until_ready`, `device_put` and the rest are
            AttributeErrors here."""

            local_devices = staticmethod(lambda: devices)
            default_backend = staticmethod(lambda: "tpu")

        monkeypatch.setattr(costmodel, "jax", OnlyTheAllocatorStats)
        stub = _StubMetrics()
        block = costmodel.stamp_device_memory(stub)
        assert [d.reads for d in devices] == [1, 1]
        assert block["available"] and len(block["devices"]) == 2
        assert stub.gauges == {
            obs.DEVICE_BYTES_IN_USE: 30, obs.DEVICE_PEAK_BYTES: 200,
        }
