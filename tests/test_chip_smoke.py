"""The on-chip check and what it stands on (ISSUE 21): `chip_smoke.py`
refuses to run without a TPU, its CPU rehearsal drives every phase at a
tiny size and prints the line schema, the compile cache is placed from
outside or at one fixed path, the hardware row comes from the device's
kind, and a native library's name follows its source."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


def _run(args, env_extra=None, cwd=REPO, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    # the suite's 8-device virtual mesh is conftest's business, not the
    # child's: the rehearsal runs on one CPU device like a real run
    env.pop("XLA_FLAGS", None)
    for name in drop:
        env.pop(name, None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=240,
    )


class TestChipSmokeScript:
    def test_refuses_to_run_without_a_tpu(self):
        proc = _run(["chip_smoke.py"])
        assert proc.returncode != 0
        assert proc.stdout == ""  # no work done, no result printed
        assert "no TPU" in proc.stderr

    def test_four_devices_must_be_present(self):
        proc = _run(["chip_smoke.py", "--rehearse-cpu", "--devices", "4"])
        assert proc.returncode != 0
        assert proc.stdout == ""
        assert "--devices 4 asked for, 1 present" in proc.stderr

    def test_rehearsal_runs_every_phase_and_prints_the_schema(self, tmp_path):
        cache = tmp_path / "placed-cache"
        proc = _run(
            ["chip_smoke.py", "--rehearse-cpu"],
            env_extra={CACHE_VAR: str(cache)},
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
        header, phases, last = lines[0], lines[1:-1], lines[-1]
        # the cache lives where the variable says, and was written there
        assert header["compile_cache_dir"] == str(cache)
        assert header["size"] == "rehearsal"
        assert any(cache.iterdir())
        assert [p["phase"] for p in phases] == [
            "A_served_daemon", "B_north_star_pipeline", "C_plugin_profiles",
        ]
        for line in [header, *phases]:
            # a rehearsal never reads as a chip run
            assert line["platform"] == "cpu"
            assert line["hardware_row"] is None
        for phase in phases:
            assert phase["ok"] is True
            for key in ("device_kind", "device_count", "wall_s", "compile_s",
                        "compile_s_by_program", "compile_count",
                        "cache_requests", "cache_hits",
                        "peak_bytes_in_use"):
                assert key in phase, (phase["phase"], key)
        served, north_star, profiles = phases
        assert served["pods_bound"] == 108
        assert served["bit_equal_to_host_twin"] is True
        assert served["rebases"] == 1
        assert served["daemon_exit"]["parked_cycles"] == 0
        assert served["compile_count"] >= 1
        assert north_star["placed"] == north_star["pods"]
        assert north_star["in_window_compiles"] == 0
        assert north_star["carry_matches_replay"] is True
        assert sorted(profiles["configs"]) == ["2", "3", "4", "5"]
        assert all(
            c["device_equals_host_cpu"] for c in profiles["configs"].values()
        )
        assert last == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        }


class TestCompileCachePlacement:
    def test_placed_from_outside_sets_no_directory_in_code(
        self, monkeypatch, tmp_path
    ):
        from scheduler_plugins_tpu.utils import compile_cache

        updates = []
        monkeypatch.setattr(
            compile_cache.jax.config, "update",
            lambda name, value: updates.append(name),
        )
        monkeypatch.setattr(compile_cache, "_listening", True)
        monkeypatch.setenv(CACHE_VAR, str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

        monkeypatch.delenv(CACHE_VAR)
        assert compile_cache.configure() == str(REPO / ".jax_cache")
        assert "jax_compilation_cache_dir" in updates

    def test_unset_is_one_fixed_path_from_any_process(self, tmp_path):
        code = (
            "from scheduler_plugins_tpu.utils import compile_cache as c;"
            "import jax;"
            "print(c.configure()); print(jax.config.jax_compilation_cache_dir)"
        )
        outs = [
            _run(["-c", code], cwd=cwd, drop=(CACHE_VAR,))
            for cwd in (REPO, tmp_path)
        ]
        for proc in outs:
            assert proc.returncode == 0, proc.stderr[-2000:]
            assert proc.stdout.split() == [str(REPO / ".jax_cache")] * 2


class TestHardwareRowFromDevice:
    def test_v5e_reports_tpu_v5_lite(self):
        from scheduler_plugins_tpu.parallel import vmem

        row = vmem.target_for_device_kind("TPU v5 lite")
        assert row == "tpu_v5e"
        assert vmem.HBM_BYTES_PER_S[row] == 0.82e12
        assert vmem.PEAK_FLOPS_PER_S[row] == 197e12
        assert row in vmem.ROOFLINE_TARGETS

    def test_unknown_kind_is_an_error_not_a_default(self):
        from scheduler_plugins_tpu.parallel import vmem

        for kind in ("cpu", "TPU v9", ""):
            with pytest.raises(ValueError, match="no hardware row"):
                vmem.target_for_device_kind(kind)

    def test_every_mapped_kind_has_a_complete_row(self):
        from scheduler_plugins_tpu.parallel import vmem

        assert set(vmem.DEVICE_KIND_TARGETS.values()) <= set(
            vmem.ROOFLINE_TARGETS
        )


class TestNativeLibraryNaming:
    def test_name_follows_the_source(self, tmp_path):
        from scheduler_plugins_tpu.bridge import native_lib_path

        src = tmp_path / "store.cc"
        src.write_text("int f() { return 1; }\n")
        first = native_lib_path(src)
        assert first.parent == tmp_path
        assert first.name.startswith("libstore.") and first.suffix == ".so"
        assert native_lib_path(src) == first  # same source, same name
        src.write_text("int f() { return 2; }\n")
        assert native_lib_path(src) != first

    def test_a_foreign_library_is_never_loaded(self, tmp_path):
        from scheduler_plugins_tpu.bridge import build_native

        src = tmp_path / "store.cc"
        src.write_text('extern "C" int answer() { return 42; }\n')
        # what the mtime rule used to pick up: a newer .so of other source
        (tmp_path / "libstore.so").write_bytes(b"not a library")
        (tmp_path / "libstore.0123456789ab.so").write_bytes(b"nor this")
        import ctypes

        lib = ctypes.CDLL(str(build_native(src)))
        assert lib.answer() == 42
