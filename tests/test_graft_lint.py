"""Linter tests (tools/graft_lint.py): each golden-bad fixture must be
flagged with its rule, the clean fixture and the current source tree must
pass, and suppression comments must work."""

import textwrap
from pathlib import Path

import pytest

from tools.graft_lint import DEFAULT_PATHS, REPO, lint_paths

FIXTURES = Path(__file__).parent / "fixtures" / "graft_lint"


def rules_for(path):
    return {f.rule for f in lint_paths([path])}


class TestGoldenBad:
    @pytest.mark.parametrize(
        "fixture, rule",
        [
            ("bad_i64_matmul.py", "GL003"),
            ("bad_i64_cumsum2d.py", "GL002"),
            ("bad_closure_config.py", "GL001"),
            ("bad_resource_slot.py", "GL005"),
            ("bad_block_timing.py", "GL004"),
            ("bad_donated_reuse.py", "GL006"),
            ("bad_config_update.py", "GL007"),
            ("bad_jit_walltime.py", "GL008"),
            ("bad_all_gather.py", "GL009"),
            ("bad_swallow.py", "GL010"),
            ("bad_pallas_kernel.py", "GL011"),
            ("bad_anonymous_thread.py", "GL012"),
            ("bad_f64_quantity_cast.py", "GL013"),
        ],
    )
    def test_flagged(self, fixture, rule):
        assert rule in rules_for(FIXTURES / fixture)

    def test_f64_cast_fixture_flags_both_forms(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_f64_quantity_cast.py"])
            if f.rule == "GL013"
        ]
        # the .astype(jnp.float64) form AND the dtype=float64 ctor form
        assert len(findings) == 2
        assert rules_for(FIXTURES / "bad_f64_quantity_cast.py") == {"GL013"}

    def test_swallow_fixture_flags_only_broad_swallows(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_swallow.py"])
            if f.rule == "GL010"
        ]
        # bare Exception pass, BaseException ..., and the tuple that
        # smuggles Exception — the narrow OSError handler and the
        # record-and-reroute handler must stay clean
        assert len(findings) == 3
        assert rules_for(FIXTURES / "bad_swallow.py") == {"GL010"}

    def test_pallas_kernel_fixture_flags_only_kernel_bodies(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_pallas_kernel.py"])
            if f.rule == "GL011"
        ]
        # io_callback, time.perf_counter, the ref branch, and the ref
        # branch reached through functools.partial — the static-closure
        # branch and the host helper outside any kernel stay clean
        assert len(findings) == 4
        assert rules_for(FIXTURES / "bad_pallas_kernel.py") == {"GL011"}

    def test_anonymous_thread_fixture_flags_only_unnamed(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_anonymous_thread.py"])
            if f.rule == "GL012"
        ]
        # fully anonymous, daemon-only, and the bare-Thread import form —
        # the named+daemon thread at the bottom must stay clean
        assert len(findings) == 3
        assert rules_for(FIXTURES / "bad_anonymous_thread.py") == {"GL012"}

    def test_all_gather_fixture_flags_only_node_axis_sites(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_all_gather.py"])
            if f.rule == "GL009"
        ]
        # literal "nodes", the NODES_AXIS constant, and the multi-axis
        # tuple — the pod-axis gather and the psum champion reduction
        # must stay clean
        assert len(findings) == 3
        assert rules_for(FIXTURES / "bad_all_gather.py") == {"GL009"}

    def test_jit_walltime_fixture_flags_all_traced_sites(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_jit_walltime.py"])
            if f.rule == "GL008"
        ]
        # two in solve_chunk, one decorated, one in the nested scope — the
        # host-side timing helper stays clean
        assert len(findings) == 4

    def test_config_update_fixture_flags_both_spellings(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_config_update.py"])
            if f.rule == "GL007"
        ]
        assert len(findings) == 2  # jax.config.update AND bare config.update

    def test_matmul_fixture_flags_both_sites(self):
        findings = [
            f for f in lint_paths([FIXTURES / "bad_i64_matmul.py"])
            if f.rule == "GL003"
        ]
        assert len(findings) == 2  # the @ operator AND the jnp.dot call


class TestClean:
    def test_good_fixture_clean(self):
        assert lint_paths([FIXTURES / "good_clean.py"]) == []

    # `slow`: ~11s full-tree AST sweep that exactly duplicates the
    # standalone `make lint` gate (tools/graft_lint.py over the same
    # tree), which runs in `make verify` and its own CI job — tier-1
    # budget headroom, ISSUE 14; run with `-m slow`
    @pytest.mark.slow
    def test_source_tree_clean(self):
        # DEFAULT_PATHS covers tests/ and tools/ too; the known-bad fixture
        # corpora are excluded via the pyproject config (not path hacks)
        findings = lint_paths([str(REPO / p) for p in DEFAULT_PATHS])
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_default_scope_covers_tests_and_tools(self):
        assert "tests" in DEFAULT_PATHS and "tools" in DEFAULT_PATHS


class TestConfig:
    def test_fixture_corpus_excluded_from_directory_sweep(self):
        # sweeping the tests/ DIRECTORY skips the known-bad corpus...
        sweep = lint_paths([FIXTURES.parent.parent])  # tests/
        assert [f for f in sweep if "fixtures" in str(f.path)] == []
        # ...while naming a corpus file explicitly still lints it
        assert rules_for(FIXTURES / "bad_i64_matmul.py") == {"GL003"}

    def test_config_owners_sanction_gl007(self):
        # __graft_entry__.py pins its virtual CPU platform via
        # jax.config.update and is a sanctioned owner; the same code
        # outside the owner list fires
        entry = REPO / "__graft_entry__.py"
        assert "GL007" not in {f.rule for f in lint_paths([str(entry)])}
        from tools.graft_lint import lint_file

        findings, _, _ = lint_file(entry)  # direct call: NOT owned
        assert "GL007" in {f.rule for f in findings}

    def test_exact_cast_owners_sanction_gl013(self):
        # parallel/solver.py's float64 matmul trick casts int64 quantity
        # masks/requests — inside the kernel auditor's traced scope, so the
        # pyproject exact-cast-owners list stands GL013 down on the sweep;
        # a direct un-owned lint of the same file fires
        solver = REPO / "scheduler_plugins_tpu" / "parallel" / "solver.py"
        sweep = lint_paths([str(REPO / "scheduler_plugins_tpu")])
        assert "GL013" not in {f.rule for f in sweep}
        from tools.graft_lint import lint_file

        findings, _, _ = lint_file(solver)  # direct call: NOT owned
        assert "GL013" in {f.rule for f in findings}

    def test_load_config_parses_lists(self):
        from tools.graft_lint import load_config

        cfg = load_config()
        assert "tests/fixtures/graft_lint" in cfg["exclude"]
        assert "__graft_entry__.py" in cfg["config-update-owners"]

    def test_load_config_tolerates_comment_lines_in_lists(self, monkeypatch,
                                                          tmp_path):
        import tools.graft_lint as G

        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
            [tool.graft-lint]
            exclude = [
             # the known-bad corpus
             "tests/fixtures/graft_lint",
            ]
        """))
        monkeypatch.setattr(G, "REPO", tmp_path)
        assert G.load_config()["exclude"] == ["tests/fixtures/graft_lint"]

    def test_load_config_strips_inline_comments(self, monkeypatch, tmp_path):
        # an inline comment on a one-line list must not cascade into
        # swallowing the NEXT key (the '#' once commented out everything
        # up to the following list's closing bracket)
        import tools.graft_lint as G

        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
            [tool.graft-lint]
            exclude = ["tests/fixtures/graft_lint"]  # known-bad corpus
            config-update-owners = [
             "__graft_entry__.py",
            ]
        """))
        monkeypatch.setattr(G, "REPO", tmp_path)
        cfg = G.load_config()
        assert cfg["exclude"] == ["tests/fixtures/graft_lint"]
        assert cfg["config-update-owners"] == ["__graft_entry__.py"]

    def test_load_config_fails_loudly_on_malformed_list(self, monkeypatch,
                                                        tmp_path):
        import tools.graft_lint as G

        (tmp_path / "pyproject.toml").write_text(
            "[tool.graft-lint]\nexclude = [\n oops,\n]\n"
        )
        monkeypatch.setattr(G, "REPO", tmp_path)
        with pytest.raises(SystemExit):
            G.load_config()

    def test_gl007_ignores_plain_dict_named_config(self, tmp_path):
        # bare `config.update` fires only when `config` is bound FROM jax
        f = tmp_path / "plain_dict.py"
        f.write_text(textwrap.dedent("""\
            config = {}

            def merge(extra):
                config.update(extra)
        """))
        assert lint_paths([f]) == []


class TestJitWalltime:
    """GL008: wall clocks only fire inside provably jit-traced scopes."""

    def test_donated_chunk_solver_arg_flagged(self, tmp_path):
        f = tmp_path / "chunk_clock.py"
        f.write_text(textwrap.dedent("""\
            import time

            from scheduler_plugins_tpu.parallel.pipeline import (
                donated_chunk_solver,
            )

            def body(raw, req, free):
                t = time.perf_counter_ns()
                return req + t, free

            solve = donated_chunk_solver(body, carry_argnum=2)
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL008"}

    def test_plugin_tensor_method_flagged(self, tmp_path):
        f = tmp_path / "plugin_clock.py"
        f.write_text(textwrap.dedent("""\
            import time

            from scheduler_plugins_tpu.framework.plugin import Plugin

            class ClockPlugin(Plugin):
                def score(self, state, snap, p):
                    return state.free[:, 0] + int(time.time())
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL008"}

    def test_host_function_not_flagged(self, tmp_path):
        # an un-jitted function reading the clock is the sanctioned
        # host-transfer timing idiom, not a finding
        f = tmp_path / "host_clock.py"
        f.write_text(textwrap.dedent("""\
            import time

            def timed(fn, x):
                start = time.perf_counter()
                out = fn(x)
                return out, time.perf_counter() - start
        """))
        assert lint_paths([f]) == []

    def test_suppression_comment(self, tmp_path):
        f = tmp_path / "supp_clock.py"
        f.write_text(textwrap.dedent("""\
            import time

            import jax

            @jax.jit
            def step(x):
                return x + time.time()  # graft-lint: ignore[GL008]
        """))
        assert lint_paths([f]) == []


class TestSuppression:
    def test_ignore_comment(self, tmp_path):
        f = tmp_path / "suppressed.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def g(a, b):
                a64 = a.astype(jnp.int64)
                return a64 @ b  # graft-lint: ignore[GL003]
        """))
        assert lint_paths([f]) == []

    def test_ignore_other_rule_does_not_suppress(self, tmp_path):
        f = tmp_path / "wrong_rule.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def g(a, b):
                a64 = a.astype(jnp.int64)
                return a64 @ b  # graft-lint: ignore[GL001]
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL003"}


class TestConservatism:
    """Unknown dtypes must never fire (the lint is evidence-based)."""

    def test_unknown_dtype_matmul_not_flagged(self, tmp_path):
        f = tmp_path / "unknown.py"
        f.write_text(textwrap.dedent("""\
            def g(a, b):
                return a @ b
        """))
        assert lint_paths([f]) == []

    def test_positional_axis_i64_cumsum_flagged(self, tmp_path):
        # regression: axis passed positionally must not evade GL002
        f = tmp_path / "pos_axis.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def g(x):
                x64 = x.astype(jnp.int64)
                return jnp.cumsum(x64, 1)
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL002"}

    def test_explicit_axis_none_i64_cumsum_not_flagged(self, tmp_path):
        # axis=None flattens — the benign 1-D form, keyword-explicit
        f = tmp_path / "axis_none.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def g(x):
                x64 = x.astype(jnp.int64)
                return jnp.cumsum(x64, axis=None)
        """))
        assert lint_paths([f]) == []

    def test_int32_cumsum_with_axis_not_flagged(self, tmp_path):
        f = tmp_path / "i32.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def g(x):
                return jnp.cumsum(x.astype(jnp.int64), axis=1,
                                  dtype=jnp.int32)
        """))
        assert lint_paths([f]) == []

    def test_nested_scope_shadowing_not_flagged(self, tmp_path):
        # an enclosing int64 local must not taint a nested function's
        # shadowing parameter of the same name
        f = tmp_path / "nested.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def outer(x, fs):
                a = x.astype(jnp.int64)
                def inner(a, b):
                    return a @ b
                return inner(fs, fs), a
        """))
        assert lint_paths([f]) == []

    def test_nested_scope_finding_reported_once(self, tmp_path):
        f = tmp_path / "nested_bad.py"
        f.write_text(textwrap.dedent("""\
            import jax.numpy as jnp

            def outer(x, y):
                def inner():
                    x64 = x.astype(jnp.int64)
                    return x64 @ y
                return inner()
        """))
        findings = lint_paths([f])
        assert len(findings) == 1 and findings[0].rule == "GL003"

    def test_presence_check_not_flagged(self):
        # good_clean.AuxPlugin.score tests `self._cost_table is None`
        assert "GL001" not in rules_for(FIXTURES / "good_clean.py")


class TestDonatedReuse:
    """GL006: donated-buffer reuse is flagged; the carry-rebind idiom and
    unrelated names stay clean."""

    def test_carry_rebind_idiom_clean(self, tmp_path):
        # the pipeline idiom: the donated carry is rebound in the SAME
        # statement as the donating call — never read stale
        f = tmp_path / "rebind.py"
        f.write_text(textwrap.dedent("""\
            import jax

            solve = jax.jit(lambda raw, free: (raw, free + 1),
                            donate_argnums=(1,))

            def drive(raw, free, chunks):
                out = []
                for _ in range(chunks):
                    a, free = solve(raw, free)
                    out.append(a)
                return out, free
        """))
        assert lint_paths([f]) == []

    def test_reassignment_revives(self, tmp_path):
        f = tmp_path / "revive.py"
        f.write_text(textwrap.dedent("""\
            import jax
            import jax.numpy as jnp

            step = jax.jit(lambda s: s + 1, donate_argnums=(0,))

            def g(s):
                y = step(s)
                s = jnp.zeros_like(y)
                return s.sum() + y.sum()
        """))
        assert lint_paths([f]) == []

    def test_donated_chunk_solver_constructor_tracked(self, tmp_path):
        f = tmp_path / "pipe.py"
        f.write_text(textwrap.dedent("""\
            from scheduler_plugins_tpu.parallel.pipeline import (
                donated_chunk_solver,
            )

            def body(raw, req, free):
                return req, free

            solve = donated_chunk_solver(body, carry_argnum=2)

            def g(raw, req, free):
                a, f2 = solve(raw, req, free)
                return free  # donated at position 2 above
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL006"}

    def test_non_donating_jit_not_tracked(self, tmp_path):
        f = tmp_path / "plain.py"
        f.write_text(textwrap.dedent("""\
            import jax

            step = jax.jit(lambda s: s + 1)

            def g(s):
                y = step(s)
                return s.sum() + y.sum()
        """))
        assert lint_paths([f]) == []

    def test_suppression_comment(self, tmp_path):
        f = tmp_path / "supp.py"
        f.write_text(textwrap.dedent("""\
            import jax

            step = jax.jit(lambda s: s + 1, donate_argnums=(0,))

            def g(s):
                y = step(s)
                return s.sum() + y.sum()  # graft-lint: ignore[GL006]
        """))
        assert lint_paths([f]) == []

    def test_loop_carried_reuse_flagged(self, tmp_path):
        # the chunk-loop bug class GL006 exists for: the carry is donated
        # each iteration but never rebound — iteration k+1 passes a dead
        # buffer. Caught via the loop-body double sweep.
        f = tmp_path / "loop_reuse.py"
        f.write_text(textwrap.dedent("""\
            import jax

            solve = jax.jit(lambda raw, free: (raw, free + 1),
                            donate_argnums=(1,))

            def drive(raw, free, chunks):
                out = []
                for _ in range(chunks):
                    a = solve(raw, free)  # free donated, never rebound
                    out.append(a)
                return out
        """))
        assert {x.rule for x in lint_paths([f])} == {"GL006"}

    def test_branch_donation_no_false_positive(self, tmp_path):
        # a donate+rebind in one branch must not poison the other branch's
        # read (branches sweep on copies)
        f = tmp_path / "branch.py"
        f.write_text(textwrap.dedent("""\
            import jax

            solve = jax.jit(lambda raw, free: (raw, free + 1),
                            donate_argnums=(1,))

            def g(raw, free, flag):
                if flag:
                    a, free = solve(raw, free)
                else:
                    a = free.sum()
                return a, free
        """))
        assert lint_paths([f]) == []

    def test_loop_target_donation_no_false_positive(self, tmp_path):
        # a donated PER-ITERATION input rebinds via the for target every
        # iteration — the back-edge sweep must re-revive it
        f = tmp_path / "loop_target.py"
        f.write_text(textwrap.dedent("""\
            import jax

            step = jax.jit(lambda a, x: a + x, donate_argnums=(1,))

            def drive(a, xs):
                out = []
                for x in xs:
                    out.append(step(a, x))
                return out
        """))
        assert lint_paths([f]) == []
