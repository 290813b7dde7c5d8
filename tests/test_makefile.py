"""The Makefile is the repo's own gate, so it has to be runnable: every
file a recipe hands to `$(PY)` exists, every prerequisite names a target
this file defines, `verify:` composes only such targets, and nothing in it
runs the root benchmark script that is gone (`BENCHMARK.json` +
`benchmark/` are the benchmark). The two `smoke` subcommands `verify`
reaches through `tools/` run in-process here at their smoke shape."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
TEXT = (REPO / "Makefile").read_text()


def _rules():
    """{target: (prerequisites, recipe lines)}; continuation lines joined."""
    rules, current = {}, None
    for line in TEXT.replace("\\\n", " ").splitlines():
        m = re.match(r"^([A-Za-z0-9_-]+):(?!=)\s*(.*)$", line)
        if m:
            current = m.group(1)
            rules[current] = (m.group(2).split(), [])
        elif line.startswith("\t") and current is not None:
            rules[current][1].append(line.strip())
        elif line.strip() and not line.startswith("#"):
            current = None
    return rules


RULES = _rules()


def _paths(recipe: str):
    """Repo paths a recipe line hands to the interpreter: the script after
    `$(PY)`, or pytest's path arguments after `$(PY) -m pytest`."""
    m = re.search(r"\$\(PY\)\s+(.*)$", recipe)
    if not m:
        return []
    words = m.group(1).split()
    if words[0] == "-m":
        return [w for w in words[2:] if "/" in w and not w.startswith("-")]
    if words[0] == "-c":
        return []
    return [words[0]]


def test_the_parser_sees_the_file():
    assert {"test", "verify", "lint", "chip-smoke"} <= set(RULES)
    assert _paths("JAX_PLATFORMS=cpu $(PY) tools/replay.py smoke") == [
        "tools/replay.py"]
    assert _paths("$(PY) -m pytest tests/ -x -q") == ["tests/"]


@pytest.mark.parametrize("target", sorted(RULES))
def test_target_runs_files_that_exist(target):
    prereqs, recipes = RULES[target]
    assert prereqs or recipes, f"{target}: neither prerequisites nor recipe"
    for recipe in recipes:
        assert "bench" + ".py" not in recipe, recipe
        for path in _paths(recipe):
            assert (REPO / path).exists(), f"{target}: {path} is not there"
    for dep in prereqs:
        assert dep in RULES, f"{target}: prerequisite {dep} is not a target"


def test_verify_names_only_defined_targets_and_keeps_the_gates():
    prereqs, recipes = RULES["verify"]
    assert not recipes
    assert len(prereqs) == len(set(prereqs))
    assert not [d for d in prereqs if d not in RULES]
    assert {"test", "multichip", "lint", "tpu-lower-check",
            "jaxpr-audit-check", "kernel-audit-check", "race-audit-check",
            "cost-audit-check", "race-smoke", "trace-smoke", "replay-smoke",
            "tune-smoke"} <= set(prereqs)


def test_every_phony_is_a_rule():
    phony = set(re.findall(r"^\.PHONY:\s*(.*)$", TEXT, flags=re.M))
    names = {n for group in phony for n in group.split()}
    assert names == set(RULES)


@pytest.mark.parametrize("tool", ["replay", "tune"])
def test_tool_smoke_exits_zero(tool, tmp_path, capsys):
    """`make replay-smoke` / `make tune-smoke`: record through the real
    `run_cycle` hooks, then replay bit-identically + validate the explain
    table (replay), or sweep 64 candidates in at most one compile and
    emit a profile that passes the hard-constraint oracles (tune)."""
    import importlib

    main = importlib.import_module(f"tools.{tool}").main
    rc = main(["smoke", "--out", str(tmp_path / "bundle")])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["ok"] is True, line
    if tool == "replay":
        assert line["replay_ok"] and not line["explain_schema_errors"]
        assert line["replay"]["placements_match"]
    else:
        assert line["sweep_compiles"] <= 1 and line["candidates"] >= 64
        assert line["emitted_profile_violations"] == 0
