"""Finding a cell's files by the names in `BENCHMARK.json`. Stdlib only.

    BENCHMARK.json          workloads: cell -> (config, traffic, chips);
                            end_to_end / per_layer: which metric in which cell
    cells/<cell>.json       what belongs to the pairing: K or the fixed rate
    configs/<config>.json   the deployment: flags, profile, cluster, reference
    traffic/<mix>.json      what is true of the traffic in every cell
    e2e_metrics/<name>.json, layer_metrics/<name>.json
                            the reader that takes the metric, and its selector

    populations/<name>.py   what a node, an object and a pod of the
                            configuration look like, as feed event lines
    audits/<name>.py        a guarantee of the configuration, checked on the
                            store after the window

A later PR adds files and entries; nothing here names a cell, a
configuration, a mix or a metric.

`use_index(path)` reads another index than the repo's `BENCHMARK.json` (a
test fixture's): the index's own directory is then searched first for every
file and module named above, and `benchmark/` after it.
"""

from __future__ import annotations

import collections
import copy
import importlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent
INDEX_PATH = REPO_DIR / "BENCHMARK.json"

#: One unit of arrival, as a population makes it: the event lines that must
#: be there before its pods (`head`: a PodGroup), one `upsert_pod` line per
#: pod, the pods' uids in the same order, the lines that take the unit away
#: again, and whether the configuration's guarantees let it bind at all.
Unit = collections.namedtuple("Unit", "head pods uids removal binds")


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def use_index(path) -> None:
    global INDEX_PATH
    INDEX_PATH = Path(path).resolve()


def index() -> dict:
    return load_json(INDEX_PATH)


def roots() -> list:
    """Where files are looked for: beside the index first, when it is not
    the repo's own, then `benchmark/`."""
    own = INDEX_PATH.parent
    return [BENCH_DIR] if own == REPO_DIR else [own, BENCH_DIR]


def find(folder: str, filename: str) -> Path:
    for root in roots():
        if (root / folder / filename).is_file():
            return root / folder / filename
    raise FileNotFoundError(
        f"no {folder}/{filename} under {[str(r) for r in roots()]}"
    )


def load_module(folder: str, name: str):
    """The module `<folder>/<name>.py` of the first root that has it."""
    path = find(folder, f"{name}.py")
    if path.parent.parent == BENCH_DIR:
        return importlib.import_module(f"{folder}.{name}")
    qualified = f"{folder}.{name}"
    if getattr(sys.modules.get(qualified), "__file__", None) == str(path):
        return sys.modules[qualified]
    module_spec = importlib.util.spec_from_file_location(qualified, path)
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[qualified] = module
    module_spec.loader.exec_module(module)
    return module


def population(config: dict, seed: int):
    """The configuration's population (`"population"`, else `plain`), given
    its `cluster` block and the seed."""
    module = load_module("populations", config.get("population", "plain"))
    return module.Population(config["cluster"], seed)


def _merge(base: dict, override: dict) -> dict:
    out = copy.deepcopy(base)
    for key, value in override.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out


class Cell:
    """One entry of `workloads`, with its configuration, its mix and the
    metrics it reports. `rehearse` applies the configuration's, the mix's
    and the cell's `rehearsal` blocks: the same cell at a size the CPU
    backend runs in seconds."""

    def __init__(self, name: str, rehearse: bool = False):
        bench = index()
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in bench["workloads"])
            raise SystemExit(f"unknown workload {name!r}; known: {known}")
        self.entry = entries[0]
        self.name = name
        self.chips = self.entry["chips"]
        self.params = load_json(find("cells", f"{name}.json"))
        by_name = {c["name"]: c for c in bench["configs"]}
        config_file = REPO_DIR / by_name[self.entry["config"]]["file"]
        self.config = load_json(config_file)
        self.mix = load_json(find("traffic", f"{self.entry['traffic']}.json"))
        self.rehearse = rehearse
        if rehearse:
            self.params = _merge(self.params, self.params.get("rehearsal", {}))
            self.config = _merge(self.config, self.config.get("rehearsal", {}))
            self.mix = _merge(self.mix, self.mix.get("rehearsal", {}))
        self.metrics = {
            kind: [
                m for m in bench[kind]
                if "workloads" not in m or name in m["workloads"]
            ]
            for kind in ("end_to_end", "per_layer")
        }

    def metric_definition(self, kind: str, name: str) -> dict:
        """A metric's file is named by what follows the last dot of its
        name: `backlog.cycle_ms_mean` and `cycle_ms_mean` are one reading,
        listed twice because in each kind of cell it moves another
        end-to-end metric."""
        folder = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}
        return load_json(find(folder[kind], f"{name.rsplit('.', 1)[-1]}.json"))


def evaluate(cell: Cell, kind: str, run) -> dict:
    """{metric: {"value", "unit"}} for the cell's metrics of `kind`. Each
    metric's file names a reader under `readers/` and the selector it is
    called with. A reader that finds nothing to read returns None, and the
    metric is left out."""
    out = {}
    for metric in cell.metrics[kind]:
        definition = cell.metric_definition(kind, metric["name"])
        reader = load_module("readers", definition["reader"])
        value = reader.read(definition.get("selector", {}), run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out
