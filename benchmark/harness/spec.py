"""Finding a cell's files by the names in `BENCHMARK.json`. Stdlib only.

    BENCHMARK.json          workloads: cell -> (config, traffic, chips);
                            end_to_end / per_layer: which metric in which cell
    cells/<cell>.json       what belongs to the pairing: K or the fixed rate
    configs/<config>.json   the deployment: flags, profile, cluster, reference
    traffic/<mix>.json      what is true of the traffic in every cell
    e2e_metrics/<name>.json, layer_metrics/<name>.json
                            the reader that takes the metric, and its selector

A later PR adds files and entries; nothing here names a cell, a
configuration, a mix or a metric.
"""

from __future__ import annotations

import copy
import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def index() -> dict:
    return load_json(REPO_DIR / "BENCHMARK.json")


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
        self.params = load_json(BENCH_DIR / "cells" / f"{name}.json")
        by_name = {c["name"]: c for c in bench["configs"]}
        config_file = REPO_DIR / by_name[self.entry["config"]]["file"]
        self.config = load_json(config_file)
        self.mix = load_json(
            BENCH_DIR / "traffic" / f"{self.entry['traffic']}.json"
        )
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
        return load_json(
            BENCH_DIR / folder[kind] / f"{name.rsplit('.', 1)[-1]}.json"
        )


def evaluate(cell: Cell, kind: str, run) -> dict:
    """{metric: {"value", "unit"}} for the cell's metrics of `kind`. Each
    metric's file names a reader under `readers/` and the selector it is
    called with. A reader that finds nothing to read returns None, and the
    metric is left out."""
    out = {}
    for metric in cell.metrics[kind]:
        definition = cell.metric_definition(kind, metric["name"])
        reader = importlib.import_module(f"readers.{definition['reader']}")
        value = reader.read(definition.get("selector", {}), run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out
