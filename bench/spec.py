"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and the metrics. Everything else belongs to one name and
sits in a file of its own, found by that name:

    bench/configs/<config>.json        sizes, engine settings, reference
    bench/traffic/<traffic>.json       parameters of the one generator
    bench/cells/<cell>.json            the cell's rate and limits
    bench/layer_metrics/<metric>.py    one reader per per-layer metric

So a later cell, mix, configuration or metric is added as files and
``BENCHMARK.json`` entries, with no edit to the harness.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list
    per_layer: list
    root: Path

    def layer_reader(self, metric_name: str):
        """The ``read(run)`` function of one per-layer metric's file."""
        path = self.root / "bench" / "layer_metrics" / f"{metric_name}.py"
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + metric_name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve cell ``name`` against the ``BENCHMARK.json`` under ``root``.
    Raises ``KeyError`` for an unknown cell and ``FileNotFoundError`` for a
    missing file."""
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    d = root / "bench"
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_json(d / "configs" / f"{entry['config']}.json"),
        traffic=_json(d / "traffic" / f"{entry['traffic']}.json"),
        cell=_json(d / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
    )
