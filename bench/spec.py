"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and the metrics. Everything else belongs to one name and
sits in a file of its own, found by that name:

    bench/configs/<config>.json        sizes, engine settings, reference
    bench/archs/<model_type>.py        the configuration's architecture
    <the configuration's "reference">  its plain float32 reference
    bench/traffic/<traffic>.json       parameters of the one generator
    bench/cells/<cell>.json            the cell's rate and limits
    bench/layer_metrics/<metric>.py    one reader per per-layer metric

So a later cell, mix, configuration, architecture or metric is added as
files and ``BENCHMARK.json`` entries, with no edit to the harness.

A new architecture brings:

  * ``bench/archs/<model_type>.py``, keyed by the configuration file's own
    ``model_type``, with ``sizes(conf)`` (a dict with at least ``layers``
    and ``vocab``), ``program_config(conf)`` (the program's
    ``ModelConfig``; it raises for anything the reference does not
    compute), ``canonical_weights(seed, conf)`` (drawn from the seed on
    the device in one jitted call, in the served dtype),
    ``to_program(w, conf)`` (the program's parameter tree, which
    ``bench/model.py:check_program_layout`` holds to the program's own),
    and the counts ``step_flops(sz, rows)`` and
    ``attention_kernel_cost(sz, rows, kv_dtype)`` per layer call;
  * its reference, the file its configuration's ``"reference"`` names:
    its layers as ``hidden`` and ``logits`` on ``bench/reference_core.py``,
    and ``served_gaps = reference_core.served_gaps(hidden, logits)``;
    it imports nothing of the program;
  * the configuration file, its cells (``bench/cells/<cell>.json``) and
    ``BENCHMARK.json`` entries;
  * a reader in ``bench/layer_metrics`` for any kernel that is new. Its
    attention reaches the chip through the fused paged dispatches
    ``paged_prefill:pallas`` and ``paged_decode:pallas``; any other
    attention dispatch fails the run (``bench/run.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

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
    arch: ModuleType
    reference: ModuleType

    def layer_reader(self, metric_name: str):
        """The ``read(run)`` function of one per-layer metric's file."""
        path = self.root / "bench" / "layer_metrics" / f"{metric_name}.py"
        return _module(path, "layer_metric_" + metric_name).read


def _module(path: Path, name: str) -> ModuleType:
    """The Python file at ``path``, loaded as a module of its own."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(config: dict, root: Path = ROOT) -> ModuleType:
    """``bench/archs/<model_type>.py`` of a configuration file."""
    model_type = config["model_type"]
    return _module(root / "bench" / "archs" / f"{model_type}.py",
                   "bench_arch_" + model_type)


def reference_module(config: dict, root: Path = ROOT) -> ModuleType:
    """The reference file a configuration's ``"reference"`` key names,
    relative to the checkout's root."""
    rel = Path(config["reference"])
    if rel.is_absolute() or ".." in rel.parts:
        raise ValueError(f"reference {rel} lies outside the checkout")
    return _module(root / rel, "bench_reference_" + rel.stem)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve cell ``name`` against the ``BENCHMARK.json`` under ``root``.
    Raises ``KeyError`` for an unknown cell and ``FileNotFoundError``,
    naming it, for a missing file."""
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    d = root / "bench"
    config = _json(d / "configs" / f"{entry['config']}.json")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=_json(d / "traffic" / f"{entry['traffic']}.json"),
        cell=_json(d / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
        arch=arch_module(config, root),
        reference=reference_module(config, root),
    )
