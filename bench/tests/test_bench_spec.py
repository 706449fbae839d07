"""Cells, configurations, traffic mixes and per-layer metrics are files
found by name: a new one is added by dropping files in, with no edit."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


def test_every_committed_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"]
        assert cell.cell["limits"]
        for m in cell.per_layer:
            assert callable(cell.layer_reader(m["name"]))
        # the architecture by model_type, the reference by its key
        assert Path(cell.arch.__file__) == \
            ROOT / "bench/archs" / f"{cell.config['model_type']}.py"
        assert Path(cell.reference.__file__) == \
            ROOT / cell.config["reference"]
        assert callable(cell.reference.served_gaps)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2


def test_files_dropped_into_a_new_root_are_found_by_name(tmp_path):
    (tmp_path / "bench").mkdir()
    for sub in ("configs", "traffic", "cells", "layer_metrics", "archs"):
        (tmp_path / "bench" / sub).mkdir()
    shutil.copy(ROOT / "bench/archs/qwen2.py", tmp_path / "bench/archs")
    shutil.copy(ROOT / "bench/reference.py", tmp_path / "bench")
    conf = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())
    conf["name"] = "newcfg"
    (tmp_path / "bench/configs/newcfg.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/newmix.json").write_text(
        json.dumps({"loop": "open", "size_seed": 9}))
    (tmp_path / "bench/cells/newcfg.newmix.json").write_text(
        json.dumps({"rate_per_s": 3.0, "limits": {"max_logit_gap": 1.0}}))
    (tmp_path / "bench/layer_metrics/new_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "other_only", "unit": "s", "workloads": ["x"]}],
        "per_layer": [{"name": "new_metric.x", "unit": "%",
                       "workloads": ["newcfg.newmix"]}],
    }))
    cell = spec.load_cell("newcfg.newmix", tmp_path)
    assert cell.config["name"] == "newcfg"
    assert cell.traffic["size_seed"] == 9
    assert cell.cell["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert cell.layer_reader("new_metric.x")(None) == 42.0
    assert Path(cell.arch.__file__) == tmp_path / "bench/archs/qwen2.py"
    assert Path(cell.reference.__file__) == tmp_path / "bench/reference.py"


def test_unknown_cell_is_refused():
    import pytest
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell")


def _run(args, cwd, env_extra):
    import os
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    p = _run(["--workload", cell, "--seed", str(2**33 + 1), "--seconds", "1",
              "--trace", "0"], ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = _run(["--workload", bench["workloads"][0]["name"], "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path,
             {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()
