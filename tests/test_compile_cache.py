"""Where the persistent compilation cache goes (repro/launch/compile_cache.py):
the environment variable wins and nothing else is set in code; otherwise a
fixed directory inside the checkout."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_nothing_is_set(monkeypatch):
    calls = _record_updates(monkeypatch)
    assert use_compile_cache({ENV_VAR: "/elsewhere/cache"}) == "/elsewhere/cache"
    assert calls == []


def test_default_dir_is_fixed_and_inside_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    first = use_compile_cache({})
    assert Path(first) == DEFAULT_DIR == REPO / ".jax_cache"
    assert use_compile_cache({ENV_VAR: ""}) == first  # empty means unset
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


_CHILD = r"""
import jax
from repro.launch.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(float(jax.jit(lambda x: x * 3 + 1)(2.0)))
"""


def test_entries_land_in_the_env_dir_only(tmp_path):
    def entries(d):
        return sorted(p.name for p in d.iterdir()) if d.is_dir() else []

    before = entries(DEFAULT_DIR)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONPATH": "src", ENV_VAR: str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "7.0"
    assert entries(tmp_path), "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    assert entries(DEFAULT_DIR) == before
