"""Where JAX keeps its persistent compilation cache.

Called from the entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``), never on import: a library that moves the cache would
override what its caller chose.

The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here sets any directory. Otherwise the cache lives at a fixed path
inside the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``). A
fixed path matters because the directory is part of what a later run looks
up: a path derived from a tempdir, a PID or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache(environ=os.environ) -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if environ.get(ENV_VAR):
        return environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
