"""Checkpoint: roundtrip, async save, elastic reshard (different mesh)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.restore import latest_step, restore_checkpoint
from repro.checkpoint.save import AsyncCheckpointer, save_checkpoint
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {
        "params": {
            "w": jax.random.normal(k1, (64, 32)),
            "units": (jax.random.normal(k2, (4, 16, 8)),),
        },
        "opt": {"step": jnp.array(7, jnp.int32)},
    }


def test_roundtrip_single_device(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    save_checkpoint(tree, str(tmp_path), 7)
    assert latest_step(str(tmp_path)) == 7
    shapes = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    mesh = make_mesh((1,), ("data",))
    shardings = jax.tree.map(lambda l: NamedSharding(mesh, P()), tree)
    restored, step = restore_checkpoint(shapes, shardings, str(tmp_path))
    assert step == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpointer_matches_sync(tmp_path):
    tree = _tree(jax.random.PRNGKey(1))
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    ck.save(tree, 10)
    ck.wait()
    shapes = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    mesh = make_mesh((1,), ("data",))
    shardings = jax.tree.map(lambda l: NamedSharding(mesh, P()), tree)
    restored, step = restore_checkpoint(shapes, shardings, str(tmp_path))
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_latest(tmp_path):
    tree = _tree(jax.random.PRNGKey(2))
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        ck.save(tree, s)
        ck.wait()
    assert latest_step(str(tmp_path)) == 3


_ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.save import save_checkpoint
from repro.checkpoint.restore import restore_checkpoint
from repro.launch.mesh import make_mesh

base = sys.argv[1]
mesh1 = make_mesh((4, 2), ("data", "model"))
w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
w1 = jax.device_put(w, NamedSharding(mesh1, P("data", "model")))
save_checkpoint({"w": w1}, base, 5)

# restore on a DIFFERENT mesh layout (elastic)
mesh2 = make_mesh((2, 4), ("data", "model"))
shapes = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
restored, step = restore_checkpoint(shapes, sh2, base)
assert step == 5
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
print("ELASTIC_OK")
"""


def test_elastic_reshard_across_meshes(tmp_path):
    # inherit the parent env: stripping it drops platform pins like
    # JAX_PLATFORMS=cpu and jax's backend discovery can hang on import
    r = subprocess.run(
        [sys.executable, "-c", _ELASTIC_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr
