"""Pipeline parallelism (GPipe schedule over 'pp' axis) on fake devices —
run in a subprocess so the main test process keeps 1 CPU device."""
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.pipeline import pipeline_forward
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("pp",))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

xs = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))
ws_sharded = jax.device_put(ws, NamedSharding(mesh, P("pp")))
out = pipeline_forward(stage_fn, ws_sharded, xs, mesh, axis="pp")

# sequential reference
ref = xs
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential():
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=300,
        # inherit the parent env: stripping it drops platform pins like
        # JAX_PLATFORMS=cpu and jax's backend discovery can hang on import
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
