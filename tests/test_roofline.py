"""Roofline machinery: trip-count-aware HLO costs vs unrolled references,
collective wire-byte parsing, and dry-run cell smoke (small mesh)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.launch.hlo_costs import analyze_text


def _flops(fn, *args):
    return analyze_text(jax.jit(fn).lower(*args).compile().as_text())["flops"]


def test_scan_flops_match_unrolled():
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def scanned(w, x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    def unrolled(w, x):
        for _ in range(7):
            x = x @ w
        return x

    fs, fu = _flops(scanned, w, x), _flops(unrolled, w, x)
    assert abs(fs - fu) / fu < 0.01, (fs, fu)


def test_nested_scan_flops():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def nested(w, x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    expected = 12 * 2 * 128**3
    got = _flops(nested, w, x)
    assert abs(got - expected) / expected < 0.01, (got, expected)


def test_remat_scan_counts_recompute():
    """jax.checkpoint recompute in the backward must be counted."""
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)

    def loss(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        body_ck = jax.checkpoint(body)
        out, _ = jax.lax.scan(body_ck, x, None, length=6)
        return jnp.sum(out)

    fwd = _flops(loss, w, x)
    bwd = _flops(lambda w, x: jax.grad(loss)(w, x), w, x)
    # backward includes: fwd scan + recompute + 2 bwd matmuls per layer
    assert bwd >= 2.5 * fwd, (fwd, bwd)


_DRYRUN_SMALL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.roofline import analyze, model_flops_per_device
from repro.configs.shapes import ShapeSpec
from repro.models.inputs import input_specs
from repro.sharding.rules import batch_shardings, state_shardings
from repro.train.step import build_train_step, make_train_state_specs
from repro.optim.adamw import adamw

cfg = get_config("qwen2-0.5b", smoke=True)
mesh = make_mesh((4, 2), ("data", "model"))
opt = adamw(1e-3)
with jax.set_mesh(mesh):
    shapes = make_train_state_specs(cfg, opt)
    st_sh = state_shardings(shapes, mesh)
    b_shapes = input_specs(cfg, seq_len=64, global_batch=8, kind="train")
    b_sh = batch_shardings(b_shapes, mesh)
    step = build_train_step(cfg, opt)
    jit_step = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
    spec_tree = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, st_sh)
    bspec = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), b_shapes, b_sh)
    compiled = jit_step.lower(spec_tree, bspec).compile()
    rf = analyze(compiled)
    assert rf.flops > 0 and rf.hbm_bytes > 0, rf
    assert rf.bottleneck in ("compute", "memory", "collective")
    print("SMALL_DRYRUN_OK", rf.bottleneck)
"""


def test_dryrun_roofline_small_mesh():
    r = subprocess.run(
        [sys.executable, "-c", _DRYRUN_SMALL],
        capture_output=True, text=True, timeout=560,
        # inherit the parent env: stripping it drops platform pins like
        # JAX_PLATFORMS=cpu and jax's backend discovery can hang on import
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "SMALL_DRYRUN_OK" in r.stdout, r.stdout + r.stderr
