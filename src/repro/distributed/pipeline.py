"""Pipeline parallelism over a 'pp' mesh axis via shard_map +
collective_permute (GPipe-style microbatch schedule).

Stages hold disjoint layer groups (params sharded on the stage axis);
microbatches stream stage-to-stage with collective_permute. The steady-state
schedule runs all stages concurrently; bubbles = (n_stages - 1) microbatch
slots at fill/drain, the standard GPipe cost. Exercised by
tests/test_pipeline.py on a fake 8-device mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, params_stacked, x_microbatches, mesh, *,
                     axis: str = "pp"):
    """GPipe forward.

    stage_fn(stage_params, x) -> y : one stage's computation.
    params_stacked: pytree with leading stage axis (sharded over `axis`).
    x_microbatches: (n_micro, mb, ...) inputs.
    Returns (n_micro, mb, ...) outputs from the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    assert n_micro >= n_stages, "need >= n_stages microbatches to fill the pipe"

    def per_stage(params_local, xs_local):
        # params_local: stage's params (leading axis 1); xs_local: full
        # microbatch stream replicated on entry (only stage 0 consumes it).
        stage = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda l: l[0], params_local)
        total_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros((n_micro,) + xs_local.shape[1:], xs_local.dtype)
        # carries become device-varying over the pp axis inside the loop
        buf = jax.lax.pcast(buf, (axis,), to="varying")
        outs = jax.lax.pcast(outs, (axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid); others use buf
            x_in = jnp.where(
                stage == 0,
                xs_local[jnp.clip(t, 0, n_micro - 1)],
                buf,
            )
            active = (t - stage >= 0) & (t - stage < n_micro)
            y = stage_fn(p, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
            outs = jnp.where(
                (stage == n_stages - 1) & active,
                outs.at[mb_idx].set(y),
                outs,
            )
            # ring-forward activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(total_ticks))
        # deliver final-stage outputs to all stages (so the result is
        # replicated on the pp axis)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    with jax.set_mesh(mesh):
        return jax.jit(fn)(params_stacked, x_microbatches)
