"""Training launcher.

Wires together: config registry, mesh, sharded synthetic/file data loader,
train step (microbatching, grad compression), async checkpointing, straggler
watchdog, and restart-on-failure supervision. On this CPU container it runs
reduced configs end-to-end; on a real fleet the same script runs per-host
(jax.distributed.initialize + the production mesh).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.restore import latest_step, restore_checkpoint
from repro.checkpoint.save import AsyncCheckpointer
from repro.configs import get_config
from repro.data.sharded_loader import ShardedLoader
from repro.data.synthetic import SyntheticLMDataset
from repro.distributed.compression import error_feedback_int8, init_residuals
from repro.distributed.fault import FaultInjector, StragglerWatchdog, TrainSupervisor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.api import init_model
from repro.optim.adamw import adamw
from repro.optim.schedule import cosine_schedule
from repro.sharding.rules import state_shardings
from repro.train.step import (
    build_train_step,
    make_train_state,
    make_train_state_specs,
)

log = logging.getLogger("repro.train")


def make_mesh_for_host(devices=None):
    """(data, model) mesh over ``devices`` (default: all of this host's):
    two-way tensor parallel from four devices up, data parallel otherwise."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    shape = (n // 2, 2) if n >= 4 else (n, 1)
    return make_mesh(shape, ("data", "model"), devices=devices)


def main(argv=None, cfg_override=None, mesh=None):
    """Train; returns the per-step losses. ``mesh`` (default: a mesh over
    every device of this host) lets a caller run the same steps on another
    device set, such as one chip of a four-chip host."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--variant", default=None, choices=[None, "exact", "expmul"])
    ap.add_argument("--inject-fault-at", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    use_compile_cache()

    overrides = {"dtype": "float32", "param_dtype": "float32"}
    if args.variant:
        overrides["attention_variant"] = args.variant
    if cfg_override is not None:
        cfg = cfg_override.replace(**overrides)
    else:
        cfg = get_config(args.arch, smoke=args.smoke, **overrides)
    mesh = make_mesh_for_host() if mesh is None else mesh
    opt = adamw(cosine_schedule(args.lr, 20, args.steps),
                moment_dtype=cfg.opt_state_dtype)

    data = SyntheticLMDataset(cfg.vocab_size, args.seq, seed=0)
    loader = ShardedLoader(data, mesh)

    residuals_holder = {}

    def grad_transform(grads):
        if not args.compress_grads:
            return grads
        res = residuals_holder["res"]
        cg, new_res = error_feedback_int8(grads, res)
        residuals_holder["res"] = new_res
        return cg

    step_fn_inner = build_train_step(
        cfg, opt, microbatches=args.microbatches,
        grad_transform=grad_transform if args.compress_grads else None,
    )

    with jax.set_mesh(mesh):
        state_shapes = make_train_state_specs(cfg, opt)
        st_sh = state_shardings(state_shapes, mesh)
        # the state lives where the sharding rules put it, for a fresh start
        # as for a restored checkpoint; it is created there, so no device
        # ever holds a whole copy
        jit_step = jax.jit(step_fn_inner, out_shardings=(st_sh, None),
                           donate_argnums=(0,))

        state = jax.jit(
            lambda key: make_train_state(init_model(key, cfg), opt),
            out_shardings=st_sh)(jax.random.PRNGKey(0))
        if args.compress_grads:
            residuals_holder["res"] = init_residuals(state["params"])

        start = 0
        ckpt = AsyncCheckpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            state, start = restore_checkpoint(state_shapes, st_sh, args.ckpt_dir)
            log.info("resumed from step %d", start)

        losses = []

        def step_fn(state, step):
            batch = {"tokens": loader.load(step, args.batch)}
            if cfg.frontend:
                batch["frontend_embeds"] = jnp.zeros(
                    (args.batch, cfg.frontend_tokens, cfg.frontend_dim), cfg.dtype
                )
            state, metrics = jit_step(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0:
                log.info("step %d loss %.4f grad_norm %.3f", step, loss,
                         float(metrics["grad_norm"]))
            return state, {"loss": loss}

        if ckpt:
            def restore():
                st, s = restore_checkpoint(state_shapes, st_sh, args.ckpt_dir)
                log.info("restarted from checkpoint step %d", s)
                return st, s

            sup = TrainSupervisor(
                step_fn, ckpt, restore, ckpt_every=args.ckpt_every,
                watchdog=StragglerWatchdog(),
                fault_injector=FaultInjector(
                    [args.inject_fault_at] if args.inject_fault_at else []
                ),
            )
            state, end = sup.run(state, start, args.steps - start)
            log.info("done at step %d; restarts=%d stragglers=%d",
                     end, sup.restarts, len(sup.watchdog.flagged))
        else:
            for s in range(start, args.steps):
                state, _ = step_fn(state, s)

        n = max(1, len(losses) // 10)
        log.info("loss first10 %.4f -> last10 %.4f",
                 float(np.mean(losses[:n])), float(np.mean(losses[-n:])))
        return losses


if __name__ == "__main__":
    main()
