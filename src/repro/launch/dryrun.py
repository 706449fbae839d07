import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, print memory/cost analysis, and emit roofline JSON.

The two lines above MUST stay first: jax locks the device count on first
init, and only the dry-run wants 512 placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
      --shape train_4k [--multi-pod] [--variant expmul] [--out out.json]

Exit code 0 == the cell lowers, SPMD-partitions and compiles.
"""
import argparse
import json
import sys
import time

import jax

from repro.configs import SHAPES, cells_for, get_config
from repro.configs.shapes import SUBQUADRATIC_ARCHS
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, model_flops_per_device
from repro.models.api import decode_step, forward, init_decode_state
from repro.models.inputs import input_specs
from repro.optim.adamw import adamw
from repro.sharding.rules import (
    batch_shardings,
    decode_state_shardings,
    param_shardings,
    state_shardings,
)
from repro.train.step import build_train_step, make_train_state_specs


def _spec_tree(tree, shardings):
    """Attach shardings to ShapeDtypeStructs (lower() consumes these)."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings,
    )


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               variant: str | None = None, moe_impl: str | None = None,
               extra_overrides: dict | None = None):
    """Build + lower + compile one cell; returns (compiled, meta dict)."""
    shape = SHAPES[shape_name]
    overrides = dict(extra_overrides or {})
    if variant:
        overrides["attention_variant"] = variant
    cfg = get_config(arch, **overrides)
    if moe_impl is None:
        # trillion-class MoE train/prefill cells use the balanced dispatch
        # (identical cost profile; DESIGN.md) — decode token counts are tiny
        moe_impl = "balanced" if (cfg.moe and shape.kind != "decode") else "scatter"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = adamw(3e-4, moment_dtype=cfg.opt_state_dtype)
            state_shapes = make_train_state_specs(cfg, opt)
            st_sh = state_shardings(state_shapes, mesh)
            batch_shapes = input_specs(cfg, seq_len=shape.seq_len,
                                       global_batch=shape.global_batch, kind="train")
            b_sh = batch_shardings(batch_shapes, mesh)
            step = build_train_step(cfg, opt, moe_impl=moe_impl)
            jit_step = jax.jit(
                step,
                in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
            )
            lowered = jit_step.lower(_spec_tree(state_shapes, st_sh),
                                     _spec_tree(batch_shapes, b_sh))
        elif shape.kind == "prefill":
            params_shapes = jax.eval_shape(
                lambda k: __import__("repro.models.api", fromlist=["init_model"]).init_model(k, cfg),
                jax.random.PRNGKey(0),
            )
            p_sh = param_shardings(params_shapes, mesh)
            batch_shapes = input_specs(cfg, seq_len=shape.seq_len,
                                       global_batch=shape.global_batch, kind="prefill")
            b_sh = batch_shardings(batch_shapes, mesh)

            def prefill_step(params, batch):
                logits = forward(params, batch, cfg, moe_impl=moe_impl)
                return logits[:, -1, :]  # last-position logits (serving prefill)

            jit_step = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
            lowered = jit_step.lower(_spec_tree(params_shapes, p_sh),
                                     _spec_tree(batch_shapes, b_sh))
        else:  # decode
            from repro.models.api import init_model

            params_shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                                           jax.random.PRNGKey(0))
            p_sh = param_shardings(params_shapes, mesh)
            B = shape.global_batch
            kw = {"enc_len": cfg.frontend_tokens} if cfg.encoder_layers else {}
            state_shapes = jax.eval_shape(
                lambda: init_decode_state(cfg, B, shape.seq_len, **kw)
            )
            s_sh = decode_state_shardings(state_shapes, mesh, cfg)
            tok_shapes = input_specs(cfg, seq_len=shape.seq_len,
                                     global_batch=B, kind="decode")
            t_sh = batch_shardings(tok_shapes, mesh)

            def serve_step(params, state, tokens1, lengths):
                return decode_step(params, state, tokens1, lengths, cfg)

            jit_step = jax.jit(
                serve_step,
                in_shardings=(p_sh, s_sh, t_sh["tokens1"], t_sh["lengths"]),
                out_shardings=(None, s_sh),
                donate_argnums=(1,),
            )
            lowered = jit_step.lower(
                _spec_tree(params_shapes, p_sh),
                _spec_tree(state_shapes, s_sh),
                _spec_tree(tok_shapes["tokens1"], t_sh["tokens1"]),
                _spec_tree(tok_shapes["lengths"], t_sh["lengths"]),
            )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    rf = analyze(
        compiled,
        model_flops_per_device=model_flops_per_device(cfg, shape, n_dev),
    )
    meta = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "variant": cfg.attention_variant,
        "moe_impl": moe_impl,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "peak_estimate": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "roofline": rf.to_dict(),
    }
    return compiled, meta


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default=None, choices=[None, "exact", "expmul"])
    ap.add_argument("--moe-impl", default=None, choices=[None, "scatter", "balanced"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (e.g. attention_block_k=1024)")
    args = ap.parse_args(argv)

    if args.shape == "long_500k" and args.arch not in SUBQUADRATIC_ARCHS:
        print(f"SKIP {args.arch} x long_500k: full-attention arch (DESIGN.md §4)")
        return 0

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    compiled, meta = lower_cell(
        args.arch, args.shape, multi_pod=args.multi_pod,
        variant=args.variant, moe_impl=args.moe_impl,
        extra_overrides=overrides,
    )
    print(json.dumps(meta, indent=2))
    print("memory_analysis:", compiled.memory_analysis())
    if args.out:
        with open(args.out, "w") as f:
            json.dump(meta, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
