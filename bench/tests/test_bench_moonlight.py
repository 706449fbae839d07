"""The ``deepseek_v3`` architecture's files (Moonlight-16B-A3B at one chip's
expert share): its program mapping, its counts against hand counts at the
cell's sizes, and the cell run end to end on the CPU at a tiny size, as
``test_bench_correct.py`` runs the Qwen decoder.

The tiny run serves float32: at a 64-wide model in bfloat16 the router's
near-ties pick other experts on rounding alone and move the mean gap to
0.02-0.04 (bfloat16 runs of this size, seeds 11-12), while in float32 the
program serves the reference's best token everywhere (gap 0), and the
float8 control reads 0.10 and more. The cell's own limit comes from chip
runs at its size (PERF.md).
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, model, run, spec  # noqa: E402

CELL = "moonlight-16b-a3b.long_gen"
TINY_LIMIT = 0.01


def _conf():
    return json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                      .read_text())


def _tiny_conf():
    conf = _conf()
    conf.update(name="tinyml", hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=96,
                moe_intermediate_size=32, vocab_size=256, num_hidden_layers=3,
                kv_lora_rank=32, qk_rope_head_dim=8, qk_nope_head_dim=16,
                v_head_dim=16, n_routed_experts=3, num_experts_per_tok=3,
                torch_dtype="float32")
    conf["deployment"] = dict(conf["deployment"], n_routed_experts=8)
    conf["engine"].update(slots=4, max_len=128, chunk_size=32)
    return conf


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinyml")
    for sub in ("configs", "traffic", "cells", "archs"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copy(ROOT / "bench/archs/deepseek_v3.py", root / "bench/archs")
    shutil.copy(ROOT / "bench/reference_deepseek_v3.py", root / "bench")
    (root / "bench/configs/tinyml.json").write_text(json.dumps(_tiny_conf()))
    mix = json.loads((ROOT / "bench/traffic/long_gen.json").read_text())
    mix.update(queue_depth=2,
               prompt_tokens={"dist": "lognormal", "median": 40, "sigma": 0.5,
                              "min": 16, "max": 64},
               output_tokens={"dist": "lognormal", "median": 24, "sigma": 0.5,
                              "min": 8, "max": 48})
    (root / "bench/traffic/tinylong.json").write_text(json.dumps(mix))
    (root / "bench/cells/tinyml.long.json").write_text(json.dumps(
        {"limits": {"mean_logit_gap": TINY_LIMIT}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tinyml.long", "config": "tinyml",
                       "traffic": "tinylong", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "output_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return spec.load_cell("tinyml.long", root)


def test_the_cell_loads_its_own_files():
    cell = spec.load_cell(CELL)
    assert cell.arch.__file__ == str(ROOT / "bench/archs/deepseek_v3.py")
    assert cell.reference.__file__ == str(
        ROOT / "bench/reference_deepseek_v3.py")
    conf = cell.config
    assert conf["n_routed_experts"] == 8 and conf["reduced"] == [
        "n_routed_experts"]
    assert conf["deployment"]["n_routed_experts"] == 64


def test_to_program_holds_to_the_program_layout(tiny):
    conf = tiny.config
    cfg = tiny.arch.program_config(conf)
    assert cfg.moe.held_experts == (0, 3) and cfg.moe.num_experts == 8
    params = tiny.arch.to_program(tiny.arch.canonical_weights(5, conf), conf)
    model.check_program_layout(params, cfg)


@pytest.mark.parametrize("key,value", [("n_group", 2), ("q_lora_rank", 64),
                                       ("scoring_func", "softmax"),
                                       ("tie_word_embeddings", True)])
def test_program_config_refuses_what_the_reference_does_not_compute(key,
                                                                    value):
    conf = _conf()
    conf[key] = value
    with pytest.raises(ValueError):
        spec.load_cell(CELL).arch.program_config(conf)


def test_counts_by_hand():
    arch = spec.load_cell(CELL).arch
    sz = arch.sizes(_conf())
    # a decode row over 100 cached tokens: 101 causal pairs; per pair each
    # of 16 heads scores 576 columns and sums 512
    flops, nbytes = arch.attention_kernel_cost(sz, [(100, 1, True)],
                                               "bfloat16")
    assert flops == 2 * 16 * (576 + 512) * 101
    # the absorbed query (16 x 576) read and the latent output (16 x 512)
    # written at 2 B, and 101 latent rows of 576 values at 2 B
    assert nbytes == 16 * (576 + 512) * 2 + 101 * 576 * 2
    # one MoE layer's grouped matmuls for a 32-row decode tick: 32 x 6 x
    # 8/64 = 24 rows on the held experts, each through 3 products of 2048
    # x 1408, read and written at 2 B; a held expert is missed by all 32
    # tokens with chance (58/64)^32, so 8 x (1 - (58/64)^32) = 7.66
    # experts' three weights
    flops, nbytes = arch.expert_kernel_cost(sz, [(1000, 1, True)] * 32)
    reached = 8 * (1 - (58 / 64) ** 32)
    assert reached == pytest.approx(7.657, abs=1e-3)
    assert flops == 2 * 24 * 3 * 2048 * 1408
    assert nbytes == pytest.approx(reached * 3 * 2048 * 1408 * 2
                                   + 2 * 24 * 2048 * 2)
    # bound by bandwidth at the chip's peaks
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline_seconds(flops, nbytes, peak) == nbytes / 819e9
    # a prefill tick of 32 x 128 tokens brings 3,072 rows to (all but
    # surely) all 8 experts, and the products bound it
    flops, nbytes = arch.expert_kernel_cost(sz, [(0, 128, False)] * 32)
    assert nbytes == pytest.approx(8 * 3 * 2048 * 1408 * 2
                                   + 2 * 3072 * 2048 * 2)
    assert costs.roofline_seconds(flops, nbytes, peak) == flops / 197e12
    # a step of one decode row: per token 2 x (27 attention layers of
    # 13,767,168 parameters + the dense MLP 69,206,016 + 26 x (router
    # 131,072 + shared experts 17,301,504) + the final norm 2,048), the
    # 0.75 rows expected on held experts through 26 x 3 x 2048 x 1408, 101
    # pairs of 27 x 2 x 16 x 1088, and the head 2 x 2048 x 163,840
    per_tok = 27 * 13_767_168 + 69_206_016 + 26 * (131_072 + 17_301_504) \
        + 2048
    want = 2 * per_tok + 2 * 0.75 * 26 * 3 * 2048 * 1408 \
        + 101 * 27 * 2 * 16 * 1088 + 2 * 2048 * 163_840
    assert arch.step_flops(sz, [(100, 1, True)]) == want


def _traced(cell, expert_ops, staging_ops=0):
    """A decode tick of 32 rows on a small made-up trace: one decode kernel
    call, ``expert_ops`` grouped-matmul calls and ``staging_ops`` copies of
    their weights."""
    ops = [["%while", 110, 280], ["%paged_decode_fwd_pallas.2", 120, 90_000]]
    ops += [[f"%expert_gmm.{i}", 100_000 + 1000 * i, 800]
            for i in range(expert_ops)]
    staging = ["%dynamic-slice_bitcast_fusion.6.remat2",
               "%dynamic-slice_bitcast_fusion.8.remat2", "%slice-done.3"]
    ops += [[staging[i % 3], 200_000 + 2000 * i, 1200]
            for i in range(staging_ops)]
    ops.sort(key=lambda o: o[1])
    trace = {"ops": ops, "window": [0, 1_000_000],
             "host": [["bench.traced", 0, 1_000_000],
                      ["bench.tick", 100, 999_000]]}
    ticks = [{"kind": "decode", "t0": 1.0, "t1": 1.3, "tokens": 32,
              "rows": [(1000, 1, True)] * 32}]
    return run.RunData(cell=cell, sz=cell.arch.sizes(cell.config),
                       peak=costs.peaks("TPU v5 lite"), slots=32, ticks=ticks,
                       tracks=[], trace=trace,
                       traced_ticks=run.pair_traced_ticks(ticks, trace, 0.5))


def test_the_expert_roofline_reads_the_expert_kernel():
    cell = spec.load_cell(CELL)
    read = cell.layer_reader("expert_gmm_roofline.offline")
    assert read(_traced(cell, 0)) is None           # no kernel in the trace
    share = read(_traced(cell, 78))                 # 3 calls x 26 layers
    need = 26 * costs.roofline_seconds(
        *cell.arch.expert_kernel_cost(cell.arch.sizes(cell.config),
                                      [(1000, 1, True)] * 32),
        costs.peaks("TPU v5 lite"))
    assert share == pytest.approx(100 * need / (78 * 800e-9))
    # the copies that stage the kernel's weights are its time too
    share = read(_traced(cell, 78, staging_ops=78))
    assert share == pytest.approx(100 * need / (78 * 800e-9 + 78 * 1200e-9))
    assert read(_traced(cell, 0, staging_ops=78)) is None
    # the decode kernel's roofline reads the latent kernel's count
    assert cell.layer_reader("paged_decode_roofline.offline")(
        _traced(cell, 78)) > 0
    # an architecture without experts gives the expert kernel no work
    qwen = spec.load_cell("qwen2-0.5b.long_gen")
    assert read(_traced(qwen, 0)) == 0.0


def test_the_served_path_dispatches_only_fused_attention_and_dropless(tiny):
    eng = run.build_engine(tiny, 3)
    for n in (40, 9):
        eng.submit(list(range(1, n + 1)), 4)
    while run.busy(eng):
        eng.tick()
    counts = {}
    for name, labels, value in eng.metrics.dump_values()["counters"]:
        if name == "expert_dispatch_total" and value:
            counts[dict(labels)["impl"]] = value
    assert set(counts) == {"dropless"}
    assert set(run.dispatch_counts(eng)) == {"paged_prefill:pallas",
                                             "paged_decode:pallas"}


@pytest.mark.parametrize("seed", [11, 2**33 + 3])
def test_the_cell_runs_and_is_correct_and_the_control_is_not(tiny, seed):
    res = run.run_cell(tiny, seed, 2.0, False, control=True,
                       require_tpu=False)
    assert res["extra"]["program_correct"], res["extra"]["readings"]
    assert res["extra"]["readings"]["mean_logit_gap"] <= TINY_LIMIT
    assert res["correct"] is False
    assert res["extra"]["control"]["mean_logit_gap"] > TINY_LIMIT
    assert res["compared"]["foreign_dispatches"]["value"] == 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
