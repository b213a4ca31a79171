"""The DeepSeek-V2 family end to end at a tiny size on the CPU: a
throw-away cell ``tiny-deepseek.longdocs`` (latent attention, a dense
layer 0, then layers that hold 4 of the 16 experts their router scores)
ADDED to ``tinytree``'s copy of the benchmark's data, driven through the
real entry point with the real reference, metrics and traffic generator.
"""
import json
import os

import pytest

import run as bench_run
import tinytree

TINY = {
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 4,
    "router_experts": 16, "first_held_expert": 4, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "dtype": "float32", "family": "deepseek_v2", "source": "test",
    "reduced": []}
CELL = "tiny-deepseek.longdocs"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tinytree.make(str(tmp_path_factory.mktemp("bench")))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(tinytree.BENCH, "configs",
                           "deepseek-v2.ep4.d5.json")) as f:
        program = json.load(f)["program"]
    tinytree._dump(os.path.join(bdir, "configs", "tiny-deepseek.json"),
                   dict(TINY, program=program))
    tinytree._dump(
        os.path.join(bdir, "cells", CELL + ".json"),
        {"engine": {"max_batch_size": 4, "prefill_chunk_size": 16,
                    "block_size": 4, "num_blocks": 128, "max_seq_len": 112},
         "clients": 3, "trace_seconds": 0.5,
         "correct": {"sample": 8, "wide_gap": 1e-3, "wide_gap_share": 0.0,
                     "capped_gap_mean": 1e-4, "router_margin_min": 1e-4,
                     "undecided_share_max": 0.2}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-deepseek", "source": "test", "reduced": [],
         "file": "benchmark/configs/tiny-deepseek.json", "why": "test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-deepseek", "traffic": "tiny-docs",
         "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "deepseek-v2.longdocs" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tinytree._dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_traced_cell_serves_what_its_reference_puts_first(root):
    """float32 on both sides: the served token is the reference's best at
    every sampled position, through chunked prefill and decode over the
    latent cache; the per-layer metrics that need no device are in the
    line, those of the device trace are left out."""
    result, rc = bench_run.run_cell(CELL, 2 ** 31 + 9, 2.0, True,
                                    root=root, require_chip=False)
    assert rc == 0 and result["correct"] is True
    assert result["reference"]["tokens_off_reference_best"] == 0
    assert result["reference"]["positions"] >= 12
    assert result["compared"]["undecided_share"][0] < 0.2
    metrics = result["metrics"]
    assert {"moe_held_share.longdocs", "moe_load_top.longdocs",
            "budget_fill.longdocs", "engine_host_ms.longdocs",
            "chunk_launch_ms.longdocs",
            "batch_occupancy.longdocs"} <= set(metrics)
    # 4 of 16 experts held: about a quarter of the assignments land here
    assert 5.0 < metrics["moe_held_share.longdocs"]["value"] < 60.0
    assert metrics["moe_load_top.longdocs"]["value"] >= 1.0
    # no chip: no peak, no device plane
    assert "mfu.longdocs" not in metrics
    assert "mla_attn_roofline.longdocs" not in metrics


def test_int8_control_is_not_correct(root, monkeypatch):
    from harness import correct
    monkeypatch.setattr(correct, "IN_PROGRAMS_PLACE", "int8")
    result, rc = bench_run.run_cell(CELL, 4, 2.0, False, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is False
    compared, ref = result["compared"], result["reference"]
    names = [n for n in compared if n in ref["program"]]
    assert any(compared[n][0] > compared[n][1] for n in names)
    assert all(ref["program"][n] <= compared[n][1] for n in names)


def test_altered_token_is_not_correct(root, monkeypatch):
    from harness import program
    build_engine = program.build_engine

    def build_tampered(model, engine_kw):
        eng = build_engine(model, engine_kw)
        orig = eng.mixed.call_packed
        calls = [0]

        def call_packed(pack, T, q_probs=None):
            out = orig(pack, T)
            calls[0] += 1
            if calls[0] % 7 == 0:
                out = (out + 1) % eng.cfg.vocab_size
            return out
        eng.mixed.call_packed = call_packed
        return eng

    monkeypatch.setattr(program, "build_engine", build_tampered)
    result, rc = bench_run.run_cell(CELL, 3, 2.0, False, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is False
    value, limit = result["compared"]["wide_gap_share"]
    assert value > limit


def test_counts_choose_the_cheaper_form():
    """The yardstick's attention: absorbed for a one-token span, expanded
    for a 512-token chunk over a long prefix, crossing near 170 rows."""
    from harness import spec
    cfg = spec.Cell("deepseek-v2.longdocs").config
    import importlib.util
    s = importlib.util.spec_from_file_location(
        "c", os.path.join(tinytree.BENCH, "metrics",
                          "deepseek_v2_counts.py"))
    c = importlib.util.module_from_spec(s)
    s.loader.exec_module(c)
    assert c.attn_params(cfg) == 149_225_472
    assert c.expert_params(cfg) == 23_592_960
    pairs = c.causal_pairs(1, 16384)
    assert c.span_attention_flops(cfg, 1, 16384) == pairs * 128 * 1088 * 2
    chunk = c.span_attention_flops(cfg, 512, 8192 + 512)
    pairs = c.causal_pairs(512, 8192 + 512)
    assert chunk == pairs * 128 * 320 * 2 + (8192 + 512) * 33_554_432
    assert c.span_attention_flops(cfg, 100, 8192) \
        == c.causal_pairs(100, 8192) * 128 * 1088 * 2
