"""The Olmo-Hybrid family end to end at a tiny size on the CPU: a
throw-away cell ``tiny-olmo.rag`` (one period: three Gated DeltaNet
layers, then a full-attention one) ADDED to ``tinytree``'s copy of the
benchmark's data, driven through the real entry point with the real
reference, metrics and traffic generator.
"""
import json
import os

import pytest

import run as bench_run
import tinytree

CELL = "tiny-olmo.rag"
OLMO = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "dtype": "float32", "family": "olmo_hybrid", "source": "test",
    "reduced": []}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tinytree.make(str(tmp_path_factory.mktemp("bench")))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(tinytree.BENCH, "configs",
                           "olmo-hybrid-7b.d8.json")) as f:
        program = json.load(f)["program"]
    tinytree._dump(os.path.join(bdir, "configs", "tiny-olmo.json"),
                   dict(OLMO, program=program))
    tinytree._dump(
        os.path.join(bdir, "cells", CELL + ".json"),
        {"engine": {"max_batch_size": 4, "prefill_chunk_size": 16,
                    "block_size": 4, "num_blocks": 128, "max_seq_len": 112},
         "clients": 3, "trace_seconds": 0.5,
         "kernels": ["ragged_gated_delta", "ragged_paged_attention"],
         "correct": {"sample": 8, "logit_gap_max": 1e-3,
                     "logit_gap_mean": 1e-4}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-olmo", "source": "test", "reduced": [],
         "file": "benchmark/configs/tiny-olmo.json", "why": "test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-olmo", "traffic": "tiny-docs",
         "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "olmo-hybrid-7b.rag" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tinytree._dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_traced_cell_serves_what_its_reference_puts_first(root):
    """float32 on both sides: the served token is the reference's best at
    every sampled position, through chunked prefill (prompts of up to 80
    tokens in chunks of 16, so states carried across chunks) and decode
    over the slots' states; the per-layer metrics that need no device are
    in the line, those of the device trace and the peak are left out."""
    result, rc = bench_run.run_cell(CELL, 2 ** 31 + 9, 2.0, True,
                                    root=root, require_chip=False)
    assert rc == 0 and result["correct"] is True
    assert result["reference"]["tokens_off_reference_best"] == 0
    assert result["reference"]["positions"] >= 12
    metrics = result["metrics"]
    assert {"budget_fill.rag", "engine_host_ms.rag"} <= set(metrics)
    for name in ("mfu.rag", "gdn_roofline.rag", "ragged_attn_roofline.rag",
                 "device_idle.rag"):
        assert name not in metrics


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


def test_carry_dropped_at_a_chunk_boundary_is_not_correct(root,
                                                          monkeypatch):
    """A program that starts every chunk of a prompt from zero state and
    a zero convolution tail, as if the slot were fresh, serves other
    tokens than the reference puts first."""
    from paddle_tpu.ops import gated_delta
    monkeypatch.setattr(gated_delta, "fresh_spans",
                        lambda q_lens, kv_lens: q_lens > 1)
    result, rc = bench_run.run_cell(CELL, 3, 2.0, False, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is False
    value, limit = result["compared"]["logit_gap_max"]
    assert value > limit


def test_counts_of_the_gated_delta_rule():
    """The yardstick at the published widths: a decode span's least work
    is token by token, and its least bytes are its state, in and out."""
    from harness import spec
    import importlib.util
    cfg = spec.Cell("olmo-hybrid-7b.rag").config
    s = importlib.util.spec_from_file_location(
        "c", os.path.join(tinytree.BENCH, "metrics",
                          "olmo_hybrid_counts.py"))
    c = importlib.util.module_from_spec(s)
    s.loader.exec_module(c)
    assert c.kinds(cfg) == (6, 2)
    # at the published widths: 215.56M a linear layer with its MLP and
    # convolution, 185.79M a full one
    assert c.linear_params(cfg) == 88_704_000
    assert c.linear_params(cfg) + c.mlp_params(cfg) + 11520 * 4 \
        == 215_562_240
    assert c.full_params(cfg) == 4 * 3840 * 3840
    assert c.span_gdn_flops(cfg, 1) == 30 * 6 * 192 * 96
    assert c.gdn_bytes(cfg, [(1, 700)]) \
        == 2 * 30 * 192 * 96 * 4 + 30 * (2 * 96 + 2 * 192 + 2) * 2
    assert c.span_gdn_flops(cfg, 512) <= 512 * 30 * 6 * 192 * 96
