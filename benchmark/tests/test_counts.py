"""The FLOP and byte counts against hand-worked values, both models."""
import json
import os

import pytest

from harness import counts
from harness.peaks import peaks_for
from tinytree import BENCH


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


MISTRAL = _cfg("mistral-7b-v0.3.d16")
MIXTRAL = _cfg("mixtral-8x7b-v0.1.d3")


def test_layer_parameters():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096
    assert counts.attn_params(MISTRAL) == 41_943_040
    # gate, up, down: 3 x 4096 x 14336
    assert counts.expert_params(MISTRAL) == 176_160_768
    assert counts.layer_matmul_params(MISTRAL, True) == 218_103_808
    # Mixtral holds 8 experts and a 4096x8 router; a token multiplies 2
    assert counts.layer_matmul_params(MIXTRAL, False) == (
        41_943_040 + 32_768 + 8 * 176_160_768)
    assert counts.layer_matmul_params(MIXTRAL, True) == (
        41_943_040 + 32_768 + 2 * 176_160_768)
    assert counts.head_params(MISTRAL) == 4096 * 32768


def test_causal_pairs():
    assert counts.causal_pairs(1, 100) == 100          # a decode token
    assert counts.causal_pairs(4, 4) == 1 + 2 + 3 + 4  # a first chunk
    assert counts.causal_pairs(3, 10) == 8 + 9 + 10    # a later chunk


def test_decode_step_of_one_token():
    spans = [(1, 1000)]
    # per layer: 2 x 218,103,808 matmul + 4 x 32 x 128 x 1000 attention
    per_layer = 2 * 218_103_808 + 4 * 32 * 128 * 1000
    assert counts.serve_step_flops(MISTRAL, spans, 1) == (
        16 * per_layer + 2 * 4096 * 32768)
    # bytes: all 16 layers' weights and the head once (bf16), the span's
    # 1000 keys and values per layer, q in and o out, one new k/v row
    weights = (16 * 218_103_808 + 4096 * 32768) * 2
    kv = 16 * 1000 * 2 * 8 * 128 * 2
    qo = 16 * 1 * 2 * 32 * 128 * 2
    new = 16 * 1 * 2 * 8 * 128 * 2
    assert counts.serve_step_bytes(MISTRAL, spans) == weights + kv + qo + new


def test_moe_step_reads_only_the_experts_its_tokens_reach():
    one = counts.serve_step_bytes(MIXTRAL, [(1, 16)])
    many = counts.serve_step_bytes(MIXTRAL, [(512, 512)])
    # one token reaches 2 experts, 512 tokens reach all 8
    assert many - one > 3 * 6 * 176_160_768 * 2 * 0.99


def test_roofline_says_which_bound():
    peaks = peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    t, bound = counts.roofline_seconds(197e12, 1.0, peaks)
    assert (round(t, 9), bound) == (1.0, "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9, peaks)
    assert (round(t, 9), bound) == (1.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
