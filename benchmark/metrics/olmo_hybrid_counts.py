"""Operations and bytes an Olmo-Hybrid serving step NEEDS, from shapes:
the yardstick's arithmetic for ``mfu.rag``, ``gdn_roofline.rag`` and
``ragged_attn_roofline.rag`` (``harness/counts.py`` knows one kind of
layer, with RoPE'd grouped-query attention in every one).

A multiply-add is 2 FLOPs; the embedding lookup counts nothing.  Spans
are ``(q_len, kv_len)`` as packed (``StepRec.spans``); a span whose
``q_len`` is 1 is a decode.

The gated delta rule (``S_t = e^g S (I - beta k k^T) + beta v k^T``, ``o =
S_t q``, a ``[dv, dk]`` state a head) can be computed token by token or
in sub-chunks; a span is counted as the CHEAPER: token by token, three
products of a ``[dv, dk]`` state with a vector a head a token
(``S k``, the rank-one update, ``S q``); chunked (sub-chunks of
``CHUNK``), the products of FLA's chunked form without the inverse of
its triangular matrix.  Its least bytes: each span's state read and
written once in float32, its rows' q, k, v, beta and g read and o
written once in the served type.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}
CHUNK = 64


def kinds(cfg: dict):
    """``(linear layers, full layers)`` at the configuration's depth."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    lin = sum(t == "linear_attention" for t in types)
    return lin, len(types) - lin


def _lin(cfg: dict):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def linear_params(cfg: dict) -> int:
    """Matmul parameters of a linear layer's mixer: W_q, W_k, W_v, W_z,
    W_b, W_a, W_o (the convolution is counted apart, per channel)."""
    h = cfg["hidden_size"]
    H, dk, dv = _lin(cfg)
    return h * H * (2 * dk + 2 * dv + 2) + H * dv * h


def full_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * h * nq * d + 2 * h * nkv * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def token_params(cfg: dict) -> int:
    """Matmul parameters every token multiplies, over all layers."""
    lin, full = kinds(cfg)
    return (lin * linear_params(cfg) + full * full_params(cfg)
            + (lin + full) * mlp_params(cfg))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def span_gdn_flops(cfg: dict, q_len: int) -> int:
    """One linear layer, one span: the cheaper of the two forms."""
    H, dk, dv = _lin(cfg)
    sequential = q_len * H * 3 * 2 * dv * dk
    n = -(-q_len // CHUNK)
    C = CHUNK
    chunked = n * H * 2 * (3 * C * C * dk + 2 * C * C * dv + 3 * C * dk * dv)
    return min(sequential, chunked)


def gdn_flops(cfg: dict, spans) -> int:
    return sum(span_gdn_flops(cfg, q) for q, _ in spans if q > 0)


def gdn_bytes(cfg: dict, spans) -> int:
    """One linear layer's launch: each span's state in and out, float32;
    q, k (dk), v, o (dv), beta and g a head a row in the served type."""
    H, dk, dv = _lin(cfg)
    item = _ITEM[cfg["dtype"]]
    states = sum(1 for q, _ in spans if q > 0) * 2 * H * dv * dk * 4
    rows = sum(q for q, _ in spans) * H * (2 * dk + 2 * dv + 2) * item
    return states + rows


def conv_flops(cfg: dict, tokens: int) -> int:
    H, dk, dv = _lin(cfg)
    return 2 * tokens * H * (2 * dk + dv) * cfg["linear_conv_kernel_dim"]


def causal_pairs(q_len: int, kv_len: int) -> int:
    return q_len * kv_len - q_len * (q_len - 1) // 2


def attention_flops(cfg: dict, spans) -> int:
    """QK^T and PV of one full layer (the published heads)."""
    pairs = sum(causal_pairs(q, kv) for q, kv in spans)
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * pairs


def attention_bytes(cfg: dict, spans) -> int:
    """One full layer: each span's keys and values read once, q read and
    o written once, at the published heads, in the served type."""
    item, d = _ITEM[cfg["dtype"]], head_dim(cfg)
    kv = sum(kv for _, kv in spans) * 2 * cfg["num_key_value_heads"] * d
    qo = sum(q for q, _ in spans) * 2 * cfg["num_attention_heads"] * d
    return (kv + qo) * item


def step_flops(cfg: dict, spans, sampled_rows: int) -> int:
    """A forward over the step's tokens: 2 x the matmul parameters every
    token multiplies, the convolution, the least recurrence in every
    linear layer, causal attention in every full one, the head over the
    sampled rows."""
    lin, full = kinds(cfg)
    tokens = sum(q for q, _ in spans)
    return (2 * tokens * token_params(cfg)
            + lin * (conv_flops(cfg, tokens) + gdn_flops(cfg, spans))
            + full * attention_flops(cfg, spans)
            + 2 * sampled_rows * head_params(cfg))
