"""Operations and bytes the work NEEDS, from shapes: the yardstick's
arithmetic.  Nothing here is read from the program or from XLA.

A multiply-add is 2 FLOPs.  The embedding lookup multiplies nothing and
counts nothing.  Attention is counted causal: a token attends to its own
context and no further.  Recomputation is never counted.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nkv * d + nq * d * h


def expert_params(cfg: dict) -> int:
    """One SwiGLU feed-forward (an expert, or the dense MLP)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_matmul_params(cfg: dict, active: bool) -> int:
    """Matmul parameters of one layer: those a token multiplies
    (``active``) or those the layer holds."""
    e = cfg.get("num_local_experts", 0)
    if not e:
        return attn_params(cfg) + expert_params(cfg)
    k = cfg["num_experts_per_tok"] if active else e
    return (attn_params(cfg) + cfg["hidden_size"] * e
            + k * expert_params(cfg))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def causal_pairs(q_len: int, kv_len: int) -> int:
    """Query-key pairs of a span of ``q_len`` new tokens that ends at
    context length ``kv_len``: token i sees kv_len - q_len + 1 + i keys."""
    return q_len * kv_len - q_len * (q_len - 1) // 2


def attention_flops(cfg: dict, spans) -> int:
    """QK^T and PV of one layer over ``spans`` [(q_len, kv_len)]."""
    pairs = sum(causal_pairs(q, kv) for q, kv in spans)
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def attention_bytes(cfg: dict, spans) -> int:
    """One layer's attention: each span's keys and values read once, the
    queries read and the outputs written once, in the served type."""
    item = _ITEM[cfg["dtype"]]
    d = cfg["head_dim"]
    kv = sum(kv for _, kv in spans) * 2 * cfg["num_key_value_heads"] * d
    qo = sum(q for q, _ in spans) * 2 * cfg["num_attention_heads"] * d
    return (kv + qo) * item


def serve_step_flops(cfg: dict, spans, sampled_rows: int) -> int:
    """A forward over the step's tokens: 2 x active matmul parameters a
    token, causal attention, and the head over the sampled rows only."""
    layers = cfg["num_hidden_layers"]
    tokens = sum(q for q, _ in spans)
    return (2 * tokens * layers * layer_matmul_params(cfg, True)
            + layers * attention_flops(cfg, spans)
            + 2 * sampled_rows * head_params(cfg))


def serve_step_bytes(cfg: dict, spans) -> int:
    """HBM bytes a step cannot avoid: every weight it multiplies by read
    once (of the experts, as many as its tokens can reach), the head, the
    live keys and values once per layer, the new ones written."""
    item = _ITEM[cfg["dtype"]]
    layers = cfg["num_hidden_layers"]
    tokens = sum(q for q, _ in spans)
    e = cfg.get("num_local_experts", 0)
    if e:
        reach = min(e, tokens * cfg["num_experts_per_tok"])
        per_layer = (attn_params(cfg) + cfg["hidden_size"] * e
                     + reach * expert_params(cfg))
    else:
        per_layer = layer_matmul_params(cfg, False)
    weights = (layers * per_layer + head_params(cfg)) * item
    kv_write = (tokens * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
                * item * layers)
    return weights + layers * attention_bytes(cfg, spans) + kv_write


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which peak sets it."""
    tc = flops / peaks["flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
