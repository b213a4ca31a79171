"""Operations and bytes a DeepSeek-V2 serving step NEEDS, from shapes and
from the program's own count of routed rows: the yardstick's arithmetic
for ``mfu.longdocs`` and ``mla_attn_roofline.longdocs`` (``harness/
counts.py`` knows grouped-query attention and whole expert banks only).

A multiply-add is 2 FLOPs; the embedding lookup counts nothing.  Latent
attention can be computed two ways and is counted, span by span, as the
CHEAPER, so the yardstick reads the same work whatever implements it:

- absorbed: every query-key pair costs ``heads x (kv_lora + rope +
  kv_lora) x 2`` (scores over the latent row, values over ``c_kv``);
- expanded: a pair costs ``heads x (nope + rope + v) x 2`` and every
  cached token of the span's context is first expanded through
  ``W_kvb``: ``kv_lora x heads x (nope + v) x 2``.

The absorb / unabsorb products the first form adds are not counted: the
least work has neither.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(cfg: dict) -> int:
    """The published factorised shapes: q_a, q_b, kv_a, kv_b, o."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * ql + ql * heads * (nope + rope) + h * (kvl + rope)
            + kvl * heads * (nope + v) + heads * v * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_params(cfg: dict) -> int:
    """Matmul parameters EVERY token multiplies, over all layers: the
    attention projections, the dense layers' SwiGLU, and in a routed
    layer the shared experts and the router (the routed experts are
    counted by the rows they were given)."""
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    h = cfg["hidden_size"]
    return (layers * attn_params(cfg)
            + dense * 3 * h * cfg["intermediate_size"]
            + (layers - dense) * (cfg["n_shared_experts"]
                                  * expert_params(cfg)
                                  + h * cfg["router_experts"]))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def causal_pairs(q_len: int, kv_len: int) -> int:
    return q_len * kv_len - q_len * (q_len - 1) // 2


def span_attention_flops(cfg: dict, q_len: int, kv_len: int) -> int:
    """One layer, one span: the cheaper of the two forms."""
    heads, kvl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    pairs = causal_pairs(q_len, kv_len)
    absorbed = pairs * heads * (kvl + rope + kvl) * 2
    expanded = (pairs * heads * (nope + rope + v) * 2
                + kv_len * kvl * heads * (nope + v) * 2)
    return min(absorbed, expanded)


def attention_flops(cfg: dict, spans) -> int:
    return sum(span_attention_flops(cfg, q, kv) for q, kv in spans)


def attention_bytes(cfg: dict, spans) -> int:
    """One layer: each span's latent rows once (``kv_lora + rope`` values
    a token, no padding), q read at ``nope + rope`` and o written at
    ``v`` wide a head."""
    item = _ITEM[cfg["dtype"]]
    heads = cfg["num_attention_heads"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    qo = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return item * (sum(kv for _, kv in spans) * row
                   + sum(q for q, _ in spans) * heads * qo)


def step_flops(cfg: dict, spans, sampled_rows: int, moe_rows: int) -> int:
    """A forward over the step's tokens: 2 x the parameters every token
    multiplies, 2 x an expert's for each row a held expert was given,
    the least attention, the head over the sampled rows."""
    tokens = sum(q for q, _ in spans)
    return (2 * tokens * token_params(cfg)
            + 2 * moe_rows * expert_params(cfg)
            + cfg["num_hidden_layers"] * attention_flops(cfg, spans)
            + 2 * sampled_rows * head_params(cfg))
