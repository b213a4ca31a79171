"""DeepSeek-V2: multi-head latent attention (MLA), a leading dense layer,
then layers of routed experts with shared experts beside them.

Every layer: ``x = x + Attn(RMSNorm(x)); x = x + FFN(RMSNorm(x))``, no
bias anywhere.

- **MLA.**  ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, per head
  ``[q_nope | q_rope]``.  ``[c_kv | k_r] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``k_r = RoPE(k_r)``, ONE rope head shared by all query
  heads; ``[k_nope_h | v_h] = c_kv W_kvb`` per head.  Scores are
  ``(q_nope_h . k_nope_h + RoPE(q_rope_h) . k_r) * scale``, causal,
  softmax in float32.  What a cache holds per token is ``c_kv`` after its
  norm and ``k_r`` after RoPE: ``kv_lora_rank + qk_rope_head_dim`` values
  (``DeepseekV2Attention.latent_row`` with the padding to whole lanes).
  The ABSORBED form is the same mathematics without ever expanding the
  cache: ``qt_h = q_nope_h W_kvb[K,h]^T``, ``score_h = (qt_h . c_kv +
  q_r_h . k_r) * scale``, ``o_h = (sum_s p c_kv(s)) W_kvb[V,h]``.  The
  eager forward here takes the expanded form, the serving step
  (``jit/serving_step.py``) the absorbed one, so a parity test of the two
  is a test of the algebra.
- **RoPE** on the ``qk_rope_head_dim`` dims with YaRN frequencies
  (``ops/pallas_kernels.yarn_inv_freq``); pairs are ``(2i, 2i + 1)`` as
  published: the row is de-interleaved, then halves are rotated.  The
  softmax scale is ``(nope + rope)^-0.5 * yarn_mscale(factor,
  mscale_all_dim)^2``.
- **Routed layers.**  ``s = softmax_f32(x W_g)`` over ``router_experts``
  experts, group-limited top-k (``ops/moe_gate.group_limited_topk``), the
  weight of a chosen expert ``s_i * routed_scaling_factor`` (never
  renormalised: ``norm_topk_prob`` true is refused), plus the shared experts'
  SwiGLU on every token.  A bank may hold a SHARE of the experts
  (``n_routed_experts`` of them, from ``first_held_expert``): the router
  keeps its width and what the experts held elsewhere would add is left
  out (``ops/moe_gate.moe_ffn_held``).

Inference only: the forward runs on raw values (no tape).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..nn import initializer as I
from ..nn.layers import Embedding, LayerList, Linear, RMSNorm
from ..ops.moe_gate import moe_ffn_held
from ..ops.pallas_kernels import (latent_row_width, rope_interleaved,
                                  rope_tables_for_positions,
                                  yarn_inv_freq, yarn_mscale)
from .llama import LlamaForCausalLM, _attr

__all__ = ["DeepseekV2Config", "DeepseekV2Attention", "DeepseekV2MLP",
           "DeepseekV2MoE", "DeepseekV2DenseLayer",
           "DeepseekV2SparseLayer", "DeepseekV2Model",
           "DeepseekV2ForCausalLM", "deepseek_v2_tiny_config"]


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288          # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536       # one expert's
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # experts HELD by this bank, and the router's own width (None: the
    # bank holds them all) with the index of the first one held
    n_routed_experts: int = 160
    router_experts: Optional[int] = None
    first_held_expert: int = 0
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # {"type": "yarn", "factor", "original_max_position_embeddings",
    #  "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} or None
    rope_scaling: Optional[dict] = None
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if not (0 <= self.first_held_expert
                <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                "DeepseekV2Config: experts %d..%d held of a router %d "
                "wide" % (self.first_held_expert,
                          self.first_held_expert + self.n_routed_experts,
                          self.router_experts))
        if self.router_experts % self.n_group:
            raise ValueError(
                "DeepseekV2Config: a router %d wide does not divide "
                "into %d groups" % (self.router_experts, self.n_group))
        if self.norm_topk_prob:
            raise ValueError(
                "DeepseekV2Config: norm_topk_prob=True (the chosen "
                "experts' weights renormalised over the k) is not "
                "implemented: DeepSeek-V2 publishes False, and no "
                "configuration here exercises the other branch")

    @property
    def num_local_experts(self) -> int:
        """The engine's MoE accounting reads this name."""
        return self.n_routed_experts


def deepseek_v2_tiny_config(**kw) -> DeepseekV2Config:
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=24, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
               n_shared_experts=2, num_experts_per_tok=3, n_group=4,
               topk_group=2, routed_scaling_factor=2.5,
               first_k_dense_replace=1, max_position_embeddings=512,
               rope_scaling={"type": "yarn", "factor": 4.0,
                             "original_max_position_embeddings": 32,
                             "beta_fast": 32, "beta_slow": 1,
                             "mscale": 0.707, "mscale_all_dim": 0.707})
    cfg.update(kw)
    return DeepseekV2Config(**cfg)


def _linear(n_in: int, n_out: int, config) -> Linear:
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=_attr(I.Normal(0.0, config.initializer_range)))


class DeepseekV2Attention(Layer):
    """Multi-head latent attention (the module's docstring)."""

    # the serving step's attention body for this module
    # (``jit/serving_step.py``): absorbed, over one ``latent_row`` a token
    serving_body = "mla"

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.num_heads = H = config.num_attention_heads
        self.nope, self.rope = (config.qk_nope_head_dim,
                                config.qk_rope_head_dim)
        self.v_dim, self.kv_lora = config.v_head_dim, config.kv_lora_rank
        h = config.hidden_size
        self.q_a_proj = _linear(h, config.q_lora_rank, config)
        self.q_a_layernorm = RMSNorm(config.q_lora_rank,
                                     config.rms_norm_eps)
        self.q_b_proj = _linear(config.q_lora_rank,
                                H * (self.nope + self.rope), config)
        self.kv_a_proj_with_mqa = _linear(h, self.kv_lora + self.rope,
                                          config)
        self.kv_a_layernorm = RMSNorm(self.kv_lora, config.rms_norm_eps)
        self.kv_b_proj = _linear(self.kv_lora,
                                 H * (self.nope + self.v_dim), config)
        self.o_proj = _linear(H * self.v_dim, h, config)
        # one cached row a token: [c_kv | k_r | zeros to whole lanes]
        self.latent_row = latent_row_width(self.kv_lora, self.rope)
        rs = config.rope_scaling or {}
        m = yarn_mscale(rs.get("factor", 1.0), rs.get("mscale_all_dim", 0))
        self.softmax_scale = (self.nope + self.rope) ** -0.5 * m * m

    def rope_tables(self, positions):
        """``(cos, sin) [N, rope]`` float32 for token positions
        ``[N]``, YaRN-blended where the configuration scales."""
        cfg, rs = self.config, self.config.rope_scaling
        if not rs:
            return rope_tables_for_positions(positions, self.rope,
                                             cfg.rope_theta)
        cos, sin = rope_tables_for_positions(
            positions, self.rope, cfg.rope_theta,
            inv_freq=yarn_inv_freq(
                self.rope, cfg.rope_theta, rs["factor"],
                rs["original_max_position_embeddings"],
                rs.get("beta_fast", 32), rs.get("beta_slow", 1)))
        m = (yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
             / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
        return (cos, sin) if m == 1.0 else (cos * m, sin * m)

    def queries(self, h):
        """``h [.., hidden]`` (a Tensor, normed) -> raw ``(q_nope [.., H,
        nope], q_rope [.., H, rope])``, the rope part not yet rotated."""
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(h)))._value
        q = q.reshape(q.shape[:-1] + (self.num_heads,
                                      self.nope + self.rope))
        return q[..., :self.nope], q[..., self.nope:]

    def latent(self, h):
        """``h`` -> raw ``(c_kv [.., kv_lora]`` after its norm, ``k_rope
        [.., rope])``, not yet rotated."""
        ckr = self.kv_a_proj_with_mqa(h)._value
        c_kv = self.kv_a_layernorm(
            Tensor._from_value(ckr[..., :self.kv_lora]))._value
        return c_kv, ckr[..., self.kv_lora:]

    def kv_b(self):
        """``(W_kvb[K] [kv_lora, H, nope], W_kvb[V] [kv_lora, H, v])``."""
        w = self.kv_b_proj.weight._value.reshape(
            self.kv_lora, self.num_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def forward(self, x, attn_mask=None, cache=None, position_offset=0):
        """The EXPANDED form over dense tensors: ``x [B, S, hidden]``;
        ``cache`` is ``(latent rows [B, L, kv_lora + rope], None)`` of
        the positions before ``position_offset`` (or ``(None, None)``),
        and the new one is returned beside the output when given."""
        if attn_mask is not None:
            raise NotImplementedError(
                "DeepseekV2Attention is causal; attn_mask is not taken")
        B, S = x.shape[0], x.shape[1]
        pos = position_offset + jnp.arange(S, dtype=jnp.int32)
        cos, sin = self.rope_tables(pos)
        q_nope, q_r = self.queries(x)
        q_r = rope_interleaved(q_r, cos[:, None, :], sin[:, None, :])
        c_kv, k_r = self.latent(x)
        rows = jnp.concatenate([c_kv, rope_interleaved(k_r, cos, sin)],
                               axis=-1)
        if cache is not None and cache[0] is not None:
            rows = jnp.concatenate([cache[0]._value, rows], axis=1)
        c_all, kr_all = rows[..., :self.kv_lora], rows[..., self.kv_lora:]
        wk, wv = self.kv_b()
        f32 = jnp.float32
        k_nope = jnp.einsum("blc,chn->blhn", c_all, wk)
        v = jnp.einsum("blc,chv->blhv", c_all, wv)
        s = (jnp.einsum("bshn,blhn->bhsl", q_nope, k_nope,
                        preferred_element_type=f32)
             + jnp.einsum("bshr,blr->bhsl", q_r, kr_all,
                          preferred_element_type=f32)) * self.softmax_scale
        seen = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("bhsl,blhv->bshv", p.astype(v.dtype), v,
                       preferred_element_type=f32).astype(v.dtype)
        out = self.o_proj(Tensor._from_value(
            o.reshape(B, S, self.num_heads * self.v_dim)))
        if cache is not None:
            return out, (Tensor._from_value(rows), None)
        return out


class DeepseekV2MLP(Layer):
    """SwiGLU of a given width (a dense layer's, or the shared experts'
    ``n_shared_experts x moe_intermediate_size`` as one block)."""

    def __init__(self, config: DeepseekV2Config,
                 width: Optional[int] = None):
        super().__init__()
        m = width or config.intermediate_size
        self.gate_proj = _linear(config.hidden_size, m, config)
        self.up_proj = _linear(config.hidden_size, m, config)
        self.down_proj = _linear(m, config.hidden_size, config)

    def forward(self, x):
        from ..nn.functional.activation import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV2MoE(Layer):
    """The router at its full width, the held experts stacked ``[El, ..]``
    and the shared experts.  ``routed(flat)`` is the held experts' part
    over raw ``[N, hidden]`` (and their load); ``forward`` adds the
    shared experts'."""

    # the serving step's FFN body for this module: the held share of a
    # wider router, sorted and multiplied by real rows
    serving_body = "moe_held"

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        D, M = config.hidden_size, config.moe_intermediate_size
        E = config.n_routed_experts
        self.top_k = config.num_experts_per_tok
        init = _attr(I.Normal(0.0, config.initializer_range))
        self.gate = _linear(D, config.router_experts, config)
        self.w_gate = self.create_parameter([E, D, M], attr=init)
        self.w_up = self.create_parameter([E, D, M], attr=init)
        self.w_down = self.create_parameter([E, M, D], attr=init)
        self.shared_experts = DeepseekV2MLP(
            config, M * config.n_shared_experts)
        self.last_load = None       # [El] int32 of the last forward

    def routed(self, flat, valid=None, use_pallas=None):
        """The held experts' part over raw ``flat [N, hidden]`` and the
        rows each was given; ``valid [N]`` marks the rows that are
        tokens (a serving pack's padding is not); ``use_pallas`` is the
        serving step's choice of the grouped product's lowering."""
        cfg = self.config
        return moe_ffn_held(
            flat, self.gate.weight._value, self.w_gate._value,
            self.w_up._value, self.w_down._value,
            top_k=cfg.num_experts_per_tok,
            first_held=cfg.first_held_expert, n_group=cfg.n_group,
            topk_group=cfg.topk_group,
            routed_scale=cfg.routed_scaling_factor, valid=valid,
            use_pallas=use_pallas)

    def forward(self, x):
        v = x._value
        out, self.last_load = self.routed(v.reshape(-1, v.shape[-1]))
        return self.shared_experts(x) + Tensor._from_value(
            out.reshape(v.shape))


class _DecoderLayer(Layer):
    def __init__(self, config: DeepseekV2Config, mlp: Layer):
        super().__init__()
        self.self_attn = DeepseekV2Attention(config)
        self.mlp = mlp
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, x, attn_mask=None):
        h = x + self.self_attn(self.input_layernorm(x), attn_mask)
        return h + self.mlp(self.post_attention_layernorm(h))

    def forward_with_cache(self, x, cache, position_offset,
                           attn_mask=None):
        attn, new_cache = self.self_attn(
            self.input_layernorm(x), attn_mask, cache=cache,
            position_offset=position_offset)
        h = x + attn
        return h + self.mlp(self.post_attention_layernorm(h)), new_cache


class DeepseekV2DenseLayer(_DecoderLayer):
    """One of the ``first_k_dense_replace`` leading layers."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__(config, DeepseekV2MLP(config))


class DeepseekV2SparseLayer(_DecoderLayer):
    """A layer whose FFN is routed experts beside shared ones."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__(config, DeepseekV2MoE(config))


class DeepseekV2Model(Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_attr(I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([
            (DeepseekV2DenseLayer if i < config.first_k_dense_replace
             else DeepseekV2SparseLayer)(config)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        h = self.embed_tokens(input_ids)
        if self.config.dtype == "bfloat16":
            h = h.astype("bfloat16")
        if caches is None:
            for layer in self.layers:
                h = layer(h, attn_mask)
            return self.norm(h)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            h, c = layer.forward_with_cache(h, cache, position_offset,
                                            attn_mask)
            new_caches.append(c)
        return self.norm(h), new_caches


class DeepseekV2ForCausalLM(Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.deepseek = DeepseekV2Model(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size,
                               config)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        if caches is None:
            return self.lm_head(self.deepseek(input_ids, attn_mask))
        h, caches = self.deepseek(input_ids, attn_mask, caches,
                                  position_offset)
        return self.lm_head(h), caches

    # the eager decode loop is model-agnostic (self.forward + config)
    generate = LlamaForCausalLM.generate
