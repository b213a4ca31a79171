"""Olmo-Hybrid: Olmo's post-norm decoder with two kinds of mixer, Gated
DeltaNet linear attention and full softmax attention, picked per layer by
``layer_types`` (three linear layers, then one full, repeated).

Every block is post-norm (Olmo 2/3)::

    h = x + RMSNorm_post_attn(Mixer(x))
    y = h + RMSNorm_post_ff(W_down(silu(W_gate h) * W_up h))

- **Full attention** (``OlmoHybridAttention``): ``q = RMSNorm_q(x W_q)``
  and ``k = RMSNorm_k(x W_k)``, each norm over the whole projection,
  ``v = x W_v``; causal softmax at ``1/sqrt(head_dim)``, then ``W_o``.  No
  rotary embedding: the published ``rope_theta`` is null, so these layers
  are NoPE and position comes from the linear layers.
- **Linear attention** (``OlmoHybridGatedDeltaNet``, FLA's
  ``GatedDeltaNet`` form): ``q, k, v = silu(conv4(x W_q | x W_k | x
  W_v))``, one causal depthwise convolution over the concatenated
  channels; ``q`` and ``k`` L2-normed per head, ``q`` scaled by
  ``d_k^-1/2``; ``beta = 2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``
  doubles it), ``g = -exp(A_log) softplus(x W_a + dt_bias)``; the gated
  delta rule of ``ops/gated_delta.py`` from a zero state; ``out = W_o
  concat_heads(RMSNorm_dv(o) * silu(x W_z))``, the gain shared by the
  heads.

In the serving step (``jit/serving_step.py``) the full-attention layers
keep K/V in the engine's paged pools and the linear layers a fixed-size
state a slot (``[heads, d_v, d_k]`` float32 and the convolution's last
``K - 1`` inputs), which the modules declare through ``serving_body``.
The eager forward here runs the rule token by token and is held to
``benchmark/references/olmo_hybrid.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layers import Embedding, LayerList, Linear, RMSNorm
from ..ops import gated_delta as gd
from .llama import LlamaMLP, _attr

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    # the mixer of each layer (its first num_hidden_layers are used);
    # None: three linear layers then one full, repeated
    layer_types: Optional[List[str]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                "OlmoHybrid: %d key heads against %d value heads; the "
                "gated delta rule here pairs one key head with each value "
                "head" % (self.linear_num_key_heads,
                          self.linear_num_value_heads))
        if self.attention_bias:
            raise ValueError("OlmoHybrid: attention_bias is not taken")

    def layer_type(self, i: int) -> str:
        if self.layer_types is not None:
            return self.layer_types[i]
        return (LINEAR, LINEAR, LINEAR, FULL)[i % 4]


def olmo_hybrid_tiny_config(**kw) -> OlmoHybridConfig:
    """Two periods (8 layers) at widths a CPU test can afford."""
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
               num_hidden_layers=8, num_attention_heads=4,
               num_key_value_heads=4, linear_num_key_heads=2,
               linear_num_value_heads=2, linear_key_head_dim=16,
               linear_value_head_dim=32, max_position_embeddings=512)
    cfg.update(kw)
    return OlmoHybridConfig(**cfg)


def _linear(n_in: int, n_out: int, config) -> Linear:
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=_attr(I.Normal(0.0, config.initializer_range)))


def _silu32(x):
    x = x.astype(jnp.float32)
    return x * jax.nn.sigmoid(x)


class OlmoHybridAttention(Layer):
    """Full causal attention with whole-projection q/k norms, no rope."""

    # the serving step's body for this module (``jit/serving_step.py``):
    # the grouped-query launch over K and V pools, after the q/k norms
    serving_body = "gqa_qk_norm"

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.q_proj = _linear(h, self.num_heads * self.head_dim, config)
        self.k_proj = _linear(h, self.num_kv_heads * self.head_dim, config)
        self.v_proj = _linear(h, self.num_kv_heads * self.head_dim, config)
        self.o_proj = _linear(self.num_heads * self.head_dim, h, config)
        self.q_norm = RMSNorm(self.num_heads * self.head_dim,
                              config.rms_norm_eps)
        self.k_norm = RMSNorm(self.num_kv_heads * self.head_dim,
                              config.rms_norm_eps)
        # the heads the serving pools store: the ragged launch moves a
        # page's heads in whole sublane tiles, so 30 are stored as 32, the
        # two more zero (queries and outputs padded alike, in the step)
        self.pool_heads = -(-self.num_kv_heads // 16) * 16
        if self.num_heads != self.num_kv_heads:
            raise ValueError(
                "OlmoHybridAttention: %d query heads over %d kv heads; the "
                "serving step pads both to %d and takes one group"
                % (self.num_heads, self.num_kv_heads, self.pool_heads))

    def qkv(self, x):
        """``x`` (a Tensor) -> ``(q, k, v)`` values ``[.., heads, D]``,
        q and k after their norms."""
        q = self.q_norm(self.q_proj(x))._value
        k = self.k_norm(self.k_proj(x))._value
        v = self.v_proj(x)._value
        D = self.head_dim
        return (q.reshape(q.shape[:-1] + (self.num_heads, D)),
                k.reshape(k.shape[:-1] + (self.num_kv_heads, D)),
                v.reshape(v.shape[:-1] + (self.num_kv_heads, D)))

    def forward(self, x):
        B, S = x.shape[0], x.shape[1]
        q, k, v = self.qkv(x)
        g = self.num_heads // self.num_kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        f32 = jnp.float32
        s = jnp.einsum("bshd,blhd->bhsl", q, k,
                       preferred_element_type=f32) / np.sqrt(self.head_dim)
        seen = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("bhsl,blhd->bshd", p.astype(v.dtype), v,
                       preferred_element_type=f32).astype(v.dtype)
        return self.o_proj(Tensor._from_value(
            o.reshape(B, S, self.num_heads * self.head_dim)))


class OlmoHybridGatedDeltaNet(Layer):
    """Gated DeltaNet linear attention (the module docstring)."""

    # the serving step's body for this module (``jit/serving_step.py``):
    # the ragged convolution and ``ragged_gated_delta`` over a state a
    # slot, beside the K/V pools of the full layers
    serving_body = "gated_delta"

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.num_heads = H = config.linear_num_value_heads
        self.dk = config.linear_key_head_dim
        self.dv = config.linear_value_head_dim
        self.conv_k = config.linear_conv_kernel_dim
        self.allow_neg_eigval = bool(config.linear_allow_neg_eigval)
        self.eps = config.rms_norm_eps
        h = config.hidden_size
        self.q_proj = _linear(h, H * self.dk, config)
        self.k_proj = _linear(h, H * self.dk, config)
        self.v_proj = _linear(h, H * self.dv, config)
        self.g_proj = _linear(h, H * self.dv, config)      # the output gate
        self.b_proj = _linear(h, H, config)
        self.a_proj = _linear(h, H, config)
        self.o_proj = _linear(H * self.dv, h, config)
        self.conv_channels = H * (2 * self.dk + self.dv)
        bound = self.conv_k ** -0.5
        self.conv_weight = self.create_parameter(
            [self.conv_channels, self.conv_k],
            default_initializer=I.Uniform(-bound, bound))
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=I.Constant(0.0))
        self.o_norm = RMSNorm(self.dv, config.rms_norm_eps)

    def projections(self, x):
        """``x`` (a Tensor) -> ``(qkv [.., C], z, b, a)`` values: the
        convolution's input, the output gate and the gates' inputs."""
        qkv = jnp.concatenate([self.q_proj(x)._value, self.k_proj(x)._value,
                               self.v_proj(x)._value], axis=-1)
        return (qkv, self.g_proj(x)._value, self.b_proj(x)._value,
                self.a_proj(x)._value)

    def qkv_heads(self, conv):
        """The convolution's float32 output ``[.., C]`` -> ``(q, k, v)``
        per head: SiLU, q and k L2-normed, q scaled by ``dk^-1/2``."""
        H, dk, dv = self.num_heads, self.dk, self.dv
        y = _silu32(conv)
        lead = y.shape[:-1]
        q = gd.l2norm(y[..., :H * dk].reshape(lead + (H, dk))) * dk ** -0.5
        k = gd.l2norm(y[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = y[..., 2 * H * dk:].reshape(lead + (H, dv))
        return q, k, v

    def gates(self, b, a):
        return gd.gates(b, a, self.A_log._value, self.dt_bias._value,
                        self.allow_neg_eigval)

    def gate_norm(self, o, z):
        """``concat_heads(RMSNorm_dv(o) * silu(z))`` in ``z``'s type:
        ``o [.., H, dv]`` float32, ``z [.., H * dv]``."""
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + self.eps) \
            * self.o_norm.weight._value.astype(jnp.float32)
        return (o.reshape(o.shape[:-2] + (-1,)) * _silu32(z)).astype(z.dtype)

    def forward(self, x):
        qkv, z, b, a = self.projections(x)
        w = self.conv_weight._value
        conv = jax.vmap(lambda s: gd.causal_conv(s, w))(qkv)
        q, k, v = self.qkv_heads(conv)
        beta, g = self.gates(b, a)
        S0 = jnp.zeros((self.num_heads, self.dv, self.dk), jnp.float32)
        o, _ = jax.vmap(gd.gated_delta_scan, in_axes=(0, 0, 0, 0, 0, None))(
            q, k, v, beta, g, S0)
        return self.o_proj(Tensor._from_value(self.gate_norm(o, z)))


class _DecoderLayer(Layer):
    # Olmo's block: the norms follow the mixer and the MLP (the serving
    # step reads this)
    post_norm = True

    def __init__(self, config: OlmoHybridConfig, mixer: Layer):
        super().__init__()
        self.self_attn = mixer
        self.mlp = LlamaMLP(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.post_feedforward_layernorm = RMSNorm(config.hidden_size,
                                                  config.rms_norm_eps)

    def forward(self, x):
        h = x + self.post_attention_layernorm(self.self_attn(x))
        return h + self.post_feedforward_layernorm(self.mlp(h))


class OlmoHybridLinearLayer(_DecoderLayer):
    """A layer whose mixer is Gated DeltaNet."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(config, OlmoHybridGatedDeltaNet(config))


class OlmoHybridFullLayer(_DecoderLayer):
    """A layer whose mixer is full attention."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(config, OlmoHybridAttention(config))


class OlmoHybridModel(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_attr(I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([
            (OlmoHybridLinearLayer if config.layer_type(i) == LINEAR
             else OlmoHybridFullLayer)(config)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        if self.config.dtype == "bfloat16":
            h = h.astype("bfloat16")
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class OlmoHybridForCausalLM(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.olmo_hybrid = OlmoHybridModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size,
                               config)

    def forward(self, input_ids):
        return self.lm_head(self.olmo_hybrid(input_ids))

    def generate(self, input_ids, max_new_tokens=16):
        """Greedy decoding by a full forward a token (no state carried:
        the serving engine is the path that carries it)."""
        from ..autograd.tape import no_grad
        ids = np.asarray(input_ids._value if isinstance(input_ids, Tensor)
                         else input_ids)
        with no_grad():
            for _ in range(max_new_tokens):
                logits = self.forward(Tensor._from_value(jnp.asarray(ids)))
                nxt = np.asarray(jnp.argmax(logits._value[:, -1], axis=-1))
                ids = np.concatenate([ids, nxt[:, None]], axis=1)
        return Tensor._from_value(jnp.asarray(ids))
