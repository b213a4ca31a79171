"""Mixtral: sparse mixture-of-experts Llama (top-k routed SwiGLU experts).

Parity intent: the reference ecosystem's MoE LLM family (PaddleNLP
mixtral; reference fused-MoE kernels paddle/phi/kernels/fusion/ and
incubate MoELayer python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 with global_scatter/global_gather all-to-all
:119,:167).

TPU-native design: expert weights are BATCHED [E, ...] parameters so the
whole expert bank runs as single einsums on the MXU (no per-expert
python loop), and routing is GShard-style dense dispatch into capacity
buffers.  Under a mesh, sharding the E dim places experts on different
devices and GSPMD emits the all-to-all dispatch/combine pair the
reference implements with NCCL collectives.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..nn.layers import Linear, LayerList
from ..nn import initializer as I
from ..ops._helpers import targ
from .llama import (LlamaConfig, LlamaAttention, LlamaForCausalLM,
                    RMSNorm, _attr, LlamaPretrainingCriterion)


@dataclass
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    expert_capacity_factor: float = 2.0


class MixtralSparseMoeBlock(Layer):
    """Top-k routed SwiGLU expert bank with batched weights.

    Parity: the reference MoELayer + fused_ec_moe
    (python/paddle/incubate/nn/functional/fused_ec_moe.py) — here one
    dense-dispatch einsum pipeline: route -> capacity buffers [E, C, D]
    -> three batched expert einsums -> weighted combine."""

    # the serving step's FFN body for this module
    # (``jit/serving_step.py: _moe_ffn``): a bank that holds every
    # expert of its router, through ``ops.moe_gate.moe_ffn`` (on one
    # chip the sorted grouped product, under ep the buffer exchange);
    # ``forward`` below is the eager form, with capacity and drops
    serving_body = "moe_full_bank"

    def __init__(self, config: MixtralConfig):
        super().__init__()
        D = config.hidden_size
        M = config.intermediate_size
        E = config.num_local_experts
        self.top_k = config.num_experts_per_tok
        self.num_experts = E
        self.capacity_factor = config.expert_capacity_factor
        self.aux_coef = config.router_aux_loss_coef
        init = I.Normal(0.0, config.initializer_range)
        self.gate = Linear(D, E, weight_attr=_attr(init), bias_attr=False)
        self.w_gate = self.create_parameter([E, D, M], attr=_attr(init))
        self.w_up = self.create_parameter([E, D, M], attr=_attr(init))
        self.w_down = self.create_parameter([E, M, D], attr=_attr(init))
        self.l_aux = None

    def forward(self, x):
        orig_shape = x.shape
        from ..ops.manipulation import reshape
        flat = reshape(x, [-1, x.shape[-1]])
        n_tokens = int(flat.shape[0])
        capacity = max(1, int(self.capacity_factor * n_tokens *
                              self.top_k / self.num_experts))
        E, k = self.num_experts, self.top_k

        def fn(v, gw, wg, wu, wd):
            from ..ops.moe_gate import (topk_gate, assignment_slots,
                                        dispatch_to_buffers,
                                        grouped_expert_swiglu,
                                        combine_from_buffers)
            logits = (v.astype(jnp.float32)
                      @ gw.astype(jnp.float32))          # [N, E]
            top_w, top_i, probs = topk_gate(logits, k)   # [N, k]

            # capacity slot per assignment (running count per expert);
            # memory stays O(N*k*E) — the buffers themselves are built
            # with scatter/gather, never an [N,k,E,C] one-hot
            slot, oh = assignment_slots(top_i, E)
            keep = slot < capacity
            disp = dispatch_to_buffers(v, top_i, slot, keep, E, capacity)
            # batched expert SwiGLU: all experts in three MXU einsums
            eo = grouped_expert_swiglu(disp, wg, wu, wd)  # [E,C,D]
            out = combine_from_buffers(eo, top_i, slot, top_w,
                                       keep).astype(v.dtype)

            # Mixtral load-balancing aux: E * sum_e f_e * P_e, with f_e
            # from the RAW assignment (pre-capacity) so router collapse
            # is penalized in full
            frac = jnp.mean(oh.sum(axis=1), axis=0)      # tokens/expert
            pmean = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(frac * pmean)
            return out, aux

        out, aux = apply_op("mixtral_moe", fn,
                            (flat, targ(self.gate.weight),
                             targ(self.w_gate), targ(self.w_up),
                             targ(self.w_down)))
        self.l_aux = aux
        return reshape(out, orig_shape)


class MixtralDecoderLayer(Layer):
    def __init__(self, config: MixtralConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.block_sparse_moe = MixtralSparseMoeBlock(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, x, attn_mask=None):
        h = x + self.self_attn(self.input_layernorm(x), attn_mask)
        return h + self.block_sparse_moe(
            self.post_attention_layernorm(h))

    def forward_with_cache(self, x, cache, position_offset,
                           attn_mask=None):
        attn, new_cache = self.self_attn(
            self.input_layernorm(x), attn_mask, cache=cache,
            position_offset=position_offset)
        h = x + attn
        return h + self.block_sparse_moe(
            self.post_attention_layernorm(h)), new_cache


class MixtralModel(Layer):
    def __init__(self, config: MixtralConfig):
        super().__init__()
        self.config = config
        from ..nn.layers import Embedding
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_attr(I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([MixtralDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
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


class MixtralForCausalLM(Layer):
    def __init__(self, config: MixtralConfig):
        super().__init__()
        self.config = config
        self.mixtral = MixtralModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=_attr(
                                  I.Normal(0.0, config.initializer_range)),
                              bias_attr=False)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        if caches is None:
            h = self.mixtral(input_ids, attn_mask)
            return self.lm_head(h)
        h, caches = self.mixtral(input_ids, attn_mask, caches,
                                 position_offset)
        return self.lm_head(h), caches

    # the eager decode loop is model-agnostic (self.forward + config
    # only) — share the llama implementation verbatim so the MoE parity
    # reference can never drift from the dense one
    generate = LlamaForCausalLM.generate

    def router_aux_loss(self):
        """Sum of per-layer load-balancing losses from the LAST forward
        (traced values — combine with the CE loss inside the same
        step/trace), scaled by router_aux_loss_coef."""
        auxes = [lyr.block_sparse_moe.l_aux
                 for lyr in self.mixtral.layers
                 if lyr.block_sparse_moe.l_aux is not None]
        if not auxes:
            raise RuntimeError(
                "router_aux_loss() needs a forward pass first (the aux "
                "terms are recorded per layer during forward)")
        total = auxes[0]
        for a in auxes[1:]:
            total = total + a
        return total * self.config.router_aux_loss_coef


class MixtralPretrainingCriterion(Layer):
    """CE + router load-balancing aux (reads the aux recorded on the
    model by the forward that produced ``logits``)."""

    def __init__(self, model: MixtralForCausalLM):
        super().__init__()
        self._model = [model]          # avoid registering as sublayer

    def forward(self, logits, labels):
        ce = LlamaPretrainingCriterion()(logits, labels)
        return ce + self._model[0].router_aux_loss()


def mixtral_tiny_config(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, max_position_embeddings=128,
               num_local_experts=4, num_experts_per_tok=2)
    cfg.update(kw)
    return MixtralConfig(**cfg)


def shard_mixtral(model: MixtralForCausalLM, mesh, ep_axis="model",
                  fsdp_axis="sharding"):
    """Expert-parallel + FSDP layout: expert banks shard their E dim over
    ``ep_axis`` (GSPMD inserts the dispatch/combine all-to-all); the
    attention/embedding layout matches shard_llama (Megatron columns/
    rows + vocab sharding) with ep_axis standing in for the tp axis;
    router + norms replicate."""
    from ..distributed.api import shard_param_
    from .llama import axis_placements

    def placements(ep_dim=None, fsdp_dim=None):
        return axis_placements(mesh, **{ep_axis: ep_dim,
                                        fsdp_axis: fsdp_dim})

    shard_param_(model.mixtral.embed_tokens.weight, mesh,
                 placements(ep_dim=0, fsdp_dim=1))
    shard_param_(model.lm_head.weight, mesh,
                 placements(ep_dim=1, fsdp_dim=0))
    for layer in model.mixtral.layers:
        a = layer.self_attn
        for lin in (a.q_proj, a.k_proj, a.v_proj):
            shard_param_(lin.weight, mesh,
                         placements(ep_dim=1, fsdp_dim=0))
        shard_param_(a.o_proj.weight, mesh,
                     placements(ep_dim=0, fsdp_dim=1))
        moe = layer.block_sparse_moe
        for w in (moe.w_gate, moe.w_up, moe.w_down):
            shard_param_(w, mesh, placements(ep_dim=0, fsdp_dim=2))
