"""Plain reference: DeepSeek-V2 decoder (HF ``DeepseekV2ForCausalLM``),
one chip's share of it as the configuration's file states.

jax.numpy, float32 through ``mistral.py``'s ``bmm32`` / ``mm``, forward
only, no kernels, no cache, no batching.  Imports nothing of the system
under test.  Every layer is ``x + Attn(RMSNorm(x))`` then ``x +
FFN(RMSNorm(x))``, no bias anywhere.

- **Attention (MLA), the EXPANDED form.**  ``c_q = RMSNorm(x W_qa)``, ``q
  = c_q W_qb`` per head ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``,
  ``c_kv = RMSNorm(c_kv)``, ``k_r = RoPE(k_r)`` (one head for all);
  ``[k_nope_h | v_h] = c_kv W_kvb`` per head; scores ``(q_nope_h .
  k_nope_h + RoPE(q_rope_h) . k_r) * scale``, causal, softmax in float32;
  ``scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2``.
  Heads go one at a time (a scan that adds each head's ``o_h W_o[h]``) and
  query rows in blocks of ``Q_BLOCK`` against the keys up to the block's
  end, so that 32k positions fit; the two products over ``[rows, keys]``
  are ``bmm32``'s six passes laid side by side (``scores32``, ``pv32``).
- **RoPE**: YaRN's blended frequencies on the rope dims.  Convention
  taken: pairs are ``(2i, 2i + 1)``; as the published code does, a row is
  de-interleaved (evens then odds) and its halves rotated.  Scores do not
  depend on that order as long as q and k share it.
- **FFN.**  The first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``.  The others: ``s = softmax(x W_g)`` over the
  router's ``router_experts`` outputs; a group (``router_experts /
  n_group`` consecutive experts) scores as its best expert, the
  ``topk_group`` best groups are kept, the ``num_experts_per_tok`` best
  experts among them chosen, weight ``s_i * routed_scaling_factor``
  (never renormalised: ``norm_topk_prob`` true raises); plus the shared experts'
  SwiGLU (``n_shared_experts * moe_intermediate_size`` wide) on every
  token.  THE SHARE: only the ``n_routed_experts`` experts from
  ``first_held_expert`` are held (the leaves hold no other); what the
  others would add is left out, here as in the program.  Each held
  expert computes the tokens routed to it, gathered into a buffer of
  ``S / 2`` rows (an expert given more than half of all tokens marks
  every position undecided, which fails the run: never silent).

The routed layer also returns, per token, the margin in logits of the
router's decision: the smaller of the group stage's (the last kept
group's best logit minus the first dropped group's) and the expert
stage's.  The expert stage counts ONLY decisions that change what the
HELD experts compute: the smallest gap between a chosen and an unchosen
candidate of which at least one is held (two experts held elsewhere
swapping places changes nothing computed here: the weights are softmax
over all and do not move).
"""
from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_mistral",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mistral.py"))
_m = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_m)

INNER = "deepseek"
Q_BLOCK = 4096
top_shapes, embed, logits, mm, rmsnorm = (
    _m.top_shapes, _m.embed, _m.logits, _m.mm, _m.rmsnorm)


def layer_kinds(cfg: dict) -> list:
    dense = int(cfg["first_k_dense_replace"])
    return ["dense" if i < dense else "sparse"
            for i in range(int(cfg["num_hidden_layers"]))]


def layer_shapes(cfg: dict, kind: str) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    shapes = {
        "self_attn.q_a_proj.weight": (h, ql),
        "self_attn.q_a_layernorm.weight": (ql,),
        "self_attn.q_b_proj.weight": (ql, heads * (nope + rope)),
        "self_attn.kv_a_proj_with_mqa.weight": (h, kvl + rope),
        "self_attn.kv_a_layernorm.weight": (kvl,),
        "self_attn.kv_b_proj.weight": (kvl, heads * (nope + v)),
        "self_attn.o_proj.weight": (heads * v, h),
        "input_layernorm.weight": (h,),
        "post_attention_layernorm.weight": (h,),
    }
    if kind == "dense":
        m = cfg["intermediate_size"]
        shapes.update({"mlp.gate_proj.weight": (h, m),
                       "mlp.up_proj.weight": (h, m),
                       "mlp.down_proj.weight": (m, h)})
        return shapes
    m, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    ms = m * cfg["n_shared_experts"]
    shapes.update({
        "mlp.gate.weight": (h, cfg["router_experts"]),
        "mlp.w_gate": (held, h, m),
        "mlp.w_up": (held, h, m),
        "mlp.w_down": (held, m, h),
        "mlp.shared_experts.gate_proj.weight": (h, ms),
        "mlp.shared_experts.up_proj.weight": (h, ms),
        "mlp.shared_experts.down_proj.weight": (ms, h),
    })
    return shapes


# -- rotary embedding ---------------------------------------------------------
def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg: dict) -> np.ndarray:
    """YaRN: ``f_i = theta^(-2i/d)``; ``low, high`` from the rotations
    ``beta_fast`` and ``beta_slow`` over the original context; ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``; ``(f_i / factor) ramp_i + f_i
    (1 - ramp_i)``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return f.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def correction(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ((f / rs["factor"]) * ramp + f * (1 - ramp)).astype(np.float32)


def rope(t, pos, cfg):
    """``t [S, d]``, pairs ``(2i, 2i + 1)``: de-interleave, rotate halves.
    cos and sin carry ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` (1 for this model: both are 0.707)."""
    d = t.shape[-1]
    rs = cfg.get("rope_scaling") or {}
    m = (_yarn_mscale(rs.get("factor", 1), rs.get("mscale", 1))
         / _yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0)))
    f = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(cfg))[None]
    emb = jnp.concatenate([f, f], axis=-1)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    t = jnp.concatenate([t[:, 0::2], t[:, 1::2]], axis=-1)
    rot = jnp.concatenate([-t[:, d // 2:], t[:, :d // 2]], axis=-1)
    return t * cos + rot * sin


def softmax_scale(cfg: dict) -> float:
    rs = cfg.get("rope_scaling") or {}
    m = _yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


# -- attention ----------------------------------------------------------------
def _side_by_side(x, order):
    """The bfloat16 pieces of float32 ``x [n, d]`` (``mistral._pieces``:
    hi, mid, lo) side by side along the last axis, in ``order``."""
    pieces = _m._pieces(x)
    return jnp.concatenate([pieces[i] for i in order], axis=-1)


def scores32(q6, k6):
    """``q @ k.T`` in float32 as ``bmm32`` computes it (the six products
    of bfloat16 pieces whose orders sum to at most 2, accumulated in
    float32), as ONE matmul: the pieces lie side by side along the
    contraction (``q6`` = hi hi hi mid mid lo, ``k6`` = hi mid lo hi mid
    hi).  One pass over the ``[rows, keys]`` result where six partial
    results and five sums were 197 s of reference for three 24k-28k
    samples (my chip run, PR 27)."""
    return jnp.matmul(q6, k6.T, preferred_element_type=jnp.float32)


def pv32(p, v3):
    """``p @ v`` likewise: each piece of ``p`` is read once, against the
    pieces of ``v`` it pairs with side by side along the OUTPUT axis
    (``v3`` = hi | mid | lo); smallest terms summed first."""
    d = v3.shape[-1] // 3
    hi, mid, lo = _m._pieces(p)
    a = jnp.matmul(hi, v3, preferred_element_type=jnp.float32)
    b = jnp.matmul(mid, v3[:, :2 * d], preferred_element_type=jnp.float32)
    c = jnp.matmul(lo, v3[:, :d], preferred_element_type=jnp.float32)
    return ((c + b[:, d:] + a[:, 2 * d:]) + (b[:, :d] + a[:, d:2 * d])
            + a[:, :d])


def attention(x, w, cfg, prec):
    s, h = x.shape
    heads = cfg["num_attention_heads"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    eps, scale = cfg["rms_norm_eps"], softmax_scale(cfg)
    pos = jnp.arange(s)
    c_q = rmsnorm(mm(x, w["self_attn.q_a_proj.weight"], prec),
                  w["self_attn.q_a_layernorm.weight"], eps)
    ckr = mm(x, w["self_attn.kv_a_proj_with_mqa.weight"], prec)
    c_kv = rmsnorm(ckr[:, :kvl], w["self_attn.kv_a_layernorm.weight"], eps)
    k_r = rope(ckr[:, kvl:], pos, cfg)
    by_head = (
        jnp.moveaxis(w["self_attn.q_b_proj.weight"].reshape(ql, heads, -1),
                     1, 0),
        jnp.moveaxis(w["self_attn.kv_b_proj.weight"].reshape(
            kvl, heads, nope + v), 1, 0),
        w["self_attn.o_proj.weight"].reshape(heads, v, h))

    def one_head(acc, ws):
        wq, wkv, wo = ws
        q = mm(c_q, wq, prec)
        q = jnp.concatenate([q[:, :nope], rope(q[:, nope:], pos, cfg)], -1)
        kv = mm(c_kv, wkv, prec)
        q6 = _side_by_side(q, (0, 0, 0, 1, 1, 2))
        k6 = _side_by_side(jnp.concatenate([kv[:, :nope], k_r], -1),
                           (0, 1, 2, 0, 1, 0))
        v3 = _side_by_side(kv[:, nope:], (0, 1, 2))
        outs = []
        for lo in range(0, s, Q_BLOCK):     # keys up to the block's end
            hi = min(lo + Q_BLOCK, s)
            sc = scores32(q6[lo:hi], k6[:hi]) * scale
            sc = jnp.where(pos[None, :hi] <= pos[lo:hi, None], sc, -jnp.inf)
            outs.append(pv32(jax.nn.softmax(sc, axis=-1), v3[:hi]))
        return acc + mm(jnp.concatenate(outs, 0), wo, prec), None

    out, _ = jax.lax.scan(one_head, jnp.zeros((s, h), jnp.float32), by_head)
    return out


# -- feed-forward -------------------------------------------------------------
def _swiglu(x, wg, wu, wd, prec):
    return mm(jax.nn.silu(mm(x, wg, prec)) * mm(x, wu, prec), wd, prec)


def moe(x, w, cfg, prec):
    """The held experts' part plus the shared experts', and per token
    ``(margin, the experts chosen, sorted)`` (the module's docstring)."""
    s, h = x.shape
    e_all, held = cfg["router_experts"], cfg["n_routed_experts"]
    first, k = cfg["first_held_expert"], cfg["num_experts_per_tok"]
    groups, kg = cfg["n_group"], cfg["topk_group"]
    per = e_all // groups
    # the router stays in float32 whatever the int8 control does to the
    # experts; the bfloat16 witness rounds what goes into it too
    router = mm(x, w["mlp.gate.weight"], prec if prec == "bf16" else None)
    best = jnp.max(router.reshape(s, groups, per), axis=-1)
    ranked_g = jax.lax.top_k(best, kg + 1)[0]
    margin_g = ranked_g[:, kg - 1] - ranked_g[:, kg]
    kept = jnp.repeat(best >= ranked_g[:, kg - 1:kg], per, axis=1)
    cand = jnp.where(kept, router, -jnp.inf)
    ranked, idx = jax.lax.top_k(cand, k + 1)
    chosen = idx[:, :k]
    is_chosen = jnp.any(chosen[:, :, None] == jnp.arange(e_all), axis=1)
    is_held = (jnp.arange(e_all) >= first) & (jnp.arange(e_all)
                                              < first + held)
    low_chosen_held = jnp.min(
        jnp.where(is_chosen & is_held, cand, jnp.inf), axis=1)
    top_unchosen_held = jnp.max(
        jnp.where(~is_chosen & is_held, cand, -jnp.inf), axis=1)
    margin_e = jnp.minimum(low_chosen_held - ranked[:, k],
                           ranked[:, k - 1] - top_unchosen_held)
    margin = jnp.minimum(margin_g, margin_e)

    probs = jax.nn.softmax(router, axis=-1)
    top_w = jnp.take_along_axis(probs, chosen, axis=1)
    if cfg.get("norm_topk_prob"):
        raise ValueError("deepseek_v2 reference: norm_topk_prob is true; "
                         "only the published false is written down here")
    top_w = top_w * cfg["routed_scaling_factor"]
    # [S, held]: the weight of held expert e for each token, 0 elsewhere
    weight = jnp.sum(jax.nn.one_hot(chosen - first, held, dtype=x.dtype)
                     * top_w[..., None], axis=1)
    cap = s if s <= 2048 else s // 2

    def one_expert(acc, args):
        wg, wu, wd, wt = args
        rows = jnp.nonzero(wt > 0, size=cap, fill_value=0)[0]
        real = jnp.arange(cap) < jnp.sum(wt > 0)
        y = _swiglu(x[rows], wg, wu, wd, prec) \
            * jnp.where(real, wt[rows], 0.0)[:, None]
        return acc.at[rows].add(y), jnp.sum(wt > 0)

    out, load = jax.lax.scan(
        one_expert, jnp.zeros((s, h), jnp.float32),
        (w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"], weight.T))
    margin = jnp.where(jnp.max(load) > cap, -1.0, margin)
    out = out + _swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                        w["mlp.shared_experts.up_proj.weight"],
                        w["mlp.shared_experts.down_proj.weight"], prec)
    return out, (margin, jnp.sort(chosen, axis=-1))


def layer(x, w, cfg, prec=None, kind="sparse"):
    """One decoder layer over one sequence: x [S, h] float32."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(rmsnorm(x, w["input_layernorm.weight"], eps), w,
                      cfg, prec)
    y, route = (_m.ffn if kind == "dense" else moe)(
        rmsnorm(h, w["post_attention_layernorm.weight"], eps), w, cfg, prec)
    return h + y, route
