"""Plain reference: Mistral-7B (v0.3: no sliding window) decoder.

jax.numpy, float32 (every matmul through ``bmm32``: float32 at the TPU's
``highest``, spelled out), forward only, no kernels, no cache, no batching.  Follows the
published architecture (HF ``MistralForCausalLM``):
pre-norm residual blocks, RMSNorm, grouped-query attention with rotate-half
RoPE, SwiGLU MLP, untied head.  Imports nothing of the system under test.

Leaf names are the system's own parameter names inside a layer, so that
``harness/program.py`` can fill its model from the same groups; weights
are stored ``[in, out]`` (``y = x @ W``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INNER = "llama"           # attribute of the CausalLM that holds the stack


def layer_shapes(cfg: dict) -> dict:
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "self_attn.q_proj.weight": (h, nq * d),
        "self_attn.k_proj.weight": (h, nkv * d),
        "self_attn.v_proj.weight": (h, nkv * d),
        "self_attn.o_proj.weight": (nq * d, h),
        "mlp.gate_proj.weight": (h, m),
        "mlp.up_proj.weight": (h, m),
        "mlp.down_proj.weight": (m, h),
        "input_layernorm.weight": (h,),
        "post_attention_layernorm.weight": (h,),
    }


def top_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens.weight": (v, h), "norm.weight": (h,),
            "lm_head.weight": (h, v)}


# -- arithmetic -------------------------------------------------------------
def fake_quant(x, axis):
    """Symmetric int8 along ``axis`` (absmax / 127), dequantized: the same
    values an int8 matmul with these scales would see."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _pieces(x):
    """A float32 array as three bfloat16 pieces that sum to it (to 2^-24);
    an array that is bfloat16 already is its own single piece."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    hi = x.astype(jnp.bfloat16)
    r1 = x - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def bmm32(a, b):
    """``[..., m, k] @ [..., k, n]`` in float32 as the TPU computes it at
    ``highest``: products of bfloat16 pieces accumulated in float32 (six
    passes for two float32 operands, three where one is bfloat16 already),
    smallest terms first.  Spelled out because XLA's own ``highest`` ran at
    0.7 TFLOP/s here and copied every weight to float32 first (my chip
    run, PR 23)."""
    pa, pb = _pieces(a), _pieces(b)
    terms = [(i + j, x, y) for i, x in enumerate(pa)
             for j, y in enumerate(pb) if i + j <= 2]
    out = None
    for _, x, y in sorted(terms, key=lambda t: -t[0]):
        part = jnp.matmul(x, y, preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def mm(x, w, prec=None):
    """``x @ w`` in float32.  ``prec`` computes it in a lower precision
    instead (``tools/control.py``; never a benchmark run): ``"int8"``, the
    control: both operands through int8 first (activations per row, weights
    per output column); ``"bf16"``, the precision the configurations state,
    as a witness: the activations rounded to bfloat16 going in and coming
    out, one pass."""
    if prec == "int8":
        x = fake_quant(x, -1)
        w = fake_quant(w.astype(jnp.float32), 0)
    elif prec == "bf16":
        x = x.astype(jnp.bfloat16)
        return bmm32(x, w).astype(jnp.bfloat16).astype(jnp.float32)
    return bmm32(x, w)


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(t, pos, theta):
    """t [S, H, D], rotate-half convention."""
    d = t.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = pos.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([f, f], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * cos + rot * sin


def attention(x, w, cfg, prec):
    """Causal GQA self-attention over one sequence x [S, h]."""
    s = x.shape[0]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    g = nq // nkv
    pos = jnp.arange(s)
    q = rope(mm(x, w["self_attn.q_proj.weight"], prec).reshape(s, nq, d),
             pos, cfg["rope_theta"])
    k = rope(mm(x, w["self_attn.k_proj.weight"], prec).reshape(s, nkv, d),
             pos, cfg["rope_theta"])
    v = mm(x, w["self_attn.v_proj.weight"], prec).reshape(s, nkv, d)
    mask = pos[None, :] <= pos[:, None]                       # [S, S]

    def one_group(args):            # one kv head and its g query heads
        qg, kg, vg = args           # [S, g, D], [S, D], [S, D]
        sc = bmm32(jnp.moveaxis(qg, 1, 0), kg.T)              # [g, S, S]
        sc = jnp.where(mask[None], sc / np.sqrt(d), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.moveaxis(bmm32(p, vg), 0, 1)               # [S, g, D]

    qg = jnp.moveaxis(q.reshape(s, nkv, g, d), 1, 0)          # [nkv,S,g,D]
    out = jax.lax.map(one_group, (qg, jnp.moveaxis(k, 1, 0),
                                  jnp.moveaxis(v, 1, 0)))     # [nkv,S,g,D]
    out = jnp.moveaxis(out, 0, 1).reshape(s, nq * d)
    return mm(out, w["self_attn.o_proj.weight"], prec)


def ffn(x, w, cfg, prec):
    """Returns the block's output and ``None`` (a dense block decides
    nothing that rounding could decide otherwise)."""
    gate = mm(x, w["mlp.gate_proj.weight"], prec)
    up = mm(x, w["mlp.up_proj.weight"], prec)
    return mm(jax.nn.silu(gate) * up, w["mlp.down_proj.weight"], prec), None


def layer(x, w, cfg, prec=None, ffn_fn=None):
    """One decoder layer over one sequence: x [S, h] float32.  Returns the
    output and what the feed-forward block decided per token (``None`` for
    a dense block; see ``mixtral.moe``)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(rmsnorm(x, w["input_layernorm.weight"], eps), w,
                      cfg, prec)
    y, route = (ffn_fn or ffn)(
        rmsnorm(h, w["post_attention_layernorm.weight"], eps), w, cfg, prec)
    return h + y, route


def embed(ids, top):
    return top["embed_tokens.weight"][ids].astype(jnp.float32)


def logits(x_rows, top, cfg, prec=None):
    """Head over the chosen rows only: [n, h] -> [n, V]."""
    return mm(rmsnorm(x_rows, top["norm.weight"], cfg["rms_norm_eps"]),
              top["lm_head.weight"], prec)
