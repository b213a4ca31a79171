"""Plain reference: Olmo-Hybrid decoder (allenai ``Olmo-Hybrid-7B``).

jax.numpy, float32 through ``mistral.py``'s ``bmm32`` / ``mm``, forward
only, no kernels, no cache, no batching.  Imports nothing of the system
under test.  Every block is post-norm, as Olmo 2 and 3 are::

    h = x + RMSNorm_post_attn(Mixer(x)),  y = h + RMSNorm_post_ff(MLP(h))

with a SwiGLU MLP, and ``layer_types`` picks the mixer:

- **full_attention**: ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x W_k)``,
  each norm over the whole projection, ``v = x W_v``; causal softmax at
  ``1/sqrt(head_dim)`` in float32, ``W_o``.  NO rotary embedding: the
  published ``rope_parameters.rope_theta`` is null, read as NoPE.
- **linear_attention** (Gated DeltaNet, FLA's ``GatedDeltaNet`` form):
  ``q | k | v = silu(conv(x W_q | x W_k | x W_v))``, one causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps over the
  concatenated channels (zeros before the first token); per head ``q <-
  q / sqrt(|q|^2 + 1e-6) * d_k^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``;
  ``beta = sigmoid(x W_b)``, doubled by ``linear_allow_neg_eigval``; ``g
  = -exp(A_log) softplus(x W_a + dt_bias)``; from ``S_0 = 0``, TOKEN BY
  TOKEN (a ``lax.scan``, never chunked: nothing of the program's chunked
  form is reused)::

      S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
      o_t = S_t q_t

  every product a float32 multiply and sum; ``out = W_o
  concat_heads(RMSNorm_dv(o_t) * silu(x W_z))``, one gain for all heads.

Departures from the published model, each also under the configuration's
``assumed``: the NoPE reading of the null ``rope_theta``; the FLA
conventions for the convolution (one over q|k|v, no bias), the L2 norm's
epsilon (1e-6), the gated norm (``rms_norm_eps``) and the state in
float32; the weights are seeded, not published (``harness/weights.py``,
with ``leaf_draws`` below for the decay, the time step and the
convolution).

Leaf names are the program's own parameter names inside a layer,
weights stored ``[in, out]``; the convolution is ``[channels, taps]``,
its last tap multiplying the token itself.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_mistral",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mistral.py"))
_m = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_m)

INNER = "olmo_hybrid"
L2_EPS = 1e-6
top_shapes, embed, logits, mm, rmsnorm, bmm32 = (
    _m.top_shapes, _m.embed, _m.logits, _m.mm, _m.rmsnorm, _m.bmm32)
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def layer_kinds(cfg: dict) -> list:
    depth = int(cfg["num_hidden_layers"])
    return [KINDS[t] for t in cfg["layer_types"][:depth]]


def _linear_dims(cfg: dict):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"])


def layer_shapes(cfg: dict, kind: str) -> dict:
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {
        "mlp.gate_proj.weight": (h, m),
        "mlp.up_proj.weight": (h, m),
        "mlp.down_proj.weight": (m, h),
        "post_attention_layernorm.weight": (h,),
        "post_feedforward_layernorm.weight": (h,),
    }
    if kind == "full":
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = h // nq
        shapes.update({
            "self_attn.q_proj.weight": (h, nq * d),
            "self_attn.k_proj.weight": (h, nkv * d),
            "self_attn.v_proj.weight": (h, nkv * d),
            "self_attn.o_proj.weight": (nq * d, h),
            "self_attn.q_norm.weight": (nq * d,),
            "self_attn.k_norm.weight": (nkv * d,),
        })
        return shapes
    H, dk, dv, K = _linear_dims(cfg)
    shapes.update({
        "self_attn.q_proj.weight": (h, H * dk),
        "self_attn.k_proj.weight": (h, H * dk),
        "self_attn.v_proj.weight": (h, H * dv),
        "self_attn.g_proj.weight": (h, H * dv),
        "self_attn.b_proj.weight": (h, H),
        "self_attn.a_proj.weight": (h, H),
        "self_attn.o_proj.weight": (H * dv, h),
        "self_attn.conv_weight": (H * (2 * dk + dv), K),
        "self_attn.A_log": (H,),
        "self_attn.dt_bias": (H,),
        "self_attn.o_norm.weight": (dv,),
    })
    return shapes


def leaf_draws(cfg: dict, kind: str) -> dict:
    """FLA's ``GatedDeltaNet`` initialisation of the leaves that are not
    matrices or gains: ``A`` uniform on (0, 16) stored as its log, the
    time step log-uniform on (0.001, 0.1) stored as softplus's inverse,
    the convolution PyTorch's ``Conv1d`` default, uniform on +-1/sqrt(taps)."""
    if kind != "linear":
        return {}
    bound = float(cfg["linear_conv_kernel_dim"]) ** -0.5
    return {"self_attn.A_log": ("uniform", 0.0, 16.0, "log"),
            "self_attn.dt_bias": ("log_uniform", 0.001, 0.1, "softplus_inv"),
            "self_attn.conv_weight": ("uniform", -bound, bound)}


def full_attention(x, w, cfg, prec):
    """Causal attention over one sequence x [S, h], q/k normed, no rope."""
    s, h = x.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, g = h // nq, nq // nkv
    eps = cfg["rms_norm_eps"]
    q = rmsnorm(mm(x, w["self_attn.q_proj.weight"], prec),
                w["self_attn.q_norm.weight"], eps).reshape(s, nq, d)
    k = rmsnorm(mm(x, w["self_attn.k_proj.weight"], prec),
                w["self_attn.k_norm.weight"], eps).reshape(s, nkv, d)
    v = mm(x, w["self_attn.v_proj.weight"], prec).reshape(s, nkv, d)
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]

    def one_group(args):            # one kv head and its g query heads
        qg, kg, vg = args
        sc = bmm32(jnp.moveaxis(qg, 1, 0), kg.T)              # [g, S, S]
        sc = jnp.where(mask[None], sc / np.sqrt(d), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.moveaxis(bmm32(p, vg), 0, 1)               # [S, g, D]

    qg = jnp.moveaxis(q.reshape(s, nkv, g, d), 1, 0)
    out = jax.lax.map(one_group, (qg, jnp.moveaxis(k, 1, 0),
                                  jnp.moveaxis(v, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(s, nq * d)
    return mm(out, w["self_attn.o_proj.weight"], prec)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gated_delta(x, w, cfg, prec):
    """Gated DeltaNet over one sequence x [S, h] (the module docstring)."""
    s = x.shape[0]
    H, dk, dv, K = _linear_dims(cfg)
    a = "self_attn."
    pre = jnp.concatenate([mm(x, w[a + "q_proj.weight"], prec),
                           mm(x, w[a + "k_proj.weight"], prec),
                           mm(x, w[a + "v_proj.weight"], prec)], axis=-1)
    cw = w[a + "conv_weight"].astype(jnp.float32)               # [C, K]
    ext = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), pre.dtype), pre],
                          axis=0)
    conv = sum(ext[j:j + s] * cw[:, j] for j in range(K))
    y = conv * jax.nn.sigmoid(conv)
    q = _l2(y[:, :H * dk].reshape(s, H, dk)) * dk ** -0.5
    k = _l2(y[:, H * dk:2 * H * dk].reshape(s, H, dk))
    v = y[:, 2 * H * dk:].reshape(s, H, dv)
    beta = jax.nn.sigmoid(mm(x, w[a + "b_proj.weight"], prec))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w[a + "A_log"].astype(jnp.float32)) * jax.nn.softplus(
        mm(x, w[a + "a_proj.weight"], prec)
        + w[a + "dt_bias"].astype(jnp.float32))

    def token(S, xs):                 # S [H, dv, dk]
        qt, kt, vt, bt, gt = xs
        S = jnp.exp(gt)[:, None, None] * S
        S = S - bt[:, None, None] * jnp.sum(
            S * kt[:, None, :], axis=-1)[:, :, None] * kt[:, None, :]
        S = S + bt[:, None, None] * vt[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], axis=-1)

    _, o = jax.lax.scan(token, jnp.zeros((H, dv, dk), jnp.float32),
                        (q, k, v, beta, g))                   # [S, H, dv]
    o = rmsnorm(o, w[a + "o_norm.weight"], cfg["rms_norm_eps"])
    z = mm(x, w[a + "g_proj.weight"], prec)
    o = o.reshape(s, H * dv) * (z * jax.nn.sigmoid(z))
    return mm(o, w[a + "o_proj.weight"], prec)


def layer(x, w, cfg, prec=None, kind="linear"):
    """One decoder layer over one sequence: x [S, h] float32.  Returns the
    output and ``None`` (no layer routes)."""
    eps = cfg["rms_norm_eps"]
    mixer = gated_delta if kind == "linear" else full_attention
    h = x + rmsnorm(mixer(x, w, cfg, prec),
                    w["post_attention_layernorm.weight"], eps)
    y, _ = _m.ffn(h, w, cfg, prec)
    return h + rmsnorm(y, w["post_feedforward_layernorm.weight"], eps), None
