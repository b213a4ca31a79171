"""Plain reference: Mixtral-8x7B decoder (HF ``MixtralForCausalLM``).

As ``mistral.py`` with the MLP replaced by the sparse block: a linear
router over all experts, softmax in float32, top-2, the two weights
renormalised to sum to 1, no token ever dropped.  Every expert is computed
on every token and masked: plain, and eight times the work, which a
reference may afford.  Imports nothing of the system under test.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference_mistral",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mistral.py"))
_m = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_m)

INNER = "mixtral"
top_shapes, embed, logits, mm, rmsnorm = (
    _m.top_shapes, _m.embed, _m.logits, _m.mm, _m.rmsnorm)


def layer_shapes(cfg: dict) -> dict:
    h, m, e = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_local_experts"])
    shapes = {k: v for k, v in _m.layer_shapes(cfg).items()
              if not k.startswith("mlp.")}
    shapes.update({
        "block_sparse_moe.gate.weight": (h, e),
        "block_sparse_moe.w_gate": (e, h, m),
        "block_sparse_moe.w_up": (e, h, m),
        "block_sparse_moe.w_down": (e, m, h),
    })
    return shapes


def moe(x, w, cfg, prec):
    """Returns the block's output and, per token, what the router decided:
    its margin (by how much the last expert chosen leads the first one left
    out, in logits) and the experts chosen.  Where the margin is within
    rounding, which experts a token gets is decided by rounding, and a
    program that rounds otherwise serves another token."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    # the router stays in float32 whatever the int8 control does to the
    # experts; the bfloat16 witness rounds what goes into it too
    router = mm(x, w["block_sparse_moe.gate.weight"],
                prec if prec == "bf16" else None)
    ranked = jax.lax.top_k(router, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(router, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # [S, E]: the weight of expert e for each token, 0 where not chosen
    weight = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype)
                     * top_w[..., None], axis=1)

    def one_expert(args):           # one at a time: the float32 copies
        wg, wu, wd, wt = args       # of all eight would not fit the chip
        g, u = mm(x, wg, prec), mm(x, wu, prec)
        return mm(jax.nn.silu(g) * u, wd, prec) * wt[:, None]

    out = jnp.sum(jax.lax.map(one_expert, (
        w["block_sparse_moe.w_gate"], w["block_sparse_moe.w_up"],
        w["block_sparse_moe.w_down"], weight.T)), axis=0)
    return out, (margin, jnp.sort(top_i, axis=-1))


def layer(x, w, cfg, prec=None):
    return _m.layer(x, w, cfg, prec, ffn_fn=moe)
