"""Online-softmax ``(m, l, o)`` carry math — the ONE implementation.

The associative flash-attention update, shared by every site that
carries it:

- :func:`online_softmax_update` — one accumulation step over a tile of
  masked scores: the page loop of ``_paged_decode_kernel``
  (ops/paged_attention.py, the eager ``paged_attention`` API) and the
  key-block loops of the serving step's two Pallas launches,
  ``_ragged_paged_kernel`` and the latent-attention kernel
  (ops/pallas_kernels.py).  Byte-parity-tested against the inlined
  expression sequence in tests/test_serving_cp.py;
- :func:`merge_partials` — the SAME math lifted to merging already
  normalized per-stripe partials ``(m, l, o)``: because the update is
  associative, N stripes computed independently merge into the exact
  full-softmax result (up to float summation order);
- :func:`cross_chip_merge` — merge_partials across a mesh axis via one
  ``all_gather`` of the three small per-token rows: the mixed step's
  context-parallel attention (``_ragged_attention_xla_partial`` per
  slot stripe, then this).  Measured smaller than a log-step ring for
  the per-span row sizes serving ships: both move ``(cp-1)/cp`` of the
  rows per chip, the single gather in one collective launch.

Everything is fp32-in/fp32-out with np.float32 constants so the
globally-on x64 mode never stages an f64 op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["online_softmax_update", "merge_partials", "cross_chip_merge"]


def online_softmax_update(carry, s, ok, pv_of_p):
    """One online-softmax accumulation step over a masked score tile.

    carry: ``(m [g,1], l [g,1], acc [g,d])`` fp32 running state
    (initialize ``m=-inf, l=0, acc=0``).  s: ``[g, t]`` fp32 scores with
    masked lanes already set to ``-inf``; ok: the ``[g, t]`` bool mask
    (re-applied after the exp so an all-masked row's ``exp(-inf - -inf)
    = nan`` never reaches the accumulators), or ``None`` where every
    lane is visible (a Python-level branch: no select is traced).
    pv_of_p: callback
    computing the ``[g, d]`` ``p @ V`` product from the ``[g, t]``
    probability tile — site-specific (fp32 matmul, int8 MXU with folded
    scales, ...).  Returns the new ``(m, l, acc)``.
    """
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if ok is not None:
        p = jnp.where(ok, p, np.float32(0.0))
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + pv_of_p(p)
    return m_new, l_new, acc_new


def merge_partials(m, l, o, axis=0):
    """Merge normalized online-softmax partials along ``axis``.

    m/l: ``[..., N, ...]`` fp32 per-partial row max and normalizer;
    o: the same shape plus a trailing feature dim, already normalized
    by its OWN ``l`` (``o_i = acc_i / max(l_i, 1e-30)``).  An empty
    partial contributes ``m=-inf, l=0`` and drops out exactly
    (``w_i = l_i·exp(m_i - m*) = 0``); the ``isfinite`` guard keeps the
    all-empty row at 0 instead of ``exp(-inf - -inf) = nan``.  Since
    ``w_i·o_i = exp(m_i - m*)·acc_i`` whenever ``l_i > 0``, the merge
    reproduces the single-pass softmax up to float summation order.
    """
    m_star = jnp.max(m, axis=axis, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m_star), m_star, np.float32(0.0))
    w = l * jnp.exp(m - m_safe)
    denom = jnp.sum(w, axis=axis)
    num = jnp.sum(w[..., None] * o, axis=axis)
    return num / jnp.maximum(denom, np.float32(1e-30))[..., None]


def cross_chip_merge(o, m, l, axis_name):
    """Merge per-chip stripe partials across mesh axis ``axis_name``
    (inside a shard_map body): ONE ``all_gather`` of the three
    per-token rows, then :func:`merge_partials` over the gathered chip
    dim.  o: ``[T, H, D]``; m/l: ``[T, H]``; returns ``[T, H, D]``
    replicated across the axis (every member computes the identical
    merge of the identical gathered rows).
    """
    og = jax.lax.all_gather(o, axis_name)          # [cp, T, H, D]
    mg = jax.lax.all_gather(m, axis_name)          # [cp, T, H]
    lg = jax.lax.all_gather(l, axis_name)
    return merge_partials(mg, lg, og, axis=0)
