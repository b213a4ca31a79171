"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464),
in the form FLA's ``GatedDeltaNet`` and Qwen3-Next use, and its causal
convolution, over one sequence and over the serving step's ragged pack.

Per head, with a state ``S`` of ``[d_v, d_k]`` (``S_0 = 0``)::

    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

``q`` and ``k`` arrive L2-normed (``q`` also scaled by ``d_k^-1/2``),
``beta`` and ``g`` one value a head a token.  Everything here is float32
and jax.numpy: the sequential rule is what the Pallas launch
(``ops/pallas_kernels.ragged_gated_delta``, chunked) is held to, and what
the serving step runs on the CPU or with ``use_pallas=False``.

The ragged forms take the mixed step's span tables (``q_offsets``,
``q_lens``, ``kv_lens`` ``[S]``, spans in pack order, padding spans of
length 0 pinned past the last token) and ``slots [S]``: the row of the
state bank each span reads and writes.  A span whose first position is 0
(``kv_len == q_len``) starts from zero state and a zero convolution
tail, whatever its slot holds: a slot is fresh for a new request with no
host-side reset.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def l2norm(x, eps: float = L2_EPS):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gates(b, a, a_log, dt_bias, allow_neg_eigval: bool):
    """``beta = sigmoid(b)`` (doubled where negative eigenvalues are
    allowed) and the log decay ``g = -exp(A_log) softplus(a +
    dt_bias)``, float32, from the projections ``b``, ``a`` ``[.., H]``."""
    b = b.astype(jnp.float32)
    beta = jax.nn.sigmoid(b) * (2.0 if allow_neg_eigval else 1.0)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return beta, g


def delta_step(S, q, k, v, beta, g):
    """One token, every head: ``S [H, dv, dk]``, ``q``, ``k [H, dk]``,
    ``v [H, dv]``, ``beta``, ``g [H]`` -> ``(S_t, o_t [H, dv])``.
    Products as multiplies and sums, so float32 on every backend."""
    S = jnp.exp(g)[:, None, None] * S
    sk = jnp.sum(S * k[:, None, :], axis=-1)
    S = S + (beta[:, None] * (v - sk))[:, :, None] * k[:, None, :]
    return S, jnp.sum(S * q[:, None, :], axis=-1)


def gated_delta_scan(q, k, v, beta, g, S0):
    """The rule over one sequence, token by token: ``q``, ``k [L, H,
    dk]``, ``v [L, H, dv]``, ``beta``, ``g [L, H]``, ``S0 [H, dv, dk]``
    -> ``(o [L, H, dv], S_L)``."""
    def body(S, xs):
        S, o = delta_step(S, *xs)
        return S, o
    S, o = jax.lax.scan(body, S0.astype(jnp.float32),
                        (q, k, v, beta, g))
    return o, S


def causal_conv(x, w, tail=None):
    """Depthwise causal convolution over one sequence: ``x [L, C]``,
    ``w [C, K]`` (``w[:, K-1]`` multiplies the token itself), ``tail
    [K-1, C]`` the inputs before ``x`` (zeros where None).  float32."""
    K = w.shape[1]
    if tail is None:
        tail = jnp.zeros((K - 1, x.shape[1]), x.dtype)
    ext = jnp.concatenate([tail, x], axis=0).astype(jnp.float32)
    L = x.shape[0]
    w = w.astype(jnp.float32)
    return sum(ext[j:j + L] * w[:, j] for j in range(K))


def _span_of_rows(T: int, q_offsets, q_lens):
    """``(span [T], real [T])``: the span that owns each row of the pack
    (0 where none does) and whether one does."""
    tok = jnp.arange(T, dtype=jnp.int32)[:, None]
    off = q_offsets.astype(jnp.int32)[None, :]
    owned = (tok >= off) & (tok < off + q_lens.astype(jnp.int32)[None, :])
    return (jnp.argmax(owned, axis=1).astype(jnp.int32),
            jnp.any(owned, axis=1))


def fresh_spans(q_lens, kv_lens):
    """bool ``[S]``: spans whose first position is 0."""
    return (kv_lens - q_lens) == 0


def ragged_causal_conv(x, w, tails, slots, q_offsets, q_lens, kv_lens):
    """The convolution over each span of the pack: ``x [T, C]``, ``w [C,
    K]``, ``tails [slots, K-1, C]`` the bank of each slot's last K-1
    inputs.  A span's first K-1 rows reach into its slot's tail (zeros
    for a fresh span) or into earlier rows of the SAME span, never into
    another span's.  Returns ``(y [T, C] float32, tails)`` with every
    span's last K-1 inputs written back to its slot (a span shorter than
    K-1 keeps the older ones it still needs)."""
    T, C = x.shape
    K = w.shape[1]
    S = slots.shape[0]
    span, _ = _span_of_rows(T, q_offsets, q_lens)
    fresh = fresh_spans(q_lens, kv_lens)
    tail = jnp.where(fresh[:, None, None], 0,
                     tails[slots]).astype(x.dtype)            # [S, K-1, C]
    ext = jnp.concatenate([tail.reshape(S * (K - 1), C), x], axis=0)
    base = S * (K - 1)
    tok = jnp.arange(T, dtype=jnp.int32)
    p = tok - q_offsets.astype(jnp.int32)[span]     # row within its span
    w = w.astype(jnp.float32)
    y = x.astype(jnp.float32) * w[:, K - 1]
    for d in range(1, K):
        idx = p - d
        row = jnp.where(idx >= 0, base + tok - d,
                        span * (K - 1) + (K - 1) + idx)
        row = jnp.clip(row, 0, base + T - 1)
        y = y + ext[row].astype(jnp.float32) * w[:, K - 1 - d]
    j = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    idx = q_lens.astype(jnp.int32)[:, None] - (K - 1) + j      # [S, K-1]
    row = jnp.where(idx >= 0,
                    base + q_offsets.astype(jnp.int32)[:, None] + idx,
                    jnp.arange(S, dtype=jnp.int32)[:, None] * (K - 1)
                    + (K - 1) + idx)
    new = ext[jnp.clip(row, 0, base + T - 1)]
    return y, tails.at[slots].set(new.astype(tails.dtype))


def ragged_gated_delta_xla(q, k, v, beta, g, state, slots, q_offsets,
                           q_lens, kv_lens):
    """The rule over each span of the pack, token by token: ``q``, ``k
    [T, H, dk]``, ``v [T, H, dv]``, ``beta``, ``g [T, H]``, ``state
    [slots, H, dv, dk]`` float32.  Returns ``(o [T, H, dv], state)``: the
    rows no span owns read zeros, every span's state is written back to
    its slot."""
    T = q.shape[0]
    span, real = _span_of_rows(T, q_offsets, q_lens)
    fresh = fresh_spans(q_lens, kv_lens)
    S0 = jnp.where(fresh[:, None, None, None], 0.0, state[slots])

    def body(carry, xs):
        sp, r, qt, kt, vt, bt, gt = xs
        St, o = delta_step(carry[sp], qt, kt, vt, bt, gt)
        carry = carry.at[sp].set(jnp.where(r, St, carry[sp]))
        return carry, jnp.where(r, o, 0.0)

    carry, o = jax.lax.scan(
        body, S0.astype(jnp.float32),
        (span, real, q.astype(jnp.float32), k.astype(jnp.float32),
         v.astype(jnp.float32), beta.astype(jnp.float32),
         g.astype(jnp.float32)))
    return o, state.at[slots].set(carry)


def ragged_gated_delta(q, k, v, beta, g, state, slots, q_offsets, q_lens,
                       kv_lens, use_pallas: bool = False):
    """The serving step's launch: the Pallas kernel
    (``ops/pallas_kernels.ragged_gated_delta``, named
    ``ragged_gated_delta``) where ``use_pallas``, the sequential rule
    above anywhere else."""
    if use_pallas:
        from .pallas_kernels import ragged_gated_delta as kernel
        return kernel(q, k, v, beta, g, state, slots, q_offsets, q_lens,
                      kv_lens)
    return ragged_gated_delta_xla(q, k, v, beta, g, state, slots,
                                  q_offsets, q_lens, kv_lens)
