"""Hand-written Pallas TPU kernels for the fused-op set.

Parity: the reference's fused CUDA kernel library
(paddle/phi/kernels/fusion/ — flash attention #18, fused_rms_norm #17).
These are the only hand-written kernels in the framework; everything else
is XLA.  Each kernel has an XLA reference the dispatchers select when the
process is NOT on a TPU (``core.device.on_tpu``) or the shape is outside
the kernel's envelope, so CPU tests exercise the same API.  The choice is
made once, from the platform and the shapes: a kernel chosen for the TPU
that fails to compile is an error, never a switch to the reference.

Design notes (see /opt/skills/guides/pallas_guide.md):
- flash attention: one (batch*heads, q_block) grid cell holds a q tile in
  VMEM and streams k/v tiles, keeping the running max/denominator in fp32
  (online softmax).  Causal masking skips fully-masked k tiles.
- rms_norm: row-tiled, stats in fp32.
- flash backward: FlashAttention-2 two-kernel scheme in Pallas (dq over q
  tiles, dk/dv over k tiles, p recomputed from the saved lse); masked or
  ragged configs fall back to the chunked XLA backward.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import device as _device
from ..core.dispatch import apply_op
from ..core.jax_compat import shard_map_compat
from ..core.tensor import Tensor
from ._helpers import targ
from .online_softmax import online_softmax_update


def _x64_off():
    """Context manager tracing with x64 disabled (mosaic cannot legalize
    the i64 scalars python-int arithmetic produces under
    jax_enable_x64)."""
    return jax.enable_x64(False)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
_DIMNUM_NT = (((1,), (1,)), ((), ()))    # x @ y.T
_DIMNUM_NN = (((1,), (0,)), ((), ()))    # x @ y
_DIMNUM_TN = (((0,), (0,)), ((), ()))    # x.T @ y
# np.float32 (not python float): a weak-typed scalar staged from inside
# an OUTER x64 trace (ring attention's shard_map/cond around interpret-
# mode pallas) lowers as tensor<f64> and fails MLIR verification
_MASK_VALUE = np.float32(-0.7 * float(np.finfo(np.float32).max))
_MASK_THRESH = np.float32(0.5) * _MASK_VALUE   # any real score is above this
_F32_0 = np.float32(0.0)
_F32_NEG_INF = np.float32(-np.inf)
_LANES = 128
# Scores are kept in exp2 space: scale*log2(e) is folded into the q (or k)
# tile ONCE per VMEM tile, so the inner loop runs exp2 directly — saving
# the per-[bq,bk]-block scale multiply AND the log2e multiply XLA would
# emit inside exp.  lse residuals stay in natural-log space at the API
# boundary (the *_LN2 conversion happens at store).
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


def _fit_block(want, total):
    """Largest usable block <= want that divides total.  Usable means the
    kernels' 128-lane VMEM softmax scratch can be adapted to it by _cols:
    either a multiple of 128 (tile) or <= 128 (slice).  A block >128 that
    is not a lane multiple (e.g. the whole axis when total=192) would
    crash at trace time, so it is never returned; sub-axis blocks must
    also be sublane-tileable (multiple of 16, covering f32 and bf16).
    Returns 0 when no divisor qualifies — dispatchers must pre-check
    shapes via _pallas_ok (which falls back to _chunked_sdpa); the
    kernel wrappers themselves raise on a 0 block."""
    b = min(want, total)
    if total % b == 0 and (b % _LANES == 0
                           or (b <= _LANES
                               and (b == total or b % 16 == 0))):
        return b
    for c in range((b // _LANES) * _LANES, 0, -_LANES):
        if total % c == 0:
            return c
    # sub-128 blocks smaller than the full axis must still be sublane
    # tileable: multiples of 16 cover both f32 (8,128) and bf16 (16,128)
    for c in range((min(b, _LANES) // 16) * 16, 0, -16):
        if total % c == 0:
            return c
    return 0


def _cols(x128, n):
    """Adapt a [rows, 128] lane-broadcast stat to n columns (n may be a
    sub-lane block size like 64, or a multiple of 128)."""
    if n < _LANES:
        return x128[:, :n]
    return jnp.tile(x128, (1, n // _LANES))


def _rope_tile(t_ref, cos_ref, sin_ref, neg_sin=False):
    """Neox-style rotary embedding applied to one [rows, d] tile in VMEM
    (the in-kernel fusion that replaces the XLA slice/negate/concat
    pattern — a 41 GiB/s HBM-bound fusion when done at graph level).
    neg_sin=True applies the inverse rotation (the rope VJP)."""
    t = t_ref if isinstance(t_ref, jnp.ndarray) else t_ref[...]
    tf = t.astype(jnp.float32)
    half = tf.shape[-1] // 2
    rot = jnp.concatenate([-tf[:, half:], tf[:, :half]], axis=1)
    c = cos_ref[...]
    sn = sin_ref[...]
    if neg_sin:
        return tf * c - rot * sn
    return tf * c + rot * sn


def _causal_run(qi, kb, block_q, block_k, causal_off):
    """True iff q tile ``qi`` has any visible column in k tile ``kb``
    (the q tile's last row reaches the k tile's first column).  Single
    source of truth shared by the kernels' skip predicate and the
    streamed-block index remaps below — they MUST agree or a skipped
    grid step would read a remapped (wrong) tile."""
    return (qi + 1) * block_q - 1 + causal_off >= kb * block_k


def _need_mask(qi, kb, block_q, block_k, causal_off):
    """True iff the (qi, kb) block contains any masked entry (its first
    row does not reach its last column); fully-visible blocks skip the
    iota/compare/select masking and the dead-row guard."""
    return qi * block_q + causal_off < kb * block_k + block_k - 1


def _causal_stream_kv(i, j, block_q, block_k, causal_off, causal):
    """Index remap for a streamed k/v grid axis under causal masking: a
    skipped (fully-masked) k block re-fetches block 0 — the block the
    NEXT q row starts with — so skipped grid steps cost no DMA and
    double as prefetch (the in-tree flash kernel's kv_index_map trick;
    without it the upper triangle streams ~60% extra k/v bytes through
    a stalled pipeline).  ``i`` is the resident q-tile index, ``j`` the
    streamed k-tile index."""
    if not causal:
        return j
    return jnp.where(_causal_run(i, j, block_q, block_k, causal_off),
                     j, 0)


def _causal_stream_q(i, j, block_q, block_k, causal_off, causal):
    """Index remap for a streamed q grid axis (k-tile-resident backward
    kernels): skipped ABOVE-diagonal q blocks re-fetch the first running
    q block of this k row.  ``i`` is the resident k-tile index, ``j``
    the streamed q-tile index."""
    if not causal:
        return j
    first = jnp.maximum(0, (i * block_k - causal_off) // block_q)
    return jnp.where(_causal_run(j, i, block_q, block_k, causal_off),
                     j, first)


def _flash_fwd_kernel(*refs, block_k: int, causal: bool, scale: float,
                      kv_blocks: int, causal_off: int = 0,
                      with_rope: bool = False):
    """Grid (BH, q_tile, k_tile): one k/v block per grid step, online
    softmax state in VMEM scratch across the (sequential) k dimension.

    The k axis as a grid dimension (not an in-kernel loop) lets Mosaic
    double-buffer the k/v HBM->VMEM DMAs against compute — the same
    pipelining structure as the in-tree pallas flash kernel.  Matmuls
    keep bf16 operands with f32 accumulation (preferred_element_type);
    an f32 upcast before the dot would quarter the MXU rate.  With
    with_rope, neox rotary embeddings are applied to the q/k tiles in
    VMEM (cos/sin tiles ride the grid like k/v)."""
    q_ref, k_ref, v_ref = refs[0:3]
    i = 3
    if with_rope:
        cos_i_ref, sin_i_ref, cos_j_ref, sin_j_ref = refs[3:7]
        i = 7
    o_ref = refs[i]
    rest = refs[i + 1:]
    qs_s = rest[-1]    # exp2-space q tile (scaled by scale*log2e; +rope)
    rest = rest[:-1]
    save_lse = len(rest) == 4
    if save_lse:
        lse_ref, m_s, l_s, acc_s = rest
    else:
        m_s, l_s, acc_s = rest
        lse_ref = None
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    bq, d = q_ref.shape[1], q_ref.shape[-1]
    c = scale * _LOG2E

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        # scale (and rope) q once per q tile — per-k-block rope dominated
        # the kernel, and a per-block scale multiply would cost a full
        # [bq, bk] VPU pass where this is [bq, d] once
        if with_rope:
            qs_s[...] = (_rope_tile(q_ref[0], cos_i_ref, sin_i_ref)
                         * c).astype(qs_s.dtype)
        else:
            qs_s[...] = (q_ref[0].astype(jnp.float32)
                         * c).astype(qs_s.dtype)

    run = True
    if causal:
        run = _causal_run(qi, kb, bq, block_k, causal_off)

    def _tile_body(mask: bool):
        q = qs_s[...]
        if with_rope:
            k = _rope_tile(k_ref[0], cos_j_ref, sin_j_ref).astype(
                k_ref.dtype)
        else:
            k = k_ref[0]                               # [bk, d]
        v = v_ref[0]
        # scores arrive pre-scaled into exp2 space via qs_s
        s = lax.dot_general(q, k, _DIMNUM_NT,
                            preferred_element_type=jnp.float32)
        if mask:
            rows = qi * bq + causal_off + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _MASK_VALUE)
        m_prev = m_s[...]                              # [bq, 128]
        l_prev = l_s[...]
        m_curr = jnp.max(s, axis=1)[:, None]           # [bq, 1]
        m_next = jnp.maximum(m_prev, m_curr)           # [bq, 128]
        p = jnp.exp2(s - _cols(m_next, block_k))
        if mask:
            # rows whose every score so far is masked must contribute
            # nothing (a finite mask value would otherwise give
            # p = exp2(0) = 1).  Dead rows can only exist in blocks with
            # masked entries, so the guard lives in the masked body only.
            p = jnp.where(_cols(m_next, block_k) > _MASK_THRESH, p, _F32_0)
        alpha = jnp.exp2(m_prev - m_next)              # [bq, 128]
        m_s[...] = m_next
        l_s[...] = jnp.sum(p, axis=1)[:, None] + alpha * l_prev
        # FA2 deferred normalization: accumulate unnormalized, divide by
        # l once at store — saves a reciprocal + [bq, d] multiply per block
        pv = lax.dot_general(p.astype(v.dtype), v, _DIMNUM_NN,
                             preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * _cols(alpha, d) + pv

    if causal:
        # skip the iota/compare/select masking entirely on fully-visible
        # blocks (the majority for block-aligned causal self-attention)
        need_mask = _need_mask(qi, kb, bq, block_k, causal_off)
        @pl.when(run & need_mask)
        def _body_masked():
            _tile_body(True)

        @pl.when(run & jnp.logical_not(need_mask))
        def _body_full():
            _tile_body(False)
    else:
        _tile_body(False)

    @pl.when(kb == kv_blocks - 1)
    def _store():
        l_v = l_s[...]
        l_inv = jnp.where(l_v > _F32_0, np.float32(1.0) / l_v, _F32_0)
        o_ref[0] = (acc_s[...] * _cols(l_inv, d)).astype(o_ref.dtype)
        if save_lse:
            # natural-log log-sum-exp residual for the backward (scores
            # live in exp2 space in-kernel: convert m back with ln2),
            # lane-broadcast to the mosaic-tileable 128-lane layout;
            # -inf marks rows that attended nothing
            lse = jnp.where(l_v > _F32_0,
                            m_s[...] * np.float32(_LN2) + jnp.log(l_v),
                            _F32_NEG_INF)
            lse_ref[0] = lse.astype(jnp.float32)


_INTERPRET = [False]  # set True in CPU tests to run kernels interpreted


def _flash_attention_value(q, k, v, causal: bool, block_q=512,
                           block_k=512, with_lse: bool = False,
                           rope=None):
    """q,k,v: [B, H, S, D] -> [B, H, S, D]
    (+ optional compact lse [B*H, Sq] when with_lse).
    rope=(cos, sin) with [S, D] f32 tables applies neox rotary to q/k
    inside the kernel (requires Sq == Sk)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = _fit_block(block_q, Sq)
    block_k = _fit_block(block_k, Sk)
    if not block_q or not block_k:
        raise ValueError(f"no usable pallas block for Sq={Sq}, Sk={Sk}")
    if rope is not None and Sq != Sk:
        raise ValueError("in-kernel rope requires Sq == Sk")
    scale = 1.0 / math.sqrt(D)
    n_kb = Sk // block_k

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               causal=causal, scale=scale,
                               kv_blocks=n_kb, causal_off=Sk - Sq,
                               with_rope=rope is not None)
    causal_off = Sk - Sq

    def _kv_j(i, j):
        return _causal_stream_kv(i, j, block_q, block_k, causal_off,
                                 causal)

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, D),
                           lambda b, i, j: (b, _kv_j(i, j), 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q.reshape(B * H, Sq, D), k.reshape(B * H, Sk, D),
            v.reshape(B * H, Sk, D)]
    if rope is not None:
        cos, sin = rope
        cs_i = pl.BlockSpec((block_q, D), lambda b, i, j: (i, 0))
        cs_j = pl.BlockSpec((block_k, D),
                            lambda b, i, j: (_kv_j(i, j), 0))
        in_specs += [cs_i, cs_i, cs_j, cs_j]
        args += [cos, sin, cos, sin]
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 128),
                                      lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, Sq, 128),
                                              jnp.float32))
    # Kernel body traced with x64 off: mosaic cannot legalize the i64
    # scalars that python-int arithmetic produces under jax_enable_x64.
    with _x64_off():
        res = pl.pallas_call(
            kernel,
            grid=(B * H, Sq // block_q, n_kb),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, D), jnp.float32),
                            pltpu.VMEM((block_q, D), q.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"))
            if not _INTERPRET[0] else None,
            interpret=_INTERPRET[0],
            name="flash_attention_fwd",
        )(*args)
    out = res[0].reshape(B, H, Sq, D)
    if with_lse:
        # compact residual [BH, Sq]: the lane broadcast is re-expanded
        # transiently in the backward (keeping it would cost 128x the
        # memory across every layer's saved residuals)
        return out, res[1][..., 0]
    return out


def _bwd_p_ds(q2, k, v, do, lse2, delta, *, mask, row_off, col_off):
    """Shared backward tile math (used by all backward kernels):
    recompute p from the saved lse, then ds = p * (dp - delta).

    exp2-space convention: EXACTLY ONE of q2/k carries the scale*log2e
    factor (folded in once per VMEM tile by the caller) and lse2 is the
    [bq, 128] lane-broadcast residual already multiplied by log2e, so
    p = exp2(q2.k - lse2) = softmax probabilities with no per-block
    scale pass.  ds is returned in natural d/ds space (p is unitless).
    ``mask`` is a static flag: fully-visible causal blocks skip the
    iota/compare/select AND the dead-row guard (dead rows can only
    exist in blocks that contain masked entries).  delta is [bq, 1]."""
    bq, bk = q2.shape[0], k.shape[0]
    s = lax.dot_general(q2, k, _DIMNUM_NT,
                        preferred_element_type=jnp.float32)
    if mask:
        rows = row_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = col_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, _MASK_VALUE)
        # dead rows have lse = -inf: exp2(s - lse2) would be inf -> 0
        finite = jnp.isfinite(lse2[:, :1])
        p = jnp.where(finite, jnp.exp2(s - _cols(lse2, bk)), _F32_0)
    else:
        p = jnp.exp2(s - _cols(lse2, bk))
    dp = lax.dot_general(do, v, _DIMNUM_NT,
                         preferred_element_type=jnp.float32)
    ds = (p * (dp - delta)).astype(k.dtype)
    return p, ds


def _flash_bwd_dq_kernel(*refs, block_k: int,
                         causal: bool, scale: float, kv_blocks: int,
                         causal_off: int, with_rope: bool = False):
    """dQ, grid (BH, q_tile, k_tile): k/v stream through as grid blocks,
    dq accumulates in VMEM scratch (FlashAttention-2 q-parallel half; p
    recomputed from the saved lse, delta = rowsum(dO*O) computed in the
    kernel from the o/do tiles — no precomputed broadcast array)."""
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[0:6]
    i = 6
    if with_rope:
        cos_i_ref, sin_i_ref, cos_j_ref, sin_j_ref = refs[6:10]
        i = 10
    dq_ref = refs[i]
    dq_s, delta_s, qs_s = refs[i + 1:]
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    bq, d = q_ref.shape[1], q_ref.shape[-1]
    c = scale * _LOG2E

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)
        do32 = do_ref[0].astype(jnp.float32)
        o32 = o_ref[0].astype(jnp.float32)
        delta_s[...] = jnp.broadcast_to(
            jnp.sum(do32 * o32, axis=1)[:, None], delta_s.shape)
        # exp2-space q tile: scale*log2e (and rope) folded in once
        if with_rope:
            qs_s[...] = (_rope_tile(q_ref[0], cos_i_ref, sin_i_ref)
                         * c).astype(qs_s.dtype)
        else:
            qs_s[...] = (q_ref[0].astype(jnp.float32)
                         * c).astype(qs_s.dtype)

    run = True
    if causal:
        run = _causal_run(qi, kb, bq, block_k, causal_off)

    def _tile_body(mask: bool):
        if with_rope:
            k = _rope_tile(k_ref[0], cos_j_ref, sin_j_ref).astype(
                k_ref.dtype)
        else:
            k = k_ref[0]
        _, ds = _bwd_p_ds(qs_s[...], k, v_ref[0], do_ref[0],
                          lse_ref[0], delta_s[:, :1], mask=mask,
                          row_off=qi * bq + causal_off,
                          col_off=kb * block_k)
        dq_s[...] += lax.dot_general(
            ds, k, _DIMNUM_NN, preferred_element_type=jnp.float32) * scale

    if causal:
        need_mask = _need_mask(qi, kb, bq, block_k, causal_off)
        @pl.when(run & need_mask)
        def _body_masked():
            _tile_body(True)

        @pl.when(run & jnp.logical_not(need_mask))
        def _body_full():
            _tile_body(False)
    else:
        _tile_body(False)

    @pl.when(kb == kv_blocks - 1)
    def _store():
        if with_rope:
            # dq was accumulated in rope space; the rope VJP is the
            # inverse rotation (same tables, negated sin)
            dq_ref[0] = _rope_tile(dq_s[...], cos_i_ref, sin_i_ref,
                                   neg_sin=True).astype(dq_ref.dtype)
        else:
            dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _flash_bwd_kv_kernel(*refs, block_q: int,
                         causal: bool, scale: float, q_blocks: int,
                         causal_off: int, with_rope: bool = False,
                         emit_dq: bool = False):
    """dK/dV (+ optional dq partials), grid (BH, k_tile, q_tile):
    q/do/o/lse stream through as grid blocks, dk/dv accumulate in VMEM
    scratch.  With emit_dq this is the FUSED backward: the same pass
    also writes one f32 dq partial per (k_tile, q_tile) cell (reduced
    over the small k-tile axis outside) — 5 matmuls and one streaming
    pass instead of the 7 matmuls / two passes of the two-kernel
    FlashAttention-2 split."""
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[0:6]
    i = 6
    if with_rope:
        # cos/sin tiles: _k indexes the k tile (this cell), _q the
        # streamed q tile
        cos_k_ref, sin_k_ref, cos_q_ref, sin_q_ref = refs[6:10]
        i = 10
    if emit_dq:
        dq_ref = refs[i]
        i += 1
    dk_ref, dv_ref = refs[i:i + 2]
    dk_s, dv_s, ks_s = refs[i + 2:]
    ki = pl.program_id(1)
    qb = pl.program_id(2)
    bk = k_ref.shape[1]
    c = scale * _LOG2E

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)
        # here k is the resident tile, so the exp2-space scale*log2e
        # factor folds into K (q streams through unscaled)
        if with_rope:
            ks_s[...] = (_rope_tile(k_ref[0], cos_k_ref, sin_k_ref)
                         * c).astype(ks_s.dtype)
        else:
            ks_s[...] = (k_ref[0].astype(jnp.float32)
                         * c).astype(ks_s.dtype)

    run = True
    if causal:
        run = _causal_run(qb, ki, block_q, bk, causal_off)

    def _tile_body(mask: bool):
        if with_rope:
            q = _rope_tile(q_ref[0], cos_q_ref, sin_q_ref).astype(
                q_ref.dtype)
        else:
            q = q_ref[0]
        do = do_ref[0]
        # delta recomputed per (k,q) cell: the o tile is DMA'd for this
        # cell regardless (block specs fetch per grid step), so caching
        # the reduction in scratch would save only the VPU mul-reduce
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32),
                        axis=1)[:, None]               # [bq, 1]
        p, ds = _bwd_p_ds(q, ks_s[...], v_ref[0], do,
                          lse_ref[0], delta, mask=mask,
                          row_off=qb * block_q + causal_off,
                          col_off=ki * bk)
        dv_s[...] += lax.dot_general(p.astype(do.dtype), do, _DIMNUM_TN,
                                     preferred_element_type=jnp.float32)
        dk_s[...] += lax.dot_general(
            ds, q, _DIMNUM_TN, preferred_element_type=jnp.float32) * scale
        if emit_dq:
            # ks_s carries the exp2-space factor c; dq wants ds @ k_rope
            # * scale, so correct by scale/c = 1/log2e
            dq = lax.dot_general(
                ds, ks_s[...], _DIMNUM_NN,
                preferred_element_type=jnp.float32) * (1.0 / _LOG2E)
            if with_rope:
                # inverse-rotate each partial in-kernel (linear, so it
                # commutes with the sum).  Measured: cheaper than one
                # XLA inverse pass over the f32 sum (-12ms/step there —
                # the graph-level slice/negate/concat fusion is the
                # HBM-bound pattern the in-kernel rope exists to avoid)
                dq = _rope_tile(dq, cos_q_ref, sin_q_ref, neg_sin=True)
            dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    if causal:
        need_mask = _need_mask(qb, ki, block_q, bk, causal_off)
        @pl.when(run & need_mask)
        def _body_masked():
            _tile_body(True)

        @pl.when(run & jnp.logical_not(need_mask))
        def _body_full():
            _tile_body(False)
    else:
        _tile_body(False)

    if emit_dq and causal:
        @pl.when(jnp.logical_not(run))
        def _dead():
            dq_ref[0, 0] = jnp.zeros(dq_ref.shape[2:], dq_ref.dtype)

    @pl.when(qb == q_blocks - 1)
    def _store():
        if with_rope:
            dk_ref[0] = _rope_tile(dk_s[...], cos_k_ref, sin_k_ref,
                                   neg_sin=True).astype(dk_ref.dtype)
        else:
            dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_attention_bwd_fused(q, k, v, out, lse, g, causal: bool,
                               block_q=256, block_k=1024, rope=None):
    """Single-kernel flash backward (_flash_bwd_kv_kernel, emit_dq=True).
    f32 dq partials [n_kb, BH, Sq, D] are reduced by XLA right after —
    a cheap fused sum over the short k-tile axis (callers bound n_kb so
    this buffer stays a small multiple of dq)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = _fit_block(block_q, Sq)
    block_k = _fit_block(block_k, Sk)
    if not block_q or not block_k:
        raise ValueError(f"no usable pallas block for Sq={Sq}, Sk={Sk}")
    scale = 1.0 / math.sqrt(D)
    causal_off = Sk - Sq
    n_qb = Sq // block_q
    n_kb = Sk // block_k
    BH = B * H

    args = (q.reshape(BH, Sq, D), k.reshape(BH, Sk, D),
            v.reshape(BH, Sk, D), out.reshape(BH, Sq, D),
            g.reshape(BH, Sq, D))
    with_rope = rope is not None
    # exp2-space residual (×log2e) built once at graph level — cheaper
    # than a per-grid-step [block_q, 128] multiply inside the kernel
    lser = jnp.broadcast_to((lse * _LOG2E).reshape(BH, Sq)[..., None],
                            (BH, Sq, 128))

    def qs(sel):
        return pl.BlockSpec((1, block_q, D),
                            lambda b, i, j: (b, sel(i, j), 0))

    def ks(sel):
        return pl.BlockSpec((1, block_k, D),
                            lambda b, i, j: (b, sel(i, j), 0))

    by_i = lambda i, j: i

    def by_j(i, j):
        return _causal_stream_q(i, j, block_q, block_k, causal_off,
                                causal)

    in_specs = [qs(by_j), ks(by_i), ks(by_i), qs(by_j), qs(by_j),
                pl.BlockSpec((1, block_q, 128),
                             lambda b, i, j: (b, by_j(i, j), 0))]
    call_args = (*args, lser)
    if with_rope:
        cos, sin = rope
        in_specs += [
            pl.BlockSpec((block_k, D), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_k, D), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_q, D), lambda b, i, j: (by_j(i, j), 0)),
            pl.BlockSpec((block_q, D), lambda b, i, j: (by_j(i, j), 0))]
        call_args += (cos, sin, cos, sin)

    with _x64_off():
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_kv_kernel, block_q=block_q, causal=causal,
                scale=scale, q_blocks=n_qb, causal_off=causal_off,
                with_rope=with_rope, emit_dq=True),
            grid=(BH, n_kb, n_qb),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, i, j: (i, b, j, 0)),
                ks(by_i), ks(by_i)],
            out_shape=[
                jax.ShapeDtypeStruct((n_kb, BH, Sq, D), jnp.float32),
                jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((BH, Sk, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), k.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"))
            if not _INTERPRET[0] else None,
            interpret=_INTERPRET[0],
            name="flash_attention_bwd",
        )(*call_args)

    dq = jnp.sum(dq_part, axis=0).astype(q.dtype)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


# fused-bwd routing: the dq-partials buffer is n_kb copies of dq, so cap
# n_kb (block_k grows with Sk) and beyond this Sk hand off to the
# two-kernel scheme whose memory stays O(S*D + S) regardless
_FUSED_BWD_MAX_SK = 8192
# block_q=512 measured ~7-11% faster than 256 on v5e at both D=64 and
# D=128 (a builder-run round-5 sweep, not reproduced since).  Module
# constant so the VMEM audit (tools/check_vmem_budget.py) sees tile
# edits.
_FUSED_BWD_BLOCK_Q = 512


def _flash_bwd_auto(q, k, v, out, lse, g, causal, rope=None):
    """Pick the backward kernel: the fused single-kernel scheme (~2.4x
    faster on v5e) when the dq-partials buffer stays small (n_kb <= 4),
    else the two-kernel FlashAttention-2 split (O(S*D + S) memory)."""
    Sk = k.shape[2]
    if Sk <= _FUSED_BWD_MAX_SK:
        bk = _fit_block(max(1024, Sk // 4), Sk)
        # the cap must hold for the block actually found: awkward seq
        # lengths can snap to a much smaller divisor (e.g. Sk=2176 ->
        # bk=128, n_kb=17), where the partials buffer would dwarf dq
        if bk and Sk // bk <= 4:
            return _flash_attention_bwd_fused(q, k, v, out, lse, g,
                                              causal, _FUSED_BWD_BLOCK_Q,
                                              bk, rope=rope)
    return _flash_attention_bwd(q, k, v, out, lse, g, causal, rope=rope)


def _flash_attention_bwd(q, k, v, out, lse, g, causal: bool,
                         block_q=512, block_k=1024, rope=None):
    """Pallas flash backward (FlashAttention-2 two-kernel scheme):
    dq parallel over q tiles; dk/dv parallel over k tiles; both stream
    the reduction axis through the grid with VMEM scratch accumulators,
    recomputing p from the forward's lse — memory stays O(S·D + S)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = _fit_block(block_q, Sq)
    block_k = _fit_block(block_k, Sk)
    if not block_q or not block_k:
        raise ValueError(f"no usable pallas block for Sq={Sq}, Sk={Sk}")
    scale = 1.0 / math.sqrt(D)
    causal_off = Sk - Sq
    n_qb = Sq // block_q
    n_kb = Sk // block_k

    args = (q.reshape(B * H, Sq, D), k.reshape(B * H, Sk, D),
            v.reshape(B * H, Sk, D), out.reshape(B * H, Sq, D),
            g.reshape(B * H, Sq, D))
    with_rope = rope is not None
    # lane-broadcast lse to the mosaic-tileable [BH, Sq, 128] layout, in
    # exp2 space (×log2e) so the kernels consume it without a per-step
    # multiply (transient per-layer; the saved residual stays compact)
    lser = jnp.broadcast_to((lse * _LOG2E).reshape(B * H, Sq)[..., None],
                            (B * H, Sq, 128))

    def qs(sel):
        return pl.BlockSpec((1, block_q, D),
                            lambda b, i, j: (b, sel(i, j), 0))

    def ks(sel):
        return pl.BlockSpec((1, block_k, D),
                            lambda b, i, j: (b, sel(i, j), 0))

    def rows(sel):
        return pl.BlockSpec((1, block_q, 128),
                            lambda b, i, j: (b, sel(i, j), 0))

    by_i = lambda i, j: i

    # causal skipped-block remaps: dq pass streams k tiles (skipped ks
    # are the LATE ones -> restart at block 0); kv pass streams q tiles
    # (skipped qs are the EARLY above-diagonal ones -> first running)
    def kb_j(i, j):
        return _causal_stream_kv(i, j, block_q, block_k, causal_off,
                                 causal)

    def qb_j(i, j):
        return _causal_stream_q(i, j, block_q, block_k, causal_off,
                                causal)

    def cs_q(sel):
        return pl.BlockSpec((block_q, D), lambda b, i, j: (sel(i, j), 0))

    def cs_k(sel):
        return pl.BlockSpec((block_k, D), lambda b, i, j: (sel(i, j), 0))

    params = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not _INTERPRET[0] else None,
        interpret=_INTERPRET[0])

    with _x64_off():
        dq_in_specs = [qs(by_i), ks(kb_j), ks(kb_j), qs(by_i), qs(by_i),
                       rows(by_i)]
        dq_args = (*args, lser)
        if with_rope:
            cos, sin = rope
            dq_in_specs += [cs_q(by_i), cs_q(by_i), cs_k(kb_j), cs_k(kb_j)]
            dq_args += (cos, sin, cos, sin)
        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                              causal=causal, scale=scale, kv_blocks=n_kb,
                              causal_off=causal_off, with_rope=with_rope),
            grid=(B * H, n_qb, n_kb),
            in_specs=dq_in_specs,
            out_specs=qs(by_i),
            out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, D), q.dtype)],
            name="flash_attention_bwd_dq",
            **params,
        )(*dq_args)

        kv_in_specs = [qs(qb_j), ks(by_i), ks(by_i), qs(qb_j), qs(qb_j),
                       rows(qb_j)]
        kv_args = (*args, lser)
        if with_rope:
            cos, sin = rope
            kv_in_specs += [cs_k(by_i), cs_k(by_i), cs_q(qb_j), cs_q(qb_j)]
            kv_args += (cos, sin, cos, sin)
        dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_kv_kernel, block_q=block_q,
                              causal=causal, scale=scale, q_blocks=n_qb,
                              causal_off=causal_off, with_rope=with_rope),
            grid=(B * H, n_kb, n_qb),
            in_specs=kv_in_specs,
            out_specs=[ks(by_i), ks(by_i)],
            out_shape=[jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
                       jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), k.dtype)],
            name="flash_attention_bwd_dkv",
            **params,
        )(*kv_args)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


def _sdpa_reference(q, k, v, causal):
    """Full-materialization XLA reference (tests / tiny shapes only)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _chunked_sdpa(q, k, v, causal, mask=None, block_k=256):
    """Memory-bounded attention: lax.scan over k/v blocks with online
    softmax; each block body is rematerialized (jax.checkpoint), so the
    BACKWARD also runs block-by-block — activation memory stays
    O(S·D + S) instead of the O(S²) of the naive formulation.  Handles
    additive/bool masks and seq lengths not divisible by the block.

    Layout [B, H, S, D].  This is both the flash VJP path and the
    fallback forward for masked/ragged configs.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_kb = (Sk + pad) // bk
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, bk), 0)
    off = jax.lax.broadcasted_iota(jnp.int32, (Sq, bk), 1)
    # bottom-right-aligned causal for Sq != Sk (decode), like _sdpa_reference
    causal_off = Sk - Sq

    if mask is not None:
        if mask.dtype != jnp.bool_:
            mask = mask.astype(jnp.float32)
        if pad:
            # pad the key axis so block slices never clamp; the padded
            # columns are killed by the `cols < Sk` validity test anyway
            widths = [(0, 0)] * (mask.ndim - 1) + [(0, pad)]
            mask = jnp.pad(mask, widths)

    def block(carry, kb):
        m_, l_, acc = carry
        ks = lax.dynamic_slice_in_dim(k, kb * bk, bk, 2)
        vs = lax.dynamic_slice_in_dim(v, kb * bk, bk, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks.astype(jnp.float32))
        cols = kb * bk + off
        valid = cols < Sk
        if causal:
            valid = valid & (rows + causal_off >= cols)
        if mask is not None:
            mb = lax.dynamic_slice_in_dim(mask, kb * bk,
                                          bk, mask.ndim - 1)
            if mb.dtype == jnp.bool_:
                valid = valid & mb
            else:
                s = s + mb
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m_, jnp.max(s, -1))
        # guard fully-masked rows (m_new = -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_), m_ - m_safe, -jnp.inf))
        alpha = jnp.where(jnp.isfinite(m_), alpha, 0.0)
        l_new = l_ * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vs.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    # derive the carries from qf so they inherit its device-varying
    # status under shard_map (a literal zeros init would mismatch the
    # scan body's output vma when run inside ulysses/ring wrappers)
    zero_rows = qf[..., 0] * 0.0                      # [B, H, Sq] f32
    init = (zero_rows - jnp.inf,
            zero_rows,
            qf * 0.0)
    (m_, l_, acc), _ = lax.scan(jax.checkpoint(block), init,
                                jnp.arange(n_kb, dtype=jnp.int32))
    out = acc / jnp.maximum(l_, 1e-30)[..., None]
    return out.astype(q.dtype)


def _pallas_ok(q, k, mask, block=256) -> bool:
    return (_device.on_tpu() and mask is None
            and q.shape[3] <= 128                      # scratch is 128-lane
            and _fit_block(block, q.shape[2]) > 0
            and _fit_block(block, k.shape[2]) > 0)


def _select_flash_blocks(q, k, v, causal):
    """(block_q, block_k) via the autotune cache (parity: the reference's
    kernel-autotune algo pick, paddle/phi/kernels/autotune/auto_tune_base.h).
    Inside a trace only the cached winner is consulted; with concrete
    buffers a miss triggers the timed search."""
    from ..incubate.autotune import (autotune_enabled, autotune_lookup,
                                     autotune_select,
                                     flash_attention_candidates)
    Sq, Sk = q.shape[2], k.shape[2]
    default = (min(512, Sq), min(512, Sk))
    if not autotune_enabled():
        return default
    sig = (tuple(q.shape), tuple(k.shape), str(q.dtype), bool(causal))
    if isinstance(q, jax.core.Tracer):
        return autotune_lookup("flash_attention", sig) or default
    return autotune_select(
        "flash_attention", sig,
        flash_attention_candidates(Sq, Sk),
        lambda cand: (lambda: _flash_attention_value(
            q, k, v, causal, cand[0], cand[1])),
        default)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_sdpa(q, k, v, causal):
    if _pallas_ok(q, k, None):
        bq, bk = _select_flash_blocks(q, k, v, causal)
        return _flash_attention_value(q, k, v, causal, bq, bk)
    return _chunked_sdpa(q, k, v, causal)


def _flash_sdpa_fwd(q, k, v, causal):
    if _pallas_ok(q, k, None):
        bq, bk = _select_flash_blocks(q, k, v, causal)
        out, lse = _flash_attention_value(q, k, v, causal, bq, bk,
                                          with_lse=True)
        return out, (q, k, v, out, lse)
    return _chunked_sdpa(q, k, v, causal), (q, k, v, None, None)


def _flash_sdpa_bwd(causal, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        # Pallas flash backward: p recomputed from lse per tile; fused
        # single-kernel scheme for bounded n_kb, two-kernel beyond
        return _flash_bwd_auto(q, k, v, out, lse, g, causal)
    # chunked backward: block recompute keeps memory bounded (fallback
    # for masked/ragged configs the Pallas kernel rejects)
    _, vjp = jax.vjp(lambda q_, k_, v_: _chunked_sdpa(q_, k_, v_, causal),
                     q, k, v)
    return vjp(g)


_flash_sdpa.defvjp(_flash_sdpa_fwd, _flash_sdpa_bwd)


# ---------------------------------------------------------------------------
# fused rope + flash attention (training fast path)
# ---------------------------------------------------------------------------
def rope_tables(seq_len, dim, base=10000.0, position_offset=0,
                dtype=jnp.float32):
    """Neox rotary cos/sin tables [S, D] (f32; fed to the fused kernel)."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    pos = jnp.arange(position_offset, position_offset + seq_len,
                     dtype=jnp.float32)
    freqs = pos[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rope_xla(t, cos, sin):
    """Graph-level neox rope on [B, H, S, D] (fallback path)."""
    tf = t.astype(jnp.float32)
    half = tf.shape[-1] // 2
    rot = jnp.concatenate([-tf[..., half:], tf[..., :half]], axis=-1)
    return (tf * cos[None, None] + rot * sin[None, None]).astype(t.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_rope_sdpa(q, k, v, cos, sin, causal):
    if _pallas_ok(q, k, None) and q.shape[2] == k.shape[2]:
        bq, bk = _select_flash_blocks(q, k, v, causal)
        return _flash_attention_value(q, k, v, causal, bq, bk,
                                      rope=(cos, sin))
    return _chunked_sdpa(_rope_xla(q, cos, sin), _rope_xla(k, cos, sin),
                         v, causal)


def _flash_rope_sdpa_fwd(q, k, v, cos, sin, causal):
    if _pallas_ok(q, k, None) and q.shape[2] == k.shape[2]:
        bq, bk = _select_flash_blocks(q, k, v, causal)
        out, lse = _flash_attention_value(q, k, v, causal, bq, bk,
                                          with_lse=True, rope=(cos, sin))
        return out, (q, k, v, cos, sin, out, lse)
    return (_chunked_sdpa(_rope_xla(q, cos, sin), _rope_xla(k, cos, sin),
                          v, causal), (q, k, v, cos, sin, None, None))


def _flash_rope_sdpa_bwd(causal, res, g):
    q, k, v, cos, sin, out, lse = res
    if lse is not None:
        dq, dk, dv = _flash_bwd_auto(q, k, v, out, lse, g, causal,
                                     rope=(cos, sin))
        return dq, dk, dv, jnp.zeros_like(cos), jnp.zeros_like(sin)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _chunked_sdpa(
            _rope_xla(q_, cos, sin), _rope_xla(k_, cos, sin), v_, causal),
        q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(cos), jnp.zeros_like(sin)


_flash_rope_sdpa.defvjp(_flash_rope_sdpa_fwd, _flash_rope_sdpa_bwd)


def flash_attention_rope(query, key, value, rotary_base=10000.0,
                         is_causal=True):
    """Fused neox-rope + flash attention, paddle layout [B, S, H, D].

    The rotary embedding is applied to the q/k tiles inside the Pallas
    kernels (fwd recompute in both backward halves, inverse rotation on
    the dq/dk stores), so the XLA graph carries NO rope ops at all —
    replacing the reference's separate fused_rotary_position_embedding +
    flash_attention pair (paddle/phi/kernels/fusion/) on the training
    path.  k/v must already be head-repeated for GQA (rope commutes with
    the repeat)."""
    def fn(q, k, v):
        S, D = q.shape[1], q.shape[3]
        cos, sin = rope_tables(S, D, rotary_base)
        out = _flash_rope_sdpa(jnp.swapaxes(q, 1, 2),
                               jnp.swapaxes(k, 1, 2),
                               jnp.swapaxes(v, 1, 2), cos, sin, is_causal)
        return jnp.swapaxes(out, 1, 2)

    return apply_op("flash_attention_rope", fn,
                    (query, targ(key), targ(value)))



def flash_attention_tpu(query, key, value, attn_mask=None, is_causal=False):
    """Flash attention, paddle layout [B, S, H, D].

    Clean configs (no mask, block-divisible) hit the Pallas forward and
    the Pallas FlashAttention-2 backward on TPU; masked or ragged-length
    configs run the chunked online-softmax path with its block-recomputed
    backward — still memory-bounded, still one dispatched op."""

    def fn(q, k, v, *m):
        q_ = jnp.swapaxes(q, 1, 2)
        k_ = jnp.swapaxes(k, 1, 2)
        v_ = jnp.swapaxes(v, 1, 2)
        if m:
            out = _chunked_sdpa(q_, k_, v_, is_causal, mask=m[0])
        else:
            out = _flash_sdpa(q_, k_, v_, is_causal)
        return jnp.swapaxes(out, 1, 2)

    args = (query, targ(key), targ(value))
    if attn_mask is not None:
        args = args + (targ(attn_mask),)
    return apply_op("flash_attention_pallas", fn, args)


# ---------------------------------------------------------------------------
# rms_norm
# ---------------------------------------------------------------------------
def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(ms + eps) *
                w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm_tpu(x, weight, eps=1e-6, block_rows=512):
    """Row-tiled Pallas RMSNorm (used by the bench path on TPU)."""
    if not _device.on_tpu():
        raise RuntimeError("requires TPU")

    def fn(xv, wv):
        shape = xv.shape
        d = shape[-1]
        rows = int(np.prod(shape[:-1]))
        xr = xv.reshape(rows, d)
        br = min(block_rows, rows)
        if rows % br:
            br = rows
        with _x64_off():
            out = pl.pallas_call(
                functools.partial(_rms_kernel, eps=eps),
                grid=(rows // br,),
                in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                          pl.BlockSpec((d,), lambda i: (0,))],
                out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((rows, d), xv.dtype),
                name="rms_norm",
            )(xr, wv)
        return out.reshape(shape)

    return apply_op("rms_norm_pallas", fn, (x, targ(weight)))


# ---------------------------------------------------------------------------
# ring attention (sequence/context parallelism over the mesh)
# ---------------------------------------------------------------------------
def _ring_flash_ok(S, D) -> bool:
    """Can the per-rotation block run the Pallas flash kernels?"""
    return ((_device.on_tpu() or _INTERPRET[0])
            and D <= 128 and _fit_block(256, S) > 0)


def _ring_block_fwd(qh, kc, vc, src, idx, causal, hop):
    """One rotation's partial attention via the Pallas flash kernel.

    Global causal structure picks the block kind: hop 0 holds the local
    shard (src == idx statically) -> diagonal causal block, no cond;
    later hops branch at runtime on the device-varying src < idx ->
    fully-visible block vs fully-masked (zero output, -inf lse).
    Returns (o f32 [B,H,S,D], lse f32 [B,H,S])."""
    B, H, S, D = qh.shape
    bq = _fit_block(512, S)
    bk = _fit_block(512, S)

    def _run(c):
        def f():
            o, lse = _flash_attention_value(qh, kc, vc, c, bq, bk,
                                            with_lse=True)
            return o.astype(jnp.float32), lse.reshape(B, H, S)
        return f

    def _empty():
        return (jnp.zeros((B, H, S, D), jnp.float32),
                jnp.full((B, H, S), -jnp.inf, jnp.float32))

    if not causal:
        return _run(False)()
    if hop == 0:
        return _run(True)()
    return lax.cond(src < idx, _run(False), _empty)


def _ring_flash_impl(qh, k0, v0, axis_name, causal):
    """Forward ring: per-rotation flash blocks combined by running
    logsumexp (same online-softmax algebra as inside the kernel, one
    level up)."""
    n = lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, H, S, D = qh.shape

    acc = jnp.zeros((B, H, S, D), jnp.float32)
    lse_run = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    kc, vc = k0, v0
    for i in range(n):                      # static unroll over the ring
        src = (idx - i) % n
        o_i, lse_i = _ring_block_fwd(qh, kc, vc, src, idx, causal, i)
        new_lse = jnp.logaddexp(lse_run, lse_i)
        w_old = jnp.where(jnp.isfinite(lse_run),
                          jnp.exp(lse_run - new_lse), 0.0)
        w_new = jnp.where(jnp.isfinite(lse_i),
                          jnp.exp(lse_i - new_lse), 0.0)
        acc = acc * w_old[..., None] + o_i * w_new[..., None]
        lse_run = new_lse
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
    return acc.astype(qh.dtype), lse_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash(qh, k0, v0, axis_name, causal):
    out, _ = _ring_flash_impl(qh, k0, v0, axis_name, causal)
    return out


def _ring_flash_fwd(qh, k0, v0, axis_name, causal):
    out, lse = _ring_flash_impl(qh, k0, v0, axis_name, causal)
    return out, (qh, k0, v0, out, lse)


def _ring_flash_bwd(axis_name, causal, res, g):
    """Ring backward: each rotation runs the FlashAttention-2 backward
    kernels against the TOTAL out/lse (p recomputed per block is then
    the correct global softmax probability); dk/dv accumulators travel
    around the ring with their k/v shard and arrive home after n hops."""
    qh, k0, v0, out, lse = res
    n = lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, H, S, D = qh.shape
    lse_c = lse.reshape(B * H, S)
    g = g.astype(out.dtype)

    def _blk(kc, vc, c):
        def f():
            return _flash_bwd_auto(qh, kc, vc, out, lse_c, g, c)
        return f

    def _empty(kc, vc):
        def f():
            return (jnp.zeros_like(qh), jnp.zeros_like(kc),
                    jnp.zeros_like(vc))
        return f

    dq = jnp.zeros((B, H, S, D), jnp.float32)
    kc, vc = k0, v0
    dkc = jnp.zeros_like(k0, jnp.float32)
    dvc = jnp.zeros_like(v0, jnp.float32)
    for i in range(n):
        src = (idx - i) % n
        if not causal:
            dq_i, dk_i, dv_i = _blk(kc, vc, False)()
        elif i == 0:                # hop 0: local shard, statically diag
            dq_i, dk_i, dv_i = _blk(kc, vc, True)()
        else:
            dq_i, dk_i, dv_i = lax.cond(
                src < idx, _blk(kc, vc, False), _empty(kc, vc))
        dq = dq + dq_i.astype(jnp.float32)
        dkc = dkc + dk_i.astype(jnp.float32)
        dvc = dvc + dv_i.astype(jnp.float32)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
    return (dq.astype(qh.dtype), dkc.astype(k0.dtype),
            dvc.astype(v0.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name: str, is_causal=False):
    """Ring attention over a mesh axis (long-context path; SURVEY.md §5.7
    notes the reference LACKS this — sep relied on model-side sharding).

    Must run inside shard_map with the sequence dim sharded over
    ``axis_name``: each step computes a local flash block then rotates k/v
    one neighbor around the ring with collective-permute (rides ICI).
    Inputs [B, S_local, H, D] (values, not Tensors).

    On TPU with kernel-compatible shapes the per-rotation block IS the
    Pallas flash kernel (fwd with lse, FlashAttention-2 bwd against the
    total lse — see _ring_flash); otherwise the einsum online-softmax
    fallback below runs (CPU mesh tests, odd shapes)."""
    # graftlint: waive[trace-shape-branch] -- static kernel dispatch (Pallas flash vs einsum fallback), two variants per shape, not a compile-budget leak
    if _ring_flash_ok(q.shape[1], q.shape[-1]):
        qh_ = jnp.swapaxes(q, 1, 2)
        out = _ring_flash(qh_, jnp.swapaxes(k, 1, 2).astype(qh_.dtype),
                          jnp.swapaxes(v, 1, 2).astype(qh_.dtype),
                          axis_name, is_causal)
        return jnp.swapaxes(out, 1, 2)

    n = lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,H,S,D]
    scale = 1.0 / math.sqrt(q.shape[-1])
    B, H, S, D = qh.shape

    # carries are device-varying under shard_map vma checking
    def vary(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m = vary(jnp.full((B, H, S, 1), -jnp.inf, jnp.float32))
    l = vary(jnp.zeros((B, H, S, 1), jnp.float32))
    acc = vary(jnp.zeros((B, H, S, D), jnp.float32))

    kv = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))

    def step(i, carry):
        m, l, acc, (kc, vc) = carry
        src = (idx - i) % n  # which shard's k/v we now hold
        s = jnp.einsum("bhqd,bhkd->bhqk", qh,
                       kc.astype(jnp.float32)) * scale
        if is_causal:
            rows = idx * S + jax.lax.broadcasted_iota(
                jnp.int32, (S, S), 0)
            cols = src * S + jax.lax.broadcasted_iota(
                jnp.int32, (S, S), 1)
            s = jnp.where((rows >= cols)[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        kc2 = jax.lax.ppermute(kc, axis_name, perm)
        vc2 = jax.lax.ppermute(vc, axis_name, perm)
        return m_new, l_new, acc_new, (kc2, vc2)

    m, l, acc, _ = jax.lax.fori_loop(0, n, step, (m, l, acc, kv))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def sdpa_ring(query, key, value, mesh, axis_name: str = "sep",
              is_causal: bool = False):
    """Sequence-parallel attention over a mesh axis (SURVEY.md §5.7 —
    the beat-the-reference long-context path; the reference's snapshot
    has NO ring attention).

    q/k/v: [B, S, H, D] with S sharded over ``axis_name``.  Each rank
    computes flash blocks against its local k/v then rotates k/v around
    the ring with collective-permute (ICI); differentiable (the rotation
    loop has a static trip count, so jax.grad reverses it)."""
    from jax.sharding import PartitionSpec as P
    from ..distributed.process_mesh import as_jax_mesh

    jmesh = as_jax_mesh(mesh)

    def _spec_for(shape):
        # all axes are manual under the flash ring (see below), so the
        # batch/head dims must be EXPLICITLY split over the data/fsdp/
        # model axes when present+divisible — P(None, sep) alone would
        # gather and redundantly recompute across those groups
        def axes(names, dim):
            chosen, prod = [], 1
            for name in names:
                sz = jmesh.shape.get(name, 1)
                if sz > 1 and dim % (prod * sz) == 0:
                    chosen.append(name)
                    prod *= sz
            if not chosen:
                return None
            return chosen[0] if len(chosen) == 1 else tuple(chosen)
        return P(axes(("data", "sharding"), shape[0]), axis_name,
                 axes(("model",), shape[2]), None)

    def fn(q, k, v):
        spec = _spec_for(q.shape)
        # check_vma off: pallas_call outputs carry no vma annotation,
        # which the checker (correctly) refuses to guess.  All axes
        # manual (required with the checker off).
        ring = shard_map_compat(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name,
                                              is_causal),
            jmesh, in_specs=(spec, spec, spec), out_specs=spec)
        return ring(q, k, v)

    return apply_op("ring_attention", fn,
                    (query, targ(key), targ(value)))


def ulysses_attention(q, k, v, axis_name: str, is_causal=False):
    """DeepSpeed-Ulysses attention over a mesh axis (SURVEY.md §5.7 —
    the all-to-all long-context modality; absent from the reference
    snapshot like ring attention).

    Must run inside shard_map with the sequence dim sharded over
    ``axis_name``: an all-to-all trades the sequence shard for a HEAD
    shard (each rank then holds the FULL sequence for H/n heads), local
    full attention runs unsharded, and a second all-to-all restores the
    sequence sharding.  Two all-to-alls ride ICI; compute is exactly the
    dense/flash kernel, so Ulysses wins over ring when heads ≥ ranks and
    the per-rank full sequence fits.  Inputs [B, S_local, H, D]."""
    n = lax.axis_size(axis_name)
    B, S, H, D = q.shape
    if H % n:
        raise ValueError(f"ulysses needs heads ({H}) divisible by the "
                         f"axis size ({n})")

    def seq_to_heads(x):
        # [B, S_loc, H, D] -> all_to_all -> [B, S_full, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qf = seq_to_heads(q)
    kf = seq_to_heads(k)
    vf = seq_to_heads(v)
    # local attention over the full sequence: [B, H/n, S_full, D] — the
    # Pallas flash kernel when shapes allow (round-4: the einsum/chunked
    # inner step was the VERDICT r3 weak item), chunked fallback otherwise
    qh = jnp.swapaxes(qf, 1, 2)
    kh = jnp.swapaxes(kf, 1, 2)
    vh = jnp.swapaxes(vf, 1, 2)
    # graftlint: waive[trace-shape-branch] -- static kernel dispatch (flash vs chunked fallback), two variants per shape, not a compile-budget leak
    if _ring_flash_ok(qh.shape[2], qh.shape[3]):
        out = _flash_sdpa(qh, kh, vh, is_causal)
    else:
        out = _chunked_sdpa(qh, kh, vh, is_causal)
    out = jnp.swapaxes(out, 1, 2).astype(q.dtype)
    return heads_to_seq(out)


def sdpa_ulysses(query, key, value, mesh, axis_name: str = "sep",
                 is_causal: bool = False):
    """Sequence-parallel attention via Ulysses all-to-all (the companion
    to sdpa_ring; pick ring for S >> heads, ulysses when heads divide
    evenly and all-to-all bandwidth beats n-step rotation).

    q/k/v: [B, S, H, D] with S sharded over ``axis_name``."""
    from jax.sharding import PartitionSpec as P
    from ..distributed.process_mesh import as_jax_mesh

    jmesh = as_jax_mesh(mesh)

    def _spec_for(shape):
        # same all-manual treatment as sdpa_ring (the flash inner path
        # has no vma annotation): batch explicitly split over data/fsdp
        def axes(names, dim):
            chosen, prod = [], 1
            for name in names:
                sz = jmesh.shape.get(name, 1)
                if sz > 1 and dim % (prod * sz) == 0:
                    chosen.append(name)
                    prod *= sz
            if not chosen:
                return None
            return chosen[0] if len(chosen) == 1 else tuple(chosen)
        return P(axes(("data", "sharding"), shape[0]), axis_name,
                 axes(("model",), shape[2]), None)

    def fn(q, k, v):
        spec = _spec_for(q.shape)
        uly = shard_map_compat(
            lambda q_, k_, v_: ulysses_attention(q_, k_, v_, axis_name,
                                                 is_causal),
            jmesh, in_specs=(spec, spec, spec), out_specs=spec)
        return uly(q, k, v)

    return apply_op("ulysses_attention", fn,
                    (query, targ(key), targ(value)))


# ---------------------------------------------------------------------------
# ragged paged attention (serving: one launch for any prefill+decode mix)
# ---------------------------------------------------------------------------
# Tile sizes.  A span's q rows are cut into tiles of as many tokens as
# give one kv head _RAGGED_TILE_ROWS query rows (the MXU's height; 32
# tokens at GQA 4:1): a speculative-verify span is one tile, a chunk
# ceil(q_len / tile).  ONE size, because the kernel's body is traced
# and lowered anew for every token budget at every start: a second
# size (8-token tiles for decode spans beside 128-token tiles for
# chunks read 1-4% better end to end: PERF.md section 6) was a second
# launch, doubled that and put seven seconds on a warm start.  At
# one group a one-token span takes the decode cell, a second body of
# the SAME launch (``_ragged_decode_cell``).  Keys stream in blocks of
# _RAGGED_KV_BLOCK keys (whole pages): a block wholly under a tile's
# diagonal is one unmasked update 512 keys wide; the diagonal's block
# and the span's last are walked _RAGGED_SUB_BLOCK keys at a time, as
# far as the tile's last row sees, under the mask.  The block halves,
# then the tile, while a cell's buffers overrun _RAGGED_TILE_VMEM.
_RAGGED_TILE_ROWS = 128
_RAGGED_MIN_TILE = 8
_RAGGED_KV_BLOCK = 512
_RAGGED_SUB_BLOCK = 128
# what the tile-sized buffers of one grid cell may take of the 16 MiB
# the serving kernels are held to (tools/graftlint/vmem.py)
_RAGGED_TILE_VMEM = 12 << 20
# Page rows (one key of one kv head: [key, head] pairs) the decode cell
# takes a step: 32 float32 vregs of K, then of V, live at once.  256 to
# 4,096 read alike on the chip, unrolled or not (PERF.md section 6).
_RAGGED_DECODE_ROWS = 256


def _ragged_compute_dtype(q_dtype, kv_dtype):
    """What the two matmuls run in: the pool's own type for an fp pool
    (bf16 x bf16 -> f32 on the MXU), int8 codes for a quantized one."""
    if jnp.dtype(kv_dtype) == jnp.int8:
        return jnp.dtype(jnp.int8)
    return jnp.promote_types(q_dtype, kv_dtype)


def _ragged_cell_vmem_bytes(bq: int, heads: int, kv_heads: int,
                            head_dim: int, kv_block: int, q_itemsize: int,
                            c_itemsize: int, kv_itemsize: int) -> int:
    """VMEM bytes of one _ragged_paged_kernel grid cell at a q tile of
    ``bq`` tokens: the [bq, H, D] q and o staging buffers, the per-kv-
    head folded q / m / l / acc state (and the q rows' scales of an
    int8 pool), the 2-slot K and V page blocks ([kv_block, Hkv, D]
    each, as stored), the current block head-major, and the live score
    and probability tiles; beside them the decode cell's m / l / acc
    (``[1, Hkv, 1 | D]``, where ``ragged_decode_cell`` engages).
    Mirrors the scratch_shapes in _ragged_paged_attention_pallas — edit
    both."""
    groups = heads // kv_heads
    rows = bq * groups
    total = 2 * _tile_bytes((bq, heads, head_dim), q_itemsize)    # q, o
    total += _tile_bytes((kv_heads, rows, head_dim), c_itemsize)  # q2
    total += _tile_bytes((kv_heads, rows, head_dim), 4)           # acc
    total += 2 * _tile_bytes((kv_heads, rows, 1), 4)              # m, l
    total += 4 * _stored_block_bytes(kv_block, kv_heads, head_dim,
                                     kv_itemsize)           # k, v x 2 slots
    total += 2 * _tile_bytes((kv_heads, kv_block, head_dim),
                             c_itemsize)                    # head-major
    total += 2 * _tile_bytes((rows, kv_block), 4)           # scores + p
    if kv_itemsize == 1:
        total += _tile_bytes((kv_heads, rows, 1), 4)        # q scales
    if ragged_decode_cell(1, groups, kv_itemsize == 1):     # decode cell
        total += 2 * _tile_bytes((kv_heads, 1), 4)
        total += _tile_bytes((kv_heads, head_dim), 4)
    return total


def _stored_block_bytes(n: int, kv_heads: int, head_dim: int,
                        itemsize: int) -> int:
    """VMEM bytes of ``[n, Hkv, D]`` pages as stored.  Mosaic tiles a
    second-minor dim narrower than the type's sublane tile at the next
    power of two, down to the type's packing, so 8 kv heads take 8
    sublanes in f32, bf16 and int8 alike; only fewer heads than the
    packing (bf16: 1, int8: 1-2) pad to the whole tile.  (Compile-only
    v5e probe, PR 36: the largest ``[N, Hkv, 128]`` scratch Mosaic takes
    is 127-128 MiB of unpadded bytes at every other width.)"""
    pack = 4 // max(1, min(itemsize, 4))
    sub = 8 * pack
    if kv_heads >= sub:
        rows = -(-kv_heads // sub) * sub
    elif kv_heads >= pack:
        rows = 1 << (kv_heads - 1).bit_length()
    else:
        rows = sub
    return n * rows * (-(-head_dim // _VMEM_LANE) * _VMEM_LANE) * itemsize


def _ragged_sub_pages(block_size: int, bt_width: int) -> int:
    """Pages of one sub-block of the tail walk (_RAGGED_SUB_BLOCK keys,
    at least a page, at most the table)."""
    return max(1, min(_RAGGED_SUB_BLOCK // block_size, bt_width))


@functools.lru_cache(maxsize=None)
def ragged_tile_geometry(heads: int, kv_heads: int, head_dim: int,
                         block_size: int, bt_width: int, q_dtype,
                         kv_dtype):
    """``(tile, pages_per_block)``: the q tile in tokens and the pages
    one key block holds (whole sub-blocks), from what the launch sees —
    the head geometry, the page size and the two dtypes, not the
    budget, so every budget's launch has the same cell.  The widest
    block up to _RAGGED_KV_BLOCK keys whose cell fits _RAGGED_TILE_VMEM
    at the full tile; the tile halves only where none does."""
    q_item = jnp.dtype(q_dtype).itemsize
    kv_item = jnp.dtype(kv_dtype).itemsize
    c_item = _ragged_compute_dtype(q_dtype, kv_dtype).itemsize
    sub = _ragged_sub_pages(block_size, bt_width)
    tile = max(_RAGGED_MIN_TILE,
               _RAGGED_TILE_ROWS // (heads // kv_heads))
    while True:
        keys = _RAGGED_KV_BLOCK
        while True:
            kb = min(keys // block_size, -(-bt_width // sub) * sub)
            kb = max(sub, kb // sub * sub)
            fits = _ragged_cell_vmem_bytes(
                tile, heads, kv_heads, head_dim, kb * block_size, q_item,
                c_item, kv_item) <= _RAGGED_TILE_VMEM
            if fits or keys <= _RAGGED_SUB_BLOCK:
                break
            keys //= 2
        if fits or tile <= _RAGGED_MIN_TILE:
            return tile, kb
        tile //= 2


def ragged_decode_cell(q_len, groups: int, quantized: bool):
    """Whether a span of ``q_len`` query tokens takes the grouped-query
    launch's decode cell (``_ragged_decode_cell``): a one-token span, at
    one group, of an fp pool.  At 4 groups the tile cell is the faster
    (its q rows fill the MXU: PERF.md section 6), and an int8 pool folds
    its scales into int8 products there.  The ONE rule for the kernel's
    choice (``q_len`` a traced scalar) and for the step record's
    ``attn_decode_cells`` (host integers: an array of span lengths gives
    one bool a span; a launch without the cell a plain False)."""
    return q_len == 1 if groups == 1 and not quantized else False


def ragged_attn_rows(q_lens, tile: int, groups: int,
                     quantized: bool = False) -> int:
    """The q rows per kv head one launch computes for spans of these
    lengths: every tile's full height, garbage rows included; a span
    that takes the decode cell its token's one row."""
    ql = np.maximum(np.asarray(q_lens, np.int64), 0)
    dec = ragged_decode_cell(ql, groups, quantized)
    return groups * int(np.sum(np.where(dec, 1, tile * (-(-ql // tile)))))


def ragged_attn_blocks(q_lens, kv_lens, tile: int, pages_per_block: int,
                       block_size: int, bt_width: int):
    """``(blocks, masked)``: the key blocks one ragged launch of q tiles
    of ``tile`` tokens and key blocks of ``pages_per_block`` pages walks
    for spans of these ``(q_len, kv_len)`` — every q tile's, as
    ``_tile_extent`` sizes them — and how many of them take the masked
    body (a block some row of the tile does not see whole).  The same
    rule in both launches: ``ragged_tile_geometry``'s for the
    grouped-query one, ``_LATENT_TILE_TOKENS`` and
    ``_latent_pages_per_block`` for the latent one.  Host integers
    only."""
    kb = pages_per_block
    ql = np.maximum(np.asarray(q_lens, np.int64), 0)
    kl = np.asarray(kv_lens, np.int64)
    per = -(-ql // tile)                          # tiles a span
    span = np.repeat(np.arange(ql.size), per)
    first = (np.arange(span.size) - np.repeat(np.cumsum(per) - per, per)) \
        * tile
    pos0 = (kl - ql)[span] + first
    kv_end = np.minimum(kl[span], pos0 + np.minimum(ql[span] - first, tile))
    n_pages = np.minimum(-(-kv_end // block_size), bt_width)
    n_blk = -(-n_pages // kb)
    whole = np.minimum(np.maximum(pos0 + 1, 0) // (kb * block_size), n_blk)
    return int(n_blk.sum()), int((n_blk - whole).sum())


def _ragged_work_list(q_lens, tile: int, n_tiles: int):
    """Traced ``(span, first_row, rows)`` int32 [n_tiles] tables: span
    s contributes ceil(q_len / tile) consecutive tiles, spans in
    order; entries past the last real tile carry rows = 0."""
    S = q_lens.shape[0]
    ql = jnp.maximum(q_lens, 0)
    per_span = (ql + (tile - 1)) // tile
    ends = jnp.cumsum(per_span)
    i = jnp.arange(n_tiles, dtype=jnp.int32)
    span = jnp.sum((ends[None, :] <= i[:, None]).astype(jnp.int32), axis=1)
    live = span < S
    span = jnp.minimum(span, S - 1)
    first = (i - (ends[span] - per_span[span])) * tile
    rows = jnp.where(live, jnp.minimum(ql[span] - first, tile), 0)
    return (span.astype(jnp.int32), first.astype(jnp.int32),
            rows.astype(jnp.int32))


def _tile_extent(i, rows, ts_ref, tr_ref, qoff_ref, qlen_ref, kvlen_ref, *,
                 block_size: int, pages_per_span: int,
                 pages_per_block: int):
    """Work-list entry ``i`` of a ragged launch, for a tile of ``rows``
    real tokens: ``(span, tok0, pos0, kv_end, n_pages, n_blk)`` — the
    tile's first token in the pack, that token's global position, one
    past the last key any of its rows may see, and the pages and key
    blocks that hold those keys."""
    bs, kb = block_size, pages_per_block
    s = ts_ref[i]
    first = tr_ref[i]
    kv_len = kvlen_ref[s]
    tok0 = qoff_ref[s] + first
    pos0 = kv_len - qlen_ref[s] + first
    kv_end = jnp.minimum(kv_len, pos0 + rows)
    n_pages = jnp.minimum((kv_end + (bs - 1)) // bs,
                          jnp.int32(pages_per_span))
    n_blk = (n_pages + (kb - 1)) // kb
    return s, tok0, pos0, kv_end, n_pages, n_blk


def _page_block_copies(bt_ref, s, n_pages, block_size: int,
                       pages_per_block: int, streams):
    """``block_copies(b, slot, go)`` for a ragged launch's page stream:
    ``go`` (start or wait) each copy of key block ``b``'s pages into
    VMEM slot ``slot``, as many pages as the tile can see.  ``streams``
    is ``[(pool in HBM, two-slot VMEM buffer, slot -> semaphore)]``: a
    page is copied as stored, once a pool."""
    bs, kb = block_size, pages_per_block

    def block_copies(b, slot, go):
        def page(j, _):
            page = bt_ref[s, b * kb + j]
            dst = pl.ds(j * bs, bs)
            for hbm, buf, sem in streams:
                go(pltpu.make_async_copy(hbm.at[page], buf.at[slot, dst],
                                         sem(slot)))
            return 0
        lax.fori_loop(jnp.int32(0),
                      jnp.minimum(n_pages - b * kb, jnp.int32(kb)),
                      page, 0)
    return block_copies


def _stream_key_blocks(n_blk, block_copies, block_math, lo=None, hi=None):
    """Walk a tile's key blocks through the two VMEM slots: block b+1's
    copies are issued before block b's are waited for and
    ``block_math(b, slot)`` runs.  Block 0's copies are already started
    (beside the q tile's).  ``lo`` / ``hi`` walk blocks ``[lo, hi)`` of
    the ``n_blk`` only (default: all), the prefetch still running on to
    ``n_blk``: consecutive calls over consecutive ranges are one stream
    under more than one body."""
    def body(b, _):
        slot = lax.rem(b, jnp.int32(2))

        @pl.when(b + 1 < n_blk)
        def _prefetch():
            block_copies(b + 1, 1 - slot, lambda c: c.start())
        block_copies(b, slot, lambda c: c.wait())
        block_math(b, slot)
        return 0

    lax.fori_loop(jnp.int32(0) if lo is None else lo,
                  n_blk if hi is None else hi, body, 0)


def _reset_softmax_state(m_s, l_s, acc_s):
    m_s[...] = jnp.full(m_s.shape, _F32_NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)


def _write_tile(obuf, o_hbm, tok0, sem):
    """The finished tile back to its tokens' rows of the pack."""
    o_copy = pltpu.make_async_copy(
        obuf, o_hbm.at[pl.ds(tok0, obuf.shape[0])], sem)
    o_copy.start()
    o_copy.wait()


def _ragged_launch(kernel, name: str, q, pools, out_width: int,
                   block_tables, q_offsets, q_lens, kv_lens, tile: int,
                   scratch_shapes, extra_prefetch=(), interpret=False):
    """One ragged ``pallas_call``: the work list of ``ceil(T / tile) +
    S`` q tiles (a static bound; the real count and every descriptor
    are traced data) and the span tables go in as scalar prefetch
    (then ``extra_prefetch``), the pack padded by one tile (the last
    tile's ``[tok0, tok0 + tile)`` window stays inside the operand and
    the output) and the ``pools`` untouched stay in HBM, and the output
    ``[T, H, out_width]`` aliases a zero buffer, so rows no tile owns
    (the pack's padding) read zeros.  Call under ``_x64_off()``."""
    T, H, _ = q.shape
    n_tiles = -(-T // tile) + block_tables.shape[0]
    q_lens = q_lens.astype(jnp.int32)
    prefetch = list(_ragged_work_list(q_lens, tile, n_tiles))
    prefetch += [q_offsets.astype(jnp.int32), q_lens,
                 kv_lens.astype(jnp.int32),
                 jnp.maximum(block_tables, 0).astype(jnp.int32)]
    prefetch += list(extra_prefetch)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_tiles,),
        in_specs=[any_spec] * (len(pools) + 2),
        out_specs=any_spec,
        scratch_shapes=scratch_shapes,
    )
    q_pad = jnp.pad(q, ((0, tile), (0, 0), (0, 0)))
    o_init = jnp.zeros((T + tile, H, out_width), q.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(o_init.shape, q.dtype),
        input_output_aliases={len(prefetch) + len(pools) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*prefetch, q_pad, *pools, o_init)
    return out[:T]


def _kv_heads(buf):
    """``(h, rows [n, D])`` for every kv head of a ``[n, Hkv, D]`` VMEM
    block that holds pages AS STORED.  Head is the second-minor dim, so
    for a packed type (bf16: 2, int8: 4 rows a 32-bit sublane) the rows
    of ``packing`` heads share each word: one strided load of the
    words, then each head's bits are shifted out (both exact)."""
    n, hkv, d = buf.shape
    pack = 4 // jnp.dtype(buf.dtype).itemsize
    if pack == 1 or hkv % pack:
        for h in range(hkv):
            yield h, buf[:, h, :]
        return
    words = buf.reshape(n * hkv, d).bitcast(jnp.uint32)
    for hp in range(hkv // pack):
        w = words[pl.ds(hp, n, stride=hkv // pack), :]
        x = None if pack == 2 else pltpu.bitcast(w, jnp.int32)
        for lane in range(pack):
            if pack == 2:                       # bf16: the word's halves
                bits = (w << 16) if lane == 0 \
                    else (w & jnp.uint32(0xFFFF0000))
                rows = pltpu.bitcast(bits, jnp.float32).astype(
                    jnp.bfloat16)
            else:
                rows = ((x << (24 - 8 * lane)) >> 24).astype(buf.dtype)
            yield hp * pack + lane, rows


def _ragged_decode_cell(decode, i, ts_ref, tr_ref, qoff_ref, qlen_ref,
                        kvlen_ref, bt_ref, q_hbm, k_hbm, v_hbm, o_hbm, qbuf,
                        obuf, kbuf, vbuf, kv_sem, qo_sem, m_s, l_s, acc_s, *,
                        block_size: int, pages_per_span: int,
                        pages_per_block: int, scale: float):
    """Grid cell i of ``_ragged_paged_kernel`` where ``decode`` holds:
    a one-token span at one group (``ragged_decode_cell``).  Its token's
    ``[H, D]`` q row comes in and its output row goes out, nothing
    more; every kv head is worked at once on the pages AS STORED, on
    the vector unit: the q row broadcast over a step's keys ``[c, Hkv,
    D]``, a lane sum to scores ``[c, Hkv, 1]``, the online softmax over
    the leading key axis, and ``p x V`` summed over the keys into
    ``[1, Hkv, D]`` — no head-major copy, no loop over the heads, no
    MXU.  Keys stream in the tile cell's blocks through the same two
    slots; a block wholly before ``kv_end`` is walked unmasked, the
    last one as far as ``kv_end`` (a traced trip count), masked by
    ``kv_end`` alone: a lone token has no diagonal.
    Everything is float32: q upcast with the softmax scale folded in,
    K and V upcast, products and sums float32 (the tile cell rounds p
    to the pool's type for ``p.V``; this one does not).  The state
    ``m_s, l_s [1, Hkv, 1]``, ``acc_s [1, Hkv, D]`` lives in VMEM
    between blocks and in vregs within one."""
    bs, kb = block_size, pages_per_block
    hkv = acc_s.shape[1]
    n = kb * bs
    # keys a step: _RAGGED_DECODE_ROWS page rows, a power of two that
    # divides the block
    ck = math.gcd(n, 1 << (max(1, _RAGGED_DECODE_ROWS // hkv)
                           .bit_length() - 1))
    f32 = jnp.float32

    def walk_blocks(n_whole, n_blk, kv_end, block_copies, q):
        def block_math(b, slot):
            def step(c, state, masked):
                k = kbuf[slot, pl.ds(c * ck, ck)].astype(f32)
                sc = jnp.sum(k * q, axis=2, keepdims=True)   # [ck, Hkv, 1]
                if masked:
                    cols = b * n + c * ck + lax.broadcasted_iota(
                        jnp.int32, sc.shape, 0)
                    sc = jnp.where(cols < kv_end, sc, _F32_NEG_INF)
                v = vbuf[slot, pl.ds(c * ck, ck)].astype(f32)
                # every step holds a visible key: m stays finite
                return online_softmax_update(
                    state, sc, None,
                    lambda p: jnp.sum(p * v, axis=0, keepdims=True),
                    axis=0)

            def walk(masked: bool):
                state = (m_s[...], l_s[...], acc_s[...])
                if masked:
                    steps = jnp.clip(lax.div(kv_end - b * n + (ck - 1),
                                             jnp.int32(ck)), 1, n // ck)
                    state = lax.fori_loop(
                        jnp.int32(0), steps,
                        lambda c, st: step(c, st, True), state)
                else:
                    state = lax.fori_loop(
                        0, n // ck, lambda c, st: step(c, st, False),
                        state)
                m_s[...], l_s[...], acc_s[...] = state

            whole = b < n_whole
            pl.when(whole)(lambda: walk(False))
            pl.when(jnp.logical_not(whole))(lambda: walk(True))

        _stream_key_blocks(n_blk, block_copies, block_math)

    @pl.when(decode)
    def _decode():
        s, tok0, _, kv_end, n_pages, n_blk = _tile_extent(
            i, jnp.int32(1), ts_ref, tr_ref, qoff_ref, qlen_ref,
            kvlen_ref, block_size=bs, pages_per_span=pages_per_span,
            pages_per_block=kb)
        block_copies = _page_block_copies(
            bt_ref, s, n_pages, bs, kb,
            [(k_hbm, kbuf, lambda slot: kv_sem.at[slot, 0]),
             (v_hbm, vbuf, lambda slot: kv_sem.at[slot, 1])])
        q_copy = pltpu.make_async_copy(q_hbm.at[pl.ds(tok0, 1)],
                                       qbuf.at[pl.ds(0, 1)], qo_sem.at[0])
        q_copy.start()
        block_copies(jnp.int32(0), 0, lambda c: c.start())
        q_copy.wait()

        m_s[...] = jnp.full(m_s.shape, _F32_NEG_INF, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)
        walk_blocks(jnp.minimum(lax.div(kv_end, jnp.int32(n)), n_blk),
                    n_blk, kv_end, block_copies,
                    (qbuf[0].astype(f32) * np.float32(scale))[None])
        o = acc_s[...] / jnp.maximum(l_s[...], np.float32(1e-30))
        obuf[0] = o[0].astype(obuf.dtype)
        _write_tile(obuf.at[pl.ds(0, 1)], o_hbm, tok0, qo_sem.at[1])


def _ragged_paged_kernel(*refs, block_size: int, pages_per_span: int,
                         pages_per_block: int, pages_per_sub: int,
                         scale: float, groups: int, quantized: bool):
    """Grid cell i: one q TILE — ``rows`` consecutive tokens of one
    span, from the work list — against every local kv head's pages
    (arXiv:2604.15464 "Ragged Paged Attention").

    Rows follow tokens.  The tile's tokens are DMA'd from the
    token-major pack ``[T, H, D]`` at their real offset and its output
    rows go back the same way; a verify span costs one tile, a chunk
    ceil(q_len / tile) tiles, a ``q_len == 0`` span nothing.  A tile
    writes its full height: the rows past ``rows`` are finite garbage
    that land on the NEXT tile's tokens (tiles run in token order on
    one core, so the owner overwrites them) or in the pack's padding.

    A one-token span (``ragged_decode_cell``: a decode step, or a
    chunk of one token) takes the DECODE CELL instead, a second body of
    the same launch chosen per grid cell from ``q_len``
    (``_ragged_decode_cell``): it copies in its token's q row and
    writes back its output row alone, and works every kv head at once
    on the vector unit.  It writes no row past its own, so the rows a
    tile cell's garbage reaches are still overwritten by their owners,
    which run later, decode cells included.  An int8 pool keeps the
    tile cell for every span (its scales fold into int8 products on
    the MXU).

    Pages arrive as stored: one contiguous ``[block_size, Hkv, D]``
    copy a page serves every kv head, ``pages_per_block`` pages make
    one key block, and blocks stream through two VMEM slots (block
    b+1's copies are issued before block b's math).  No copy is issued
    and no block-table entry is read for a page past what the tile can
    see — ``kv_end = min(kv_len, position of its last row + 1)`` — so
    unused pages are never touched (the poisoned-pages invariant) and a
    chunk tile skips the keys its causal mask would drop anyway.

    How a block is walked follows what the tile observes.  A block that
    the tile's FIRST row sees whole (its last key at or before that
    row's position, hence before ``kv_end``) is visible to every row:
    one update over its whole width with no mask.  The diagonal's block
    and a partly filled last one are walked in sub-blocks of
    ``pages_per_sub`` pages, as far as ``kv_end`` (a traced trip
    count), each under the mask.  An arrived block is laid head-major
    (``[Hkv, sub-block, n, D]``: each head's rows shifted out of the
    packed sublanes once), sub-block by sub-block as far as it will be
    walked; that copy is traced once, and only the per-head update (a
    loop over the kv heads with a traced index) twice, once a body.

    Causality is positional: row r of span s sits at global position
    ``kv_len - q_len + r`` and sees keys at positions <= that, so decode
    steps, mid-prompt chunks, and prefix-hit suffixes are all the same
    span shape to this kernel.  Online softmax in fp32; ``q.K^T`` and
    ``p.V`` run in the pool's type with fp32 accumulation, the softmax
    scale applied to the fp32 scores.

    int8 pools (``quantized=True``): the MXU consumes the int8 codes
    directly.  The tile's q rows are quantized once per cell to per-row
    int8 (``quantize_rows_symmetric``), ``q.K^T`` is int8 x int8 with
    int32 accumulate, and the per-row q scale, the per-page-per-head k
    scales (two scalar-prefetch tables, laid along the keys' columns)
    and the softmax scale fold into the scores; ``p.V`` is int8 x int8
    too, the v scales folded into p before its per-row quantization.
    """
    from ..quantization.functional import (fold_int8_scores,
                                           quantize_rows_symmetric)
    (ts_ref, tr_ref, tn_ref, qoff_ref, qlen_ref, kvlen_ref,
     bt_ref) = refs[:7]
    n_pref = 9 if quantized else 7
    ks_ref, vs_ref = (refs[7], refs[8]) if quantized else (None, None)
    (q_hbm, k_hbm, v_hbm, _, o_hbm, qbuf, obuf, q2, m_s, l_s, acc_s,
     kbuf, vbuf, k_hm, v_hm, kv_sem, qo_sem) = refs[n_pref:n_pref + 17]
    qs_s = refs[-1] if quantized else None      # per-row q scales
    i = pl.program_id(0)
    rows = tn_ref[i]
    hkv = kbuf.shape[2]
    bq, _, d = qbuf.shape
    r = bq * groups
    bs, kb, sp = block_size, pages_per_block, pages_per_sub
    n, ns = kb * bs, sp * bs            # keys of a block, of a sub-block
    cdt = q2.dtype

    @pl.when(i == 0)
    def _clean_slots():
        # a partly filled last block multiplies p = 0 into whatever its
        # unfetched rows hold: make that finite once
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    tile_cell = rows > 0
    if ragged_decode_cell(1, groups, quantized):    # the launch has one
        decode = tile_cell & ragged_decode_cell(qlen_ref[ts_ref[i]],
                                                groups, quantized)
        tile_cell = tile_cell & jnp.logical_not(decode)
        _ragged_decode_cell(
            decode, i, ts_ref, tr_ref, qoff_ref, qlen_ref, kvlen_ref,
            bt_ref, q_hbm, k_hbm, v_hbm, o_hbm, qbuf, obuf, kbuf, vbuf,
            kv_sem, qo_sem, *refs[n_pref + 17:n_pref + 20],
            block_size=bs, pages_per_span=pages_per_span,
            pages_per_block=kb, scale=scale)

    @pl.when(tile_cell)
    def _tile():
        s, tok0, pos0, kv_end, n_pages, n_blk = _tile_extent(
            i, rows, ts_ref, tr_ref, qoff_ref, qlen_ref, kvlen_ref,
            block_size=bs, pages_per_span=pages_per_span,
            pages_per_block=kb)
        # blocks [0, n_whole) lie wholly under the diagonal: the first
        # row sees keys [0, pos0], all of them before kv_end
        n_whole = jnp.minimum(
            lax.div(jnp.maximum(pos0 + 1, 0), jnp.int32(n)), n_blk)
        block_copies = _page_block_copies(
            bt_ref, s, n_pages, bs, kb,
            [(k_hbm, kbuf, lambda slot: kv_sem.at[slot, 0]),
             (v_hbm, vbuf, lambda slot: kv_sem.at[slot, 1])])

        q_copy = pltpu.make_async_copy(q_hbm.at[pl.ds(tok0, bq)], qbuf,
                                       qo_sem.at[0])
        q_copy.start()
        block_copies(jnp.int32(0), 0, lambda c: c.start())
        q_copy.wait()

        for h in range(hkv):
            # [bq, groups, D] -> [bq * groups, D]: row t * groups + j
            # is token t, q head j of the group (a sub-word second-minor
            # slice folds in f32)
            qh = qbuf[:, h * groups:(h + 1) * groups, :]
            if groups % (4 // qh.dtype.itemsize):
                qh = qh.astype(jnp.float32)
            qh = qh.reshape(r, d)
            if quantized:
                qh, qs_s[h] = quantize_rows_symmetric(qh)
            q2[h] = qh.astype(cdt)
        _reset_softmax_state(m_s, l_s, acc_s)

        tok = lax.div(lax.broadcasted_iota(jnp.int32, (r, 1), 0),
                      jnp.int32(groups))
        qpos = pos0 + tok

        def col_scales(tab_ref, h, p0, width):
            """[1, width] f32: each column's page's scale of kv head h,
            the columns' pages starting at the span's page p0."""
            page_of_col = lax.div(
                lax.broadcasted_iota(jnp.int32, (1, width), 1),
                jnp.int32(bs))
            out = jnp.zeros((1, width), jnp.float32)
            for j in range(width // bs):
                p = jnp.minimum(p0 + j, n_pages - 1)
                out = jnp.where(page_of_col == j,
                                tab_ref[h, bt_ref[s, p]], out)
            return out

        def head_math(h, k, v, c0, p0, masked: bool):
            """Keys ``[c0, c0 + width)`` of the span (``k``, ``v``:
            ``[width, D]`` of kv head h, from the span's page p0)
            against the head's q rows (a traced head index: the state
            and the head-major block are indexed on their leading
            dim)."""
            width = k.shape[0]
            qh = q2[h]
            if quantized:
                si = lax.dot_general(qh, k, _DIMNUM_NT,
                                     preferred_element_type=jnp.int32)
                sc = fold_int8_scores(si, qs_s[h],
                                      col_scales(ks_ref, h, p0, width),
                                      scale)
                v_s = col_scales(vs_ref, h, p0, width)
            else:
                sc = lax.dot_general(
                    qh, k, _DIMNUM_NT,
                    preferred_element_type=jnp.float32) * np.float32(scale)
            ok = None
            if masked:
                cols = c0 + lax.broadcasted_iota(jnp.int32, (r, width), 1)
                ok = (cols <= qpos) & (cols < kv_end)
                sc = jnp.where(ok, sc, _F32_NEG_INF)

            def pv_of_p(p):
                if quantized:
                    p_codes, p_s = quantize_rows_symmetric(p * v_s)
                    pvi = lax.dot_general(
                        p_codes, v, _DIMNUM_NN,
                        preferred_element_type=jnp.int32)
                    return fold_int8_scores(pvi, p_s, 1.0)
                return lax.dot_general(p.astype(cdt), v, _DIMNUM_NN,
                                       preferred_element_type=jnp.float32)

            m_s[h], l_s[h], acc_s[h] = online_softmax_update(
                (m_s[h], l_s[h], acc_s[h]), sc, ok, pv_of_p)

        def block_math(b, slot):
            whole = b < n_whole
            # the sub-blocks walked: all of a whole block; of the
            # diagonal's or the span's last, as far as kv_end
            n_sub = jnp.where(
                whole, jnp.int32(kb // sp),
                jnp.clip(lax.div(kv_end - b * n + (ns - 1), jnp.int32(ns)),
                         1, kb // sp))

            def lay_head_major(c, _):
                # [ns, Hkv, D] as stored -> [Hkv, ns, D]
                for src, dst in ((kbuf, k_hm), (vbuf, v_hm)):
                    for h, rows_h in _kv_heads(
                            src.at[slot, pl.ds(c * ns, ns)]):
                        dst[h, c] = rows_h.astype(cdt)
                return 0
            lax.fori_loop(jnp.int32(0), n_sub, lay_head_major, 0)

            @pl.when(whole)
            def _unmasked():
                def head(h, _):
                    head_math(h, k_hm[h].reshape(n, d),
                              v_hm[h].reshape(n, d), b * n, b * kb, False)
                    return 0
                lax.fori_loop(jnp.int32(0), jnp.int32(hkv), head, 0)

            @pl.when(jnp.logical_not(whole))
            def _masked():
                def head(h, _):
                    def sub(c, _):
                        head_math(h, k_hm[h, c], v_hm[h, c],
                                  b * n + c * ns, b * kb + c * sp, True)
                        return 0
                    lax.fori_loop(jnp.int32(0), n_sub, sub, 0)
                    return 0
                lax.fori_loop(jnp.int32(0), jnp.int32(hkv), head, 0)

        _stream_key_blocks(n_blk, block_copies, block_math)

        for h in range(hkv):
            o = acc_s[h] / jnp.maximum(l_s[h], np.float32(1e-30))
            obuf[:, h * groups:(h + 1) * groups, :] = (
                o.reshape(bq, groups, d).astype(obuf.dtype))
        _write_tile(obuf, o_hbm, tok0, qo_sem.at[1])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_paged_attention_pallas(q, key_cache, value_cache,
                                   block_tables, q_offsets, q_lens,
                                   kv_lens, scale, interpret=False,
                                   key_scale=None, value_scale=None):
    """q: [T, H, D] packed ragged tokens; block_tables [S, W]; span
    tables [S] (q_offsets ascending, padding spans pinned past the last
    token — the same contract as ``_ragged_attention_xla``); pools
    ``[num_blocks, block_size, Hkv, D]`` in their stored type.  Returns
    [T, H, D].  Jitted so that a step traces and lowers the launch once
    a budget, not once a layer (the kernel body is unrolled over the kv
    heads: about a second of Python a trace).

    Nothing here is sized by a span window or by the pool
    (``_ragged_launch``: the work list, the pack's padding of one tile,
    the pools handed over untouched).

    Head sharding (tensor-parallel serving): the kernel is
    shard-oblivious — every head index here is LOCAL.  Each chip calls
    it with its own head shard (H/tp queries, Hkv/tp kv heads) against
    its head shard of every page, and no global head id ever appears,
    so the same kernel serves single-chip and per-chip-shard launches
    without index plumbing.  The only cross-shard invariant is that the
    GQA group size H/Hkv survives the shard (both divide by tp) —
    checked below.
    """
    T, H, D = q.shape
    bs, Hkv = key_cache.shape[1], key_cache.shape[2]
    if Hkv <= 0 or H % Hkv:
        raise ValueError(
            "ragged paged attention: %d query heads do not group over "
            "%d kv heads — under tensor parallelism shard both by the "
            "same tp degree so the GQA group size is preserved"
            % (H, Hkv))
    groups = H // Hkv
    S, W = block_tables.shape
    quantized = key_scale is not None
    tile, kb = ragged_tile_geometry(H, Hkv, D, bs, W, q.dtype,
                                    key_cache.dtype)
    sp = _ragged_sub_pages(bs, W)
    cdt = _ragged_compute_dtype(q.dtype, key_cache.dtype)
    rows = tile * groups

    kernel = functools.partial(
        _ragged_paged_kernel, block_size=bs, pages_per_span=W,
        pages_per_block=kb, pages_per_sub=sp, scale=scale, groups=groups,
        quantized=quantized)
    with _x64_off(), jax.named_scope("attn.kernel"):
        # [phys, Hkv] -> [Hkv, phys] so the kernel indexes [h, page]
        scales = [key_scale.astype(jnp.float32).T,
                  value_scale.astype(jnp.float32).T] if quantized else []
        scratch = [
            pltpu.VMEM((tile, H, D), q.dtype),             # q tile
            pltpu.VMEM((tile, H, D), q.dtype),             # o tile
            pltpu.VMEM((Hkv, rows, D), cdt),               # folded q
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),       # m
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),       # l
            pltpu.VMEM((Hkv, rows, D), jnp.float32),       # acc
            pltpu.VMEM((2, kb * bs, Hkv, D), key_cache.dtype),
            pltpu.VMEM((2, kb * bs, Hkv, D), value_cache.dtype),
            # the block head-major, sub-block by sub-block
            pltpu.VMEM((Hkv, kb // sp, sp * bs, D), cdt),
            pltpu.VMEM((Hkv, kb // sp, sp * bs, D), cdt),
            pltpu.SemaphoreType.DMA((2, 2)),     # [slot, k | v]
            pltpu.SemaphoreType.DMA((2,)),       # q in, o out
        ] + ([pltpu.VMEM((Hkv, rows, 1), jnp.float32)]   # q scales
             if quantized else [])
        if ragged_decode_cell(1, groups, quantized):
            scratch += [pltpu.VMEM((1, Hkv, 1), jnp.float32)] * 2 \
                + [pltpu.VMEM((1, Hkv, D), jnp.float32)]  # decode m, l, acc
        return _ragged_launch(
            kernel, "ragged_paged_attention", q,
            (key_cache, value_cache), D, block_tables, q_offsets, q_lens,
            kv_lens, tile, scratch, extra_prefetch=scales,
            interpret=interpret)


# ---------------------------------------------------------------------------
# ragged paged LATENT attention (MLA, absorbed form): every query head
# of a token reads the SAME cached row
# ---------------------------------------------------------------------------
# A page row is one token's latent: ``[c_kv | k_rope | zeros]`` padded to
# whole 128-lane tiles (``latent_row_width``).  Keys are the whole row
# (the absorbed query is zero over the padding), values its first
# ``v_width`` columns, so one DMA a page serves both matmuls and there
# is no second pool.  A q tile is _LATENT_TILE_TOKENS tokens x H heads
# rows; inside a cell the rows are walked _LATENT_SUB_TOKENS tokens at a
# time with a traced trip count, so a one-token decode span multiplies
# H rows and a chunk tile all of them.  Keys stream in blocks of
# _LATENT_KV_BLOCK; the body is traced twice, without a mask for the
# blocks wholly under a tile's diagonal and with one for its last.
_LATENT_TILE_TOKENS = 8
_LATENT_SUB_TOKENS = 2
# Keys of one block of the latent launch (whole pages: eight at the
# cell's 128-token pages).  A sub-tile's float32 accumulator ([256, 512])
# is four times its score tile at 128 keys, and its round trip, the
# m / l update and the two lane reductions a row come once a block: the
# launch alone reads 63 / 97 / 114 / 119 TFLOP/s at 128 / 256 / 512 /
# 1,024 keys (PERF.md, PR 30); 2,048 would overrun _RAGGED_TILE_VMEM.
_LATENT_KV_BLOCK = 1024


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Columns of one cached latent row: ``kv_lora_rank + rope_dim``
    rounded up to whole 128-lane tiles (576 -> 640), which is what the
    row takes in HBM's tiled layout and in VMEM whatever is declared."""
    return -(-(int(kv_lora_rank) + int(rope_dim)) // 128) * 128


def _latent_pages_per_block(block_size: int, bt_width: int) -> int:
    return max(1, min(_LATENT_KV_BLOCK // block_size, bt_width))


def _latent_cell_vmem_bytes(heads: int, row: int, v_width: int,
                            kv_block: int, itemsize: int) -> int:
    """VMEM bytes of one _latent_paged_kernel grid cell (mirrors the
    scratch_shapes of _ragged_latent_attention_pallas — edit both)."""
    bq, sub = _LATENT_TILE_TOKENS, _LATENT_SUB_TOKENS
    total = _tile_bytes((bq, heads, row), itemsize)             # q
    total += _tile_bytes((bq, heads, v_width), itemsize)        # o
    total += _tile_bytes((bq // sub, sub * heads, v_width), 4)  # acc
    total += 2 * _tile_bytes((bq // sub, sub * heads, 1), 4)    # m, l
    total += _tile_bytes((2, kv_block, row), itemsize)          # 2 slots
    total += 2 * _tile_bytes((sub * heads, kv_block), 4)        # scores, p
    return total


def latent_kernel_vmem_bytes(*, heads: int, kv_lora_rank: int,
                             rope_dim: int, block_size: int,
                             bt_width: int, dtype="bfloat16") -> int:
    kb = _latent_pages_per_block(block_size, bt_width)
    return _latent_cell_vmem_bytes(
        heads, latent_row_width(kv_lora_rank, rope_dim), kv_lora_rank,
        kb * block_size, jnp.dtype(dtype).itemsize)


def latent_attn_rows(q_lens, heads: int) -> int:
    """The q rows (tokens x heads) one latent launch multiplies for
    spans of these lengths: whole sub-tiles of the real tokens."""
    sub = _LATENT_SUB_TOKENS
    return heads * sub * sum(-(-max(int(n), 0) // sub) for n in q_lens)


def _latent_paged_kernel(*refs, block_size: int, pages_per_span: int,
                         pages_per_block: int, scale: float,
                         v_width: int):
    """Grid cell i: one q tile (``rows`` consecutive tokens of one span,
    every head of each) against the span's latent pages.  As
    ``_ragged_paged_kernel`` in its work list, its q / o copies at the
    tokens' real offset in the token-major pack, its two-slot page
    stream and its causal rule; what differs is that there is ONE
    cached row a token for all heads: a page ``[block_size, row]`` is
    the key block as stored (no head-major copy), the value block is its
    first ``v_width`` columns, and the per-head loop is a loop over
    sub-tiles of tokens with a traced bound.

    A key block that the tile's FIRST row sees whole (its last key at
    or before that row's position, hence before ``kv_end``) is visible
    to every row: it runs the update with no mask.  Only the tile's
    last blocks (the diagonal's, and a partly filled last one) build
    the mask; both bodies walk the one page stream in order."""
    (ts_ref, tr_ref, tn_ref, qoff_ref, qlen_ref, kvlen_ref,
     bt_ref) = refs[:7]
    (q_hbm, c_hbm, _, o_hbm, qbuf, obuf, m_s, l_s, acc_s, cbuf, kv_sem,
     qo_sem) = refs[7:]
    i = pl.program_id(0)
    rows = tn_ref[i]
    bq, heads, dk = qbuf.shape
    n_sub_max, r, _ = acc_s.shape
    sub = bq // n_sub_max
    bs, kb = block_size, pages_per_block
    n = kb * bs

    @pl.when(i == 0)
    def _clean_slots():
        # a partly filled last block multiplies p = 0 into whatever its
        # unfetched rows hold: make that finite once
        cbuf[...] = jnp.zeros(cbuf.shape, cbuf.dtype)

    @pl.when(rows > 0)
    def _tile():
        s, tok0, pos0, kv_end, n_pages, n_blk = _tile_extent(
            i, rows, ts_ref, tr_ref, qoff_ref, qlen_ref, kvlen_ref,
            block_size=bs, pages_per_span=pages_per_span,
            pages_per_block=kb)
        n_sub = (rows + (sub - 1)) // sub
        # blocks [0, n_whole) lie wholly under the diagonal: the first
        # row sees keys [0, pos0], all of them before kv_end
        n_whole = jnp.minimum(
            lax.div(jnp.maximum(pos0 + 1, 0), jnp.int32(n)), n_blk)
        block_copies = _page_block_copies(
            bt_ref, s, n_pages, bs, kb,
            [(c_hbm, cbuf, lambda slot: kv_sem.at[slot])])

        q_copy = pltpu.make_async_copy(q_hbm.at[pl.ds(tok0, bq)], qbuf,
                                       qo_sem.at[0])
        q_copy.start()
        block_copies(jnp.int32(0), 0, lambda c: c.start())
        _reset_softmax_state(m_s, l_s, acc_s)
        q_copy.wait()

        tok = lax.div(lax.broadcasted_iota(jnp.int32, (r, 1), 0),
                      jnp.int32(heads))

        def block_math(masked: bool):
            def math(b, slot):
                if masked:
                    cols = b * n + lax.broadcasted_iota(jnp.int32,
                                                        (r, n), 1)

                def sub_math(t, _):
                    k = cbuf[slot]                           # [n, row]
                    qh = qbuf[pl.ds(t * sub, sub)].reshape(r, dk)
                    sc = lax.dot_general(
                        qh, k, _DIMNUM_NT,
                        preferred_element_type=jnp.float32) \
                        * np.float32(scale)
                    ok = None
                    if masked:
                        ok = (cols <= pos0 + t * sub + tok) \
                            & (cols < kv_end)
                        sc = jnp.where(ok, sc, _F32_NEG_INF)

                    def pv_of_p(p):
                        return lax.dot_general(
                            p.astype(k.dtype), k[:, :v_width], _DIMNUM_NN,
                            preferred_element_type=jnp.float32)

                    m_s[t], l_s[t], acc_s[t] = online_softmax_update(
                        (m_s[t], l_s[t], acc_s[t]), sc, ok, pv_of_p)
                    return 0

                lax.fori_loop(jnp.int32(0), n_sub, sub_math, 0)
            return math

        _stream_key_blocks(n_blk, block_copies, block_math(False),
                           hi=n_whole)
        _stream_key_blocks(n_blk, block_copies, block_math(True),
                           lo=n_whole)

        o = acc_s[...] / jnp.maximum(l_s[...], np.float32(1e-30))
        obuf[...] = o.reshape(bq, heads, v_width).astype(obuf.dtype)
        _write_tile(obuf, o_hbm, tok0, qo_sem.at[1])


@functools.partial(jax.jit,
                   static_argnames=("scale", "v_width", "interpret"))
def _ragged_latent_attention_pallas(q, latent_cache, block_tables,
                                    q_offsets, q_lens, kv_lens, scale,
                                    v_width, interpret=False):
    """q: ``[T, H, row]`` absorbed queries (``[q_nope W_kvb[K] | q_rope |
    0]``) of a packed ragged batch; ``latent_cache [num_blocks,
    block_size, row]``; span tables as ``_ragged_paged_attention_pallas``
    (whose work list this reuses).  Returns ``[T, H, v_width]``: each
    head's probabilities over the cached ``c_kv`` rows, still to be
    multiplied by ``W_kvb[V]``."""
    T, H, row = q.shape
    bs = latent_cache.shape[1]
    S, W = block_tables.shape
    tile, sub = _LATENT_TILE_TOKENS, _LATENT_SUB_TOKENS
    kb = _latent_pages_per_block(bs, W)
    kernel = functools.partial(
        _latent_paged_kernel, block_size=bs, pages_per_span=W,
        pages_per_block=kb, scale=scale, v_width=v_width)
    with _x64_off(), jax.named_scope("attn.kernel"):
        scratch = [
            pltpu.VMEM((tile, H, row), q.dtype),             # q tile
            pltpu.VMEM((tile, H, v_width), q.dtype),         # o tile
            pltpu.VMEM((tile // sub, sub * H, 1), jnp.float32),   # m
            pltpu.VMEM((tile // sub, sub * H, 1), jnp.float32),   # l
            pltpu.VMEM((tile // sub, sub * H, v_width),
                       jnp.float32),                         # acc
            pltpu.VMEM((2, kb * bs, row), latent_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),       # page slots
            pltpu.SemaphoreType.DMA((2,)),       # q in, o out
        ]
        return _ragged_launch(
            kernel, "ragged_latent_attention", q, (latent_cache,),
            v_width, block_tables, q_offsets, q_lens, kv_lens, tile,
            scratch, interpret=interpret)


# ---------------------------------------------------------------------------
# fused RoPE + QKV epilogue (serving: one HBM round trip per layer's
# pre-attention transforms instead of three)
# ---------------------------------------------------------------------------
def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's blended inverse frequencies ``[dim / 2]`` (float32): the
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than
    ``beta_slow`` times are interpolated by ``factor``, and a linear
    ramp over the pair index blends the ones between."""
    def correction_dim(rotations):
        return (dim * math.log(original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    f = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature term ``0.1 mscale ln(factor) + 1``
    (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables_for_positions(positions, dim, base=10000.0,
                              inv_freq=None):
    """Neox cos/sin tables for a TOKEN-INDEXED position vector:
    positions [N] int32 (each token's GLOBAL position) -> (cos, sin)
    [N, dim] f32.  Bit-identical to the tables
    ``incubate.nn.functional.fused_rotary_position_embedding`` builds
    from ``position_ids`` (same inv-frequency expression, same f32
    order of operations), so swapping the serving steps onto the fused
    epilogue keeps fp32 engines byte-identical end-to-end.  Traceable;
    the serving steps call it ONCE per step and reuse the tables across
    every layer (the per-layer rebuild was pure waste — positions do
    not change between layers).  ``inv_freq [dim / 2]`` replaces the
    plain ``base`` frequencies (``yarn_inv_freq``)."""
    inv = (1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
           if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rope_interleaved(t, cos, sin):
    """RoPE over the last dim of ``t`` whose pairs are ``(2i, 2i + 1)``:
    de-interleave (evens, then odds), then rotate halves, as the
    published code does.  ``cos``/``sin`` broadcast against ``t`` and are
    ``[.., d]`` tables of ``[f, f]``.  float32 inside, ``t``'s type out."""
    d = t.shape[-1]
    tf = t.astype(jnp.float32)
    tf = jnp.swapaxes(tf.reshape(t.shape[:-1] + (d // 2, 2)), -1, -2
                      ).reshape(t.shape)
    rot = jnp.concatenate([-tf[..., d // 2:], tf[..., :d // 2]], axis=-1)
    return (tf * cos + rot * sin).astype(t.dtype)


def _rope_rows(t, cos, sin):
    """Neox rotation of token-major rows: t [N, Hx, D] x cos/sin [N, D]
    (broadcast over the head axis).  The SAME op order as
    ``fused_rotary_position_embedding``'s rope_one, so the values are
    bit-identical; shared by the XLA reference and the kernel body."""
    tf = t.astype(jnp.float32)
    half = tf.shape[-1] // 2
    rot = jnp.concatenate([-tf[..., half:], tf[..., :half]], axis=-1)
    return tf * cos[:, None, :] + rot * sin[:, None, :]


def _rope_qkv_kernel(*refs, with_amax: bool):
    """One row tile of the fused pre-attention epilogue: rope(q),
    rope(k), and (quantized pools) the per-token per-head K/V absmax
    rows the quantize-on-write scatter needs — one read of the
    projection outputs and one write, where the graph-level path cost
    a rope pass over q, a rope pass over k, and an abs-max pass over
    k/v (three HBM round trips of the same data)."""
    if with_amax:
        (q_ref, k_ref, v_ref, cos_ref, sin_ref,
         qo_ref, ko_ref, ka_ref, va_ref) = refs
    else:
        q_ref, k_ref, cos_ref, sin_ref, qo_ref, ko_ref = refs
        v_ref = ka_ref = va_ref = None
    cos = cos_ref[...]
    sin = sin_ref[...]
    qo_ref[...] = _rope_rows(q_ref[...], cos, sin).astype(qo_ref.dtype)
    ko = _rope_rows(k_ref[...], cos, sin).astype(ko_ref.dtype)
    ko_ref[...] = ko
    if with_amax:
        # absmax of the STORED values (post-cast), bit-matching what
        # _quant_write_tokens would recompute from the scattered rows
        ka_ref[...] = jnp.max(jnp.abs(ko.astype(jnp.float32)), axis=-1)
        va_ref[...] = jnp.max(jnp.abs(v_ref[...].astype(jnp.float32)),
                              axis=-1)


def _rope_qkv_epilogue_xla(q, k, v, cos, sin, with_amax):
    """Graph-level reference (CPU serving path + parity tests): the
    exact same f32 expressions as the kernel, so interpret-vs-XLA
    parity is byte-level and the CPU engines keep their end-to-end
    byte identity with eager generate."""
    q_rot = _rope_rows(q, cos, sin).astype(q.dtype)
    k_rot = _rope_rows(k, cos, sin).astype(k.dtype)
    if not with_amax:
        return q_rot, k_rot, None, None
    k_amax = jnp.max(jnp.abs(k_rot.astype(jnp.float32)), axis=-1)
    v_amax = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1)
    return q_rot, k_rot, k_amax, v_amax


def _rope_epilogue_tile(heads: int, head_dim: int, itemsize: int,
                        cap_rows: int = 512) -> int:
    """Row-tile chooser shared by the epilogue wrapper and the VMEM
    audit: the widest operand's tile stays under ~1 MiB so the kernel
    fits the 16 MiB serving budget at any head count (64 q heads ×
    D=128 would need 109 MiB at a fixed 512-row tile — the audit
    caught exactly that)."""
    cap = max(1, (1 << 20) // max(1, heads * head_dim * itemsize))
    tile = min(cap_rows, cap)
    if tile > 8:
        tile = (tile // 8) * 8
    return max(1, tile)


def rope_qkv_epilogue(q, k, v, cos, sin, with_amax: bool = False,
                      use_pallas=None, interpret=False, block_rows=512):
    """Fused pre-attention epilogue for the serving steps (round 17).

    q: [N, H, D], k/v: [N, Hkv, D] token-major projection outputs;
    cos/sin: [N, D] from :func:`rope_tables_for_positions`.  Applies
    neox RoPE to q and k at each token's global position and, for int8
    KV pools (``with_amax``), also emits the per-token per-head K/V
    absmax rows consumed by the quantize-on-write scatter — ONE Pallas
    pass over the projection outputs on TPU, replacing the separate
    rope-q / rope-k / absmax graph passes.  v itself is returned
    untouched by the caller (never copied here).

    Returns ``(q_rot, k_rot, k_amax, v_amax)`` (amaxes None unless
    ``with_amax``).  The XLA fallback is bit-identical to the kernel's
    math, so CPU dryrun engines stay byte-identical end-to-end.
    """
    if use_pallas is None:
        use_pallas = _device.on_tpu()
    if not (use_pallas or interpret):
        return _rope_qkv_epilogue_xla(q, k, v, cos, sin, with_amax)

    N, H, D = q.shape
    Hkv = k.shape[1]
    tile = min(_rope_epilogue_tile(H, D, q.dtype.itemsize, block_rows),
               N)
    pad = (-N) % tile
    if pad:
        widths = ((0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        if with_amax:
            v = jnp.pad(v, widths)
        cos = jnp.pad(cos, ((0, pad), (0, 0)))
        sin = jnp.pad(sin, ((0, pad), (0, 0)))
    rows = N + pad

    def spec(hx):
        return pl.BlockSpec((tile, hx, D), lambda i: (i, 0, 0))

    cs_spec = pl.BlockSpec((tile, D), lambda i: (i, 0))
    amax_spec = pl.BlockSpec((tile, Hkv), lambda i: (i, 0))
    in_specs = [spec(H), spec(Hkv)]
    args = [q, k]
    if with_amax:
        in_specs.append(spec(Hkv))
        args.append(v)
    in_specs += [cs_spec, cs_spec]
    args += [cos, sin]
    out_specs = [spec(H), spec(Hkv)]
    out_shape = [jax.ShapeDtypeStruct((rows, H, D), q.dtype),
                 jax.ShapeDtypeStruct((rows, Hkv, D), k.dtype)]
    if with_amax:
        out_specs += [amax_spec, amax_spec]
        out_shape += [jax.ShapeDtypeStruct((rows, Hkv), jnp.float32),
                      jax.ShapeDtypeStruct((rows, Hkv), jnp.float32)]

    with _x64_off():
        res = pl.pallas_call(
            functools.partial(_rope_qkv_kernel, with_amax=with_amax),
            grid=(rows // tile,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="rope_qkv_epilogue",
        )(*args)
    q_rot, k_rot = res[0][:N], res[1][:N]
    if with_amax:
        return q_rot, k_rot, res[2][:N], res[3][:N]
    return q_rot, k_rot, None, None


# ---------------------------------------------------------------------------
# grouped expert matmul: the product of a dropless MoE FFN over rows
# sorted by expert (``ops/moe_gate.sorted_expert_swiglu``)
# ---------------------------------------------------------------------------
# ``out[r] = xs[r] @ w[expert of r]`` for a buffer whose rows are sorted
# by expert, each expert's rows starting on a row-tile boundary.  The grid
# is (column tile of the weights, expert that has rows): an expert's
# ``[K, tn]`` weight block comes in through the BlockSpec pipeline, so the
# NEXT expert's block streams from HBM while this one's row tiles pass
# under the block that is resident, and every weight crosses HBM once a
# product however many row tiles its expert has.  The rows themselves stay
# in HBM: a cell copies its expert's tiles in (two slots) and its results
# out (two slots) itself, so nothing in VMEM is sized by the buffer and a
# tile past an expert's last row is never touched.  Experts without rows
# are not visited at all (their weights are not read).  With two weight
# banks the cell is the SwiGLU's first half: ``silu(x @ wg) * (x @ wu)``
# in float32 before the one cast, the rows read once for both.
_GROUPED_MIN_TILE = 16          # one packed bf16 sublane tile
_GROUPED_MAX_TILE = 128
_GROUPED_ROW_BUCKET = 512       # a multiple of every tile
# what one cell's buffers may take (tools/graftlint/vmem.py holds the
# family to half a v5e core); the compiler is given a quarter more
_GROUPED_VMEM = 48 << 20


def grouped_tile_rows(rows: int, experts: int) -> int:
    """The row tile of the grouped expert product, from the shapes a
    trace sees: the largest power of two, between one packed sublane
    tile and _GROUPED_MAX_TILE rows, that is at most HALF the rows an
    expert has if the ``rows`` of the sorted buffer spread evenly over
    the ``experts`` held (so padding each expert's rows to the tile
    wastes about a quarter of them, not more)."""
    tile = _GROUPED_MIN_TILE
    while tile < _GROUPED_MAX_TILE and 4 * tile * experts <= rows:
        tile *= 2
    return tile


def _grouped_step_rows(tile: int) -> int:
    """Rows one product of a cell multiplies: two tiles where the tile
    is _GROUPED_MAX_TILE (a weight tile latched into the MXU is worth
    more the more rows pass under it; the odd last tile goes alone),
    else the tile: smaller tiles belong to budgets whose products are
    bound by the weights' bytes, and a second body is a second trace."""
    return 2 * tile if tile == _GROUPED_MAX_TILE else tile


def grouped_widths_ok(*widths: int) -> bool:
    """The kernel's envelope: every width of the expert FFN (hidden,
    intermediate) a whole number of 128-lane tiles.  Anything else
    keeps XLA's grouped product."""
    return all(w > 0 and w % _LANES == 0 for w in widths)


def grouped_buffer_rows(rows: int, experts: int, tile: int) -> int:
    """Rows of the sorted buffer once every expert's rows start on a
    tile boundary: at most ``tile - 1`` rows of padding an expert, one
    tile of slack behind the last (the launch reads rows two tiles at a
    time where it multiplies them so), and the sum rounded up to
    _GROUPED_ROW_BUCKET, so that the small budgets of a step, which
    share a tile, share one traced launch too (a warm start is made of
    tracing: a launch is traced and lowered anew for every shape)."""
    need = (-(-(rows + experts * (tile - 1)) // tile) + 1) * tile
    return -(-need // _GROUPED_ROW_BUCKET) * _GROUPED_ROW_BUCKET


def _grouped_cell_vmem_bytes(k: int, tn: int, banks: int, tile: int,
                             itemsize: int) -> int:
    """VMEM bytes of one _grouped_matmul_kernel cell: the ``[K, tn]``
    weight block of each bank (x2: the pipeline's next block), the two
    slots of rows in and out (``_grouped_step_rows`` each), and the
    float32 products of that many rows."""
    step = _grouped_step_rows(tile)
    total = 2 * banks * _tile_bytes((k, tn), itemsize)
    total += 2 * _tile_bytes((step, k), itemsize)           # rows in
    total += 2 * _tile_bytes((step, tn), itemsize)          # rows out
    total += (banks + 1) * _tile_bytes((step, tn), 4)       # products
    return total


@functools.lru_cache(maxsize=None)
def grouped_column_tile(k: int, n: int, banks: int, tile: int,
                        itemsize: int = 2) -> int:
    """The widest column tile (a multiple of 128 lanes that divides
    ``n``) whose cell fits _GROUPED_VMEM: the rows are read once a
    column tile, so fewer and wider is less traffic."""
    fits = [tn for tn in range(_LANES, n + 1, _LANES)
            if n % tn == 0 and _grouped_cell_vmem_bytes(
                k, tn, banks, tile, itemsize) <= _GROUPED_VMEM]
    if not fits:
        raise ValueError(
            "grouped expert matmul: no column tile of a [%d, %d] bank "
            "fits %d MiB of VMEM" % (k, n, _GROUPED_VMEM >> 20))
    return fits[-1]


def grouped_kernel_vmem_bytes(*, hidden: int, ffn: int, tile: int,
                              itemsize: int = 2) -> int:
    """Worst-case VMEM bytes of one _grouped_matmul_kernel cell over an
    expert FFN of these widths: the larger of its two launches (gate
    and up in one, then down), each at the column tile the launch
    itself would pick."""
    return max(
        _grouped_cell_vmem_bytes(
            hidden, grouped_column_tile(hidden, ffn, 2, tile, itemsize),
            2, tile, itemsize),
        _grouped_cell_vmem_bytes(
            ffn, grouped_column_tile(ffn, hidden, 1, tile, itemsize),
            1, tile, itemsize))


def grouped_row_starts(load, tile: int):
    """``(tiles, starts)`` int32 ``[E]``: the row tiles each expert's
    ``load`` rows take and the tile-aligned row of the sorted buffer at
    which they start, the experts laid out in order."""
    tiles = (load.astype(jnp.int32) + (tile - 1)) // tile
    return tiles, (jnp.cumsum(tiles) - tiles) * tile


def grouped_slot_tables(load, tile: int):
    """Traced int32 ``[E]`` tables of the grouped launch from the rows
    each expert was given: ``(expert, first_row, tiles)`` of grid slot
    ``s``.  The experts that have rows come first, in order, each at
    the tile-aligned row where its rows start in the sorted buffer;
    the slots behind them repeat the last such expert with 0 tiles
    (the same weight block: nothing is fetched for them)."""
    e = load.shape[0]
    tiles, first = grouped_row_starts(load, tile)
    has = load > 0
    rank = jnp.cumsum(has.astype(jnp.int32)) - 1   # among those with rows
    live = rank[-1] + 1
    s = jnp.arange(e, dtype=jnp.int32)
    # slot s -> the expert of rank s (an [E, E] compare, no sort)
    ranked = jnp.sum(jnp.where(has[None, :] & (rank[None, :] == s[:, None]),
                               s[None, :], 0), axis=1)
    expert = ranked[jnp.minimum(s, jnp.maximum(live - 1, 0))].astype(
        jnp.int32)
    return (expert, first[expert].astype(jnp.int32),
            jnp.where(s < live, tiles[expert], 0).astype(jnp.int32))


def _grouped_matmul_kernel(expert_ref, first_ref, tiles_ref, x_hbm, *refs,
                           tile: int, tn: int, banks: int, step: int):
    """Grid cell (column tile j, slot s): the slot's expert's row tiles
    under its resident weight block(s), ``step`` rows a product: the
    tile, or two tiles while two are left and the odd last one alone;
    rows come in ``step`` at a time either way (the buffer ends in a
    tile of slack), results go out by what was computed.  The cell's
    first rows were asked for by the cell before it (the launch's first
    by itself), so only the launch's first copy is waited for with
    nothing to do."""
    del expert_ref                      # the weight BlockSpecs' own
    w_refs = refs[:banks]
    o_hbm, xbuf, obuf, sem = refs[banks:]
    j, s = pl.program_id(0), pl.program_id(1)
    n_j, n_s = pl.num_programs(0), pl.num_programs(1)
    n_sub = tiles_ref[s]
    pairs = step != tile
    n_full = n_sub // 2 if pairs else n_sub     # products of `step` rows

    def rows(slot_s, c, size):  # chunk c: an expert's rows start on a tile
        return pl.ds(pl.multiple_of(first_ref[slot_s] + c * step, tile),
                     size)

    def rows_in(slot_s, c, slot):
        return pltpu.make_async_copy(
            x_hbm.at[rows(slot_s, c, step)], xbuf.at[slot],
            sem.at[0, slot])

    def rows_out(c, slot, size):
        return pltpu.make_async_copy(
            obuf.at[slot, pl.ds(0, size)],
            o_hbm.at[rows(s, c, size),
                     pl.ds(pl.multiple_of(j * tn, tn), tn)],
            sem.at[1, slot])

    @pl.when((j == 0) & (s == 0) & (n_sub > 0))
    def _first():
        rows_in(s, 0, 0).start()

    def chunk(c, size):
        slot = lax.rem(c, jnp.int32(2))
        if size == step:                # a lone last tile has no next

            @pl.when((c + 1) * step < n_sub * tile)
            def _prefetch():
                rows_in(s, c + 1, 1 - slot).start()
        rows_in(s, c, slot).wait()
        x = xbuf[slot, pl.ds(0, size)]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if banks == 2:
            y = jax.nn.silu(y) * jnp.dot(
                x, w_refs[1][...], preferred_element_type=jnp.float32)

        @pl.when(c >= 2)
        def _slot_free():               # only a last chunk is a lone tile
            rows_out(c - 2, slot, step).wait()
        obuf[slot, pl.ds(0, size)] = y.astype(obuf.dtype)
        rows_out(c, slot, size).start()

    def full(c, _):
        chunk(c, step)
        return 0

    lax.fori_loop(jnp.int32(0), n_full, full, 0)
    # the next cell with rows: the slot behind this one, or (the slots
    # with rows come first) slot 0 under the next column tile.  Its
    # first rows go into slot 0, where it will look for them; both row
    # slots are free once this cell's last product is done
    behind = jnp.minimum(s + 1, n_s - 1)
    more = (s + 1 < n_s) & (tiles_ref[behind] > 0)
    n_chunk = n_full
    if pairs:
        odd = lax.rem(n_sub, jnp.int32(2)) == 1
        n_chunk = n_full + odd.astype(jnp.int32)

        @pl.when(odd)
        def _last_tile():
            chunk(n_full, tile)

    @pl.when((n_sub > 0) & (more | (j + 1 < n_j)))
    def _next_cell():
        rows_in(jnp.where(more, behind, 0), 0, 0).start()

    def out_done(c, size):
        rows_out(c, lax.rem(c, jnp.int32(2)), size).wait()

    # the cell's last one or two products are still on their way out;
    # the one before the last is never a lone tile
    @pl.when(n_chunk >= 2)
    def _drain_before_last():
        out_done(n_chunk - 2, step)
    last_full = n_full >= 1
    if pairs:
        last_full &= jnp.logical_not(odd)

        @pl.when(odd)
        def _drain_tile():
            out_done(n_full, tile)

    @pl.when(last_full)
    def _drain_last():
        out_done(n_full - 1, step)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_expert_matmul(xs, slots, *banks, tile: int, interpret=False):
    """``xs [R, K]`` rows sorted by expert, each expert's rows starting
    on a ``tile`` boundary and a tile of slack behind the last
    (``grouped_buffer_rows``; ``slots`` the launch's tables,
    ``grouped_slot_tables(load, tile)``), times that expert's ``[K,
    N]`` of ``banks``: one bank ``[E, K, N]`` gives ``xs @ w``, two
    give ``silu(xs @ wg) * (xs @ wu)``.  Operands in their own type
    (bf16), every product accumulated in float32, SiLU and the
    elementwise product in float32, one cast to ``xs``'s type.  Returns
    ``[R, N]``; a row of a tile no expert owns is never written (it
    holds whatever the buffer held).  Jitted with the tile static, so
    the layers of a step share one traced body a product shape."""
    R, K = xs.shape
    E, _, N = banks[0].shape
    if R % tile or any(b.shape != (E, K, N) for b in banks):
        raise ValueError(
            "grouped expert matmul: %d rows in tiles of %d against banks "
            "%s" % (R, tile, [b.shape for b in banks]))
    tn = grouped_column_tile(K, N, len(banks), tile, xs.dtype.itemsize)
    step = _grouped_step_rows(tile)
    kernel = functools.partial(_grouped_matmul_kernel, tile=tile, tn=tn,
                               banks=len(banks), step=step)
    with _x64_off():
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        w_spec = pl.BlockSpec(
            (None, K, tn), lambda j, s, expert, first, tiles:
            (expert[s], 0, j))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, E),
            in_specs=[any_spec] + [w_spec] * len(banks),
            out_specs=any_spec,
            scratch_shapes=[
                pltpu.VMEM((2, step, K), xs.dtype),        # rows in
                pltpu.VMEM((2, step, tn), xs.dtype),       # rows out
                pltpu.SemaphoreType.DMA((2, 2)),     # [in | out, slot]
            ])
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((R, N), xs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_GROUPED_VMEM * 5 // 4),
            interpret=interpret,
            name="grouped_expert_matmul",
        )(*slots, xs, *banks)


# ---------------------------------------------------------------------------
# ragged gated delta rule (Gated DeltaNet linear attention): one grid cell
# a span of the pack, its slot's state read once and written once
# ---------------------------------------------------------------------------
# The rule and its sequential form are ``ops/gated_delta.py``.  A span's
# state ``[H, dv, dk]`` float32 comes in and goes out through the BlockSpec
# pipeline (the bank aliased in and out, the block of the span's slot), so
# the next span's state streams while this one computes.  Rows come from
# the token-major pack by DMA at the span's offset, every head at once,
# with head widths padded to whole lanes: ``q`` carries beta and g in the
# two lanes after its ``dk`` (one copy for the three).  A decode span is
# the one-token update on the vector unit (``_gdn_decode_head``); a chunk
# is walked in sub-chunks of _GDN_CHUNK rows in FLA's chunked form
# (``_gdn_chunk_head``: the WY/UT representation within a sub-chunk, the
# decays cumulated in float32, the state carried to the next).  Every
# product is float32 (``Precision.HIGHEST``).  Output rows are written a
# whole window at a time: the rows past a span's end are finite garbage
# that the next span, later in the sequential grid, overwrites (the
# ragged attention launch's rule).
_GDN_CHUNK = 64
_GDN_DECODE_ROWS = 8
_GDN_VMEM = 96 << 20
_HI = lax.Precision.HIGHEST


def _gdn_widths(dk: int, dv: int):
    """``(dk_pad, dv_pad)``: q/k rows with beta and g behind ``dk``, and
    v / o rows, in whole 128-lane tiles."""
    return (-(-(dk + 2) // _LANES) * _LANES, -(-dv // _LANES) * _LANES)


def gdn_sub_chunks(q_lens) -> int:
    """The sub-chunks of _GDN_CHUNK rows the launch scans for spans of
    these lengths (a one-token span takes the decode update, no
    sub-chunk)."""
    q = np.asarray(q_lens, np.int64)
    q = q[q > 1]
    return int(np.sum(-(-q // _GDN_CHUNK)))


def _gdn_lane(x, lane: int):
    """Column ``lane`` of ``x [R, L]`` as ``[R, 1]``, by a masked lane
    sum: a layout Mosaic broadcasts along one axis at a time."""
    at = lax.broadcasted_iota(jnp.int32, x.shape, 1) == lane
    return jnp.sum(jnp.where(at, x, 0.0), axis=1, keepdims=True)


def _gdn_decode_head(st, h, qbuf, kbuf, vbuf, obuf, *, dk: int, dv: int):
    """Head ``h`` of a one-token span: ``S' = e^g S + beta (v - e^g S k)
    k^T``, ``o = S' q``, as broadcasts and lane / sublane sums (no
    matrix unit: a matrix-vector product).  ``v`` and ``o`` change
    between row and column through an identity mask."""
    qrow = qbuf[pl.ds(0, 1), h, :]                       # [1, dk_pad]
    krow = kbuf[pl.ds(0, 1), h, :][:, :dk]
    vrow = vbuf[pl.ds(0, 1), h, :]                       # [1, dv_pad]
    q = qrow[:, :dk]
    beta = jnp.broadcast_to(_gdn_lane(qrow, dk), (dv, 1))
    decay = jnp.broadcast_to(jnp.exp(_gdn_lane(qrow, dk + 1)), (1, dk))
    dvp = vrow.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (dv, dvp), 0)
           == lax.broadcasted_iota(jnp.int32, (dv, dvp), 1)
           ).astype(jnp.float32)
    s = st[0, h] * decay                                 # [dv, dk]
    sk = jnp.sum(s * krow, axis=1, keepdims=True)        # [dv, 1]
    vcol = jnp.sum(eye * vrow, axis=1, keepdims=True)
    s = s + (beta * (vcol - sk)) * krow
    st[0, h] = s
    ocol = jnp.sum(s * q, axis=1, keepdims=True)
    obuf[pl.ds(0, 1), h, :] = jnp.sum(eye * ocol, axis=0, keepdims=True)


def _unit_lower_inverse(a, t_i, i_i):
    """``(I + a)^-1`` of a strictly lower triangular ``a [C, C]`` (C a
    power of two, at least 4), by blocks: the 4 x 4 diagonal blocks as
    ``I - P + P^2 - P^3`` (exact, ``P^4 = 0``), then doubling: with ``X``
    the inverse of the diagonal blocks of size ``s`` and ``B`` the part
    of ``a`` below them in blocks of ``2s``, ``X - X B X`` is the inverse
    of the diagonal blocks of size ``2s``.  Each level multiplies
    inverses of the size before, so no power of ``a`` beyond the third
    is formed: the series ``sum (-a)^k`` over the whole chunk cancels
    terms of 1e16 to an answer of order 1 where keys are alike, as
    SiLU'd keys are, and ``beta`` nears 2."""
    C = a.shape[0]
    f32 = jnp.float32

    def dot(x, y):
        return jnp.dot(x, y, precision=_HI, preferred_element_type=f32)

    p = -jnp.where((t_i // 4) == (i_i // 4), a, 0.0)
    p2 = dot(p, p)
    eye = (t_i == i_i).astype(f32)
    x = eye + p + dot(p2, eye + p)
    s = 4
    while s < C:
        b = jnp.where(((t_i // (2 * s)) == (i_i // (2 * s)))
                      & ((t_i // s) % 2 == 1) & ((i_i // s) % 2 == 0),
                      a, 0.0)
        x = x - dot(x, dot(b, x))
        s *= 2
    return x


def _gdn_chunk_head(st, h, qbuf, kbuf, vbuf, obuf, rows, *, dk: int,
                    dv: int):
    """Head ``h`` of one sub-chunk of ``rows`` real rows (the rest
    masked: no key, no beta, no decay).  With ``G`` the decay cumulated
    within the sub-chunk, ``Gam[t, i] = exp(G_t - G_i)`` for ``i <= t``
    and ``A = strict_lower(beta k k^T Gam)``: ``T = (I + A)^-1``
    (``_unit_lower_inverse``, by blocks), ``W =
    T (beta e^G k)``, ``U = T (beta v) - W S^T``, ``o = e^G q S^T +
    lower(q k^T Gam) U`` and ``S' = e^G_C S + U^T (e^(G_C - G) k)``."""
    C = qbuf.shape[0]
    f32 = jnp.float32
    qrow = qbuf[:, h, :]                                   # [C, dk_pad]
    valid = lax.broadcasted_iota(jnp.int32, (C, 1), 0) < rows
    q = qrow[:, :dk]
    k = jnp.where(valid, kbuf[:, h, :][:, :dk], 0.0)
    v = vbuf[:, h, :][:, :dv]
    beta = jnp.where(valid, _gdn_lane(qrow, dk), 0.0)        # [C, 1]
    g = jnp.where(valid, _gdn_lane(qrow, dk + 1), 0.0)
    t_i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i_i = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (t_i == i_i).astype(f32)
    incl, strict = t_i >= i_i, t_i > i_i
    g_row = jnp.sum(eye * g, axis=0, keepdims=True)         # [1, C]
    G = jnp.sum(jnp.where(incl, g_row, 0.0), axis=1, keepdims=True)
    G_row = jnp.sum(eye * G, axis=0, keepdims=True)
    gam = jnp.where(incl, jnp.exp(jnp.where(incl, G - G_row, 0.0)), 0.0)
    G_last = G[C - 1:C, :]                                   # [1, 1]
    kk = lax.dot_general(k, k, _DIMNUM_NT, precision=_HI,
                         preferred_element_type=f32)
    a = jnp.where(strict, beta * kk * gam, 0.0)
    t_inv = _unit_lower_inverse(a, t_i, i_i)
    w = jnp.dot(t_inv, k * (beta * jnp.exp(G)), precision=_HI,
                preferred_element_type=f32)                  # [C, dk]
    u = jnp.dot(t_inv, v * beta, precision=_HI,
                preferred_element_type=f32)                  # [C, dv]
    s = st[0, h]                                             # [dv, dk]
    u = u - lax.dot_general(w, s, _DIMNUM_NT, precision=_HI,
                            preferred_element_type=f32)
    qk = lax.dot_general(q, k, _DIMNUM_NT, precision=_HI,
                         preferred_element_type=f32)
    o = (jnp.exp(G) * lax.dot_general(q, s, _DIMNUM_NT, precision=_HI,
                                      preferred_element_type=f32)
         + jnp.dot(jnp.where(incl, qk * gam, 0.0), u, precision=_HI,
                   preferred_element_type=f32))              # [C, dv]
    st[0, h] = jnp.exp(G_last) * s + lax.dot_general(
        u, k * jnp.exp(G_last - G), _DIMNUM_TN, precision=_HI,
        preferred_element_type=f32)
    obuf[:, h, :] = jnp.concatenate(
        [o, jnp.zeros((C, obuf.shape[2] - dv), f32)], axis=1) \
        if obuf.shape[2] > dv else o


def _gated_delta_kernel(slot_ref, qoff_ref, qlen_ref, kvlen_ref, q_hbm,
                        k_hbm, v_hbm, st_in, o_init, o_hbm, st, qbuf,
                        kbuf, vbuf, obuf, sem, *, heads: int, dk: int,
                        dv: int):
    """Grid cell s: span s of the pack against its slot's state (the
    section's comment).  ``heads`` of the windows' head rows are real."""
    del slot_ref, o_init
    s = pl.program_id(0)
    n = qlen_ref[s]
    off = qoff_ref[s]
    H = heads

    @pl.when(s == 0)
    def _clear():               # the rows a window writes past its span
        obuf[...] = jnp.zeros(obuf.shape, jnp.float32)

    @pl.when((n == 0) | (kvlen_ref[s] > n))
    def _carry():               # a padding span keeps what it read
        st[...] = st_in[...]

    @pl.when((n > 0) & (kvlen_ref[s] == n))
    def _fresh():
        st[...] = jnp.zeros(st.shape, jnp.float32)

    def window(row0, m):
        copies = [pltpu.make_async_copy(src.at[pl.ds(row0, m)],
                                        buf.at[pl.ds(0, m)], sem.at[i])
                  for i, (src, buf) in enumerate(
                      ((q_hbm, qbuf), (k_hbm, kbuf), (v_hbm, vbuf)))]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    def write(row0, m):
        c = pltpu.make_async_copy(obuf.at[pl.ds(0, m)],
                                  o_hbm.at[pl.ds(row0, m)], sem.at[3])
        c.start()
        c.wait()

    @pl.when(n == 1)
    def _decode():
        window(off, _GDN_DECODE_ROWS)

        def head(h, _):
            _gdn_decode_head(st, h, qbuf, kbuf, vbuf, obuf, dk=dk, dv=dv)
            return 0
        lax.fori_loop(0, H, head, 0)
        write(off, _GDN_DECODE_ROWS)

    @pl.when(n > 1)
    def _chunks():
        def sub_chunk(c, _):
            row0 = off + c * _GDN_CHUNK
            rows = n - c * _GDN_CHUNK
            window(row0, _GDN_CHUNK)

            def head(h, _):
                _gdn_chunk_head(st, h, qbuf, kbuf, vbuf, obuf, rows,
                                dk=dk, dv=dv)
                return 0
            lax.fori_loop(0, H, head, 0)
            write(row0, _GDN_CHUNK)
            return 0
        lax.fori_loop(0, (n + _GDN_CHUNK - 1) // _GDN_CHUNK, sub_chunk, 0)


def gdn_kernel_vmem_bytes(*, heads: int, dk: int, dv: int) -> int:
    """The launch's VMEM: the state block in and out, two buffers each,
    and the q / k / v / o windows of one sub-chunk."""
    dkp, dvp = _gdn_widths(dk, dv)
    C, hp = _GDN_CHUNK, -(-heads // 8) * 8
    return (4 * _tile_bytes((heads, dv, dk), 4)
            + 2 * _tile_bytes((C, hp, dkp), 4)
            + 2 * _tile_bytes((C, hp, dvp), 4))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_gated_delta(q, k, v, beta, g, state, slots, q_offsets, q_lens,
                       kv_lens, interpret=False):
    """The rule over every span of the pack (``ops/gated_delta.
    ragged_gated_delta_xla`` is the same contract, token by token): ``q``,
    ``k [T, H, dk]`` (normed, q scaled), ``v [T, H, dv]``, ``beta``, ``g
    [T, H]``, ``state [slots, H, dv, dk]`` float32, the bank ALIASED in
    and out; span tables ``[S]`` in pack order.  Returns ``(o [T, H, dv]
    float32, state)``; a row no span owns holds finite garbage, read by
    nothing."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    dkp, dvp = _gdn_widths(dk, dv)
    Hp = -(-H // 8) * 8           # a DMA moves whole sublane tiles of heads
    f32 = jnp.float32
    pad = _GDN_CHUNK
    with _x64_off():
        qp = jnp.concatenate(
            [q.astype(f32), beta.astype(f32)[..., None],
             g.astype(f32)[..., None],
             jnp.zeros((T, H, dkp - dk - 2), f32)], axis=-1)
        qp = jnp.pad(qp, ((0, pad), (0, Hp - H), (0, 0)))
        kp = jnp.pad(k.astype(f32), ((0, pad), (0, Hp - H), (0, dkp - dk)))
        vp = jnp.pad(v.astype(f32), ((0, pad), (0, Hp - H), (0, dvp - dv)))
        o_init = jnp.zeros((T + pad, Hp, dvp), f32)
        prefetch = [slots.astype(jnp.int32), q_offsets.astype(jnp.int32),
                    q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32)]
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        st_spec = pl.BlockSpec((1, H, dv, dk),
                               lambda s, slot, *_: (slot[s], 0, 0, 0))
        kernel = functools.partial(_gated_delta_kernel, heads=H, dk=dk,
                                   dv=dv)
        o, state = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(slots.shape[0],),
                in_specs=[any_spec, any_spec, any_spec, st_spec, any_spec],
                out_specs=[any_spec, st_spec],
                scratch_shapes=[
                    pltpu.VMEM((_GDN_CHUNK, Hp, dkp), f32),     # q | b | g
                    pltpu.VMEM((_GDN_CHUNK, Hp, dkp), f32),     # k
                    pltpu.VMEM((_GDN_CHUNK, Hp, dvp), f32),     # v
                    pltpu.VMEM((_GDN_CHUNK, Hp, dvp), f32),     # o
                    pltpu.SemaphoreType.DMA((4,)),
                ]),
            out_shape=[jax.ShapeDtypeStruct(o_init.shape, f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            input_output_aliases={len(prefetch) + 4: 0,
                                  len(prefetch) + 3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_GDN_VMEM),
            interpret=interpret,
            name="ragged_gated_delta",
        )(*prefetch, qp, kp, vp, state, o_init)
    return o[:T, :H, :dv], state


# ---------------------------------------------------------------------------
# VMEM footprint audit (consumed by tools/check_vmem_budget.py)
# ---------------------------------------------------------------------------
# Mosaic tiles every VMEM-resident buffer to (sublane, 128) vregs; the
# sublane count depends on itemsize (f32: 8, bf16: 16, int8: 32).  The
# audit pads every tile the way the hardware will, so a "small" [g, 1]
# running-max column is honestly counted as the [g, 128] lane broadcast
# it occupies on silicon.
_VMEM_LANE = 128


def _tile_bytes(shape, itemsize: int) -> int:
    """Lane/sublane-padded bytes of one VMEM-resident tile."""
    shape = (1, 1) + tuple(int(s) for s in shape)     # at least 2-D
    sub = 8 * (4 // max(1, min(itemsize, 4)))  # f32:8, bf16:16, int8:32
    lead = 1
    for s in shape[:-2]:
        lead *= s
    rows = -(-shape[-2] // sub) * sub
    cols = -(-shape[-1] // _VMEM_LANE) * _VMEM_LANE
    return lead * rows * cols * itemsize


def _paged_cell_vmem_bytes(g: int, d: int, block_size: int,
                           kv_itemsize: int, pipelined: bool,
                           quantized: bool) -> int:
    """What the paged decode kernel holds besides its q/o blocks: the page
    buffers (×2 per operand when pipelined — the round-17 double
    buffering) and the live compute tiles (online-softmax m/l/acc, the
    [g, block_size] score/probability tile, and the int8 q codes +
    per-row scales on the quantized MXU path)."""
    bufs = 2 if pipelined else 1
    total = 2 * bufs * _tile_bytes((block_size, d), kv_itemsize)  # k+v
    total += _tile_bytes((g, d), 4)                       # acc
    total += 2 * _tile_bytes((g, 1), 4)                   # m, l
    total += 2 * _tile_bytes((g, block_size), 4)          # scores + p
    if quantized and pipelined:
        total += _tile_bytes((g, d), 1)                   # q int8 codes
        total += _tile_bytes((g, 1), 4)                   # q row scales
        total += _tile_bytes((g, block_size), 4)          # i32 scores
    return total


def ragged_kernel_vmem_bytes(*, heads: int, kv_heads: int,
                             head_dim: int, block_size: int,
                             bt_width: int, q_dtype="bfloat16",
                             kv_dtype="bfloat16") -> int:
    """Worst-case VMEM bytes of ONE _ragged_paged_kernel grid cell: a q
    tile's buffers and state plus the two-slot K/V page blocks, at the
    tile size the launch itself would pick (``ragged_tile_geometry``;
    scratch shapes accounted in ``_ragged_cell_vmem_bytes`` — edit both
    or tools/check_vmem_budget.py fails)."""
    tile, kb = ragged_tile_geometry(
        heads, kv_heads, head_dim, block_size, bt_width, q_dtype,
        kv_dtype)
    return _ragged_cell_vmem_bytes(
        tile, heads, kv_heads, head_dim, kb * block_size,
        jnp.dtype(q_dtype).itemsize,
        _ragged_compute_dtype(q_dtype, kv_dtype).itemsize,
        jnp.dtype(kv_dtype).itemsize)


def decode_kernel_vmem_bytes(*, groups: int, head_dim: int,
                             block_size: int, q_itemsize: int = 4,
                             kv_itemsize: int = 4, pipelined: bool = True,
                             quantized: bool = False) -> int:
    """Worst-case VMEM bytes of ONE _paged_decode_kernel grid cell.
    The [groups, D] q/o operands are BlockSpec-streamed in the model
    dtype (Mosaic double-buffers them: ×2 each); pages go through the
    manual 2-slot DMA buffers."""
    return 4 * _tile_bytes((groups, head_dim), q_itemsize) \
        + _paged_cell_vmem_bytes(groups, head_dim, block_size,
                                 kv_itemsize, pipelined, quantized)


def rope_epilogue_vmem_bytes(*, heads: int, kv_heads: int,
                             head_dim: int, itemsize: int = 4,
                             with_amax: bool = True) -> int:
    """One _rope_qkv_kernel row tile: q/k (+v) in, q/k (+amax) out —
    every operand BlockSpec-streamed, so ×2 for Mosaic's pipeline —
    plus the f32 rotation temporaries for the widest operand.  Rows
    come from the SAME chooser the wrapper uses, so a tile-cap edit is
    audited automatically."""
    rows = _rope_epilogue_tile(heads, head_dim, itemsize)
    per_buf = (_tile_bytes((rows, heads, head_dim), itemsize)
               + _tile_bytes((rows, kv_heads, head_dim), itemsize))
    n_v = _tile_bytes((rows, kv_heads, head_dim), itemsize) \
        if with_amax else 0
    amax = 2 * _tile_bytes((rows, kv_heads), 4) if with_amax else 0
    rot = 2 * _tile_bytes((rows, heads, head_dim), 4)     # tf + rot f32
    return 2 * (2 * per_buf + n_v + amax) + rot


def flash_fwd_vmem_bytes(*, block_q: int, block_k: int, head_dim: int,
                         itemsize: int = 4, with_lse: bool = True,
                         with_rope: bool = False) -> int:
    """One _flash_fwd_kernel grid cell: BlockSpec-streamed q/k/v/out
    (×2 each), the m/l/acc/qs scratch, and the [bq, bk] score tile."""
    d = head_dim
    blocks = _tile_bytes((block_q, d), itemsize) * 2 \
        + 2 * _tile_bytes((block_k, d), itemsize) * 2 \
        + _tile_bytes((block_q, d), itemsize) * 2            # out
    if with_lse:
        blocks += _tile_bytes((block_q, _VMEM_LANE), 4) * 2
    if with_rope:
        blocks += 4 * _tile_bytes((max(block_q, block_k), d), 4) * 2
    scratch = 2 * _tile_bytes((block_q, _VMEM_LANE), 4) \
        + _tile_bytes((block_q, d), 4) \
        + _tile_bytes((block_q, d), itemsize)
    tiles = 2 * _tile_bytes((block_q, block_k), 4)           # s + p
    return blocks + scratch + tiles


def flash_bwd_fused_vmem_bytes(*, block_q: int, block_k: int,
                               head_dim: int, itemsize: int = 4,
                               with_rope: bool = False) -> int:
    """One _flash_bwd_kv_kernel (emit_dq) grid cell — the largest
    kernel in the tree: streamed q/o/do/lse blocks, resident k/v
    blocks, dq/dk/dv outputs, dk/dv/ks scratch, and the [bq, bk]
    p/ds/dp tiles."""
    d = head_dim
    blocks = 3 * _tile_bytes((block_q, d), itemsize) * 2 \
        + _tile_bytes((block_q, _VMEM_LANE), 4) * 2 \
        + 2 * _tile_bytes((block_k, d), itemsize) * 2 \
        + _tile_bytes((block_q, d), 4) * 2 \
        + 2 * _tile_bytes((block_k, d), itemsize) * 2
    if with_rope:
        blocks += 4 * _tile_bytes((max(block_q, block_k), d), 4) * 2
    scratch = 2 * _tile_bytes((block_k, d), 4) \
        + _tile_bytes((block_k, d), itemsize)
    tiles = 3 * _tile_bytes((block_q, block_k), 4)           # p, dp, ds
    return blocks + scratch + tiles


def kernel_vmem_report(envelope=None):
    """name -> worst-case per-core VMEM bytes for every Pallas kernel
    family, at the declared serving/training ENVELOPE (the largest
    configuration the repo's engines and benches actually launch).
    tools/check_vmem_budget.py gates this against the per-core budget;
    grow the envelope here FIRST when a new config is introduced."""
    env = {
        # serving envelope: the benchmark's cells — 32 q heads over 8
        # kv heads, 16-token pages, head_dim 128, block tables up to
        # 388 pages; the decode and rope kernels are sized for GQA
        # grouping up to 8
        "heads": 32, "kv_heads": 8, "bt_width": 388,
        "groups": 8, "head_dim": 128, "block_size": 16,
        # ... and the one-group cell (Olmo-Hybrid's 30 heads over 30,
        # stored as 32 kv heads; a 208-page table): bf16, the decode
        # cell's state beside the tile's
        "one_group_kv_heads": 32, "one_group_bt_width": 208,
        # latent (MLA) envelope: 128 heads over one 512 + 64 row a
        # token, 128-token pages, contexts to 33,280
        "latent_heads": 128, "kv_lora_rank": 512, "latent_rope_dim": 64,
        "latent_block_size": 128, "latent_bt_width": 260,
        # grouped expert product: the two MoE cells' banks at their top
        # budgets' sorted rows — 8 wide experts (Mixtral-8x7B, 1,024
        # tokens x 2) and 40 thin ones (DeepSeek-V2's share, 528 x 6)
        "wide_experts": (2048, 8, 4096, 14336),
        "thin_experts": (3168, 40, 5120, 1536),
        # gated delta rule: Olmo-Hybrid's 30 linear heads of 96 / 192
        "gdn": (30, 96, 192),
        # training envelope: the default/autotuned flash tiles
        "block_q": 512, "block_k": 512,
        "bwd_block_q": _FUSED_BWD_BLOCK_Q,
        "bwd_block_k": _FUSED_BWD_MAX_SK // 4,
    }
    if envelope:
        env.update(envelope)
    ragged = {k: env[k] for k in ("heads", "kv_heads", "head_dim",
                                  "block_size", "bt_width")}
    return {
        "ragged_paged_fp32": ragged_kernel_vmem_bytes(
            q_dtype="float32", kv_dtype="float32", **ragged),
        "ragged_paged_int8": ragged_kernel_vmem_bytes(
            kv_dtype="int8", **ragged),
        "ragged_paged_one_group": ragged_kernel_vmem_bytes(
            **dict(ragged, heads=env["one_group_kv_heads"],
                   kv_heads=env["one_group_kv_heads"],
                   bt_width=env["one_group_bt_width"])),
        "paged_decode_fp32": decode_kernel_vmem_bytes(
            groups=env["groups"], head_dim=env["head_dim"],
            block_size=env["block_size"]),
        "paged_decode_int8": decode_kernel_vmem_bytes(
            groups=env["groups"], head_dim=env["head_dim"],
            block_size=env["block_size"], kv_itemsize=1,
            quantized=True),
        "rope_qkv_epilogue": rope_epilogue_vmem_bytes(
            heads=8 * env["groups"], kv_heads=env["groups"],
            head_dim=env["head_dim"]),
        "ragged_latent_bf16": latent_kernel_vmem_bytes(
            heads=env["latent_heads"], kv_lora_rank=env["kv_lora_rank"],
            rope_dim=env["latent_rope_dim"],
            block_size=env["latent_block_size"],
            bt_width=env["latent_bt_width"]),
        **{"grouped_expert_" + kind: grouped_kernel_vmem_bytes(
            hidden=hidden, ffn=ffn,
            tile=grouped_tile_rows(rows, experts))
           for kind, (rows, experts, hidden, ffn) in (
               ("wide", env["wide_experts"]),
               ("thin", env["thin_experts"]))},
        "ragged_gated_delta": gdn_kernel_vmem_bytes(
            heads=env["gdn"][0], dk=env["gdn"][1], dv=env["gdn"][2]),
        "flash_fwd": flash_fwd_vmem_bytes(
            block_q=env["block_q"], block_k=env["block_k"],
            head_dim=env["head_dim"], with_rope=True),
        "flash_bwd_fused": flash_bwd_fused_vmem_bytes(
            block_q=env["bwd_block_q"], block_k=env["bwd_block_k"],
            head_dim=env["head_dim"], with_rope=True),
    }
