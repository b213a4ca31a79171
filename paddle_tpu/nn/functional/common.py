"""Common functional ops: linear, dropout, embedding, attention, etc.

Parity: python/paddle/nn/functional/common.py + input.py (reference);
flash_attention parity: python/paddle/nn/functional/flash_attention.py:146
(reference #18) — here a fused softmax(QK^T)V with optional Pallas flash
kernel on TPU (see paddle_tpu/ops/pallas_kernels.py).
"""
from __future__ import annotations

import math as _math

import numpy as np

import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op
from ...core.tensor import Tensor
from ...core.flags import get_flag
from ...ops._helpers import targ, wrap
from ...ops.random import next_key
from ...ops import manipulation as _manip

pad = _manip.pad  # re-export paddle.nn.functional.pad


def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b); W is [in, out] (parity: F.linear, phi matmul+add —
    one MXU dot under XLA)."""
    def fn(v, w, *b):
        out = jnp.matmul(v, w)
        if b:
            out = out + b[0]
        return out
    args = (x, targ(weight)) + ((targ(bias),) if bias is not None else ())
    return apply_op("linear", fn, args)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and p > 0.0 and not training:
            return apply_op("dropout_infer_scale",
                            lambda v: (v * (1.0 - p)).astype(v.dtype), (x,))
        return x if isinstance(x, Tensor) else wrap(targ(x))
    # key passed as a visible arg (not a closure) so jit/sot recording can
    # substitute a fresh key per replay
    def fn(v, key):
        shape = list(v.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(v.shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)
        return jnp.where(keep, v, 0.0).astype(v.dtype)

    return apply_op("dropout", fn, (x, next_key()))


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    def fn(v, key):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = (1.0 / _math.sqrt((1 - p) * (1 + p * alpha_p ** 2))) \
            if p < 1 else 0.0
        b = -a * alpha_p * p
        return (a * jnp.where(keep, v, alpha_p) + b).astype(v.dtype)

    return apply_op("alpha_dropout", fn, (x, next_key()))


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Parity: F.embedding (phi embedding kernel). A gather on TPU."""
    def fn(idx, w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply_op("embedding", fn, (targ(x), weight))


def one_hot(x, num_classes, name=None):
    from ...ops.creation import one_hot as _oh
    return _oh(x, num_classes)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fn(l, *pd):
        k = l.shape[-1]
        if pd:
            return (1 - epsilon) * l + epsilon * pd[0]
        return (1 - epsilon) * l + epsilon / k
    args = (label,) + ((targ(prior_dist),) if prior_dist is not None else ())
    return apply_op("label_smooth", fn, args)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def fn(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return apply_op("cosine_similarity", fn, (x1, targ(x2)))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(v):
        n = jnp.power(jnp.sum(jnp.power(jnp.abs(v), p), axis=axis,
                              keepdims=True), 1.0 / p)
        return v / jnp.maximum(n, epsilon)
    return apply_op("normalize", fn, (x,))


def bilinear(x1, x2, weight, bias=None, name=None):
    def fn(a, b, w, *bs):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bs:
            out = out + bs[0]
        return out
    args = (x1, targ(x2), targ(weight)) + (
        (targ(bias),) if bias is not None else ())
    return apply_op("bilinear", fn, args)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Parity: paddle.nn.functional.scaled_dot_product_attention.
    Inputs [batch, seq, heads, head_dim] (paddle layout).  Uses the Pallas
    flash kernel on TPU when enabled, else an XLA-fused reference path."""
    use_dropout = dropout_p > 0.0 and training
    if get_flag("use_pallas_kernels") and not use_dropout:
        # flash_attention_tpu picks kernel vs chunked reference itself,
        # from the platform and the shapes; a failure in either is an
        # error, not a reason to try another path
        from ...ops.pallas_kernels import flash_attention_tpu
        return flash_attention_tpu(query, key, value, attn_mask,
                                   is_causal)

    def fn(q, k, v, *m):
        # trailing arg is the dropout key when use_dropout (visible arg so
        # jit/sot replay re-randomizes; see dropout above)
        drop_key = None
        if use_dropout:
            drop_key, m = m[-1], m[:-1]
        # BSHD -> BHSD
        q_ = jnp.swapaxes(q, 1, 2)
        k_ = jnp.swapaxes(k, 1, 2)
        v_ = jnp.swapaxes(v, 1, 2)
        scale = 1.0 / _math.sqrt(q.shape[-1])
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * scale
        logits = logits.astype(jnp.float32)
        if is_causal:
            sq, sk = logits.shape[-2], logits.shape[-1]
            causal = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
            logits = jnp.where(causal, logits, -jnp.inf)
        if m:
            mask = m[0]
            if mask.dtype == jnp.bool_:
                logits = jnp.where(mask, logits, -jnp.inf)
            else:
                logits = logits + mask.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        if use_dropout:
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p),
                              0.0).astype(probs.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_)
        return jnp.swapaxes(out, 1, 2)

    args = (query, targ(key), targ(value)) + (
        (targ(attn_mask),) if attn_mask is not None else ())
    if use_dropout:
        args = args + (next_key(),)
    return apply_op("scaled_dot_product_attention", fn, args)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """Parity: F.flash_attention (reference
    python/paddle/nn/functional/flash_attention.py:146).  Returns
    (out, softmax) like the reference."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (parity: F.unfold)."""
    from .conv import _pair
    k = _pair(kernel_sizes, 2)
    s = _pair(strides, 2)
    p = _pair(paddings, 2)
    d = _pair(dilations, 2)

    def fn(v):
        N, C, H, W = v.shape
        vp = jnp.pad(v, [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
        oh = (H + 2 * p[0] - d[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (W + 2 * p[1] - d[1] * (k[1] - 1) - 1) // s[1] + 1
        cols = []
        for i in range(k[0]):
            for j in range(k[1]):
                patch = jax.lax.slice(
                    vp, (0, 0, i * d[0], j * d[1]),
                    (N, C, i * d[0] + (oh - 1) * s[0] + 1,
                     j * d[1] + (ow - 1) * s[1] + 1),
                    (1, 1, s[0], s[1]))
                cols.append(patch)
        out = jnp.stack(cols, axis=2)  # N,C,k*k,oh,ow
        return out.reshape(N, C * k[0] * k[1], oh * ow)
    return apply_op("unfold", fn, (x,))


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    from .conv import _pair
    out_sz = _pair(output_sizes, 2)
    k = _pair(kernel_sizes, 2)
    s = _pair(strides, 2)
    p = _pair(paddings, 2)
    d = _pair(dilations, 2)

    def fn(v):
        N = v.shape[0]
        C = v.shape[1] // (k[0] * k[1])
        H, W = out_sz
        oh = (H + 2 * p[0] - d[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (W + 2 * p[1] - d[1] * (k[1] - 1) - 1) // s[1] + 1
        vr = v.reshape(N, C, k[0], k[1], oh, ow)
        out = jnp.zeros((N, C, H + 2 * p[0], W + 2 * p[1]), v.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                patch = vr[:, :, i, j]
                out = out.at[:, :,
                             i * d[0]:i * d[0] + (oh - 1) * s[0] + 1:s[0],
                             j * d[1]:j * d[1] + (ow - 1) * s[1] + 1:s[1]
                             ].add(patch)
        return out[:, :, p[0]:p[0] + H, p[1]:p[1] + W]
    return apply_op("fold", fn, (x,))


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def fn(v):
        if data_format == "NCHW":
            N, C, H, W = v.shape
            out = v.reshape(N, C // (r * r), r, r, H, W)
            out = out.transpose(0, 1, 4, 2, 5, 3)
            return out.reshape(N, C // (r * r), H * r, W * r)
        N, H, W, C = v.shape
        out = v.reshape(N, H, W, r, r, C // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(N, H * r, W * r, C // (r * r))
    return apply_op("pixel_shuffle", fn, (x,))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def fn(v):
        if data_format == "NCHW":
            N, C, H, W = v.shape
            out = v.reshape(N, C, H // r, r, W // r, r)
            out = out.transpose(0, 1, 3, 5, 2, 4)
            return out.reshape(N, C * r * r, H // r, W // r)
        N, H, W, C = v.shape
        out = v.reshape(N, H // r, r, W // r, r, C)
        out = out.transpose(0, 2, 4, 1, 3, 5)
        return out.reshape(N, H // r, W // r, C * r * r)
    return apply_op("pixel_unshuffle", fn, (x,))


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    """Parity: F.interpolate.

    nearest/linear/bilinear/trilinear/bicubic via jax.image (half-pixel);
    ``align_corners=True`` uses explicit corner-aligned coordinate mapping
    through jax.image.scale_and_translate; ``area`` = adaptive average
    pooling (matching the reference's area semantics).
    """
    channel_last = not data_format.startswith("NC")
    if mode == "area":
        from .pooling import (adaptive_avg_pool1d, adaptive_avg_pool2d,
                              adaptive_avg_pool3d)
        if size is not None:
            sz = tuple(size) if isinstance(size, (list, tuple)) else (size,)
        else:
            xv = x._value if hasattr(x, "_value") else x
            spatial = xv.shape[1:-1] if channel_last else xv.shape[2:]
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * len(spatial)
            sz = tuple(int(s * f) for s, f in zip(spatial, sf))
        if channel_last:
            raise NotImplementedError(
                "mode='area' supports channel-first layouts only")
        pool = {1: adaptive_avg_pool1d, 2: adaptive_avg_pool2d,
                3: adaptive_avg_pool3d}[len(sz)]
        return pool(x, sz if len(sz) > 1 else sz[0])

    def fn(v):
        nd = v.ndim - 2
        spatial = v.shape[1:-1] if channel_last else v.shape[2:]
        if size is not None:
            tgt = tuple(int(s) for s in
                        (size if isinstance(size, (list, tuple)) else [size]))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * nd
            tgt = tuple(int(s * f) for s, f in zip(spatial, sf))
        if channel_last:
            full = (v.shape[0],) + tgt + (v.shape[-1],)
            sp_dims = tuple(range(1, 1 + nd))
        else:
            full = v.shape[:2] + tgt
            sp_dims = tuple(range(2, 2 + nd))
        method = {"nearest": "nearest", "bilinear": "bilinear",
                  "trilinear": "trilinear", "bicubic": "bicubic",
                  "linear": "linear"}[mode]
        if align_corners and mode != "nearest":
            # corner-aligned mapping: in-coord = out-coord*(in-1)/(out-1),
            # i.e. scale s = (out-1)/(in-1) with translation 0.5*(1-s)
            # (pixel-center convention; calibrated against the reference)
            scales = jnp.array(
                [(o - 1) / (i - 1) if i > 1 else 1.0
                 for i, o in zip(spatial, tgt)], jnp.float32)
            trans = 0.5 * (1.0 - scales)
            out = jax.image.scale_and_translate(
                v.astype(jnp.float32), full, sp_dims, scales, trans,
                method="linear" if method in ("linear", "bilinear",
                                              "trilinear") else method,
                antialias=False)
            return out.astype(v.dtype)
        return jax.image.resize(v, full, method=method).astype(v.dtype)
    return apply_op("interpolate", fn, (x,))


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW",
             name=None):
    return interpolate(x, size, scale_factor, mode, align_corners,
                       align_mode, data_format)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    """Parity: reference nn/functional/common.py:1802 — pad H/W with
    zeros; ``padding`` = int | [left, right, top, bottom] | Tensor."""
    if hasattr(padding, "numpy"):
        padding = padding.numpy().tolist()
    if isinstance(padding, (int, np.integer)):
        padding = [padding] * 4
    return pad(x, [int(p) for p in padding], mode="constant", value=0.0,
               data_format=data_format)
