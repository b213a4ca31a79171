"""Paged (block) attention for serving.

Parity: the reference's LLM-serving fused kernels
(paddle/phi/kernels/fusion/block_multihead_attention_kernel.cu — paged KV
cache addressed through per-sequence block tables — and
masked_multihead_attention for dense-cache decode).

TPU-native design: the KV cache is a pool of fixed-size pages
``[num_blocks, block_size, kv_heads, head_dim]`` living in HBM; a batch
addresses it through ``block_tables [B, max_blocks]``.  Decode attention
runs as a Pallas kernel — grid over (batch, kv_head), the page list is a
scalar-prefetch operand, and pages are DMA'd HBM→VMEM with online-softmax
accumulation — so one query token never materializes the gathered
[L, D] cache in HBM.  An XLA gather fallback covers CPU and is the
numerics reference in tests.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import device as _device
from ..core.tensor import Tensor
from .online_softmax import merge_partials, online_softmax_update

__all__ = ["PagedKVCache", "KVPageBuffer",
           "paged_attention", "write_kv_to_cache",
           "write_ragged_kv", "write_ragged_latent",
           "ragged_paged_attention",
           "write_ragged_kv_q8", "dequant_pages",
           "reconstruct_kv", "block_multihead_attention",
           "masked_multihead_attention"]

# symmetric int8 bound == quantization.functional.symmetric_bound(8).
# The quant/dequant math itself routes through that module (the ONE
# clamp implementation); this constant exists only for the in-kernel
# scale folds in the Pallas paths, where the float literal must be a
# trace-time static (contract locked by tests/test_serving_quant.py).
_KV_BNT = 127.0

# Round-17 declared tolerance (r13 convention: int8 paths are
# tolerance-gated, never byte-gated) for the int8 MXU kernels vs the
# dequantizing XLA reference: those kernels (the ragged launch, the
# pipelined decode kernel) quantize the q rows
# to int8 in-kernel (per-row absmax), so scores pick up one extra
# quantization (<= q_absmax/254 per element before the dot) the
# reference doesn't have.  The bound is RELATIVE to the pool's
# dequantized value magnitude because attention outputs are convex
# combinations of V rows — measured max deviation on the parity sweep
# is ~5e-3 at unit-variance data; 0.02 carries ~4x headroom.  Validity
# regime: the q-quant error perturbs the SOFTMAX EXPONENT by up to
# softmax_scale * (q_absmax/254) * sum|k_row| per score, so the bound
# holds while that perturbation stays well under 1 (K magnitudes up to
# a few tens at D=16..128 — comfortably covering rope'd projection
# outputs); beyond it, softmax exponentiation amplifies without bound
# and the meaningful gate is the engine-level token-match rate, not a
# tensor atol.  The legacy (pipelined=False) decode kernel keeps the
# r13 dequant math and stays within 1e-5 of the reference.
KERNEL_INT8_REL_TOL = 0.02


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


# ---------------------------------------------------------------------------
# page-migration wire format (round 19)
# ---------------------------------------------------------------------------
@dataclass
class KVPageBuffer:
    """A sequence's physical KV pages serialized to host RAM — the unit
    both page MIGRATION (engine → engine) and the host-RAM prefix-cache
    spill tier move around.

    Wire format: ``codes`` is ONE contiguous host array
    ``[2*num_layers, n_pages, block_size, num_kv_heads, head_dim]`` in
    the pool dtype — rows ``0..L-1`` are the K pages of layers
    ``0..L-1``, rows ``L..2L-1`` the V pages (the per-layer extents).
    An int8 pool additionally carries its per-page-per-head fp32 absmax
    rows as ``scales [2L, n_pages, num_kv_heads]`` in the same layer
    order — scales live per PHYSICAL page, so they travel with their
    pages for free and an injected page dequantizes bit-identically to
    its source.  The header fields pin the pool geometry; ``inject``
    into a pool with a different geometry (including a different
    ``kv_dtype``) is rejected with a construction-time ValueError, never
    a shape failure inside a trace.

    ``n_tokens`` records how many tokens of KV the pages actually cover
    (the last page may be partial) — the resume seq_len on the target
    engine."""
    codes: np.ndarray
    scales: Optional[np.ndarray]
    n_pages: int
    n_tokens: int
    block_size: int
    num_kv_heads: int
    head_dim: int
    num_layers: int
    kv_dtype: str

    @property
    def nbytes(self) -> int:
        return int(self.codes.nbytes
                   + (self.scales.nbytes if self.scales is not None
                      else 0))

    def geometry(self) -> tuple:
        return (self.num_layers, self.block_size, self.num_kv_heads,
                self.head_dim, self.kv_dtype)


# ---------------------------------------------------------------------------
# cache pool management (host-side; the reference keeps this in the
# serving runtime around the kernel too)
# ---------------------------------------------------------------------------
class PagedKVCache:
    """A pool of KV pages plus a per-layer free-list/block-table manager.

    One instance serves one transformer layer.  Arrays are jax arrays so
    updates stay on device; the free list is host state (allocation is
    control flow, not compute).

    Pages are REFCOUNTED: ``allocate_block`` hands out a page with one
    reference, ``share_blocks`` adds references (prefix caching — two
    requests whose prompts share a prefix address the same physical
    pages), and ``free_sequence`` is the single release path: it drops
    one reference per page and only returns a page to the free list
    when its count reaches zero.  A page shared by a prefix-cache table
    or another live request's block table therefore survives any one
    holder finishing (including pool-dry victim truncation and
    lazy-alloc growth — both funnel through ``free_sequence``).

    ``kv_dtype="int8"`` quantizes the pools: K/V pages store symmetric
    int8 codes plus per-PAGE-per-HEAD fp32 absmax scales
    (``key_scale``/``value_scale`` [phys, Hkv]) — ~4× (vs fp32) /
    ~2× (vs bf16) pages per HBM byte, scales included in the byte
    accounting.  Every compiled write path (``write_*_kv_q8``)
    quantizes on write with a running-max scale (existing codes are
    rescaled in the same dispatch when a new token raises a page's
    absmax), every attention path dequantizes into the same fp32
    online-softmax, and because scales live per PHYSICAL page, prefix
    sharing (``share_blocks``), copy-on-write (``serving_step.
    copy_block`` copies the scale row with the page) and refcounted
    release all carry scales with their pages for free.

    ``latent_width=R`` makes the pool a LATENT one (MLA): ``key_cache``
    is ``[phys, block_size, R]``, one row a token that every query head
    reads (keys the whole row, values its leading columns), and
    ``value_cache`` is ``None``.  The page bookkeeping is the same.
    """

    def __init__(self, num_blocks: int, block_size: int, num_kv_heads: int,
                 head_dim: int, dtype=jnp.float32, sink_block: bool = False,
                 kv_dtype: Optional[str] = None,
                 latent_width: Optional[int] = None):
        self.num_blocks = num_blocks
        self.block_size = block_size
        # a LATENT pool (MLA): one row of ``latent_width`` values a
        # token, read by every query head — no kv-head axis, and no
        # second pool (values are columns of the same row)
        self.latent = latent_width is not None
        if self.latent:
            if kv_dtype == "int8":
                raise ValueError(
                    "PagedKVCache: a latent pool (latent_width=) is not "
                    "quantized — kv_dtype='int8' scales pages per kv "
                    "head, and a latent row has none")
            num_kv_heads, head_dim = 1, int(latent_width)
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        if kv_dtype not in (None, "float32", "bfloat16", "int8"):
            raise ValueError(
                "PagedKVCache kv_dtype must be one of None (use dtype), "
                "'float32', 'bfloat16' or 'int8'; got %r" % (kv_dtype,))
        self.quantized = kv_dtype == "int8"
        if kv_dtype is not None:
            dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                     "int8": jnp.int8}[kv_dtype]
        self.kv_dtype = jnp.dtype(dtype).name
        # sink_block=True adds ONE extra physical page, never in the free
        # list, exposed as .sink: a fixed-shape compiled decode step
        # routes the writes of inactive (masked) batch slots there, so
        # slot occupancy changes never corrupt live pages and never
        # change any traced shape.
        self.sink = num_blocks if sink_block else -1
        phys = num_blocks + (1 if sink_block else 0)
        shape = ((phys, block_size, head_dim) if self.latent
                 else (phys, block_size, num_kv_heads, head_dim))
        self.key_cache = jnp.zeros(shape, dtype)
        self.value_cache = None if self.latent \
            else jnp.zeros(shape, dtype)
        if self.quantized:
            # per-page-per-head absmax; 0 = "nothing written yet" (the
            # quantized writes grow it monotonically per page lifetime)
            self.key_scale = jnp.zeros((phys, num_kv_heads), jnp.float32)
            self.value_scale = jnp.zeros((phys, num_kv_heads),
                                         jnp.float32)
        else:
            self.key_scale = None
            self.value_scale = None
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: dict = {}            # block id -> live reference count

    def place(self, sharding, scale_sharding=None):
        """Place both pools with a ``NamedSharding`` — the
        tensor-parallel serving engine head-shards them
        (``P(None, None, 'tp', None)``): each chip physically holds
        only its kv-head slice of every page, so per-chip pool HBM is
        exactly 1/tp.  A quantized pool's scale tables follow with
        ``scale_sharding`` (head axis: ``P(None, 'tp')``).  Free-list/
        refcount state is host bookkeeping and needs no placement.
        Call once at engine construction, before any compiled step
        consumes (donates) the arrays."""
        if self.latent:
            raise ValueError(
                "PagedKVCache.place: a latent pool has no kv-head axis "
                "to shard over")
        self.key_cache = jax.device_put(self.key_cache, sharding)
        self.value_cache = jax.device_put(self.value_cache, sharding)
        if self.quantized and scale_sharding is not None:
            self.key_scale = jax.device_put(self.key_scale,
                                            scale_sharding)
            self.value_scale = jax.device_put(self.value_scale,
                                              scale_sharding)

    def per_chip_pool_bytes(self) -> int:
        """Bytes of ONE chip's shard of this layer's K+V pools (the
        whole pool when unsharded) — the capacity number the
        multi-chip serving bench gates at ≈ pool/tp, and the
        quantization bench gates at ≥1.9× pages per HBM byte.  A
        quantized pool COUNTS ITS SCALE TABLES, so the capacity claim
        stays honest."""
        total = 0
        arrs = [self.key_cache] if self.latent \
            else [self.key_cache, self.value_cache]
        if self.quantized:
            arrs += [self.key_scale, self.value_scale]
        for arr in arrs:
            shape = arr.sharding.shard_shape(arr.shape) \
                if getattr(arr, "sharding", None) is not None \
                else arr.shape
            total += int(np.prod(shape)) * arr.dtype.itemsize
        return total

    def page_geometry(self) -> tuple:
        """One layer-pool's page geometry ``(block_size, num_kv_heads,
        head_dim, kv_dtype)`` — the per-layer part of the migration
        wire-format header (``KVPageBuffer`` adds the layer count)."""
        return (self.block_size, self.num_kv_heads, self.head_dim,
                self.kv_dtype)

    def allocate_block(self) -> int:
        if not self._free:
            raise RuntimeError(
                "PagedKVCache out of blocks (%d in pool); raise num_blocks "
                "or free finished sequences" % self.num_blocks)
        b = self._free.pop()
        self._ref[b] = 1
        return b

    def share_blocks(self, block_ids):
        """Add one reference to each page (prefix sharing)."""
        for b in block_ids:
            b = int(b)
            if b < 0 or b == self.sink:
                continue
            if b not in self._ref:
                raise RuntimeError(
                    "share_blocks(%d): page is not allocated" % b)
            self._ref[b] += 1

    def refcount(self, block_id: int) -> int:
        return self._ref.get(int(block_id), 0)

    def free_sequence(self, block_ids):
        """Drop one reference per page; recycle pages that hit zero.
        The ONLY release path — every finish/truncate/evict goes
        through here, so a shared page is never recycled while another
        holder's block table still references it."""
        for b in block_ids:
            b = int(b)
            if b < 0 or b == self.sink:
                continue
            n = self._ref.pop(b, 1) - 1
            if n > 0:
                self._ref[b] = n
            else:
                self._free.append(b)

    def blocks_needed(self, seq_len: int) -> int:
        return -(-seq_len // self.block_size)

    def trim_blocks(self, block_ids, n_tokens: int):
        """Speculative-decode rollback: release the TAIL pages past
        what ``n_tokens`` needs (pages grown for draft positions the
        verifier rejected) through the refcounted release path, and
        return the kept prefix.  A trimmed page shared with the prefix
        table or another request survives, exactly like any other
        ``free_sequence`` drop."""
        keep = self.blocks_needed(max(int(n_tokens), 1))
        if keep >= len(block_ids):
            return list(block_ids)
        self.free_sequence(block_ids[keep:])
        return list(block_ids[:keep])

    def build_block_table(self, seq_lens, max_blocks=None) -> np.ndarray:
        """Allocate pages for new sequences; returns [B, max_blocks]
        int32 table (-1 padded)."""
        tables = []
        for L in seq_lens:
            n = self.blocks_needed(max(int(L), 1))
            tables.append([self.allocate_block() for _ in range(n)])
        width = max_blocks or max(len(t) for t in tables)
        out = np.full((len(tables), width), -1, np.int32)
        for i, t in enumerate(tables):
            out[i, :len(t)] = t
        return out

    def append(self, k_new, v_new, block_tables, seq_lens):
        """Donating in-place append: updates self.key_cache/value_cache
        (the old buffers are consumed — use this, not the functional
        write_kv_to_cache, when the pool object owns the arrays)."""
        if self.quantized:
            raise NotImplementedError(
                "PagedKVCache.append is the legacy dense-cache API and "
                "does not quantize; an int8 pool must be written through "
                "the compiled serving step (write_ragged_kv_q8)")
        self.key_cache, self.value_cache = _write_decode_donated(
            _val(k_new), _val(v_new), self.key_cache, self.value_cache,
            jnp.asarray(np.asarray(block_tables), jnp.int32),
            jnp.asarray(np.asarray(seq_lens), jnp.int32))

    def ensure_capacity(self, block_tables: np.ndarray,
                        seq_lens) -> np.ndarray:
        """Grow tables so every sequence can hold seq_len+1 tokens."""
        bt = np.asarray(block_tables).copy()
        for i, L in enumerate(np.asarray(seq_lens)):
            need = self.blocks_needed(int(L) + 1)
            have = int((bt[i] >= 0).sum())
            while have < need:
                if (bt[i] >= 0).sum() == bt.shape[1]:
                    bt = np.concatenate(
                        [bt, np.full((bt.shape[0], 1), -1, np.int32)], 1)
                bt[i, have] = self.allocate_block()
                have += 1
        return bt


# ---------------------------------------------------------------------------
# cache write (scatter one new token per sequence)
# ---------------------------------------------------------------------------
def _write_decode_impl(k_new, v_new, key_cache, value_cache, block_tables,
                       seq_lens):
    """k_new/v_new [B, Hkv, D]; writes at position seq_lens[b]."""
    bs = key_cache.shape[1]
    pos = seq_lens.astype(jnp.int32)
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    off = pos % bs
    key_cache = key_cache.at[blk, off].set(k_new)
    value_cache = value_cache.at[blk, off].set(v_new)
    return key_cache, value_cache


# functional API: callers keep ownership of all buffers (no donation);
# PagedKVCache.append is the donating variant that rebinds its own state
_write_decode = jax.jit(_write_decode_impl)
_write_decode_donated = jax.jit(_write_decode_impl, donate_argnums=(2, 3))


def _write_prefill_impl(k_new, v_new, key_cache, value_cache, block_tables,
                        seq_lens):
    """k_new/v_new [B, S, Hkv, D]: one vectorized scatter for the whole
    prompt (not S sequential dispatches)."""
    B, S = k_new.shape[:2]
    bs = key_cache.shape[1]
    pos = seq_lens[:, None].astype(jnp.int32) + jnp.arange(
        S, dtype=jnp.int32)[None, :]                      # [B, S]
    blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)  # [B, S]
    off = pos % bs
    key_cache = key_cache.at[blk, off].set(k_new)
    value_cache = value_cache.at[blk, off].set(v_new)
    return key_cache, value_cache


_write_prefill = jax.jit(_write_prefill_impl)
_write_prefill_donated = jax.jit(_write_prefill_impl, donate_argnums=(2, 3))


def write_ragged_kv(k_new, v_new, key_cache, value_cache, dest_blocks,
                    dest_offsets):
    """Scatter a packed ragged token batch's K/V into cache pages
    (traceable — composed inside the fused ``MixedStep`` trace).

    k_new/v_new: [T, Hkv, D] — one row per packed token (decode slots
    and prefill-chunk tokens interleaved).  Token t lands at
    ``(dest_blocks[t], dest_offsets[t])``; the caller routes padding
    tokens to the sink page, so one compile per token budget serves
    every admission mix without corrupting live pages.
    """
    key_cache = key_cache.at[dest_blocks, dest_offsets].set(k_new)
    value_cache = value_cache.at[dest_blocks, dest_offsets].set(v_new)
    return key_cache, value_cache


def write_ragged_latent(rows, latent_cache, dest_blocks, dest_offsets):
    """``write_ragged_kv`` for a latent pool: ``rows [T, row]`` (one
    row a packed token, ``[c_kv | k_rope | 0]``) land at
    ``(dest_blocks[t], dest_offsets[t])`` of ``[phys, block, row]``."""
    return latent_cache.at[dest_blocks, dest_offsets].set(
        rows.astype(latent_cache.dtype))


# ---------------------------------------------------------------------------
# quantized (int8) write paths: quantize ON WRITE inside the compiled step
# ---------------------------------------------------------------------------
def _quant_write_tokens(cache, scale, new_vals, blks, offs, amax=None):
    """Core of the int8 write path (traceable).

    cache [phys, bs, Hkv, D] int8, scale [phys, Hkv] fp32 absmax,
    new_vals [N, Hkv, D] float, blks/offs [N] int32 (token t lands at
    ``(blks[t], offs[t])``; padding routed to the sink page by the
    caller, exactly like the fp32 paths).

    Per-page-per-head RUNNING-MAX scale: a scatter-max folds the new
    tokens' absmax into each touched page's scale (duplicate pages in
    one write accumulate correctly), then the touched pages' EXISTING
    codes are rescaled by old/new in the same dispatch (ratio 1 —
    bit-exact round trip — whenever the scale didn't move, which is the
    steady state) and the new tokens are quantized with the final
    scale.  Dequantization therefore always uses the exact scale each
    code was (re)quantized with.  Scales are monotone per page
    lifetime in the pool array; a recycled page keeps its last absmax
    as the quantization floor — bounded coarseness, zero extra
    dispatches in the hot loop (K/V magnitudes are stationary across
    requests, so the floor tracks the data).

    ``amax`` (round 17): the fused RoPE+QKV epilogue already computed
    each token's per-head absmax in its single pass over the
    projection outputs — pass it here to skip the re-read (it is
    bit-identical to what this function would recompute).
    """
    from ..quantization.functional import quantize_symmetric
    f32 = jnp.float32
    vals = new_vals.astype(f32)
    if amax is None:
        amax = jnp.max(jnp.abs(vals), axis=-1)           # [N, Hkv]
    new_scale = scale.at[blks].max(amax)                 # running max
    ratio = jnp.where(new_scale > 0,
                      scale / jnp.maximum(new_scale, 1e-30),
                      jnp.ones((), f32))
    # rescale the touched pages' existing codes (gather → scatter;
    # duplicate blks write identical content, so order is irrelevant)
    pages = cache[blks].astype(f32) * ratio[blks][:, None, :, None]
    cache = cache.at[blks].set(jnp.round(pages).astype(cache.dtype))
    q = quantize_symmetric(vals, new_scale[blks][:, :, None])
    cache = cache.at[blks, offs].set(q.astype(cache.dtype))
    return cache, new_scale


def write_ragged_kv_q8(k_new, v_new, key_cache, value_cache, key_scale,
                       value_scale, dest_blocks, dest_offsets,
                       k_amax=None, v_amax=None):
    """int8 variant of ``write_ragged_kv``: the packed ragged token
    batch (decode spans + prefill chunks) quantized in ONE scatter
    inside the fused MixedStep trace."""
    key_cache, key_scale = _quant_write_tokens(
        key_cache, key_scale, k_new, dest_blocks, dest_offsets,
        amax=k_amax)
    value_cache, value_scale = _quant_write_tokens(
        value_cache, value_scale, v_new, dest_blocks, dest_offsets,
        amax=v_amax)
    return key_cache, value_cache, key_scale, value_scale


def dequant_pages(pages, page_scale):
    """Dequantize gathered int8 pages: ``pages [..., bs, Hkv, D]`` ×
    their ``page_scale [..., Hkv]`` → fp32 (traceable; the read-side
    inverse of ``_quant_write_tokens``)."""
    from ..quantization.functional import dequantize_symmetric
    return dequantize_symmetric(pages, page_scale[..., None, :, None])


def _ragged_attention_xla(q, key_cache, value_cache, block_tables,
                          q_offsets, q_lens, kv_lens, scale,
                          key_scale=None, value_scale=None):
    """Ragged paged attention, XLA reference path (CPU + parity tests).

    q: [T, H, D] packed ragged tokens; block_tables [S, W]; q_offsets /
    q_lens / kv_lens [S] describe the spans (q_offsets ascending, with
    padding spans pinned past the last token so no token maps to them).
    Token t of span s sits at global position
    ``kv_lens[s] - q_lens[s] + (t - q_offsets[s])`` and attends keys at
    positions <= that — the same mask decode (q_len=1) and chunked
    prefill use, so one code path covers any admission mix.  Same
    gather + fp32 masked softmax pattern as ``_paged_attention_xla``.
    """
    T, H, D = q.shape
    Hkv = key_cache.shape[2]
    bs = key_cache.shape[1]
    W = block_tables.shape[1]
    max_len = W * bs
    tok = jnp.arange(T, dtype=jnp.int32)
    sid = jnp.clip(
        jnp.searchsorted(q_offsets.astype(jnp.int32), tok, side="right")
        - 1, 0, q_offsets.shape[0] - 1).astype(jnp.int32)
    qpos = (kv_lens[sid] - q_lens[sid] + (tok - q_offsets[sid]))
    qpos = jnp.maximum(qpos, 0)       # padding tokens: finite garbage
    bt = jnp.maximum(block_tables, 0)[sid]               # [T, W]
    if key_scale is not None:
        # int8 pool: dequantize the GATHERED pages (cast + one fused
        # broadcast multiply — measured fastest of the CPU variants;
        # the Pallas kernel dequantizes per DMA'd page instead)
        k = dequant_pages(key_cache[bt], key_scale[bt])
        v = dequant_pages(value_cache[bt], value_scale[bt])
    else:
        k, v = key_cache[bt], value_cache[bt]
    k = k.reshape(T, max_len, Hkv, D)
    v = v.reshape(T, max_len, Hkv, D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("thd,tlhd->thl",
                   q.astype(jnp.float32) * jnp.float32(scale),
                   k.astype(jnp.float32))
    cols = jnp.arange(max_len, dtype=jnp.int32)
    valid = cols[None, None, :] <= qpos[:, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("thl,tlhd->thd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _ragged_latent_attention_xla(q, latent_cache, block_tables,
                                 q_offsets, q_lens, kv_lens, scale,
                                 v_width):
    """Ragged paged LATENT attention, XLA reference path (CPU + parity
    tests): ``_ragged_attention_xla`` where every head of a token reads
    the same cached row.  q ``[T, H, row]`` absorbed queries, pool
    ``[phys, block, row]``; keys are the whole row, values its first
    ``v_width`` columns.  Returns ``[T, H, v_width]``."""
    T = q.shape[0]
    bs = latent_cache.shape[1]
    W = block_tables.shape[1]
    tok = jnp.arange(T, dtype=jnp.int32)
    sid = jnp.clip(
        jnp.searchsorted(q_offsets.astype(jnp.int32), tok, side="right")
        - 1, 0, q_offsets.shape[0] - 1).astype(jnp.int32)
    qpos = jnp.maximum(
        kv_lens[sid] - q_lens[sid] + (tok - q_offsets[sid]), 0)
    bt = jnp.maximum(block_tables, 0)[sid]               # [T, W]
    c = latent_cache[bt].reshape(T, W * bs, -1).astype(jnp.float32)
    s = jnp.einsum("thd,tld->thl",
                   q.astype(jnp.float32) * jnp.float32(scale), c)
    cols = jnp.arange(W * bs, dtype=jnp.int32)
    s = jnp.where(cols[None, None, :] <= qpos[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("thl,tlv->thv", p, c[..., :v_width]).astype(q.dtype)


# ---------------------------------------------------------------------------
# context-parallel (round 22) per-stripe partials: each chip's pool shard
# holds slot sub-range [r*bsl, (r+1)*bsl) of EVERY page (the
# P(None, cp, tp, None) dim-1 striping), so the local flattened kv index
# j maps to GLOBAL position (j // bsl)*block_size + stripe_offset +
# (j % bsl).  These variants run the same gather + fp32 masked softmax
# as their full counterparts but over the local stripe only, returning
# the NORMALIZED (o, m, l) rows the cross-chip merge
# (ops/online_softmax.merge_partials) combines exactly.  XLA-only for
# now: CPU dryruns and the parity/bench gates use these; a per-stripe
# (m, l)-emitting Pallas variant is the TPU follow-up.  int8 pools are
# rejected under cp at engine construction (per-chip absmax scales over
# a replicated [phys, Hkv] table would diverge), so no scale operands.
# ---------------------------------------------------------------------------
def _stripe_cols(n_pages, bsl, stripe_offset, global_block_size):
    """Global kv position of each local flattened stripe index."""
    j = jnp.arange(n_pages * bsl, dtype=jnp.int32)
    return ((j // bsl) * jnp.int32(global_block_size)
            + stripe_offset.astype(jnp.int32) + (j % bsl))


def _partial_softmax_rows(s, valid, v, contract):
    """Masked partial softmax over the last score axis: returns the
    normalized output plus the (m, l) merge rows; an all-masked row
    yields (o=0, m=-inf, l=0) — the exact empty-stripe identity
    ``merge_partials`` drops."""
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, np.float32(0.0))
    p = jnp.where(valid, jnp.exp(s - m_safe[..., None]), np.float32(0.0))
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(contract, p, v)
    return o / jnp.maximum(l, np.float32(1e-30))[..., None], m, l


def _ragged_attention_xla_partial(q, key_cache, value_cache,
                                  block_tables, q_offsets, q_lens,
                                  kv_lens, scale, stripe_offset,
                                  global_block_size):
    """Per-stripe ragged attention partial (cp shard of
    ``_ragged_attention_xla``): q [T, H, D] against the LOCAL pool
    stripe [phys, bsl, Hkv, D]; returns fp32 ``(o [T,H,D], m [T,H],
    l [T,H])`` for the cross-chip merge."""
    T, H, D = q.shape
    Hkv = key_cache.shape[2]
    bsl = key_cache.shape[1]
    W = block_tables.shape[1]
    tok = jnp.arange(T, dtype=jnp.int32)
    sid = jnp.clip(
        jnp.searchsorted(q_offsets.astype(jnp.int32), tok, side="right")
        - 1, 0, q_offsets.shape[0] - 1).astype(jnp.int32)
    qpos = (kv_lens[sid] - q_lens[sid] + (tok - q_offsets[sid]))
    qpos = jnp.maximum(qpos, 0)
    bt = jnp.maximum(block_tables, 0)[sid]               # [T, W]
    k = key_cache[bt].reshape(T, W * bsl, Hkv, D)
    v = value_cache[bt].reshape(T, W * bsl, Hkv, D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("thd,tlhd->thl",
                   q.astype(jnp.float32) * jnp.float32(scale),
                   k.astype(jnp.float32))
    gcol = _stripe_cols(W, bsl, stripe_offset, global_block_size)
    valid = gcol[None, None, :] <= qpos[:, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    return _partial_softmax_rows(s, valid, v.astype(jnp.float32),
                                 "thl,tlhd->thd")


def ragged_paged_attention(q, key_cache, value_cache, block_tables,
                           q_offsets, q_lens, kv_lens,
                           use_pallas: Optional[bool] = None,
                           interpret=False,
                           key_scale=None, value_scale=None):
    """One fused attention launch over a packed ragged query batch
    against the paged KV pool (arXiv:2604.15464).

    q: [T, H, D] — decode slots contribute length-1 spans, prefill
    chunks length-C spans, concatenated on the token axis.
    block_tables: [S, W] int32 per-span page lists (-1/sink padded).
    q_offsets/q_lens/kv_lens: [S] int32 span tables (kv_len INCLUDES the
    span's own tokens, which must already be written to the pages).
    key_scale/value_scale: per-page-per-head [phys, Hkv] fp32 absmax
    tables of an int8 pool (folded into the int8 MXU products on the
    kernel path, dequantized pages on the reference path); None for fp
    pools.  Returns [T, H, D].
    """
    tensor_in = isinstance(q, Tensor)
    qv = _val(q)
    kc, vc = _val(key_cache), _val(value_cache)
    bt = jnp.asarray(np.asarray(block_tables), jnp.int32)
    qo = jnp.asarray(np.asarray(q_offsets), jnp.int32)
    ql = jnp.asarray(np.asarray(q_lens), jnp.int32)
    kl = jnp.asarray(np.asarray(kv_lens), jnp.int32)
    scale = 1.0 / math.sqrt(qv.shape[-1])
    if use_pallas is None:
        use_pallas = _device.on_tpu()
    if use_pallas or interpret:
        from .pallas_kernels import _ragged_paged_attention_pallas
        out = _ragged_paged_attention_pallas(
            qv, kc, vc, bt, qo, ql, kl, scale, interpret=interpret,
            key_scale=key_scale, value_scale=value_scale)
    else:
        out = _ragged_attention_xla(qv, kc, vc, bt, qo, ql, kl, scale,
                                    key_scale, value_scale)
    return Tensor._from_value(out) if tensor_in else out


def write_kv_to_cache(k_new, v_new, key_cache, value_cache, block_tables,
                      seq_lens, donate: bool = False):
    """Append K/V into page slots; returns NEW (key_cache, value_cache).

    k_new/v_new: [B, Hkv, D] (decode) or [B, S, Hkv, D] (prefill,
    written starting at seq_lens).  donate=True consumes the passed cache
    buffers (in-place HBM update — the serving loop's mode); the default
    keeps them valid for the caller."""
    k_new, v_new = _val(k_new), _val(v_new)
    key_cache, value_cache = _val(key_cache), _val(value_cache)
    block_tables = jnp.asarray(np.asarray(block_tables), jnp.int32)
    seq_lens = jnp.asarray(np.asarray(seq_lens), jnp.int32)
    if k_new.ndim == 3:
        fn = _write_decode_donated if donate else _write_decode
    else:
        fn = _write_prefill_donated if donate else _write_prefill
    return fn(k_new, v_new, key_cache, value_cache, block_tables,
              seq_lens)


def reconstruct_kv(key_cache, value_cache, block_tables, max_len,
                   key_scale=None, value_scale=None):
    """Gather pages back to dense [B, max_len, Hkv, D] (XLA path);
    int8 pools dequantize through their per-page-per-head scales."""
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    k = key_cache[bt]          # [B, max_blocks, bs, Hkv, D]
    v = value_cache[bt]
    if key_scale is not None:
        k = dequant_pages(k, key_scale[bt])
        v = dequant_pages(v, value_scale[bt])
    B, nb, bs, H, D = k.shape
    k = k.reshape(B, nb * bs, H, D)[:, :max_len]
    v = v.reshape(B, nb * bs, H, D)[:, :max_len]
    return k, v


# ---------------------------------------------------------------------------
# decode attention: XLA gather path (reference + CPU)
# ---------------------------------------------------------------------------
def _paged_attention_xla(q, key_cache, value_cache, block_tables, seq_lens,
                         scale, key_scale=None, value_scale=None):
    B, H, D = q.shape
    Hkv = key_cache.shape[2]
    bs = key_cache.shape[1]
    max_len = int(block_tables.shape[1]) * bs
    k, v = reconstruct_kv(key_cache, value_cache, block_tables, max_len,
                          key_scale, value_scale)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    cols = jnp.arange(s.shape[-1], dtype=jnp.int32)
    valid = cols[None, None, :] < seq_lens[:, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention: Pallas TPU kernel
# ---------------------------------------------------------------------------
def _paged_decode_kernel(# scalar prefetch (+2 f32 scale tables
                         # when quantized)
                         *refs,
                         block_size: int, pages_per_seq: int,
                         scale: float, groups: int,
                         quantized: bool = False,
                         pipelined: bool = True):
    """Grid cell (b, hkv): one batch row, one kv head; q carries the
    `groups` query heads mapped to this kv head.

    Pages stream HBM->VMEM through two buffers per operand (round 17,
    ``pipelined=True``): page i+1's async copy is issued before the
    attention math on page i, the wait lands at the buffer swap, and
    the prefetch is clamped to the sequence's used page count so the
    block table is never read past ``seq_len``'s coverage.
    ``pipelined=False`` keeps the r16 issue-then-wait loop for
    old-vs-new benching.  Online-softmax state stays in fp32 registers.

    An int8 pool's per-page-per-head fp32 scales ride as TWO EXTRA
    f32 scalar-prefetch tables ([Hkv, phys] — SMEM scalar
    reads with a dynamic page index, the same mechanism as the block
    table).  Pipelined, the q heads are quantized once per cell to
    per-row int8 and ``q·Kᵀ`` runs int8×int8 on the MXU with the q/k/
    softmax scales folded into the int32-accumulated scores
    (``quantization.functional.fold_int8_scores``); the v scale folds
    into the [groups, D] ``p·V`` product.  Legacy (non-pipelined)
    dequantizes each page right after its DMA, exactly the r13 math.
    Only int8 bytes ever cross HBM→VMEM on either path."""
    from ..quantization.functional import (fold_int8_scores,
                                           quantize_rows_symmetric)
    if quantized:
        (block_tables_ref, seq_lens_ref, ks_ref, vs_ref,
         q_ref, k_pages_ref, v_pages_ref, o_ref,
         k_vmem, v_vmem, sem) = refs
    else:
        (block_tables_ref, seq_lens_ref,
         q_ref, k_pages_ref, v_pages_ref, o_ref,
         k_vmem, v_vmem, sem) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    seq_len = seq_lens_ref[b]
    int8_mxu = quantized and pipelined
    if int8_mxu:
        q_codes, q_s = quantize_rows_symmetric(q_ref[0, 0])
        g, d = q_codes.shape
        q = None
    else:
        q = q_ref[0, 0].astype(jnp.float32) * scale    # [groups, D]
        g, d = q.shape

    m0 = jnp.full((g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, d), jnp.float32)

    n_pages = jnp.minimum(
        (seq_len + jnp.int32(block_size - 1)) // jnp.int32(block_size),
        jnp.int32(pages_per_seq))

    def page_math(p_idx, page, kbuf, vbuf, carry):
        if quantized:
            sk = ks_ref[h, page]
            sv = vs_ref[h, page]
        if int8_mxu:
            si = jax.lax.dot_general(
                q_codes, kbuf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            s = fold_int8_scores(si, q_s, sk, scale)
        else:
            k = kbuf.astype(jnp.float32)               # [bs, D]
            if quantized:
                k = k * (sk / np.float32(_KV_BNT))
            s = q @ k.T                                # [groups, bs]
        base = p_idx * jnp.int32(block_size)
        cols = base + jax.lax.broadcasted_iota(jnp.int32, (g, block_size), 1)
        ok = cols < seq_len
        s = jnp.where(ok, s, -jnp.inf)

        def pv_of_p(p):
            if int8_mxu:
                # p·V as int8×int8 as well: per-row p scales + the
                # page's v scale fold into the [groups, D] product, so
                # the page never materializes in fp32
                p_codes, p_s = quantize_rows_symmetric(p)
                pvi = jax.lax.dot_general(
                    p_codes, vbuf, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                return fold_int8_scores(pvi, p_s, sv)
            v = vbuf.astype(jnp.float32)
            if quantized:
                v = v * (sv / np.float32(_KV_BNT))
            return p @ v

        return online_softmax_update(carry, s, ok, pv_of_p)

    if pipelined:
        def start_page(p_idx, slot):
            page = block_tables_ref[b, p_idx]
            pltpu.make_async_copy(k_pages_ref.at[h, page],
                                  k_vmem.at[slot], sem.at[slot, 0]).start()
            pltpu.make_async_copy(v_pages_ref.at[h, page],
                                  v_vmem.at[slot], sem.at[slot, 1]).start()

        def wait_page(p_idx, slot):
            page = block_tables_ref[b, p_idx]
            pltpu.make_async_copy(k_pages_ref.at[h, page],
                                  k_vmem.at[slot], sem.at[slot, 0]).wait()
            pltpu.make_async_copy(v_pages_ref.at[h, page],
                                  v_vmem.at[slot], sem.at[slot, 1]).wait()

        # a masked slot (seq_len 0) has NO used page: nothing to warm
        @pl.when(n_pages > 0)
        def _warm():
            start_page(jnp.int32(0), jnp.int32(0))

        def body(p_idx, carry):
            slot = jax.lax.rem(p_idx, jnp.int32(2))
            # prefetch clamp: the last used page issues no copy, so the
            # block table is never read past the used page count
            @pl.when(p_idx + 1 < n_pages)
            def _prefetch():
                start_page(p_idx + 1, jnp.int32(1) - slot)
            wait_page(p_idx, slot)
            return page_math(p_idx, block_tables_ref[b, p_idx],
                             k_vmem[slot], v_vmem[slot], carry)
    else:
        def body(p_idx, carry):
            page = block_tables_ref[b, p_idx]
            k_copy = pltpu.make_async_copy(
                k_pages_ref.at[h, page], k_vmem, sem)
            k_copy.start()
            k_copy.wait()
            v_copy = pltpu.make_async_copy(
                v_pages_ref.at[h, page], v_vmem, sem)
            v_copy.start()
            v_copy.wait()
            return page_math(p_idx, page, k_vmem[...], v_vmem[...], carry)

    m, l, acc = jax.lax.fori_loop(jnp.int32(0), n_pages, body,
                                  (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_attention_pallas(q, key_cache, value_cache, block_tables,
                            seq_lens, scale, interpret=False,
                            key_scale=None, value_scale=None,
                            pipelined: bool = True):
    B, H, D = q.shape
    Hkv = key_cache.shape[2]
    bs = key_cache.shape[1]
    groups = H // Hkv
    pages_per_seq = block_tables.shape[1]
    quantized = key_scale is not None
    # [B, H, D] -> [B, Hkv, groups, D]; pages -> [Hkv, nb, bs, D]
    qg = q.reshape(B, Hkv, groups, D)
    kp = jnp.moveaxis(key_cache, 2, 0)      # [Hkv, nb, bs, D]
    vp = jnp.moveaxis(value_cache, 2, 0)
    if not quantized:
        kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
    bt = jnp.maximum(block_tables, 0)

    kernel = functools.partial(
        _paged_decode_kernel, block_size=bs, pages_per_seq=pages_per_seq,
        scale=scale, groups=groups, quantized=quantized,
        pipelined=pipelined)
    if pipelined:
        page_scratch = [pltpu.VMEM((2, bs, D), kp.dtype),
                        pltpu.VMEM((2, bs, D), vp.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))]
    else:
        page_scratch = [pltpu.VMEM((bs, D), kp.dtype),
                        pltpu.VMEM((bs, D), vp.dtype),
                        pltpu.SemaphoreType.DMA]

    with jax.enable_x64(False):
        prefetch = [bt.astype(jnp.int32), seq_lens.astype(jnp.int32)]
        if quantized:
            # [phys, Hkv] -> [Hkv, phys] so the kernel indexes [h, page]
            prefetch += [key_scale.astype(jnp.float32).T,
                         value_scale.astype(jnp.float32).T]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, Hkv),
            in_specs=[
                pl.BlockSpec((1, 1, groups, D),
                             lambda b, h, *_: (b, h, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, groups, D),
                                   lambda b, h, *_: (b, h, 0, 0)),
            scratch_shapes=page_scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype),
            interpret=interpret,
            name="paged_decode_attention",
        )(*prefetch, qg, kp, vp)
    return out.reshape(B, H, D)


def paged_attention(q, key_cache, value_cache, block_tables, seq_lens,
                    use_pallas: Optional[bool] = None, interpret=False,
                    key_scale=None, value_scale=None,
                    pipelined: bool = True):
    """Decode-step attention over a paged KV cache.

    q: [B, H, D] (one query token per sequence)
    key_cache/value_cache: [num_blocks, block_size, Hkv, D]
    block_tables: [B, max_blocks] int32, -1 padded
    seq_lens: [B] int32 — number of valid tokens ALREADY in the cache
    key_scale/value_scale: [phys, Hkv] fp32 absmax tables of an int8
    pool (None for fp pools).  Returns [B, H, D].
    """
    tensor_in = isinstance(q, Tensor)
    qv = _val(q)
    kc, vc = _val(key_cache), _val(value_cache)
    bt = jnp.asarray(np.asarray(block_tables), jnp.int32)
    sl = jnp.asarray(np.asarray(seq_lens), jnp.int32)
    scale = 1.0 / math.sqrt(qv.shape[-1])
    if use_pallas is None:
        use_pallas = _device.on_tpu()
    if use_pallas or interpret:
        out = _paged_attention_pallas(qv, kc, vc, bt, sl, scale,
                                      interpret=interpret,
                                      key_scale=key_scale,
                                      value_scale=value_scale,
                                      pipelined=pipelined)
    else:
        out = _paged_attention_xla(qv, kc, vc, bt, sl, scale,
                                   key_scale, value_scale)
    return Tensor._from_value(out) if tensor_in else out


# ---------------------------------------------------------------------------
# fused serving ops (reference API parity)
# ---------------------------------------------------------------------------
def block_multihead_attention(qkv, key_cache, value_cache, seq_lens,
                              block_tables, num_heads: int,
                              head_dim: Optional[int] = None,
                              donate_cache: bool = False):
    """Parity: paddle.incubate.nn.functional.block_multihead_attention
    (phi/kernels/fusion/block_multihead_attention_kernel.cu), simplified to
    the two serving phases:

    - prefill (qkv [B, S, (H+2Hkv)*D], seq_lens==0): causal self-attention,
      writes K/V pages, returns [B, S, H*D]
    - decode (qkv [B, 1, ...], seq_lens>0): appends one token and runs
      paged attention, returns [B, 1, H*D]

    Returns (out, key_cache, value_cache, new_seq_lens).
    """
    qkv_v = _val(qkv)
    kc, vc = _val(key_cache), _val(value_cache)
    B, S = qkv_v.shape[:2]
    Hkv = kc.shape[2]
    D = head_dim or kc.shape[3]
    H = num_heads
    q, k, v = jnp.split(qkv_v.reshape(B, S, -1, D), [H, H + Hkv], axis=2)
    sl = jnp.asarray(np.asarray(seq_lens), jnp.int32)

    # donate_cache=True is the serving-loop fast path (in-place HBM write
    # per token) — ONLY safe when the caller rebinds to the returned
    # caches and holds no other reference to the passed buffers; the
    # default keeps the inputs valid
    kc, vc = write_kv_to_cache(k, v, kc, vc, block_tables, sl,
                               donate=donate_cache)
    new_len = sl + S

    if S > 1:
        # prefill: dense causal attention over what was just written
        from .pallas_kernels import _chunked_sdpa
        qh = jnp.moveaxis(q, 2, 1)        # [B, H, S, D]
        kh = jnp.moveaxis(k, 2, 1)
        vh = jnp.moveaxis(v, 2, 1)
        if Hkv != H:
            rep = H // Hkv
            kh = jnp.repeat(kh, rep, axis=1)
            vh = jnp.repeat(vh, rep, axis=1)
        out = _chunked_sdpa(qh, kh, vh, True)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * D)
    else:
        out = paged_attention(q[:, 0], kc, vc, block_tables, new_len)
        out = out.reshape(B, 1, H * D)
    if isinstance(qkv, Tensor):
        out = Tensor._from_value(jnp.asarray(out))
    return out, kc, vc, new_len


def masked_multihead_attention(x, cache_kv, seq_lens=None,
                               num_heads: Optional[int] = None):
    """Parity: masked_multihead_attention (dense-cache decode step).

    x: packed qkv [B, 3*H*D] for ONE new token.
    cache_kv: [2, B, H, max_len, D]; seq_lens [B] tokens already cached.
    Returns (out [B, H*D], updated cache_kv, new_seq_lens)."""
    xv = _val(x)
    cache = _val(cache_kv)
    B = xv.shape[0]
    H = num_heads or cache.shape[2]
    D = cache.shape[4]
    max_len = cache.shape[3]
    q, k, v = jnp.split(xv.reshape(B, 3, H, D), 3, axis=1)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    if seq_lens is None:
        seq_lens = jnp.zeros((B,), jnp.int32)
    sl = jnp.asarray(np.asarray(seq_lens), jnp.int32)

    bidx = jnp.arange(B)
    cache = cache.at[0, bidx, :, sl].set(k)
    cache = cache.at[1, bidx, :, sl].set(v)
    new_len = sl + 1

    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32) * scale,
                   cache[0].astype(jnp.float32))
    cols = jnp.arange(max_len, dtype=jnp.int32)
    s = jnp.where(cols[None, None, :] < new_len[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,bhld->bhd", p,
                     cache[1].astype(jnp.float32)).astype(xv.dtype)
    out = out.reshape(B, H * D)
    if isinstance(x, Tensor):
        out = Tensor._from_value(out)
    return out, cache, new_len
