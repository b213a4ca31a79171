#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, no arguments: ``python3 chip_smoke.py``.  It drives the two
main paths through the entry points a user calls — the fused ``TrainStep``
and ``ContinuousBatchingEngine`` — at the full WIDTH of
Llama-2-7B (hidden 4096, 32 heads x 128, 32 KV heads, ffn 11008, vocab
32000, bf16), cut by DEPTH only (``DEPTH`` layers) to what one 16 GB v5e
chip holds, with seeded random weights and prompts.  On a host with four
or more chips both phases repeat sharded (tp=4 serving, fsdp=2 x tp=2
training).

This is a smoke, not a measurement: compile seconds are set-up time, and
no rate is printed under a benchmark metric's name.  Any failed assertion
or exception ends the run with a non-zero exit code; nothing here catches
an error to keep going.  With no TPU it exits 2 before building anything.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can run them tiny on the CPU backend (XLA
reference paths, ``expect_kernels=False``).
"""
from __future__ import annotations

import gc
import importlib.metadata
import json
import sys
import time

import numpy as np

DEPTH = 4                       # layers kept; every width is the published one
V5E_PEAK_TFLOPS = 197.0         # bf16, Google Cloud "TPU v5e" documentation

# Declared before the first chip run.  All are max|got - ref| / max|ref|
# against an all-f32 reference at HIGHEST matmul precision on the same
# (bf16-representable) inputs.  bf16 keeps 8 mantissa bits (2^-8 = 3.9e-3
# per rounding); the kernels round q.k products, the probabilities and
# the output once each, the backward additionally rounds dS.
FLASH_FWD_TOL = 2e-2
FLASH_BWD_TOL = 4e-2
RAGGED_TOL = 2e-2
BARRIER_AGREE_TOL = 0.05        # block_until_ready vs host fetch, relative


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def report(phase: str, result: dict) -> None:
    say(f"{phase} {json.dumps(result)}")


def llama2_7b_width(depth: int = DEPTH, **kw):
    """Llama-2-7B's published widths at ``depth`` layers."""
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=depth, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=2048,
        dtype="bfloat16", **kw)


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _memory(devices) -> dict:
    """Allocator counters of each device (None on backends that keep
    none, e.g. CPU).  ``peak`` is the PROCESS high-water mark so far."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return {"bytes_in_use": None, "peak_bytes_in_use": None}
    return {"bytes_in_use": [int(s["bytes_in_use"]) for s in stats],
            "peak_bytes_in_use": [int(s["peak_bytes_in_use"])
                                  for s in stats]}


def _assert_spread(arrays, n_devices: int, what: str) -> None:
    """Every array is split over ``n_devices`` DISTINCT devices, each
    holding a strict part of it — not everything on device 0."""
    for a in arrays:
        shards = a.addressable_shards
        devs = {s.device for s in shards}
        assert len(devs) == n_devices, (
            f"{what}: {a.shape} lives on {len(devs)} device(s), "
            f"wanted {n_devices}")
        assert all(s.data.size < a.size for s in shards), (
            f"{what}: {a.shape} is replicated, not sharded")


def _assert_share(memory: dict, expect_bytes: int, what: str):
    """Every device holds at least its share of the sharded state
    (device 0 may hold more: the unsharded source copy lives there).
    Returns the per-device bytes and their max/min ratio; None on a
    backend without allocator counters."""
    held = memory["bytes_in_use"]
    if held is None:
        return None
    share = expect_bytes // len(held)
    assert min(held) >= 0.9 * share, (
        f"{what}: a device holds {min(held)} bytes, its share of the "
        f"sharded state is {share} ({held})")
    return {"expected_share_bytes": share,
            "max_over_min": round(max(held) / min(held), 3)}


# ---------------------------------------------------------------------------
# phase 0: the device, and what a barrier is on it
# ---------------------------------------------------------------------------
def device_facts() -> dict:
    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu")}


def barrier_fact(n: int = 8192, chain: int = 16, reps: int = 8) -> dict:
    """Time one chain of ``chain * reps`` [n,n]x[n,n] bf16 matmuls twice:
    ending in ``block_until_ready`` and ending in a host fetch.  Both
    must be a physically possible rate and agree — i.e.
    ``block_until_ready`` IS a barrier on this runtime."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, w):
        for _ in range(chain):
            x = jnp.dot(x, w)
        return x

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (n, n), jnp.float32).astype(jnp.bfloat16)
    # variance-preserving weight: the chain stays finite at any length
    w = (jax.random.normal(k2, (n, n), jnp.float32)
         / np.sqrt(n)).astype(jnp.bfloat16)
    for _ in range(2):                         # compile + warm both ends
        y = f(x, w)
        y.block_until_ready()
        float(y[0, 0])

    def run(end) -> float:
        y = x
        t0 = time.perf_counter()
        for _ in range(reps):
            y = f(y, w)
        end(y)
        return time.perf_counter() - t0

    t_block = run(lambda y: y.block_until_ready())
    t_fetch = run(lambda y: float(y[0, 0]))
    flops = 2.0 * n ** 3 * chain * reps
    out = {"matmul": f"{chain * reps} x [{n},{n}]x[{n},{n}] bf16",
           "block_until_ready_tflops": round(flops / t_block / 1e12, 2),
           "host_fetch_tflops": round(flops / t_fetch / 1e12, 2),
           "relative_gap": round(abs(t_block - t_fetch)
                                 / max(t_block, t_fetch), 4)}
    for key in ("block_until_ready_tflops", "host_fetch_tflops"):
        assert 0.0 < out[key] < V5E_PEAK_TFLOPS, (
            f"{key}={out[key]} TF/s is not a possible rate on a chip "
            f"whose bf16 peak is {V5E_PEAK_TFLOPS}: {out}")
    assert out["relative_gap"] <= BARRIER_AGREE_TOL, (
        f"block_until_ready and a host fetch disagree: {out}")
    return out


# ---------------------------------------------------------------------------
# kernel-level numeric checks (the gates; token agreement is only reported)
# ---------------------------------------------------------------------------
def flash_check(batch: int, heads: int, seq: int, head_dim: int,
                expect_kernels: bool = True) -> dict:
    """Fused rope+flash attention forward AND backward (the training
    path, ``_flash_rope_sdpa``) against the chunked XLA reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    shape = (batch, heads, seq, head_dim)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32)
                  .astype(jnp.bfloat16) for kk in ks)
    cos, sin = pk.rope_tables(seq, head_dim)

    def fused(q, k, v):
        return pk._flash_rope_sdpa(q, k, v, cos, sin, True)

    def reference(q, k, v):
        return pk._chunked_sdpa(pk._rope_xla(q, cos, sin),
                                pk._rope_xla(k, cos, sin), v, True)

    def value_and_grads(fn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(run)

    fused_fn = value_and_grads(fused)
    mosaic = fused_fn.lower(q, k, v, g).as_text().count("tpu_custom_call")
    if expect_kernels:
        assert mosaic >= 2, (
            f"flash fwd+bwd lowered to {mosaic} Mosaic calls: the "
            f"dispatcher picked the chunked reference")
    got = fused_fn(q, k, v, g)
    with jax.default_matmul_precision("highest"):
        ref = value_and_grads(reference)(
            *(t.astype(jnp.float32) for t in (q, k, v, g)))
    errs = {name: _rel_err(a, b)
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref)}
    for name, err in errs.items():
        tol = FLASH_FWD_TOL if name == "out" else FLASH_BWD_TOL
        assert np.isfinite(err) and err <= tol, (
            f"flash {name}: rel err {err:.3e} > declared {tol}: {errs}")
    return {"shape": list(shape), "mosaic_calls": mosaic,
            "rel_err": {k_: float(f"{e:.3e}") for k_, e in errs.items()},
            "tol": {"fwd": FLASH_FWD_TOL, "bwd": FLASH_BWD_TOL}}


def ragged_check(heads: int, kv_heads: int, head_dim: int,
                 block_size: int, bt_width: int, max_spans: int,
                 chunk: int, budgets, expect_kernels: bool = True) -> dict:
    """``ragged_paged_attention(use_pallas=True)`` against
    ``use_pallas=False`` at the engine's own shapes (its heads, page
    geometry, table width, span count, chunk and token budgets): a
    decode-only pack and a pack that carries a prefill chunk, bf16 q
    and pools.  The reference gathers every token's whole context, so
    it runs in kv-head slices to stay inside HBM."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import ragged_paged_attention

    rng = np.random.RandomState(2)
    max_len = bt_width * block_size
    num_blocks = max_spans * bt_width
    pool = (num_blocks + 1, block_size, kv_heads, head_dim)
    kc = jnp.asarray(rng.randn(*pool), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(*pool), jnp.bfloat16)
    # a page list per span, disjoint and shuffled like a live pool's
    pages = rng.permutation(num_blocks).reshape(max_spans, bt_width)

    def pack(spans, budget):
        """spans: [(q_len, kv_len)] -> the engine's packed descriptors
        (padding spans pinned past the last token, all-sink tables)."""
        bt = np.full((max_spans, bt_width), num_blocks, np.int32)
        q_off = np.full((max_spans,), budget, np.int32)
        q_len = np.zeros((max_spans,), np.int32)
        kv_len = np.ones((max_spans,), np.int32)
        off = 0
        for i, (ql, kl) in enumerate(spans):
            bt[i] = pages[i]
            q_off[i], q_len[i], kv_len[i] = off, ql, kl
            off += ql
        return bt, q_off, q_len, kv_len, off

    def budget_of(n):
        return next(b for b in budgets if b >= n)

    decode = [(1, int(rng.randint(1, max_len + 1)))
              for _ in range(max_spans)]
    start = block_size * 3 + 5                  # chunk begins mid-page
    mixed = decode[:max_spans - 1] + [(chunk, start + chunk)]
    cases = {"decode_only": (decode, budget_of(max_spans)),
             "with_chunk": (mixed, budget_of(max_spans - 1 + chunk))}
    groups = heads // kv_heads
    head_slice = max(1, kv_heads // 8)
    out = {}
    for name, (spans, budget) in cases.items():
        bt, q_off, q_len, kv_len, real = pack(spans, budget)
        q = jnp.asarray(rng.randn(budget, heads, head_dim), jnp.bfloat16)

        @jax.jit
        def kernel(q, kc, vc):
            return ragged_paged_attention(
                q, kc, vc, bt, q_off, q_len, kv_len,
                use_pallas=expect_kernels)

        @jax.jit
        def reference(q, kc, vc, i):
            """kv heads [i * head_slice, (i + 1) * head_slice) and
            their ``groups`` q heads each."""
            def heads_at(x, axis, n):
                return jax.lax.dynamic_slice_in_dim(
                    x, i * head_slice * n, head_slice * n,
                    axis).astype(jnp.float32)
            return ragged_paged_attention(
                heads_at(q[:real], 1, groups), heads_at(kc, 2, 1),
                heads_at(vc, 2, 1), bt, q_off, q_len, kv_len,
                use_pallas=False)

        got = np.asarray(kernel(q, kc, vc), np.float32)[:real]
        assert np.isfinite(got).all(), f"ragged {name}: non-finite output"
        with jax.default_matmul_precision("highest"):
            ref = np.concatenate(
                [np.asarray(reference(q, kc, vc, i))
                 for i in range(kv_heads // head_slice)], axis=1)
        err = _rel_err(got, ref)
        assert err <= RAGGED_TOL, (
            f"ragged {name}: rel err {err:.3e} > declared {RAGGED_TOL}")
        out[name] = {"tokens": real, "budget": budget,
                     "rel_err": float(f"{err:.3e}")}
    out["tol"] = RAGGED_TOL
    return out


def latent_check(heads: int = 128, kv_lora: int = 512, rope: int = 64,
                 block_size: int = 128, prefix: int = 8192,
                 chunk: int = 512, decodes: int = 16,
                 expect_kernels: bool = True) -> dict:
    """One latent-attention (MLA, absorbed form) launch at DeepSeek-V2's
    widths, bf16: a ``chunk``-row span over a ``prefix`` of cached rows
    beside ``decodes`` one-row spans, the Pallas launch against the XLA
    fallback in float32.  The fallback gathers each token's whole
    context, so it goes span by span and the chunk in slices."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import _ragged_latent_attention_xla
    from paddle_tpu.ops.pallas_kernels import (
        _ragged_latent_attention_pallas, latent_row_width)

    rng = np.random.RandomState(3)
    row = latent_row_width(kv_lora, rope)
    width = -(-(prefix + chunk) // block_size)
    spans = decodes + 1
    num_blocks = spans * width
    scale = (kv_lora // 4 + rope) ** -0.5
    pool = rng.randn(num_blocks + 1, block_size, row)
    pool[..., kv_lora + rope:] = 0.0
    pool = jnp.asarray(pool, jnp.bfloat16)
    pages = rng.permutation(num_blocks).reshape(spans, width).astype(
        np.int32)
    kv = [int(rng.randint(1, width * block_size + 1))
          for _ in range(decodes)]
    q_len = np.asarray([1] * decodes + [chunk], np.int32)
    kv_len = np.asarray(kv + [prefix + chunk], np.int32)
    q_off = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    tokens = int(q_len.sum())
    q = rng.randn(tokens, heads, row) / 8.0
    q[..., kv_lora + rope:] = 0.0
    q = jnp.asarray(q, jnp.bfloat16)

    launch = _ragged_latent_attention_pallas if expect_kernels \
        else _ragged_latent_attention_xla
    got = np.asarray(launch(q, pool, jnp.asarray(pages),
                            jnp.asarray(q_off), jnp.asarray(q_len),
                            jnp.asarray(kv_len), scale, kv_lora),
                     np.float32)
    assert np.isfinite(got).all(), "latent: non-finite output"

    @jax.jit
    def reference(qs, pool, bt, off, ql, kl):
        return _ragged_latent_attention_xla(
            qs.astype(jnp.float32), pool.astype(jnp.float32), bt, off,
            ql, kl, scale, kv_lora)

    ref = [np.asarray(reference(
        q[:decodes], pool, jnp.asarray(pages[:decodes]),
        jnp.asarray(q_off[:decodes]), jnp.asarray(q_len[:decodes]),
        jnp.asarray(kv_len[:decodes])))]
    step = 32
    with jax.default_matmul_precision("highest"):
        for a in range(0, chunk, step):
            n = min(step, chunk - a)
            ref.append(np.asarray(reference(
                q[decodes + a:decodes + a + n], pool,
                jnp.asarray(pages[decodes:]), jnp.zeros((1,), jnp.int32),
                jnp.asarray([n], jnp.int32),
                jnp.asarray([prefix + a + n], jnp.int32))))
    err = _rel_err(got, np.concatenate(ref, axis=0))
    assert err <= RAGGED_TOL, (
        f"latent: rel err {err:.3e} > declared {RAGGED_TOL}")
    return {"tokens": tokens, "row": row, "pages_a_span": width,
            "rel_err": float(f"{err:.3e}"), "tol": RAGGED_TOL}


# name -> (tokens, k, router width, experts held, first held, D, M)
GROUPED_CASES = {"wide": (512, 2, 8, 8, 0, 4096, 14336),
                 "thin": (96, 6, 160, 40, 40, 5120, 1536)}


def grouped_check(cases=None, expect_kernels: bool = True) -> dict:
    """The experts' product of a dropless MoE FFN
    (``ops.moe_gate.sorted_expert_swiglu`` on the Pallas grouped
    matmul) at both MoE cells' widths, bf16, against a plain loop over
    the experts in float32: 8 wide experts that hold every assignment
    (Mixtral-8x7B, tiles of 64 rows here) and 40 thin ones that hold a
    quarter of a router 160 wide (DeepSeek-V2's share, tiles of 16),
    one expert of each left without a row."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_gate import sorted_expert_swiglu

    out = {"tol": RAGGED_TOL}
    for name, (n, k, e_all, e_held, first, d, m) in (
            cases or GROUPED_CASES).items():
        rng = np.random.RandomState(11)
        keys = jax.random.split(jax.random.PRNGKey(11), 4)
        x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
        wg, wu = (jax.random.normal(kk, (e_held, d, m), jnp.bfloat16)
                  * 0.02 for kk in keys[1:3])
        wd = jax.random.normal(keys[3], (e_held, m, d), jnp.bfloat16) * 0.02
        # k distinct experts a row, none on the last held expert
        pool = np.delete(np.arange(e_all), first + e_held - 1)
        top_i = np.stack([rng.permutation(pool)[:k] for _ in range(n)]
                         ).astype(np.int32)
        top_w = rng.rand(n, k).astype(np.float32) + 0.1
        valid = np.arange(n) < n - 5
        with jax.enable_x64(False):
            got, load = jax.jit(
                lambda *a: sorted_expert_swiglu(
                    *a, use_pallas=expect_kernels)
            )(x, jnp.asarray(top_i), jnp.asarray(top_w), wg, wu, wd,
              first, jnp.asarray(valid))
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), f"grouped {name}: non-finite"
        local = top_i - first
        held = (local >= 0) & (local < e_held) & valid[:, None]
        assert np.asarray(load).tolist() == np.bincount(
            local[held], minlength=e_held).tolist(), f"grouped {name}: load"

        @jax.jit
        def expert(xf, g, u, dn):
            with jax.default_matmul_precision("highest"):
                a = xf @ g.astype(jnp.float32)
                return (jax.nn.silu(a) * (xf @ u.astype(jnp.float32))
                        ) @ dn.astype(jnp.float32)

        want = np.zeros((n, d), np.float32)
        xf = x.astype(jnp.float32)
        for e in range(e_held):
            w_e = np.sum(np.where(held & (local == e), top_w, 0.0), axis=1)
            if w_e.any():
                want += w_e[:, None] * np.asarray(
                    expert(xf, wg[e], wu[e], wd[e]))
        err = _rel_err(got, want)
        assert err <= RAGGED_TOL, (
            f"grouped {name}: rel err {err:.3e} > declared {RAGGED_TOL}")
        out[name] = {"rows": int(held.sum()),
                     "rel_err": float(f"{err:.3e}")}
    return out


# ---------------------------------------------------------------------------
# phase 1: serve — ContinuousBatchingEngine
# ---------------------------------------------------------------------------
def serve_phase(cfg, prompt_lens, max_new_tokens: int, chunk: int,
                num_blocks: int, max_batch_size: int, block_size: int = 16,
                mesh=None, expect_kernels: bool = True) -> dict:
    """>= 6 seeded requests, admitted at different steps, prompt lengths
    on both sides of ``chunk``, through the fused mixed step."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaForCausalLM

    n_dev = 1 if mesh is None else int(np.prod(mesh.shape))
    devices = jax.devices()[:n_dev]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in prompt_lens]
    max_seq_len = -(-(max(prompt_lens) + max_new_tokens) // block_size) \
        * block_size
    eng = ContinuousBatchingEngine(
        model, max_batch_size=max_batch_size, num_blocks=num_blocks,
        block_size=block_size, max_seq_len=max_seq_len,
        prefill_chunk_size=chunk, mesh=mesh)
    assert eng.mixed.use_pallas is expect_kernels, (
        f"mixed step use_pallas={eng.mixed.use_pallas!r}, expected "
        f"{expect_kernels}")
    kernels = []
    if expect_kernels:
        text = eng.mixed.aot_lower(eng.token_budgets[-1]).as_text()
        kernels = sorted({name for name in ("ragged_paged_attention",
                                            "rope_qkv_epilogue")
                          if f'kernel_name = "{name}"' in text})
        assert "tpu_custom_call" in text and len(kernels) == 2, (
            f"mixed step lowered without its Mosaic kernels: {kernels}")
    tp = eng.tp_degree                    # the kernel sees one chip's heads
    ragged = ragged_check(
        cfg.num_attention_heads // tp, cfg.num_key_value_heads // tp,
        eng.head_dim, block_size, eng.bt_width, max_batch_size,
        eng.chunk_size, eng.token_budgets, expect_kernels)
    free0 = len(eng.caches[0]._free)

    # staggered admission: two up front, then two more every other step
    compile_s, steps, rids = 0.0, 0, []
    pending = list(prompts)

    def step():
        nonlocal compile_s, steps
        pre = eng.mixed.total_compiles
        t0 = time.perf_counter()
        eng.step()
        if eng.mixed.total_compiles > pre:
            compile_s += time.perf_counter() - t0
        steps += 1

    while pending:
        for p in pending[:2]:
            rids.append(eng.add_request(p, max_new_tokens))
        del pending[:2]
        step()
        step()
    while eng.has_work():
        step()

    outs = [eng.result(r) for r in rids]
    for n, rid, toks in zip(prompt_lens, rids, outs):
        assert len(toks) == max_new_tokens \
            and not eng.finished[rid].truncated, (
                f"prompt of {n}: {len(toks)} tokens, wanted "
                f"{max_new_tokens}")
        assert all(0 <= t < cfg.vocab_size for t in toks), (
            f"prompt of {n}: out-of-vocab token in {toks}")
    assert eng.mixed.total_compiles <= len(eng.token_budgets), (
        f"{eng.mixed.total_compiles} compiles for "
        f"{len(eng.token_budgets)} budgets")
    assert len(eng.caches[0]._free) == free0, (
        f"page leak: {len(eng.caches[0]._free)} free, started {free0}")
    after = _memory(devices)

    sharded = None
    if mesh is not None:
        placed = eng.tp.place_params(
            {k: t._value for k, t in model.state_dict().items()})
        weights = [v for k, v in placed.items() if "_proj" in k]
        pools = [c.key_cache for c in eng.caches] \
            + [c.value_cache for c in eng.caches]
        _assert_spread(weights, n_dev, "tp weight shard")
        _assert_spread(pools, n_dev, "tp kv pool shard")
        sharded = _assert_share(
            after, sum(int(v.nbytes) for v in weights + pools),
            "tp serving")

    # reported, not gated: seeded random bf16 weights give near-flat
    # logits, so argmax flips on rounding.  Teacher-forced: one eager
    # forward over prompt+output, argmax at each generated position.
    # All right-padded to one power-of-two length (causal: a position
    # cannot see the padding after it): one set of eager op shapes, and
    # the eager attention keeps training's tiling.
    agree = total = 0
    padded = 1 << (max_seq_len - 1).bit_length()
    with paddle.no_grad():
        for p, toks in zip(prompts, outs):
            ids = np.concatenate([p, np.asarray(toks[:-1], np.int64)])
            n = len(ids)
            ids = np.pad(ids, (0, padded - n))
            logits = model(paddle.to_tensor(ids[None, :]))
            pred = np.asarray(logits._value[0, len(p) - 1:n].argmax(-1))
            agree += int((pred == np.asarray(toks)).sum())
            total += len(toks)
    return {"requests": len(rids), "prompt_lens": list(prompt_lens),
            "new_tokens": sum(len(t) for t in outs), "steps": steps,
            "token_budgets": list(eng.token_budgets),
            "compiles": eng.mixed.total_compiles,
            "cold_compile_seconds": round(compile_s, 1),
            "mosaic_kernels": kernels, "ragged_check": ragged,
            "eager_argmax_agreement": round(agree / total, 4),
            "memory": after, "sharded": sharded}


# ---------------------------------------------------------------------------
# phase 2: train — the fused TrainStep (bench.py's construction)
# ---------------------------------------------------------------------------
def train_phase(cfg, batch: int, seq: int, steps: int = 8,
                n_batches: int = 2, lr: float = 3e-4, mesh=None,
                expect_kernels: bool = True) -> dict:
    """``steps`` fused AdamW steps cycling ``n_batches`` fixed batches."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import ShardingConfig, TrainStep
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    n_dev = 1 if mesh is None else int(np.prod(mesh.shape))
    devices = jax.devices()[:n_dev]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    criterion = LlamaPretrainingCriterion()
    # bf16 moments, no f32 master copy: the 16 GB budget of one chip
    opt = paddle.optimizer.AdamW(lr, parameters=model.parameters(),
                                 multi_precision=False,
                                 moment_dtype=cfg.dtype)
    kw = {} if mesh is None else dict(
        mesh=mesh, sharding=ShardingConfig(axis="fsdp"))
    step = TrainStep(model, lambda lg, lb: criterion(lg, lb), opt,
                     clip_norm=1.0, **kw)
    rng = np.random.RandomState(4)
    batches = [(paddle.to_tensor(rng.randint(
                    0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
                paddle.to_tensor(rng.randint(
                    0, cfg.vocab_size, (batch, seq)).astype(np.int64)))
               for _ in range(n_batches)]

    mosaic = step.lower(*batches[0]).as_text().count("tpu_custom_call")
    if expect_kernels:
        assert mosaic >= 1, (
            "the lowered train step has no Mosaic call: _pallas_ok "
            "picked _chunked_sdpa")
    t0 = time.perf_counter()
    losses = [float(np.asarray(step(*batches[0])._value))]
    compile_s = time.perf_counter() - t0
    for i in range(1, steps):
        losses.append(float(np.asarray(
            step(*batches[i % n_batches])._value)))
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert step.compile_count == 1, (
        f"train step traced {step.compile_count} times")
    after = _memory(devices)

    sharded = None
    if mesh is not None:
        params = [p._value for p in model.parameters()
                  if p._value.ndim == 2]
        moments = [v for st in step._opt_states.values()
                   for v in st.values() if getattr(v, "ndim", 0) == 2]
        _assert_spread(params, n_dev, "fsdp x tp param shard")
        _assert_spread(moments, n_dev, "fsdp x tp moment shard")
        sharded = _assert_share(
            after, sum(int(v.nbytes) for v in params + moments),
            "fsdp x tp training")
    return {"steps": steps, "batch": batch, "seq": seq,
            "tokens": steps * batch * seq,
            "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            "compile_count": step.compile_count,
            "mosaic_calls": mosaic,
            "cold_compile_seconds": round(compile_s, 1),
            "memory": after, "sharded": sharded}


# ---------------------------------------------------------------------------
def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # JAX falls back to the CPU with a warning when it finds no
        # accelerator; a smoke that then passes proves nothing
        print(f"chip_smoke: needs a TPU, JAX reports "
              f"platform={dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    from paddle_tpu.core.device import enable_compile_cache
    facts = device_facts()
    facts["compile_cache_dir"] = enable_compile_cache()
    report("device", facts)
    report("barrier", barrier_fact())

    cfg = llama2_7b_width()
    width = (f"hidden={cfg.hidden_size} heads={cfg.num_attention_heads}x"
             f"{cfg.hidden_size // cfg.num_attention_heads} "
             f"kv_heads={cfg.num_key_value_heads} "
             f"ffn={cfg.intermediate_size} vocab={cfg.vocab_size} "
             f"dtype={cfg.dtype} depth={cfg.num_hidden_layers}")
    say(f"config llama-2-7b width, depth cut: {width}")
    serve_kw = dict(prompt_lens=(40, 300, 700, 130, 520, 257),
                    max_new_tokens=32, chunk=256, num_blocks=512,
                    max_batch_size=4)
    train_kw = dict(batch=4, seq=2048, steps=8)

    report("serve", serve_phase(cfg, **serve_kw))
    gc.collect()
    # the latent (MLA) launch at DeepSeek-V2's widths: a broken lowering
    # shows here in a minute and not in a cell
    report("latent_check", latent_check())
    gc.collect()
    # the grouped expert product at both MoE cells' widths
    report("grouped_check", grouped_check())
    gc.collect()
    report("flash_check", flash_check(
        train_kw["batch"], cfg.num_attention_heads, train_kw["seq"],
        cfg.hidden_size // cfg.num_attention_heads))
    train_cfg = llama2_7b_width(recompute=True)
    report("train", train_phase(train_cfg, **train_kw))
    gc.collect()

    if facts["count"] >= 4:
        from paddle_tpu.jit.spmd import mesh_2d, tp_mesh
        say("four devices: repeating both phases sharded")
        report("serve_tp4", serve_phase(cfg, mesh=tp_mesh(4), **serve_kw))
        gc.collect()
        report("train_fsdp2xtp2", train_phase(
            train_cfg, mesh=mesh_2d(2, 2), **train_kw))

    print(json.dumps({"ok": True, "device": {
        "platform": facts["platform"], "kind": facts["kind"],
        "count": facts["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
