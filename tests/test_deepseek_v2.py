"""DeepSeek-V2 (latent attention, a leading dense layer, a held share of
routed experts behind a group-limited router, shared experts): the
program against the plain reference of ``benchmark/references/`` at tiny
widths, seeded weights, float32, on the CPU.

Tolerances.  Both sides compute in float32 on the CPU, in different
orders (the reference one head at a time in bf16 pieces accumulated in
float32, the program batched einsums; the step the ABSORBED form of
attention, the eager forward and the reference the expanded one): logits
of magnitude about 1 agree to a few 1e-6, so ``LOGIT_TOL`` = 2e-4 is a
hundred times the rounding seen and a hundredth of what bfloat16 does
(about 2e-2: ``test_bfloat16_fails_the_float32_tolerances``).
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                           deepseek_v2_tiny_config)
from paddle_tpu.ops.pallas_kernels import _LATENT_KV_BLOCK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_deepseek_v2", os.path.join(
            ROOT, "benchmark", "references", "deepseek_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _cfg_dict(cfg):
    keys = ("vocab_size hidden_size intermediate_size "
            "moe_intermediate_size num_hidden_layers num_attention_heads "
            "q_lora_rank kv_lora_rank qk_nope_head_dim qk_rope_head_dim "
            "v_head_dim n_routed_experts router_experts first_held_expert "
            "n_shared_experts num_experts_per_tok n_group topk_group "
            "routed_scaling_factor norm_topk_prob first_k_dense_replace "
            "rms_norm_eps rope_theta rope_scaling dtype").split()
    return {k: getattr(cfg, k) for k in keys}


def _weights(model, ref_cfg):
    """The program's own (seeded) parameters under the reference's leaf
    names: ``(top, [layer leaves])``."""
    inner = model.deepseek
    top = {"embed_tokens.weight": inner.embed_tokens.weight._value,
           "norm.weight": inner.norm.weight._value,
           "lm_head.weight": model.lm_head.weight._value}
    layers = []
    for layer, kind in zip(inner.layers, REF.layer_kinds(ref_cfg)):
        params = {k: p._value for k, p in layer.named_parameters()}
        assert set(params) == set(REF.layer_shapes(ref_cfg, kind))
        layers.append((kind, params))
    return top, layers


def _reference_logits(model, ids):
    cfg = _cfg_dict(model.config)
    top, layers = _weights(model, cfg)
    x = REF.embed(jnp.asarray(ids), top)
    margins = []
    for kind, w in layers:
        x, route = REF.layer(x, w, cfg, kind=kind)
        if route is not None:
            margins.append(np.asarray(route[0]))
    return np.asarray(REF.logits(x, top, cfg)), np.min(margins, axis=0)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    # a share: 4 of the router's 8 experts held, from the third on
    return DeepseekV2ForCausalLM(deepseek_v2_tiny_config(
        n_routed_experts=4, router_experts=8, first_held_expert=2)).eval()


def _engine(model, **kw):
    kw = dict(dict(max_batch_size=4, num_blocks=64, block_size=4,
                   max_seq_len=96,
                   prefill_chunk_size=8), **kw)
    return ContinuousBatchingEngine(model, **kw)


def test_eager_forward_matches_the_reference(model):
    ids = np.random.default_rng(0).integers(1, 256, 70)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want, _ = _reference_logits(model, ids)
    assert np.abs(got - want).max() < LOGIT_TOL


def test_chunked_prefill_then_decode_matches_the_reference(model):
    """Through the paged latent cache: prompts longer than the chunk are
    prefilled 8 tokens a step beside decoding slots, then decoded one
    token a step (the absorbed form throughout).  The reference makes ONE
    full forward over prompt + served tokens; at every served position
    whose router margin is not within rounding, the served token's
    reference logit lies within LOGIT_TOL of the reference's best."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n) for n in (5, 27, 9, 41, 16)]
    eng = _engine(model)
    rids = [eng.add_request(p, 10) for p in prompts]
    out = eng.run_to_completion()
    checked = 0
    for p, rid in zip(prompts, rids):
        served = np.asarray(out[rid])
        logits, margin = _reference_logits(
            model, np.concatenate([p, served[:-1]]))
        at = np.arange(len(p) - 1, len(p) - 1 + len(served))
        gap = logits[at].max(-1) - logits[at, served]
        decided = margin[at] > 1e-4
        assert gap[decided].max() < LOGIT_TOL
        checked += int(decided.sum())
    assert checked >= 40
    assert len(eng.caches[0]._free) == eng.caches[0].num_blocks


def test_engine_tokens_equal_eager_generate(model):
    """Absorbed (the step) against expanded (the eager forward)."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, n) for n in (3, 19, 30)]
    eng = _engine(model)
    rids = [eng.add_request(p, 6) for p in prompts]
    out = eng.run_to_completion()
    for p, rid in zip(prompts, rids):
        want = np.asarray(model.generate(
            paddle.to_tensor(p[None]), max_new_tokens=6)._value)
        assert out[rid] == want[0, len(p):].tolist()
    assert eng.mixed.total_compiles <= len(eng.token_budgets)


def test_absorbed_equals_expanded_attention(model):
    """One layer's attention both ways over the same rows: the eager
    module (expanded: K and V rebuilt from the latent rows) against the
    absorbed launch over a paged pool, the Pallas kernel in interpret
    mode and the XLA fallback.  Float32, summed in another order: 1e-5
    of values of magnitude 0.1-1."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.pallas_kernels import rope_interleaved
    from paddle_tpu.ops.paged_attention import (
        _ragged_latent_attention_xla, write_ragged_latent)
    from paddle_tpu.ops.pallas_kernels import \
        _ragged_latent_attention_pallas
    at = model.deepseek.layers[1].self_attn
    S, bs = 21, 4
    x = jax.random.normal(jax.random.key(3), (1, S, 64), jnp.float32)
    want = np.asarray(at(Tensor._from_value(x))._value)[0]
    h = Tensor._from_value(x)
    pos = jnp.arange(S, dtype=jnp.int32)
    cos, sin = at.rope_tables(pos)
    q_nope, q_r = at.queries(h)
    c_kv, k_r = at.latent(h)
    q_r = rope_interleaved(q_r[0], cos[:, None], sin[:, None])
    k_r = rope_interleaved(k_r[0], cos, sin)
    wk, wv = at.kv_b()
    pad = at.latent_row - at.kv_lora - at.rope
    q_abs = jnp.concatenate(
        [jnp.einsum("thn,chn->thc", q_nope[0], wk), q_r,
         jnp.zeros((S, at.num_heads, pad), jnp.float32)], -1)
    rows = jnp.concatenate(
        [c_kv[0], k_r, jnp.zeros((S, pad), jnp.float32)], -1)
    pages = np.random.default_rng(0).permutation(16)[:6].astype(np.int32)
    pool = write_ragged_latent(
        rows, jnp.zeros((17, bs, at.latent_row), jnp.float32),
        jnp.asarray(pages[np.arange(S) // bs]), jnp.asarray(
            np.arange(S, dtype=np.int32) % bs))
    # two spans over the same sequence: a 13-token "prefix" chunk and
    # the 8 tokens after it, as a later chunk of the same request
    tabs = (jnp.asarray(np.stack([pages, pages])), jnp.asarray([0, 13]),
            jnp.asarray([13, 8]), jnp.asarray([13, 21]))
    for o_lat in (
            _ragged_latent_attention_xla(q_abs, pool, *tabs,
                                         at.softmax_scale, at.kv_lora),
            _ragged_latent_attention_pallas(q_abs, pool, *tabs,
                                            at.softmax_scale, at.kv_lora,
                                            interpret=True)):
        o = jnp.einsum("thc,chv->thv", o_lat, wv).reshape(S, -1)
        got = np.asarray(o @ at.o_proj.weight._value)
        assert np.abs(got - want).max() < 1e-5


# (q_len, kv_len) spans of one pack, in units of the committed key
# block's width n; every case is padded to the same shapes, so the
# interpreted kernel is traced once
_BLOCK_CASES = {
    "kv_n_minus_1": lambda n: [(1, n - 1)],
    "kv_n": lambda n: [(1, n)],
    "kv_n_plus_1": lambda n: [(1, n + 1)],
    "kv_3n_plus_5": lambda n: [(1, 3 * n + 5)],
    # rows at positions n - 3 .. n + 4: the diagonal leaves block 0
    # inside the tile
    "diagonal_crosses_mid_tile": lambda n: [(8, n + 5)],
    "chunk_over_prefix": lambda n: [(13, 2 * n + 9)],
    "only_block_masked": lambda n: [(1, 5)],
    "empty_span": lambda n: [(0, 0), (3, 7)],
    "mixed": lambda n: [(1, n - 1), (0, 0), (8, n + 5), (1, 5), (1, n),
                        (11, 3 * n + 5), (1, n + 1)],
}
_BLOCK_T, _BLOCK_S, _BLOCK_PAGE = 32, 8, 8


def _latent_pack(spans, n, seed=0):
    """Random absorbed queries, a pool and span tables for ``spans``,
    padded to ``_BLOCK_T`` tokens and ``_BLOCK_S`` spans."""
    rng = np.random.default_rng(seed)
    H, row, bs = 4, 128, _BLOCK_PAGE
    W = -(-(3 * n + 5) // bs)
    q = rng.standard_normal((_BLOCK_T, H, row)).astype(np.float32)
    pool = rng.standard_normal((len(spans) * W + 1, bs, row)).astype(
        np.float32) * 0.3
    pages = rng.permutation(len(spans) * W).astype(np.int32)
    bt = np.full((_BLOCK_S, W), -1, np.int32)
    q_off = np.full(_BLOCK_S, _BLOCK_T, np.int32)
    q_len = np.zeros(_BLOCK_S, np.int32)
    kv_len = np.zeros(_BLOCK_S, np.int32)
    off = 0
    for i, (ql, kl) in enumerate(spans):
        used = -(-kl // bs)
        bt[i, :used] = pages[i * W:i * W + used]
        q_off[i], q_len[i], kv_len[i] = off, ql, kl
        off += ql
    assert off <= _BLOCK_T
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
            jnp.asarray(q_off), jnp.asarray(q_len), jnp.asarray(kv_len))


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_latent_kernel_block_edges(case):
    """The latent launch's two bodies (blocks wholly under a tile's
    diagonal unmasked, the last ones masked) against the XLA fallback,
    float32, at spans that put ``kv_len`` and the diagonal on, one short
    of and one past a key block's edge."""
    from paddle_tpu.ops.paged_attention import _ragged_latent_attention_xla
    from paddle_tpu.ops.pallas_kernels import (
        _LATENT_TILE_TOKENS, _latent_pages_per_block,
        _ragged_latent_attention_pallas, ragged_attn_blocks)
    n = _LATENT_KV_BLOCK
    spans = _BLOCK_CASES[case](n)
    args = _latent_pack(spans, n)
    want = _ragged_latent_attention_xla(*args, 0.09, 64)
    got = _ragged_latent_attention_pallas(*args, 0.09, 64, interpret=True)
    real = sum(ql for ql, _ in spans)
    assert real
    np.testing.assert_allclose(np.asarray(got)[:real],
                               np.asarray(want)[:real], atol=2e-5)
    W = args[2].shape[1]
    blocks, masked = ragged_attn_blocks(
        [ql for ql, _ in spans], [kl for _, kl in spans],
        _LATENT_TILE_TOKENS, _latent_pages_per_block(_BLOCK_PAGE, W),
        _BLOCK_PAGE, W)
    assert blocks >= masked >= 0
    if case.startswith("kv_n") and case != "kv_n_plus_1":
        # a one-row span sees every key it walks: kv_len == n is one
        # whole block, n - 1 one partly filled (masked) one
        assert (blocks, masked) == (1, int(case == "kv_n_minus_1"))


def _brute_force_blocks(q_lens, kv_lens, n, tile):
    blocks = masked = 0
    for ql, kl in zip(q_lens, kv_lens):
        for first in range(0, max(ql, 0), tile):
            rows = min(ql - first, tile)
            pos = [kl - ql + first + j for j in range(rows)]
            kv_end = min(kl, pos[-1] + 1)
            for b in range(-(-kv_end // n)):
                blocks += 1
                cols = range(b * n, (b + 1) * n)
                masked += any(c > p or c >= kv_end
                              for p in pos for c in cols)
    return blocks, masked


@pytest.mark.parametrize("launch", ["latent", "grouped_query"])
def test_attn_blocks_closed_form(launch):
    """``ragged_attn_blocks`` at each launch's tile and key block (the
    latent one's 8 tokens and 1,024 keys, the grouped-query one's 32
    tokens and 512 keys at the cells' 32 / 8 / 128 heads in bf16)
    against a count made key by key, row by row, and each cell's own
    step by hand."""
    from paddle_tpu.ops.pallas_kernels import (_LATENT_TILE_TOKENS,
                                               _latent_pages_per_block,
                                               ragged_attn_blocks,
                                               ragged_tile_geometry)
    if launch == "latent":
        bs, n = 4, _LATENT_KV_BLOCK
        W = 4 * n // bs
        tile, kb = _LATENT_TILE_TOKENS, _latent_pages_per_block(bs, W)
    else:
        bs, W = 16, 388                     # docs' pages and table
        tile, kb = ragged_tile_geometry(32, 8, 128, bs, W, "bfloat16",
                                        "bfloat16")
        n = kb * bs
        assert (tile, n) == (32, 512)

    def count(ql, kl, page=bs, width=W):
        return ragged_attn_blocks(ql, kl, tile, kb, page, width)

    rng = np.random.default_rng(5)
    for _ in range(20):
        ql = rng.integers(0, 5 * tile, 6)
        kl = ql + rng.integers(0, 3 * n, 6)
        kl[ql == 0] = rng.integers(0, 2 * n, int((ql == 0).sum()))
        assert count(ql, kl) == _brute_force_blocks(
            ql.tolist(), kl.tolist(), n, tile)
    # edges: on, before and after a block's last key
    for kl in (n - 1, n, n + 1, 2 * n, 3 * n + 5):
        for ql in (1, tile - 1, tile, tile + 1, 2 * tile):
            if ql <= kl:
                assert count([ql], [kl]) == _brute_force_blocks(
                    [ql], [kl], n, tile)
    assert count([], []) == (0, 0)
    if launch == "latent":
        # pages of 128, as the cell has them: 64 tiles a chunk, each
        # walking 8,192 / n whole blocks and the diagonal's one
        kb = _latent_pages_per_block(128, 260)
        blocks, masked = count([512, 1], [8192 + 512, 16385], 128, 260)
        tiles = 512 // tile
        assert masked == tiles + 1
        assert blocks == tiles * (8192 // n + 1) + 16384 // n + 1
    else:
        # docs' step: a chunk at prefix 1,024 (16 tiles of two whole
        # blocks and the diagonal's) beside a decode span at 3,000
        # (five whole blocks and a partly filled sixth)
        assert count([512, 1], [1536, 3000]) == (16 * 3 + 6, 16 + 1)


def test_bfloat16_fails_the_float32_tolerances(model):
    """The same weights rounded to bfloat16 and served in bfloat16: the
    logits leave LOGIT_TOL by two orders of magnitude, so a lower
    precision than stated cannot pass the tests above."""
    import copy
    low = copy.deepcopy(model)
    low.config.dtype = "bfloat16"
    low.bfloat16()
    ids = np.random.default_rng(0).integers(1, 256, 70)
    got = np.asarray(low(paddle.to_tensor(ids[None]))._value.astype(
        jnp.float32))[0]
    want, _ = _reference_logits(model, ids)
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


def test_yarn_tables_match_the_closed_form():
    """cos / sin at positions past the original 4,096 against the formula
    in float64: ``inv_freq_i = (f_i / 40) ramp_i + f_i (1 - ramp_i)``,
    ``ramp`` from find_correction_range(32, 1, 64, 10000, 4096)."""
    import math
    from paddle_tpu.ops.pallas_kernels import (rope_tables_for_positions,
                                               yarn_inv_freq)
    d, theta, factor, orig = 64, 10000.0, 40.0, 4096

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            theta))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), d - 1)
    assert (low, high) == (10, 23)
    want = []
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / factor * ramp + f * (1 - ramp))
    inv = yarn_inv_freq(d, theta, factor, orig, 32, 1)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert inv[0] == 1.0 and abs(inv[-1] * factor
                                 - theta ** (-62 / 64)) < 1e-9
    pos = np.array([0, 1, 4095, 4096, 5000, 32767, 163839])
    cos, sin = rope_tables_for_positions(jnp.asarray(pos), d, theta,
                                         inv_freq=inv)
    ang = pos[:, None].astype(np.float64) * np.asarray(want)[None]
    # float32 angles of up to 1.6e5 radians: 1.6e5 x 2^-24 = 1e-2
    np.testing.assert_allclose(np.asarray(cos)[:, :32], np.cos(ang),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(sin)[:5, 32:], np.sin(ang)[:5],
                               atol=1e-3)
    # and the reference's own tables are the same frequencies
    np.testing.assert_allclose(REF.inv_freq(
        {"qk_rope_head_dim": d, "rope_theta": theta, "rope_scaling": {
            "factor": factor, "original_max_position_embeddings": orig,
            "beta_fast": 32, "beta_slow": 1}}), want, rtol=1e-6)


def test_group_limited_topk_against_a_loop():
    from paddle_tpu.ops.moe_gate import group_limited_topk
    rng = np.random.default_rng(5)
    s = rng.random((50, 24)).astype(np.float32)
    top_s, top_i = group_limited_topk(jnp.asarray(s), 4, n_group=6,
                                      topk_group=2)
    for t in range(50):
        best = s[t].reshape(6, 4).max(-1)
        keep = np.argsort(-best)[:2]
        cand = [e for e in range(24) if e // 4 in keep]
        want = sorted(cand, key=lambda e: -s[t, e])[:4]
        assert list(np.asarray(top_i[t])) == want
        np.testing.assert_array_equal(np.asarray(top_s[t]), s[t, want])


def test_four_shares_add_up_to_the_whole_layer():
    """The guide's share test: each of four banks holds a quarter of the
    experts behind the SAME 16-wide router; their routed parts, with the
    shared experts counted once, add up to what the UNCUT reference gives
    for the whole routed layer.  Float32: 1e-5 of values about 0.1."""
    from paddle_tpu.ops.moe_gate import moe_ffn_held
    cfg = {"hidden_size": 32, "moe_intermediate_size": 16,
           "router_experts": 16, "n_routed_experts": 16,
           "first_held_expert": 0, "num_experts_per_tok": 3, "n_group": 4,
           "topk_group": 2, "routed_scaling_factor": 2.5,
           "n_shared_experts": 2}
    k = jax.random.key(7)
    shapes = {"mlp.gate.weight": (32, 16), "mlp.w_gate": (16, 32, 16),
              "mlp.w_up": (16, 32, 16), "mlp.w_down": (16, 16, 32),
              "mlp.shared_experts.gate_proj.weight": (32, 32),
              "mlp.shared_experts.up_proj.weight": (32, 32),
              "mlp.shared_experts.down_proj.weight": (32, 32)}
    w = {n: 0.3 * jax.random.normal(jax.random.fold_in(k, i), s)
         for i, (n, s) in enumerate(shapes.items())}
    x = jax.random.normal(jax.random.fold_in(k, 99), (40, 32))
    whole, (margin, chosen) = REF.moe(x, w, cfg, None)
    shared = REF._swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                         w["mlp.shared_experts.up_proj.weight"],
                         w["mlp.shared_experts.down_proj.weight"], None)
    total, rows = shared, 0
    for first in (0, 4, 8, 12):
        part, load = moe_ffn_held(
            x, w["mlp.gate.weight"], w["mlp.w_gate"][first:first + 4],
            w["mlp.w_up"][first:first + 4],
            w["mlp.w_down"][first:first + 4], top_k=3, first_held=first,
            n_group=4, topk_group=2, routed_scale=2.5)
        total = total + part
        rows += int(load.sum())
        # and the reference given the same share agrees part by part
        ref_part, _ = REF.moe(x, {**w, **{
            n: w[n][first:first + 4]
            for n in ("mlp.w_gate", "mlp.w_up", "mlp.w_down")}},
            dict(cfg, n_routed_experts=4, first_held_expert=first), None)
        assert np.abs(np.asarray(ref_part - shared - part)).max() < 1e-5
    assert rows == 40 * 3               # every assignment lands once
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    assert np.asarray(chosen).shape == (40, 3) and float(margin.min()) > 0


def test_pool_holds_one_latent_row_a_token(model):
    from paddle_tpu.observability import default_registry, generate_latest
    from paddle_tpu.ops.pallas_kernels import latent_row_width
    eng = _engine(model)
    cfg = model.config
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    for c in eng.caches:
        assert c.value_cache is None and c.latent
        assert c.key_cache.shape == (65, 4, latent_row_width(
            cfg.kv_lora_rank, cfg.qk_rope_head_dim))
        # bytes a token: the row, padded to whole 128-lane tiles
        per_token = c.per_chip_pool_bytes() / (65 * 4)
        assert row * 4 <= per_token < (row + 128) * 4
    # the published widths: 512 + 64 stored 640 wide, at most 640
    assert latent_row_width(512, 64) == 640
    assert b"serving_kv_latent_row_bytes 512" in generate_latest(
        default_registry())


def test_step_record_and_counters_of_the_held_share(model):
    from paddle_tpu.observability import default_registry, span_log
    eng = _engine(model)
    load = default_registry().get("serving_moe_expert_load_total")
    before = sum(load.labels(expert=str(e)).value for e in range(4))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 256, n) for n in (11, 23)]
    rids = [eng.add_request(p, 4) for p in prompts]
    out = eng.run_to_completion()
    recs = [e[5] for e in span_log.events()
            if e[1] == "serving.step" and e[5]["engine"] == eng.engine_id]
    made = sum(r["tokens"] for r in recs) * 3 * 2     # k x routed layers
    rows = sum(r["moe_rows"] for r in recs)
    assert 0 < rows < made
    # the pack's padding is given to no expert and counted in no load:
    # the step counts what the eager layers count over the same tokens
    # (each prompt and its outputs but the last), and steps here are
    # padded (11 + 23 tokens do not fill whole budgets)
    assert sum(r["budget"] for r in recs) > sum(r["tokens"] for r in recs)
    want = 0
    for p, rid in zip(prompts, rids):
        model(paddle.to_tensor(np.concatenate([p, out[rid][:-1]])[None]))
        want += sum(int(layer.mlp.last_load.sum())
                    for layer in model.deepseek.layers[1:])
    assert rows == want
    assert all(r["moe_rows_top"] * 4 >= r["moe_rows"] / 1.0001
               for r in recs if r["budget"])
    # XLA fallback: every row of the budget, every head, no key block
    assert all(r["attn_rows"] == r["budget"] * 4 for r in recs)
    assert all(r["attn_blocks"] == r["attn_blocks_masked"] == 0
               for r in recs)
    after = sum(load.labels(expert=str(e)).value for e in range(4))
    assert after - before == rows
    scopes = set(eng.mixed.op_scopes(eng.token_budgets[0]).values())
    assert {"attn.q_lora", "attn.kv_latent", "attn.absorb",
            "attn.unabsorb", "moe.sort", "moe.shared"} <= scopes


def test_padding_rows_are_given_to_no_expert():
    from paddle_tpu.ops.moe_gate import moe_ffn_held
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.float32)
    kw = dict(top_k=3, first_held=2, n_group=4, topk_group=2,
              routed_scale=2.5)
    valid = jnp.arange(12) < 7
    out, load = moe_ffn_held(x, gate, wg, wu, wd, valid=valid, **kw)
    want, want_load = moe_ffn_held(x[:7], gate, wg, wu, wd, **kw)
    np.testing.assert_allclose(out[:7], want, atol=1e-5)
    assert not np.asarray(out[7:]).any()
    np.testing.assert_array_equal(load, want_load)
    assert int(moe_ffn_held(x, gate, wg, wu, wd, **kw)[1].sum()) \
        > int(load.sum())


def test_renormalised_gate_is_refused():
    """``norm_topk_prob`` true is a branch no configuration here runs:
    the config refuses it, nothing implements it."""
    with pytest.raises(ValueError, match="norm_topk_prob"):
        deepseek_v2_tiny_config(norm_topk_prob=True)
    cfg = dict(_cfg_dict(deepseek_v2_tiny_config()), norm_topk_prob=True)
    w = {k: jnp.zeros(shape, jnp.float32)
         for k, shape in REF.layer_shapes(cfg, "sparse").items()}
    with pytest.raises(ValueError, match="norm_topk_prob"):
        REF.layer(jnp.zeros((4, 64)), w, cfg, kind="sparse")


def test_step_record_counts_the_latent_launch_blocks(model):
    """``attn_blocks`` / ``attn_blocks_masked`` of the record: what the
    TPU launch (sized here, never run) walks for the spans a CPU engine
    packed, against the count made key by key."""
    from paddle_tpu.observability import span_log
    from paddle_tpu.ops.pallas_kernels import (_LATENT_TILE_TOKENS,
                                               _latent_pages_per_block)
    eng = _engine(model)
    rng = np.random.default_rng(6)
    for n in (19, 37, 5):
        eng.add_request(rng.integers(1, 256, n), 5)
    eng.run_to_completion()
    recs = [e[5] for e in span_log.events()
            if e[1] == "serving.step" and e[5]["engine"] == eng.engine_id]
    launched = [r for r in recs if r["budget"]]
    assert launched and all("attn_blocks" in r for r in recs)
    tpu = _engine(model, use_pallas=True).mixed
    n = 4 * _latent_pages_per_block(4, tpu.bt_width)
    total = masked = 0
    for r in launched:
        q_lens, kv_lens = r["spans"][:, 1], r["spans"][:, 2]
        got = tpu.attn_blocks(q_lens, kv_lens)
        assert got == _brute_force_blocks(
            q_lens.tolist(), kv_lens.tolist(), n, _LATENT_TILE_TOKENS)
        # every tile walks a block, and its last block holds the
        # diagonal unless the tile's first row sees it whole
        tiles = sum(-(-int(q) // _LATENT_TILE_TOKENS) for q in q_lens)
        assert got[0] >= tiles and got[1] <= got[0]
        total, masked = total + got[0], masked + got[1]
    assert 0 < masked <= total
    assert eng.mixed.attn_blocks([8], [40]) == (0, 0)      # XLA fallback


def test_latent_vmem_mirror_is_the_launch_scratch():
    """``latent_kernel_vmem_bytes`` (what ``tools/check_vmem_budget.py``
    gates) against the scratch the launch itself declares at the cell's
    widths, read from its jaxpr, plus the live score and probability
    tiles of one sub-tile against one key block."""
    from paddle_tpu.ops import pallas_kernels as pk
    sds = jax.ShapeDtypeStruct
    spans = sds((16,), jnp.int32)
    (call,) = jax.make_jaxpr(
        lambda q, c, bt, qo, ql, kl: pk._ragged_latent_attention_pallas(
            q, c, bt, qo, ql, kl, 0.1147, 512))(
        sds((16, 128, 640), jnp.bfloat16), sds((261, 128, 640),
                                               jnp.bfloat16),
        sds((16, 260), jnp.int32), spans, spans, spans).eqns
    (launch,) = [e for e in call.params["jaxpr"].jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
    n_scratch = launch.params["grid_mapping"].num_scratch_operands
    scratch = [v.aval for v in launch.params["jaxpr"].invars[-n_scratch:]
               if str(v.aval.memory_space) == "vmem"]
    assert len(scratch) == 6
    n = _LATENT_KV_BLOCK
    assert scratch[-1].shape == (2, n, 640)          # the two page slots
    declared = sum(pk._tile_bytes(a.shape, a.dtype.itemsize)
                   for a in scratch)
    live = 2 * pk._tile_bytes((pk._LATENT_SUB_TOKENS * 128, n), 4)
    got = pk.latent_kernel_vmem_bytes(
        heads=128, kv_lora_rank=512, rope_dim=64, block_size=128,
        bt_width=260)
    assert got == declared + live
    assert got == pk.kernel_vmem_report()["ragged_latent_bf16"]
    assert got <= pk._RAGGED_TILE_VMEM


def test_latent_attn_rows_counts_real_sub_tiles():
    from paddle_tpu.ops.pallas_kernels import (_LATENT_SUB_TOKENS,
                                               latent_attn_rows)
    sub = _LATENT_SUB_TOKENS
    assert latent_attn_rows([1, 1, 0, 512], 128) == 128 * (
        2 * sub + 512)


@pytest.mark.parametrize("kw, word", [
    (dict(kv_dtype="int8"), "int8"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(role="prefill"), "migration"),
    (dict(draft_model="same", spec_k=2), "draft"),
    (dict(mesh="tp2"), "mesh"),
])
def test_engine_refuses_what_is_not_taught_the_latent_row(model, kw, word):
    kw = dict(kw)
    if kw.get("draft_model") == "same":
        kw["draft_model"] = model
    if kw.get("mesh") == "tp2":
        from paddle_tpu.jit.spmd import tp_mesh
        kw["mesh"] = tp_mesh(2)
    with pytest.raises(ValueError, match=word) as err:
        _engine(model, **kw)
    assert "latent" in str(err.value)


def test_migration_refuses_a_latent_engine(model):
    eng = _engine(model)
    assert eng.migration_geometry() is None
    with pytest.raises(ValueError, match="latent"):
        eng.inject_request(np.arange(1, 5), object())


def test_bank_narrower_than_its_router_names_both_widths():
    from paddle_tpu.ops.moe_gate import moe_ffn
    x = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match=r"16 experts wide.*holds 4"):
        moe_ffn(x, jnp.zeros((8, 16)), jnp.zeros((4, 8, 8)),
                jnp.zeros((4, 8, 8)), jnp.zeros((4, 8, 8)), top_k=2)
