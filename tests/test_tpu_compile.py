"""AOT-compile every Pallas kernel, and one whole serving step, with the
REAL XLA:TPU + Mosaic compilers for v5e — on a box with no chip.

``jax.experimental.topologies`` hands out compile-only ``TpuDevice``s;
lowering against a ``ShapeDtypeStruct`` that carries one of them runs
the same compiler the chip machine runs.  Interpret mode (every other
kernel test in this suite) checks a kernel's arithmetic and nothing
about whether Mosaic accepts it: the first chip bring-up found the
ragged span kernel rejected for bf16, for GQA and for int8 pools while
all its interpret tests were green.  Whether a kernel RUNS right is
chip_smoke.py's job; whether it COMPILES is checked here, on every PR.

Shapes are the published ones: head_dim 128, 16-token pages, the
serving chunk 256, training sequence 2048.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core import device as _device

D, BLOCK, PAGES, WIDTH, SPANS, CHUNK = 128, 16, 512, 46, 4, 256
HEADS = {"mha": (32, 1), "gqa4": (8, 4)}          # name -> (Hkv, groups)


@pytest.fixture(scope="module")
def v5e():
    """One compile-only v5e device's sharding."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        print(f"SKIP test_tpu_compile: get_topology_desc raised {e!r}")
        pytest.skip(f"no compile-only TPU topology: {e!r}")
    dev = topo.devices[0]
    assert dev.platform == "tpu" and "v5" in dev.device_kind
    return SingleDeviceSharding(dev)


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """The dispatchers ask core.device.on_tpu(); the target is a TPU."""
    monkeypatch.setattr(_device, "on_tpu", lambda: True)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), "no Mosaic kernel"
    return lowered.compile()


def _kernel_calls(scopes, kernel_name: str):
    """The optimized program's launches of the Pallas kernel
    ``kernel_name`` (XLA names a ``tpu_custom_call`` after its
    ``pallas_call(name=)``), out of ``hlo_op_scopes``'s names."""
    return [n for n in scopes if n.startswith(kernel_name)]


def _pool(hkv, dtype):
    return ((PAGES, BLOCK, hkv, D), dtype)


@pytest.mark.parametrize("seq,d", [(2048, 128), (2048, 64), (1024, 128)])
def test_flash_forward(v5e, seq, d):
    from paddle_tpu.ops.pallas_kernels import _flash_attention_value
    qkv = ((1, 32, seq, d), jnp.bfloat16)
    _compile(lambda q, k, v: _flash_attention_value(q, k, v, True),
             v5e, qkv, qkv, qkv)


@pytest.mark.parametrize("d", [128, 64])
def test_flash_rope_forward_backward(v5e, as_if_on_tpu, d):
    from paddle_tpu.ops import pallas_kernels as pk
    seq = 2048
    cos, sin = pk.rope_tables(seq, d)

    def loss(q, k, v):
        return pk._flash_rope_sdpa(q, k, v, cos, sin, True).astype(
            jnp.float32).sum()

    qkv = ((1, 32, seq, d), jnp.bfloat16)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, qkv, qkv, qkv)


@pytest.mark.parametrize("rows", [8, CHUNK])
@pytest.mark.parametrize("with_amax", [False, True])
def test_rope_qkv_epilogue(v5e, rows, with_amax):
    from paddle_tpu.ops.pallas_kernels import rope_qkv_epilogue
    qkv = ((rows, 32, D), jnp.bfloat16)
    cs = ((rows, D), jnp.float32)
    _compile(lambda q, k, v, c, s: rope_qkv_epilogue(
        q, k, v, c, s, with_amax=with_amax, use_pallas=True),
        v5e, qkv, qkv, qkv, cs, cs)


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode(v5e, dtype, heads, kv):
    from paddle_tpu.ops.paged_attention import _paged_attention_pallas
    hkv, groups = HEADS[heads]
    quant = kv == "int8"
    shapes = [((SPANS, hkv * groups, D), dtype),
              _pool(hkv, jnp.int8 if quant else dtype),
              _pool(hkv, jnp.int8 if quant else dtype),
              ((SPANS, WIDTH), jnp.int32), ((SPANS,), jnp.int32)]
    if quant:
        shapes += [((PAGES, hkv), jnp.float32)] * 2

    def fn(q, kc, vc, bt, sl, ks=None, vs=None):
        return _paged_attention_pallas(q, kc, vc, bt, sl, D ** -0.5,
                                       key_scale=ks, value_scale=vs)
    _compile(fn, v5e, *shapes)


# name -> (token budget, spans, block-table width, pool pages).  The
# first two are ISSUE 21's table (a pack of one-token spans; three of
# them beside one CHUNK-token chunk); the last two are the benchmark
# cells' own launches (GQA 4:1 only: an int8 pool's scale tables are
# [Hkv, pages] in SMEM, and 32 kv heads x 4096 pages do not fit it): a
# budget-64 pack of one-token spans, and budget 1024 with 40 one-token
# spans beside two 512-token chunks, at the chat cell's 64 spans x 224
# pages over a 4096-page pool.
RAGGED = {"decode": (SPANS, SPANS, WIDTH, PAGES),
          "chunk": (SPANS - 1 + CHUNK, SPANS, WIDTH, PAGES),
          "cell64": (64, 64, 224, 4096),
          "cell1024": (1024, 64, 224, 4096)}


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("heads,pack", [
    (h, p) for h in sorted(HEADS) for p in ("decode", "chunk")]
    + [("gqa4", p) for p in ("cell64", "cell1024")])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_span(v5e, dtype, heads, pack, kv):
    """The table of ISSUE 21 (bf16 x MHA, GQA x chunks and every int8
    case were MosaicErrors once) and the cells' own shapes.  Every
    descriptor is traced data, so one compile a budget covers any mix
    of spans: q tiles (128 rows a kv head) DMA'd from the token-major
    pack at a dynamic offset, pages DMA'd as stored ([page, Hkv, D], bf16 or int8) and
    each head's rows shifted out of the packed sublanes."""
    from paddle_tpu.ops.pallas_kernels import \
        _ragged_paged_attention_pallas
    hkv, groups = HEADS[heads]
    quant = kv == "int8"
    tokens, n_spans, width, pages = RAGGED[pack]
    spans = ((n_spans,), jnp.int32)
    pool = ((pages, BLOCK, hkv, D), jnp.int8 if quant else dtype)
    shapes = [((tokens, hkv * groups, D), dtype), pool, pool,
              ((n_spans, width), jnp.int32), spans, spans, spans]
    if quant:
        shapes += [((pages, hkv), jnp.float32)] * 2

    def fn(q, kc, vc, bt, qo, ql, kl, ks=None, vs=None):
        return _ragged_paged_attention_pallas(
            q, kc, vc, bt, qo, ql, kl, D ** -0.5,
            key_scale=ks, value_scale=vs)
    _compile(fn, v5e, *shapes)


def test_mixed_step_full_width_two_layers(v5e):
    """One whole fused serving step at Llama-2-7B width, bf16, 2 layers:
    the top token budget (decodes + one 256-token chunk)."""
    import chip_smoke
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(0)
    model = LlamaForCausalLM(chip_smoke.llama2_7b_width(depth=2))
    model.bfloat16()
    model.eval()
    eng = ContinuousBatchingEngine(
        model, max_batch_size=SPANS, num_blocks=PAGES, block_size=BLOCK,
        max_seq_len=WIDTH * BLOCK,
        prefill_chunk_size=CHUNK, use_pallas=True)
    lowered = eng.mixed.aot_lower(eng.token_budgets[-1],
                                  device_sharding=v5e)
    text = lowered.as_text()
    assert text.splitlines()[0].startswith("module @jit_mixed_step")
    for name in ("ragged_paged_attention", "rope_qkv_epilogue"):
        assert f'kernel_name = "{name}"' in text
    compiled = lowered.compile()
    # the v5e program keeps the step's named scopes: each Mosaic kernel
    # and the ops a trace could not tell apart (every matmul a `fusion`)
    # belong to a part
    from paddle_tpu.jit.serving_step import STEP_SCOPES, hlo_op_scopes
    hlo = compiled.as_text()
    scopes = hlo_op_scopes(hlo)
    kernels = {n: s for n, s in scopes.items()
               if n.startswith(("ragged_paged_attention",
                                "rope_qkv_epilogue"))}
    assert sorted(kernels.values()) == ["attn.kernel"] * 2 \
        + ["attn.rope"] * 2
    assert {"attn.kernel", "attn.kv_write", "attn.qkv", "attn.out",
            "ffn", "lm_head", "embed"} <= set(scopes.values()) \
        <= STEP_SCOPES | {None}
    # no pass over a pool: the only instructions of the optimized
    # program that produce or take a whole layer's pool are the
    # donated scatter of attn.kv_write (aliased in place), the ragged
    # kernel that reads its pages, and the plumbing that hands the
    # buffers through — no convert, no copy, no transpose of one
    cache = eng.caches[0].key_cache
    pool = "bf16[%s]" % ",".join(str(d) for d in cache.shape)
    ops = set()
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if m and pool in line:
            ops.add(m.group(2))
    assert ops <= {"parameter", "tuple", "get-tuple-element", "bitcast",
                   "fusion", "scatter", "custom-call",
                   "dynamic-update-slice"}, ops
    for line in hlo.splitlines():
        if pool in line and " fusion(" in line:
            # a fusion over a pool is the scatter that writes it
            name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
            assert scopes[name] == "attn.kv_write", line
    mem = compiled.memory_analysis()
    # pools are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= sum(
        2 * int(c.key_cache.nbytes) for c in eng.caches)


def test_mixed_step_mixtral_full_width_two_layers(v5e):
    """One whole fused step at Mixtral-8x7B's widths (hidden 4096, 8
    experts of 14336, 2 a token, 32 x 128 heads over 8 kv heads), bf16,
    2 layers, under the framework's own x64 setting: the optimized v5e
    program multiplies the experts' rows with the Pallas grouped product
    (``grouped_expert_matmul`` under ``moe.experts``, two launches a
    layer), holds no ``ragged_dot`` and no buffer an expert (no shape
    that leads with ``[E, N*k``)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.jit.serving_step import STEP_SCOPES, hlo_op_scopes
    from paddle_tpu.models.mixtral import (MixtralConfig,
                                           MixtralForCausalLM)
    assert jax.config.jax_enable_x64
    paddle.seed(0)
    E, K, HID, FFN = 8, 2, 4096, 14336
    # built with thin experts, then every bank handed ONE full-width
    # array of its shape: the compiler sees the published widths and the
    # host neither draws nor holds 2.8 G random weights
    cfg = MixtralConfig(
        vocab_size=32000, hidden_size=HID, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=2048,
        num_local_experts=E, num_experts_per_tok=K, dtype="bfloat16")
    model = MixtralForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    up = jnp.zeros((E, HID, FFN), jnp.bfloat16)
    down = jnp.zeros((E, FFN, HID), jnp.bfloat16)
    for layer in model.mixtral.layers:
        blk = layer.block_sparse_moe
        blk.w_gate._value, blk.w_up._value, blk.w_down._value = up, up, down
    eng = ContinuousBatchingEngine(
        model, max_batch_size=SPANS, num_blocks=PAGES, block_size=BLOCK,
        max_seq_len=WIDTH * BLOCK,
        prefill_chunk_size=CHUNK, use_pallas=True)
    top = eng.token_budgets[-1]
    assert eng.mixed.n_stats == 3 + E
    lowered = eng.mixed.aot_lower(top, device_sharding=v5e)
    text = lowered.as_text()
    assert "ragged_dot" not in text
    assert text.count('kernel_name = "grouped_expert_matmul"') >= 1
    hlo = lowered.compile().as_text()
    assert "ragged-dot" not in hlo
    scopes = hlo_op_scopes(hlo)
    assert {"moe.gate", "moe.sort", "moe.experts", "moe.combine",
            "attn.kernel"} <= set(scopes.values()) <= STEP_SCOPES | {None}
    assert not {"moe.dispatch", "ep.all_to_all"} & set(scopes.values())
    grouped = _kernel_calls(scopes, "grouped_expert_matmul")
    assert len(grouped) == 2 * cfg.num_hidden_layers
    assert {scopes[n] for n in grouped} == {"moe.experts"}
    # the buffers are gone: no instruction's shape leads with [E, N*k
    assert not re.search(r"\[%d,%d[,\]]" % (E, top * K), hlo)


# the grouped expert product alone at the published widths of both MoE
# cells: name -> (rows of the sorted buffer before padding, experts
# held, K, N, weight banks)
GROUPED = {
    "mixtral_gate_up": (2048, 8, 4096, 14336, 2),
    "mixtral_down": (2048, 8, 14336, 4096, 1),
    "deepseek_gate_up": (3168, 40, 5120, 1536, 2),
    "deepseek_down": (3168, 40, 1536, 5120, 1),
}


@pytest.mark.parametrize("shape", sorted(GROUPED))
def test_grouped_expert_matmul(v5e, shape):
    """``[rows, K] x [E, K, N]`` over rows sorted by expert, at the top
    budget of each cell (Mixtral 1,024 tokens x 2, DeepSeek-V2 528 x 6):
    whole-K weight blocks through the pipeline, row tiles sized by the
    rows an expert has (128 / 32), more VMEM than Mosaic's default
    limit allows (``vmem_limit_bytes``)."""
    from paddle_tpu.ops import pallas_kernels as pk
    rows, experts, k, n, banks = GROUPED[shape]
    tile = pk.grouped_tile_rows(rows, experts)
    assert tile == (128 if experts == 8 else 32)
    buf = pk.grouped_buffer_rows(rows, experts, tile)
    compiled = _compile(
        lambda xs, load, *w: pk.grouped_expert_matmul(
            xs, pk.grouped_slot_tables(load, tile), *w, tile=tile),
        v5e, ((buf, k), jnp.bfloat16), ((experts,), jnp.int32),
        *[((experts, k, n), jnp.bfloat16)] * banks)
    assert "grouped_expert_matmul" in compiled.as_text()
    # the audit's cell is the launch's own
    tn = pk.grouped_column_tile(k, n, banks, tile)
    assert n % tn == 0 and pk._grouped_cell_vmem_bytes(
        k, tn, banks, tile, 2) <= pk._GROUPED_VMEM


# the latent (MLA) launch at DeepSeek-V2's widths: 128 heads over one
# 512 + 64 row a token stored 640 wide, 128-token pages, 260 a span
# (budgets 16 and 528 are the cell's smallest and largest)
LATENT = {"decode": (16, 16), "chunk": (528, 16), "top": (1024, 16)}


@pytest.mark.parametrize("pack", sorted(LATENT))
def test_ragged_latent(v5e, pack):
    """Every head of a token against ONE cached row: keys the whole row,
    values its first 512 columns, no second pool.  q tiles of 8 tokens x
    128 heads DMA'd from the token-major pack, pages as stored."""
    from paddle_tpu.ops.pallas_kernels import \
        _ragged_latent_attention_pallas
    tokens, n_spans = LATENT[pack]
    spans = ((n_spans,), jnp.int32)
    compiled = _compile(
        lambda q, c, bt, qo, ql, kl: _ragged_latent_attention_pallas(
            q, c, bt, qo, ql, kl, 0.1147, 512),
        v5e, ((tokens, 128, 640), jnp.bfloat16),
        ((4161, 128, 640), jnp.bfloat16), ((n_spans, 260), jnp.int32),
        spans, spans, spans)
    assert "ragged_latent_attention" in compiled.as_text()


def test_mixed_step_latent_two_kinds(v5e):
    """One fused step of a DeepSeek-V2-shaped model (a dense layer, then
    a layer holding 8 of 32 experts; widths cut, the kernel's geometry
    kept: 128 heads, a 640-wide row): the optimized v5e program launches
    the latent kernel, has ONE pool a layer, no buffer a held expert,
    and every new scope owns an op."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.jit.serving_step import STEP_SCOPES, hlo_op_scopes
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    paddle.seed(0)
    cfg = DeepseekV2Config(
        vocab_size=1024, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256, num_hidden_layers=2,
        num_attention_heads=128, num_key_value_heads=128, q_lora_rank=256,
        n_routed_experts=8, router_experts=32, first_held_expert=8,
        rope_scaling={"type": "yarn", "factor": 40,
                      "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707}, dtype="bfloat16")
    model = DeepseekV2ForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    eng = ContinuousBatchingEngine(
        model, max_batch_size=4, num_blocks=64, block_size=128,
        max_seq_len=2048, prefill_chunk_size=256,
        use_pallas=True)
    top = eng.token_budgets[-1]
    lowered = eng.mixed.aot_lower(top, device_sharding=v5e)
    assert 'kernel_name = "ragged_latent_attention"' in lowered.as_text()
    hlo = lowered.compile().as_text()
    scopes = hlo_op_scopes(hlo)
    assert {"attn.q_lora", "attn.kv_latent", "attn.absorb",
            "attn.unabsorb", "attn.kernel", "attn.kv_write", "moe.gate",
            "moe.sort", "moe.experts", "moe.combine", "moe.shared"} \
        <= set(scopes.values()) <= STEP_SCOPES | {None}
    assert "ragged_dot" not in lowered.as_text()
    assert "ragged-dot" not in hlo
    # the Pallas launches keep their scope: the latent kernel once a
    # layer, the grouped product twice a routed layer
    assert {scopes[n] for n in _kernel_calls(
        scopes, "ragged_latent_attention")} == {"attn.kernel"}
    grouped = _kernel_calls(scopes, "grouped_expert_matmul")
    assert len(grouped) == 2
    assert {scopes[n] for n in grouped} == {"moe.experts"}
    # the experts multiply rows, not experts x rows
    k = cfg.num_experts_per_tok
    assert not re.search(r"\[8,%d,\d+\]" % (top * k), hlo)
    assert not re.search(r"\[32,%d,\d+\]" % (top * k), hlo)
    assert [c.value_cache for c in eng.caches] == [None, None]
