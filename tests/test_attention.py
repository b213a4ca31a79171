"""Attention kernels: chunked flash (bounded-memory backward, masks,
ragged lengths) and ring attention over the sep axis.

VERDICT r1 item 7: ring_attention must be wired + tested; flash backward
must not materialize O(S^2); masks and non-divisible seq supported.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.ops.pallas_kernels import (_chunked_sdpa, _sdpa_reference,
                                           flash_attention_tpu, sdpa_ring)

rng = np.random.RandomState(0)


def _qkv(B=2, H=2, S=16, D=8, dtype=np.float32):
    return (rng.randn(B, H, S, D).astype(dtype),
            rng.randn(B, H, S, D).astype(dtype),
            rng.randn(B, H, S, D).astype(dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_reference(causal):
    q, k, v = _qkv()
    got = _chunked_sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal, block_k=4)
    want = _sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_ragged_length():
    # S=13 not divisible by block 4: padding must not change results
    q, k, v = _qkv(S=13)
    got = _chunked_sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        True, block_k=4)
    want = _sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_masks_bool_and_additive():
    q, k, v = _qkv()
    bool_mask = rng.rand(2, 1, 16, 16) > 0.3
    add_mask = np.where(bool_mask, 0.0, -1e9).astype(np.float32)

    ref = jax.nn.softmax(
        jnp.where(jnp.asarray(bool_mask),
                  jnp.einsum("bhqd,bhkd->bhqk", jnp.asarray(q),
                             jnp.asarray(k)) / np.sqrt(8.0),
                  -jnp.inf), -1) @ jnp.asarray(v)
    for m in (bool_mask, add_mask):
        got = _chunked_sdpa(jnp.asarray(q), jnp.asarray(k),
                            jnp.asarray(v), False, mask=jnp.asarray(m),
                            block_k=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_chunked_rectangular_causal_decode():
    # Sq != Sk causal must be bottom-right aligned (decode: 1 query over a
    # 16-entry KV cache sees ALL of it, not just col 0)
    q, _, _ = _qkv(S=1)
    _, k, v = _qkv(S=16)
    got = _chunked_sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        True, block_k=4)
    want = _sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # wider: Sq=5 against Sk=13 (also non-divisible)
    q5, _, _ = _qkv(S=5)
    _, k13, v13 = _qkv(S=13)
    got = _chunked_sdpa(jnp.asarray(q5), jnp.asarray(k13),
                        jnp.asarray(v13), True, block_k=4)
    want = _sdpa_reference(jnp.asarray(q5), jnp.asarray(k13),
                           jnp.asarray(v13), True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_mask_with_nondivisible_seq():
    # mask on Sk=13 with block 4: the mask must be padded with the k/v,
    # not clamp-sliced (which misaligns the final block)
    q, k, v = _qkv(S=13)
    bool_mask = rng.rand(2, 1, 13, 13) > 0.3
    got = _chunked_sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        False, mask=jnp.asarray(bool_mask), block_k=4)
    ref = jax.nn.softmax(
        jnp.where(jnp.asarray(bool_mask),
                  jnp.einsum("bhqd,bhkd->bhqk", jnp.asarray(q),
                             jnp.asarray(k)) / np.sqrt(8.0),
                  -jnp.inf), -1) @ jnp.asarray(v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_chunked_grad_matches_reference():
    q, k, v = _qkv(S=8)

    def loss_c(q_, k_, v_):
        return jnp.sum(_chunked_sdpa(q_, k_, v_, True, block_k=4) ** 2)

    def loss_r(q_, k_, v_):
        return jnp.sum(_sdpa_reference(q_, k_, v_, True) ** 2)

    gc = jax.grad(loss_c, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v))
    gr = jax.grad(loss_r, (0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v))
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_op_mask_and_backward_through_tape():
    # paddle layout [B, S, H, D]
    qp = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype(np.float32),
                          stop_gradient=False)
    kp = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype(np.float32))
    vp = paddle.to_tensor(rng.randn(2, 16, 2, 8).astype(np.float32))
    mask = paddle.to_tensor(
        np.where(rng.rand(2, 1, 16, 16) > 0.3, 0.0, -1e9)
        .astype(np.float32))
    out = flash_attention_tpu(qp, kp, vp, attn_mask=mask)
    assert out.shape == [2, 16, 2, 8]
    (out ** 2).sum().backward()
    assert qp.grad is not None and np.isfinite(qp.grad.numpy()).all()


def test_ring_attention_matches_full():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    B, S, H, D = 2, 32, 2, 8
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)

    qp = paddle.to_tensor(q, stop_gradient=False)
    kp = paddle.to_tensor(k)
    vp = paddle.to_tensor(v)

    for causal in (False, True):
        got = sdpa_ring(qp, kp, vp, hcg.mesh, axis_name="sep",
                        is_causal=causal)
        want = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=causal)
        np.testing.assert_allclose(got.numpy(), want.numpy(),
                                   rtol=1e-4, atol=1e-4)

    # output is sep-sharded on the sequence dim
    got = sdpa_ring(qp, kp, vp, hcg.mesh, axis_name="sep", is_causal=True)
    shard_shapes = {s.data.shape[1] for s in got._value.addressable_shards}
    assert shard_shapes == {S // 8}, shard_shapes

    # gradient flows through the ring (ppermute loop is reversible)
    (got ** 2).sum().backward()
    assert qp.grad is not None and np.isfinite(qp.grad.numpy()).all()


def test_llama_uses_ring_under_sep():
    from paddle_tpu.models import llama_tiny_config, LlamaForCausalLM
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)

    cfg = llama_tiny_config(hidden_size=32, num_hidden_layers=1,
                            num_attention_heads=2, num_key_value_heads=2,
                            vocab_size=128, intermediate_size=88,
                            sequence_parallel=True)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    ids = rng.randint(0, 128, (2, 32)).astype(np.int32)
    out_sep = m(paddle.to_tensor(ids))

    # same weights, sequence_parallel off -> plain attention path
    cfg2 = llama_tiny_config(hidden_size=32, num_hidden_layers=1,
                             num_attention_heads=2, num_key_value_heads=2,
                             vocab_size=128, intermediate_size=88,
                             sequence_parallel=False)
    paddle.seed(0)
    m2 = LlamaForCausalLM(cfg2)
    out_full = m2(paddle.to_tensor(ids))
    np.testing.assert_allclose(out_sep.numpy(), out_full.numpy(),
                               rtol=1e-4, atol=1e-4)


def test_pallas_flash_backward_matches_reference():
    """Interpret-mode check of the Pallas flash backward kernels
    (_flash_bwd_dq_kernel/_flash_bwd_kv_kernel) against the
    full-materialization reference VJP."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 32
    q = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        for causal in (False, True):
            out, lse = pk._flash_attention_value(
                q, k, v, causal, block_q=128, block_k=128, with_lse=True)
            ref = pk._sdpa_reference(q, k, v, causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
            dq, dk, dv = pk._flash_attention_bwd(
                q, k, v, out, lse, g, causal, block_q=128, block_k=128)
            _, vjp = jax.vjp(
                lambda q_, k_, v_: pk._sdpa_reference(q_, k_, v_, causal),
                q, k, v)
            rdq, rdk, rdv = vjp(g)
            np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                       rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                       rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                       rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_rope", [False, True])
def test_pallas_flash_backward_fused(causal, with_rope):
    """Interpret-mode check of the single-kernel fused backward
    (_flash_bwd_kv_kernel emit_dq=True: dk/dv scratch + dq partials)
    against the full-materialization reference VJP, with and without
    in-kernel neox rope."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    B, H, S, D = 2, 2, 256, 32
    q = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    rope = None
    if with_rope:
        cos, sin = pk.rope_tables(S, D)
        rope = (cos, sin)

    def ref_fn(q_, k_, v_):
        if with_rope:
            q_ = pk._rope_xla(q_, cos, sin)
            k_ = pk._rope_xla(k_, cos, sin)
        return pk._sdpa_reference(q_, k_, v_, causal)

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(
            q, k, v, causal, block_q=128, block_k=128, with_lse=True,
            rope=rope)
        dq, dk, dv = pk._flash_attention_bwd_fused(
            q, k, v, out, lse, g, causal, block_q=64, block_k=128,
            rope=rope)
        _, vjp = jax.vjp(ref_fn, q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_pallas_flash_backward_fused_rectangular():
    """Sq != Sk (bottom-right-aligned causal) through the FUSED bwd —
    the production path for decode-style rectangular shapes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(6)
    B, H, Sq, Sk, D = 1, 2, 128, 256, 32
    q = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))

    def ref_fn(q_, k_, v_):
        return pk._sdpa_reference(q_, k_, v_, True)

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(
            q, k, v, True, block_q=64, block_k=128, with_lse=True)
        dq, dk, dv = pk._flash_attention_bwd_fused(
            q, k, v, out, lse, g, True, block_q=64, block_k=128)
        _, vjp = jax.vjp(ref_fn, q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_pallas_flash_backward_rectangular_decode():
    """Sq != Sk (bottom-right-aligned causal) through the Pallas bwd."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(1)
    B, H, Sq, Sk, D = 1, 2, 128, 256, 32
    q = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(
            q, k, v, True, block_q=128, block_k=128, with_lse=True)
        dq, dk, dv = pk._flash_attention_bwd(
            q, k, v, out, lse, g, True, block_q=128, block_k=128)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: pk._sdpa_reference(q_, k_, v_, True),
            q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_pallas_flash_backward_fully_masked_rows_finite():
    """Sq > Sk causal (causal_off < 0): leading query rows attend nothing;
    their lse is -inf and gradients must be exactly 0, not NaN."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(2)
    B, H, Sq, Sk, D = 1, 1, 256, 128, 32
    q = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(
            q, k, v, True, block_q=128, block_k=128, with_lse=True)
        dq, dk, dv = pk._flash_attention_bwd(
            q, k, v, out, lse, g, True, block_q=128, block_k=128)
        assert np.isfinite(np.asarray(dq)).all()
        assert np.isfinite(np.asarray(dk)).all()
        assert np.isfinite(np.asarray(dv)).all()
        # rows that attend nothing (first Sq-Sk rows) get zero dq
        np.testing.assert_allclose(np.asarray(dq)[:, :, :Sq - Sk], 0.0)
        # the attending tail matches the chunked backward
        _, vjp = jax.vjp(
            lambda q_, k_, v_: pk._chunked_sdpa(q_, k_, v_, True), q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq)[:, :, Sq - Sk:],
                                   np.asarray(rdq)[:, :, Sq - Sk:],
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_ulysses_attention_matches_full():
    """Ulysses all-to-all sequence parallelism (SURVEY §5.7): seq shard
    -> head shard -> full local attention -> seq shard."""
    from paddle_tpu.ops.pallas_kernels import sdpa_ulysses
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    B, S, H, D = 2, 32, 8, 8
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    qp = paddle.to_tensor(q, stop_gradient=False)
    kp = paddle.to_tensor(k)
    vp = paddle.to_tensor(v)

    for causal in (False, True):
        got = sdpa_ulysses(qp, kp, vp, hcg.mesh, axis_name="sep",
                           is_causal=causal)
        want = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=causal)
        np.testing.assert_allclose(got.numpy(), want.numpy(),
                                   rtol=1e-4, atol=1e-4)

    # output stays sequence-sharded over sep
    got = sdpa_ulysses(qp, kp, vp, hcg.mesh, axis_name="sep",
                       is_causal=True)
    shard_shapes = {s.data.shape[1] for s in got._value.addressable_shards}
    assert shard_shapes == {S // 8}, shard_shapes

    # differentiable through both all-to-alls
    (got ** 2).sum().backward()
    assert qp.grad is not None and np.isfinite(qp.grad.numpy()).all()

    # heads not divisible by the axis -> clear error
    import pytest as _pytest
    bad = paddle.to_tensor(rng.randn(2, 32, 6, 8).astype(np.float32))
    with _pytest.raises(Exception, match="divisible"):
        sdpa_ulysses(bad, bad, bad, hcg.mesh, axis_name="sep")


def test_pallas_flash_small_seq_sub128_blocks():
    """Seq/block sizes below one 128-lane tile must not crash (review
    regression: rep = block//128 == 0 made jnp.tile produce 0 columns)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(3)
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(q, k, v, True, block_q=64,
                                             block_k=64, with_lse=True)
        ref = pk._sdpa_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        g = jnp.ones_like(out)
        dq, dk, dv = pk._flash_attention_bwd(q, k, v, out, lse, g, True,
                                             block_q=64, block_k=64)
        assert np.isfinite(np.asarray(dq)).all()
    finally:
        pk._INTERPRET[0] = old


def test_pallas_flash_dead_rows_inside_live_tile():
    """Sq > Sk causal with block_q spanning both dead and live rows: the
    dead rows must output 0 with lse=-inf (review regression: the finite
    mask value made them output mean(V) with a finite lse)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(4)
    B, H, Sq, Sk, D = 1, 1, 256, 128, 32
    q = jnp.asarray(rng.rand(B, H, Sq, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, Sk, D).astype(np.float32))
    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        # ONE q tile covering rows 0..255: rows < 128 attend nothing
        out, lse = pk._flash_attention_value(q, k, v, True, block_q=256,
                                             block_k=128, with_lse=True)
        np.testing.assert_allclose(np.asarray(out)[:, :, :Sq - Sk], 0.0)
        assert np.all(np.isneginf(np.asarray(lse)[:, :Sq - Sk]))
        # live tail matches the reference
        ref = pk._sdpa_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out)[:, :, Sq - Sk:],
                                   np.asarray(ref)[:, :, Sq - Sk:],
                                   rtol=2e-4, atol=2e-4)
        # backward stays zero for dead rows
        g = jnp.ones_like(out)
        dq, _, _ = pk._flash_attention_bwd(q, k, v, out, lse, g, True,
                                           block_q=256, block_k=128)
        np.testing.assert_allclose(np.asarray(dq)[:, :, :Sq - Sk], 0.0)
    finally:
        pk._INTERPRET[0] = old


def test_flash_attention_rope_matches_composed():
    """Fused in-kernel rope+flash == fused_rotary_position_embedding
    followed by attention (forward and grads), interpret mode."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    B, S, H, D = 2, 256, 2, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    cos, sin = pk.rope_tables(S, D)

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        def fused(q, k, v):
            out, lse = pk._flash_attention_value(
                q, k, v, True, block_q=128, block_k=128, with_lse=True,
                rope=(cos, sin))
            return out, lse

        out, lse = fused(q, k, v)
        ref = pk._sdpa_reference(pk._rope_xla(q, cos, sin),
                                 pk._rope_xla(k, cos, sin), v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

        g = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        dq, dk, dv = pk._flash_attention_bwd(
            q, k, v, out, lse, g, True, block_q=128, block_k=128,
            rope=(cos, sin))
        _, vjp = jax.vjp(
            lambda q_, k_, v_: pk._sdpa_reference(
                pk._rope_xla(q_, cos, sin), pk._rope_xla(k_, cos, sin),
                v_, True), q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_llama_attention_fused_rope_path_matches_general():
    """LlamaAttention training fast path (fused rope+flash) must equal
    the general path (explicit rope + sdpa) on CPU."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaAttention, llama_tiny_config

    paddle.seed(0)
    cfg = llama_tiny_config(hidden_size=64, num_hidden_layers=1,
                            num_attention_heads=2, num_key_value_heads=2,
                            intermediate_size=128, vocab_size=128)
    attn = LlamaAttention(cfg)
    x = paddle.to_tensor(
        np.random.RandomState(6).randn(2, 64, 64).astype(np.float32))
    fast = attn(x)                       # cache=None, mask=None
    # general path: force via a None-mask equivalent (explicit zeros mask
    # changes semantics, so instead call with position_offset=0 but
    # cache=(None, None) to route the old branch)
    general, _ = attn(x, cache=(None, None))
    np.testing.assert_allclose(fast.numpy(), general.numpy(),
                               rtol=3e-4, atol=3e-4)


def test_pallas_flash_non_power_block_seq():
    """Seq lengths divisible by 256 but not 512/1024 (e.g. 1536) must
    produce correct grads — the default blocks snap to divisors (review
    regression: floor-truncated grids silently dropped key blocks)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(7)
    B, H, S, D = 1, 1, 1536, 32
    q = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        # defaults: fwd wants 512 (1536 % 512 == 0), bwd wants 1024
        # (1536 % 1024 != 0 -> must snap, not truncate)
        out, lse = pk._flash_attention_value(q, k, v, True, with_lse=True)
        ref = pk._sdpa_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        dq, dk, dv = pk._flash_attention_bwd(q, k, v, out, lse, g, True)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: pk._sdpa_reference(q_, k_, v_, True),
            q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
        assert np.isfinite(np.asarray(dk)).all()
    finally:
        pk._INTERPRET[0] = old


def test_fit_block():
    from paddle_tpu.ops.pallas_kernels import _fit_block
    assert _fit_block(512, 1536) == 512
    assert _fit_block(1024, 1536) == 768
    assert _fit_block(512, 768) == 384
    assert _fit_block(512, 2048) == 512
    assert _fit_block(512, 120) == 120
    assert _fit_block(256, 64) == 64
    # advisor regression: blocks >128 that aren't lane multiples crash
    # at trace time (128-lane scratch) — must snap to a sub-128 divisor
    for total, want_block in [(192, 96), (320, 80), (576, 96)]:
        b = _fit_block(512, total)
        assert b == want_block and (b <= 128 or b % 128 == 0)
    assert _fit_block(512, 257) == 0       # prime: no usable block
    # sub-128 blocks must be sublane-tileable (multiple of 16): 254's
    # only sub-128 divisor is 127, which is not -> fall back to chunked
    assert _fit_block(512, 254) == 0


def test_pallas_flash_lane_unaligned_seq():
    """S=192: whole axis is not a lane multiple; kernel must pick a
    sub-128 block instead of crashing (advisor round-2 regression)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(11)
    B, H, S, D = 1, 2, 192, 32
    q = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        out, lse = pk._flash_attention_value(q, k, v, True, with_lse=True)
        ref = pk._sdpa_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        dq, dk, dv = pk._flash_attention_bwd(q, k, v, out, lse, g, True)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: pk._sdpa_reference(q_, k_, v_, True),
            q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_long_context_training_parity_under_sep(mode):
    """TRAIN (fwd+bwd+update) a llama under sep=8 sequence parallelism
    and under serial attention with identical weights/data: losses must
    match step for step — the ring rotation / all-to-all is fully
    differentiable (jax.grad reverses the static-trip-count loop).
    SURVEY §5.7: the reference snapshot has no such kernel at all."""
    from paddle_tpu.models import llama_tiny_config, LlamaForCausalLM, \
        LlamaPretrainingCriterion

    def run(sequence_parallel):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
            "sharding_degree": 1,
            "sep_degree": 8 if sequence_parallel else 1}
        fleet.init(is_collective=True, strategy=strategy)
        # ulysses swaps the seq shard for a head shard: heads must be
        # divisible by the sep axis size (8)
        heads = 8 if mode == "ulysses" else 2
        cfg = llama_tiny_config(
            hidden_size=64, num_hidden_layers=1,
            num_attention_heads=heads, num_key_value_heads=heads,
            vocab_size=128, intermediate_size=88,
            sequence_parallel=sequence_parallel, seq_parallel_mode=mode)
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        rs = np.random.RandomState(42)
        ids = paddle.to_tensor(
            rs.randint(0, 128, (2, 32)).astype(np.int32))
        labels = paddle.to_tensor(
            rs.randint(0, 128, (2, 32)).astype(np.int64))
        losses = []
        for _ in range(3):
            loss = crit(m(ids), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        return losses

    sep_losses = run(True)
    serial_losses = run(False)
    np.testing.assert_allclose(sep_losses, serial_losses, rtol=2e-4,
                               atol=2e-4)


def test_long_sequence_bounded_memory_backward():
    """S=16384 causal attention fwd+bwd through the chunked path: the
    O(S^2) score matrix (1 GiB f32 per head here) is never materialized
    — the block-recomputed backward keeps residuals O(S*D).  This is
    the 'a long-seq config that OOMs with naive attention trains'
    capability (VERDICT r1 item 7)."""
    S, D = 16384, 64
    q = jnp.asarray(np.random.RandomState(9).randn(1, 1, S, D),
                    jnp.float32)

    def loss(q, k, v):
        return _chunked_sdpa(q, k, v, True).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    dq, dk, dv = g(q, q, q)
    assert np.isfinite(np.asarray(dq)).all()
    # spot-check against the reference on a slice of rows: row r of dv
    # depends on all rows <= ... use a small-S consistency check instead
    S2 = 256
    q2 = q[:, :, :S2]
    d_small = jax.jit(jax.grad(
        lambda q, k, v: _chunked_sdpa(q, k, v, True).sum(),
        argnums=(0, 1, 2)))(q2, q2, q2)
    _, vjp = jax.vjp(lambda a, b, c: _sdpa_reference(a, b, c, True),
                     q2, q2, q2)
    ref = vjp(jnp.ones((1, 1, S2, D), jnp.float32))
    for got, want in zip(d_small, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_ring_flash_kernel_matches_full():
    """Flash-kernel ring attention (round-4 ask #7): per-rotation Pallas
    flash blocks (interpret mode on the CPU mesh) + FlashAttention-2
    backward against the total lse must match full attention in value
    AND gradient."""
    import jax
    import paddle_tpu.ops.pallas_kernels as pk

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    B, S, H, D = 1, 256, 2, 64       # S/8 = 32: pallas-block compatible
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)

    old = pk._INTERPRET[0]
    pk._INTERPRET[0] = True
    try:
        assert pk._ring_flash_ok(S // 8, D)   # the flash path is taken
        for causal in (False, True):
            qp = paddle.to_tensor(q, stop_gradient=False)
            kp = paddle.to_tensor(k, stop_gradient=False)
            vp = paddle.to_tensor(v, stop_gradient=False)
            got = sdpa_ring(qp, kp, vp, hcg.mesh, axis_name="sep",
                            is_causal=causal)
            want = F.scaled_dot_product_attention(
                paddle.to_tensor(q), paddle.to_tensor(k),
                paddle.to_tensor(v), is_causal=causal)
            np.testing.assert_allclose(got.numpy(), want.numpy(),
                                       rtol=2e-4, atol=2e-4)

            # gradient parity vs the dense reference
            (got ** 2).sum().backward()
            qr = paddle.to_tensor(q, stop_gradient=False)
            kr = paddle.to_tensor(k, stop_gradient=False)
            vr = paddle.to_tensor(v, stop_gradient=False)
            ref = F.scaled_dot_product_attention(qr, kr, vr,
                                                 is_causal=causal)
            (ref ** 2).sum().backward()
            np.testing.assert_allclose(qp.grad.numpy(), qr.grad.numpy(),
                                       rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(kp.grad.numpy(), kr.grad.numpy(),
                                       rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(vp.grad.numpy(), vr.grad.numpy(),
                                       rtol=2e-3, atol=2e-3)
    finally:
        pk._INTERPRET[0] = old


def test_ring_attention_hybrid_mesh_dp_sep():
    """sdpa_ring on a dp2 x sep4 mesh: batch rides the data axis (split,
    not redundantly recomputed) while the ring runs over sep."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    B, S, H, D = 4, 32, 2, 8
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    qp = paddle.to_tensor(q, stop_gradient=False)
    got = sdpa_ring(qp, paddle.to_tensor(k), paddle.to_tensor(v),
                    hcg.mesh, axis_name="sep", is_causal=True)
    want = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        is_causal=True)
    np.testing.assert_allclose(got.numpy(), want.numpy(), rtol=1e-4,
                               atol=1e-4)
    (got ** 2).sum().backward()
    assert np.isfinite(qp.grad.numpy()).all()


def test_ring_bench_artifact_gate():
    """The ring-vs-flash perf gate is a driver-readable artifact
    (VERDICT r4 ask #7): when BENCH_ATTN_r05.json exists (written by
    tools/ring_bench.py on TPU), its recorded ratio must satisfy the
    1.5x gate; the artifact also carries the flash-block table."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_ATTN_r05.json")
    if not os.path.exists(path):
        import pytest
        pytest.skip("artifact not generated on this host (needs TPU)")
    rec = json.load(open(path))
    assert rec["passed"]   # the unrounded gate decision at measurement time
    assert rec["flash_blocks"]
    assert rec["max_abs_err_vs_full"] < 0.1


def test_causal_stream_remap_lockstep_with_run_predicate():
    """The streamed-block DMA remaps (_causal_stream_kv/_q) must agree
    with the kernels' _causal_run skip predicate for EVERY grid cell:
    running cells keep their own index, skipped cells must re-fetch a
    block that is itself valid (so the fetch doubles as prefetch and
    never reads out of range).  Pure-python sweep over block shapes and
    decode offsets — guards the lock-step invariant the kernel relies
    on (a desync would make a skipped step DMA a wrong tile)."""
    from paddle_tpu.ops.pallas_kernels import (
        _causal_run, _causal_stream_kv, _causal_stream_q)

    for Sq, Sk, bq, bk in ((512, 512, 128, 128), (512, 512, 128, 256),
                           (256, 512, 128, 128), (128, 512, 64, 128),
                           (512, 512, 256, 128), (384, 768, 128, 128)):
        off = Sk - Sq
        n_q, n_k = Sq // bq, Sk // bk
        for qi in range(n_q):
            for kb in range(n_k):
                run = bool(_causal_run(qi, kb, bq, bk, off))
                kv = int(_causal_stream_kv(qi, kb, bq, bk, off, True))
                qv = int(_causal_stream_q(kb, qi, bq, bk, off, True))
                if run:
                    assert kv == kb, (Sq, Sk, bq, bk, qi, kb)
                else:
                    # skipped k block -> block 0 (next q row's start)
                    assert kv == 0
                # _causal_stream_q: i = resident k tile (kb), j =
                # streamed q tile (qi); skipped q blocks must remap to
                # the FIRST running q block of this k row
                if bool(_causal_run(qi, kb, bq, bk, off)):
                    assert qv == qi
                else:
                    assert 0 <= qv < n_q
                    assert bool(_causal_run(qv, kb, bq, bk, off)), \
                        (Sq, Sk, bq, bk, qi, kb, qv)
                # non-causal: identity
                assert int(_causal_stream_kv(qi, kb, bq, bk, off,
                                             False)) == kb
                assert int(_causal_stream_q(kb, qi, bq, bk, off,
                                            False)) == qi


# ---------------------------------------------------------------------------
# VMEM budget lint (round-17 satellite: runs in the verify flow here)
# ---------------------------------------------------------------------------
def test_vmem_budget_lint():
    """Every Pallas kernel family's worst-case VMEM footprint (q tile
    + double-buffered page DMA slots + accumulators, lane/
    sublane-padded) must fit its declared per-core budget at the
    serving/training envelope — a tile-size edit that blows VMEM fails
    here, not as a Mosaic allocation error on first TPU contact."""
    import os
    import sys
    tools_dir = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools")
    saved_path = list(sys.path)
    sys.path.insert(0, tools_dir)
    try:
        from check_vmem_budget import BUDGETS, check
    finally:
        # restore wholesale: the tool's own module-level REPO insert
        # would otherwise make a bare pop(0) remove the wrong entry
        # and leak tools/ onto sys.path for the rest of the suite
        sys.path[:] = saved_path
    rows, errors = check()
    assert errors == []
    assert {r[0] for r in rows} == set(BUDGETS)
    # the audit must track the kernels' real knobs: doubling the fused
    # backward's resident k block doubles its footprint past HALF the
    # declared budget (i.e. the formula is live, not a constant)
    from paddle_tpu.ops.pallas_kernels import kernel_vmem_report
    base = kernel_vmem_report()
    grown = kernel_vmem_report({"bwd_block_k": 2 * 2048})
    assert grown["flash_bwd_fused"] > 1.5 * base["flash_bwd_fused"]
    # and the ragged cell's accounting is visible: its page blocks
    # hold pages AS STORED ([tokens, Hkv, D], two slots each of K and
    # V: 8 heads take 8 sublanes in bf16 and in int8 alike, as Mosaic
    # lays them out), so an int8 pool's cell differs from the bf16
    # pool's by its slots, its folded q, its head-major block and its
    # q scales; the q tile is 128 rows a kv head, so fewer heads a
    # group means more tokens
    from paddle_tpu.ops.pallas_kernels import (_stored_block_bytes,
                                               _tile_bytes,
                                               ragged_kernel_vmem_bytes,
                                               ragged_tile_geometry)
    cell = dict(heads=32, kv_heads=8, head_dim=128, block_size=16,
                bt_width=224)
    bf16 = ragged_kernel_vmem_bytes(**cell)
    int8 = ragged_kernel_vmem_bytes(kv_dtype="int8", **cell)
    assert bf16 - int8 == 4 * (_stored_block_bytes(512, 8, 128, 2)
                               - _stored_block_bytes(512, 8, 128, 1)) \
        + _tile_bytes((8, 128, 128), 2) - _tile_bytes((8, 128, 128), 1) \
        + 2 * (_tile_bytes((8, 512, 128), 2)
               - _tile_bytes((8, 512, 128), 1)) \
        - _tile_bytes((8, 128, 1), 4)
    assert _stored_block_bytes(512, 8, 128, 2) == 512 * 8 * 128 * 2
    assert ragged_tile_geometry(32, 8, 128, 16, 224, "bfloat16",
                                "bfloat16") == (32, 32)
    # (float32 MHA: 128 tokens would overrun the cell's VMEM, so the
    # tile halves; a 4-page table caps the key block)
    assert ragged_tile_geometry(32, 32, 128, 16, 4, "float32",
                                "float32") == (32, 4)


# (heads, kv heads, block-table width, kv dtype): the docs cell's and
# the chat cell's geometry (32 / 8 / 128, pages of 16, bf16), an int8
# pool, and a tensor-parallel 4-way shard (8 / 2 heads a chip)
_CELL_GEOMETRY = {
    "docs_bf16": (32, 8, 388, "bfloat16"),
    "chat_bf16": (32, 8, 224, "bfloat16"),
    "docs_int8": (32, 8, 388, "int8"),
    "chat_tp4_bf16": (8, 2, 224, "bfloat16"),
    "chat_tp4_int8": (8, 2, 224, "int8"),
}


@pytest.mark.parametrize("case", sorted(_CELL_GEOMETRY))
def test_ragged_geometry_keeps_the_tile(case):
    """The grouped-query launch's cell at the cells' geometry: a 32-token
    q tile (128 rows a kv head) beside 512-key blocks of 32 pages — the
    wider block never buys its room by halving the tile, which would
    double every chunk's K/V re-reads — within _RAGGED_TILE_VMEM and
    the 16 MiB the serving kernels are held to."""
    from paddle_tpu.ops.pallas_kernels import (_RAGGED_KV_BLOCK,
                                               _RAGGED_TILE_VMEM,
                                               ragged_kernel_vmem_bytes,
                                               ragged_tile_geometry)
    heads, kv_heads, width, kv_dtype = _CELL_GEOMETRY[case]
    assert ragged_tile_geometry(heads, kv_heads, 128, 16, width,
                                "bfloat16", kv_dtype) \
        == (32, _RAGGED_KV_BLOCK // 16) == (32, 32)
    used = ragged_kernel_vmem_bytes(heads=heads, kv_heads=kv_heads,
                                    head_dim=128, block_size=16,
                                    bt_width=width, kv_dtype=kv_dtype)
    assert used <= _RAGGED_TILE_VMEM < 16 << 20


# ---------------------------------------------------------------------------
# the grouped-query launch's decode cell: one-token spans take a body of
# their own (one q row in, one out, every kv head at once on the pages
# as stored), chosen per grid cell from q_len
# ---------------------------------------------------------------------------
_DECODE_CONTEXTS = [1, 15, 16, 17, 127, 128, 129, 1100, 3328]
# name -> (q heads, kv heads, table width, spans [(q_len, kv_len)],
# pool dtype); pages of 16, head_dim 128: the cells' own geometry
_DECODE_PACKS = {
    # Olmo-Hybrid's full layers: one group, a 208-page table (a 64-token
    # tile, 128-key blocks), the table's last key included
    "one_group": (32, 32, 208, [(1, c) for c in _DECODE_CONTEXTS],
                  "bfloat16"),
    # Mistral's and Mixtral's: 4 groups, 512-key blocks
    "four_groups": (32, 8, 224, [(1, c) for c in _DECODE_CONTEXTS],
                    "bfloat16"),
    # one-token spans between a 512-token chunk and a 40-token one
    # (whose tile's garbage rows land on the decode tokens after it),
    # and q_len 0 padding spans on the sink page; in bf16 and float32
    "mixed_pack": (32, 32, 208, [(1, 17), (512, 700), (1, 1100),
                                 (40, 90), (1, 129), (1, 1), (0, 1),
                                 (0, 1)], "bfloat16"),
    "mixed_pack_fp32": (32, 32, 64, [(1, 17), (512, 700), (1, 1000),
                                    (40, 90), (1, 129), (0, 1)],
                        "float32"),
}


def _sink_pack(spans, H, Hkv, W, dtype, seed=0):
    """Operands of one launch over a pool whose LAST page is the sink:
    NaN, where a freed slot's table points (every entry of a padding
    span, every entry past a live span's pages), so a cell that reads a
    page it does not own poisons its output."""
    rs = np.random.RandomState(seed)
    bs, D = 16, 128
    used = [-(-kv // bs) if q else 0 for q, kv in spans]
    sink = sum(used)
    kc = rs.randn(sink + 1, bs, Hkv, D).astype(np.float32)
    vc = rs.randn(sink + 1, bs, Hkv, D).astype(np.float32)
    kc[sink] = vc[sink] = np.nan
    bt = np.full((len(spans), W), sink, np.int32)
    order, start = rs.permutation(sink), 0
    for s, n in enumerate(used):
        bt[s, :n] = order[start:start + n]
        start += n
    T = sum(q for q, _ in spans)
    q = rs.randn(T, H, D).astype(np.float32)
    qo = np.cumsum([0] + [ql for ql, _ in spans[:-1]]).astype(np.int32)
    qo = np.where([ql > 0 for ql, _ in spans], qo, T).astype(np.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(kc, dtype),
            jnp.asarray(vc, dtype), bt, qo,
            np.asarray([ql for ql, _ in spans], np.int32),
            np.asarray([kv for _, kv in spans], np.int32))


def _span_reference(q, kc, vc, bt, qo, ql, kl, rows=8):
    """``_ragged_attention_xla`` in float32, one span at a time and a few
    of its rows at a time (a row sits at its own position, so a piece
    of a chunk is a span of its own), against that span's pages only:
    the reference's gather is a row's whole table, too large for a
    512-token chunk over 700 keys."""
    from paddle_tpu.ops.paged_attention import _ragged_attention_xla
    f32 = [np.asarray(x.astype(jnp.float32)) for x in (q, kc, vc)]
    q, kc, vc = f32
    out = np.zeros(q.shape, np.float32)
    ref = jax.jit(_ragged_attention_xla, static_argnames="scale")
    for s, (n, kv) in enumerate(zip(ql, kl)):
        pages = -(-int(kv) // kc.shape[1])
        for r0 in range(0, int(n), rows):
            r = min(rows, int(n) - r0)
            piece = np.zeros((rows, q.shape[1], q.shape[2]), np.float32)
            piece[:r] = q[qo[s] + r0:qo[s] + r0 + r]
            got = ref(jnp.asarray(piece), jnp.asarray(kc), jnp.asarray(vc),
                      jnp.asarray(bt[s:s + 1, :pages]),
                      jnp.asarray([0, rows], jnp.int32),
                      jnp.asarray([r, 0], jnp.int32),
                      jnp.asarray([int(kv) - int(n) + r0 + r, 1],
                                  jnp.int32), scale=q.shape[2] ** -0.5)
            out[qo[s] + r0:qo[s] + r0 + r] = np.asarray(got)[:r]
    return out


@pytest.mark.parametrize("pack", sorted(_DECODE_PACKS))
def test_ragged_decode_cell_matches_reference(pack):
    """The launch with its decode cell (interpret mode) against the XLA
    reference at the cells' geometry: every one-token span takes the
    decode cell (``ragged_decode_cell``), the rest the tile cell, in ONE
    launch; a context of one key, at a page's edges, at a 128-key
    block's edges, mid-table and the table's last key; no page past a
    span's own is read (the NaN sink stays out of every row) and every
    row a tile's garbage reached is rewritten by its owner."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    from paddle_tpu.ops.pallas_kernels import (ragged_decode_cell,
                                               ragged_tile_geometry)
    H, Hkv, W, spans, dtype = _DECODE_PACKS[pack]
    args = _sink_pack(spans, H, Hkv, W, dtype)
    q_lens = args[5]
    # one group: every one-token span takes the decode cell; 4 groups:
    # none does (the tile cell is the faster there)
    assert int(np.sum(ragged_decode_cell(q_lens, H // Hkv, False))) \
        == (sum(1 for q, _ in spans if q == 1) if H == Hkv else 0)
    if dtype == "bfloat16":
        assert ragged_tile_geometry(H, Hkv, 128, 16, W, dtype, dtype) \
            == ((64, 8) if H == Hkv else (32, 32))
    got = np.asarray(ragged_paged_attention(*args, interpret=True),
                     np.float32)
    want = _span_reference(*args)
    real = np.concatenate([np.arange(o, o + n) for o, n
                           in zip(args[4], q_lens) if n])
    assert np.isfinite(got[real]).all()
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got[real], want[real], err_msg=pack, **tol)


def test_ragged_decode_cell_int8_takes_the_tile():
    """An int8 pool keeps the tile cell for every span, at one group
    too (its scales fold into int8 products on the MXU): the rule says
    so, and one-token spans beside a chunk match the dequantizing
    reference within the declared int8 tolerance."""
    from paddle_tpu.ops.paged_attention import (KERNEL_INT8_REL_TOL,
                                                PagedKVCache, dequant_pages,
                                                ragged_paged_attention,
                                                write_ragged_kv_q8)
    from paddle_tpu.ops.pallas_kernels import ragged_decode_cell
    assert not np.any(ragged_decode_cell(np.asarray([1, 1, 5]), 1, True))
    assert ragged_decode_cell(np.int32(1), 1, False)
    rs = np.random.RandomState(5)
    bs, hkv, d, nb, W = 16, 32, 128, 24, 8
    cq = PagedKVCache(nb, bs, hkv, d, sink_block=True, kv_dtype="int8")
    n = bs * nb
    (cq.key_cache, cq.value_cache, cq.key_scale,
     cq.value_scale) = write_ragged_kv_q8(
        jnp.asarray(rs.randn(n, hkv, d), jnp.float32),
        jnp.asarray(rs.randn(n, hkv, d), jnp.float32), cq.key_cache,
        cq.value_cache, cq.key_scale, cq.value_scale,
        jnp.asarray(np.repeat(np.arange(nb, dtype=np.int32), bs)),
        jnp.asarray(np.tile(np.arange(bs, dtype=np.int32), nb)))
    spans = [(1, 17), (40, 90), (1, 100), (1, 1), (0, 1)]
    bt = np.full((len(spans), W), cq.sink, np.int32)
    for s, (ql, kv) in enumerate(spans):
        if ql:
            bt[s, :-(-kv // bs)] = (np.arange(-(-kv // bs)) + 3 * s) % nb
    T = sum(ql for ql, _ in spans)
    q = rs.randn(T, 32, d).astype(np.float32)
    qo = np.asarray([0, 1, 41, 42, T], np.int32)
    ql = np.asarray([s[0] for s in spans], np.int32)
    kl = np.asarray([s[1] for s in spans], np.int32)
    common = (bt, qo, ql, kl)
    kw = dict(key_scale=cq.key_scale, value_scale=cq.value_scale)
    want = np.asarray(ragged_paged_attention(
        q, cq.key_cache, cq.value_cache, *common, use_pallas=False, **kw))
    got = np.asarray(ragged_paged_attention(
        q, cq.key_cache, cq.value_cache, *common, interpret=True, **kw))
    vmag = float(np.abs(np.asarray(dequant_pages(
        cq.value_cache, cq.value_scale))).max())
    np.testing.assert_allclose(got[:T], want[:T],
                               atol=KERNEL_INT8_REL_TOL * vmag)


def test_ragged_geometry_one_group():
    """The one-group cell keeps its tile and block: 64 tokens and 8
    pages at 32 kv heads over a 208-page table (128 tokens or 512-key
    blocks overrun _RAGGED_TILE_VMEM there), 32 and 32 at 32 over 8 kv
    heads with 224- and 388-page tables; the decode cell's state fits
    beside either, and the VMEM lint gates the one-group cell."""
    from paddle_tpu.ops.pallas_kernels import (_RAGGED_TILE_VMEM,
                                               kernel_vmem_report,
                                               ragged_kernel_vmem_bytes,
                                               ragged_tile_geometry)
    cells = {(32, 32, 208): (64, 8), (32, 8, 224): (32, 32),
             (32, 8, 388): (32, 32)}
    for (heads, kv_heads, width), want in cells.items():
        assert ragged_tile_geometry(heads, kv_heads, 128, 16, width,
                                    "bfloat16", "bfloat16") == want
        assert ragged_kernel_vmem_bytes(
            heads=heads, kv_heads=kv_heads, head_dim=128, block_size=16,
            bt_width=width) <= _RAGGED_TILE_VMEM
    assert kernel_vmem_report()["ragged_paged_one_group"] \
        == ragged_kernel_vmem_bytes(heads=32, kv_heads=32, head_dim=128,
                                    block_size=16, bt_width=208)
