"""Olmo-Hybrid (Gated DeltaNet linear-attention layers beside full
attention, post-norm blocks): the program against the plain reference of
``benchmark/references/`` at tiny widths (two periods of three linear
layers and a full one, 2 linear heads, 4 full ones), seeded weights,
float32, on the CPU.

Tolerances.  Both sides compute in float32 on the CPU in different
orders (the reference token by token with every product a multiply and a
sum, the program batched matmuls; the Pallas launch in sub-chunks of 64):
logits of magnitude about 1 agree to a few 1e-5, so ``LOGIT_TOL`` = 2e-4
is several times the rounding seen.  A launch's outputs (magnitude about
1) agree with the token-by-token rule to a few 1e-6; ``KERNEL_TOL`` =
1e-4 is twenty times that and a twentieth of what a state stored once in
bfloat16 between two chunks does (2e-3:
``test_state_carried_across_launches``).
"""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                           olmo_hybrid_tiny_config)
from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4
KERNEL_TOL = 1e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_olmo_hybrid", os.path.join(
            ROOT, "benchmark", "references", "olmo_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _cfg_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["layer_types"] = [cfg.layer_type(i)
                        for i in range(cfg.num_hidden_layers)]
    return d


@pytest.fixture(scope="module")
def model():
    """Decays as the published initialisation draws them (strong and
    weak: a state is forgotten in a few tokens in one head and carried
    over hundreds in the other), so a dropped carry shows."""
    paddle.seed(11)
    m = OlmoHybridForCausalLM(olmo_hybrid_tiny_config()).eval()
    for layer in m.olmo_hybrid.layers:
        at = layer.self_attn
        if hasattr(at, "A_log"):
            at.A_log.set_value(paddle.to_tensor(
                np.log(np.array([6.0, 0.05], np.float32))))
            at.dt_bias.set_value(paddle.to_tensor(
                np.array([0.05, -3.0], np.float32)))
    return m


_JITTED = {}


def _reference_logits(model, ids, pad_to=192):
    """The reference's logits over ``ids``, padded at the end to one
    length (causal: the padding changes no earlier position), so that
    one program a kind of layer serves every call."""
    cfg = _cfg_dict(model.config)
    inner = model.olmo_hybrid
    top = {"embed_tokens.weight": inner.embed_tokens.weight._value,
           "norm.weight": inner.norm.weight._value,
           "lm_head.weight": model.lm_head.weight._value}
    n = len(ids)
    x = REF.embed(jnp.asarray(np.pad(ids, (0, pad_to - n))), top)
    for layer, kind in zip(inner.layers, REF.layer_kinds(cfg)):
        w = {k: p._value for k, p in layer.named_parameters()}
        assert set(w) == set(REF.layer_shapes(cfg, kind))
        if kind not in _JITTED:
            _JITTED[kind] = jax.jit(
                lambda x, w, kind=kind: REF.layer(x, w, cfg, kind=kind)[0])
        x = _JITTED[kind](x, w)
    return np.asarray(REF.logits(x, top, cfg))[:n]


def _engine(model, **kw):
    kw = dict(dict(max_batch_size=4, num_blocks=96, block_size=8,
                   max_seq_len=160, prefill_chunk_size=64), **kw)
    return ContinuousBatchingEngine(model, **kw)


def _gaps(model, prompt, served):
    logits = _reference_logits(model, np.concatenate([prompt, served[:-1]]))
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return logits[at].max(-1) - logits[at, served]


def test_eager_forward_matches_the_reference(model):
    ids = np.random.default_rng(0).integers(1, 256, 70)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    assert np.abs(got - _reference_logits(model, ids)).max() < LOGIT_TOL


@pytest.fixture(scope="module")
def served(model):
    """Prompts whose chunks of 64 end at 1, 63, 64 and 65 tokens and run
    to two and three chunks, prefilled beside decoding slots, then
    decoded through the slots' states; one engine, its step records."""
    from paddle_tpu.observability import span_log
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n) for n in (1, 63, 64, 65, 130, 20)]
    eng = _engine(model)
    rids = [eng.add_request(p, 10) for p in prompts]
    out = eng.run_to_completion()
    recs = [e[5] for e in span_log.events() if e[1] == "serving.step"
            and e[5].get("engine") == eng.engine_id]
    return eng, prompts, [np.asarray(out[r]) for r in rids], recs


def test_chunked_prefill_then_decode_matches_the_reference(model, served):
    """The reference makes ONE full forward over prompt + served tokens;
    at every served position the served token's reference logit lies
    within LOGIT_TOL of the reference's best."""
    eng, prompts, outs, _ = served
    for p, o in zip(prompts, outs):
        assert len(o) == 10
        assert _gaps(model, p, o).max() < LOGIT_TOL
    assert len(eng.caches[0]._free) == eng.caches[0].num_blocks


def test_pools_only_for_full_layers_and_a_state_a_slot(served):
    eng = served[0]
    assert eng.layer_pools == [None, None, None, 0, None, None, None, 1]
    assert len(eng.caches) == 2 and eng.caches[0].num_kv_heads == 16
    assert eng.states.layers == 6
    assert eng.states.states[0].shape == (5, 2, 32, 16)
    assert eng.states.states[0].dtype == jnp.float32
    assert eng.states.tails[0].shape == (5, 3, 2 * (2 * 16 + 32))
    assert eng.migration_geometry() is None


def test_step_record_and_counters_of_the_linear_layers(served):
    from paddle_tpu.observability import default_registry
    from paddle_tpu.observability import generate_latest
    eng, prompts, _, recs = served
    booked = [r for r in recs if r["budget"]]
    assert booked
    for r in booked:
        q = r["spans"][:, 1]
        assert r["gdn_rows"] == 6 * int(q.sum())
        assert r["gdn_states"] == 6 * int(np.sum(q > 0))
        assert r["gdn_chunks"] == 6 * int(np.sum(-(-q[q > 1] // 64)))
    assert sum(r["gdn_chunks"] for r in booked) >= 6 * 5
    text = generate_latest(default_registry()).decode()
    slot = 6 * (2 * 32 * 16 * 4 + 3 * 128 * 4)
    assert "serving_linear_state_bytes %s" % float(slot) in text \
        or "serving_linear_state_bytes %d" % slot in text
    assert "serving_state_fresh_total" in text


@pytest.mark.parametrize("case", ["chunks", "fresh_and_padding",
                                  "alike_keys"])
def test_kernel_interpret_equals_the_sequential_rule(case):
    """The Pallas launch (interpret mode) against the token-by-token rule
    on one pack: spans of 1, 63, 64, 65 and 200 tokens, from non-zero
    states, or from fresh slots beside padding spans; and keys as SiLU
    leaves them, all alike, with beta near 2 and hardly any decay, where
    a chunk's triangular system is the hardest to invert (a power series
    over the whole chunk gave NaN here, and garbage on the chip)."""
    rng = np.random.default_rng(5)
    H, dk, dv, T = 2, 16, 32, 448
    lens = np.array([1, 63, 64, 65, 200, 0, 0])
    start = np.array([9, 3, 70, 1, 12, 0, 0]) if case != "fresh_and_padding" \
        else np.array([0, 0, 5, 0, 0, 0, 0])
    off = np.where(lens > 0, np.cumsum(lens) - lens, T)
    kv = np.where(lens > 0, start + lens, 1)
    slots = np.array([3, 0, 4, 1, 2, 5, 5])
    q = gd.l2norm(rng.standard_normal((T, H, dk))) * dk ** -0.5
    k = gd.l2norm(rng.standard_normal((T, H, dk)))
    v = jnp.asarray(rng.standard_normal((T, H, dv)), jnp.float32)
    beta, g = gd.gates(jnp.asarray(rng.standard_normal((T, H))),
                       jnp.asarray(rng.standard_normal((T, H))),
                       jnp.log(jnp.asarray([6.0, 0.05])),
                       jnp.asarray([0.05, -3.0]), True)
    if case == "alike_keys":
        k = gd.l2norm(jax.nn.silu(jnp.asarray(
            rng.standard_normal((T, H, dk)) + 3.0, jnp.float32)))
        beta = jnp.asarray(rng.uniform(1.9, 2.0, (T, H)), jnp.float32)
        g = jnp.asarray(-rng.uniform(0.0, 1e-3, (T, H)), jnp.float32)
    bank = jnp.asarray(rng.standard_normal((6, H, dv, dk)) * 0.3,
                       jnp.float32)
    tabs = [jnp.asarray(a, jnp.int32) for a in (slots, off, lens, kv)]
    o1, s1 = gd.ragged_gated_delta_xla(q, k, v, beta, g, bank, *tabs)
    o2, s2 = pk.ragged_gated_delta(q, k, v, beta, g, bank, *tabs,
                                   interpret=True)
    real = np.zeros(T, bool)
    for o_, n in zip(off, lens):
        real[o_:o_ + n] = True
    assert np.abs(np.asarray(o1 - o2)[real]).max() < KERNEL_TOL
    assert np.all(np.isfinite(np.asarray(o2)))
    assert np.abs(np.asarray(s1 - s2)[:5]).max() < KERNEL_TOL
    assert pk.gdn_sub_chunks(lens) == 1 + 1 + 2 + 4


@pytest.mark.parametrize("stored, fails", [("float32", False),
                                           ("bfloat16", True),
                                           ("dropped", True)])
def test_state_carried_across_launches(stored, fails):
    """A 160-token span prefilled in two launches (96 + 64 rows), the
    bank carried between them as stored: in float32 the second launch's
    outputs equal the rule over the whole span; stored in bfloat16, or
    dropped at the chunk boundary, they leave KERNEL_TOL."""
    rng = np.random.default_rng(7)
    H, dk, dv, n = 2, 16, 32, 160
    q = gd.l2norm(rng.standard_normal((n, H, dk))) * dk ** -0.5
    k = gd.l2norm(rng.standard_normal((n, H, dk)))
    v = jnp.asarray(rng.standard_normal((n, H, dv)), jnp.float32)
    beta, g = gd.gates(jnp.asarray(rng.standard_normal((n, H))),
                       jnp.asarray(rng.standard_normal((n, H))),
                       jnp.log(jnp.asarray([6.0, 0.05])),
                       jnp.asarray([0.05, -3.0]), True)
    want, _ = gd.gated_delta_scan(q, k, v, beta, g,
                                  jnp.zeros((H, dv, dk), jnp.float32))
    bank = jnp.zeros((2, H, dv, dk), jnp.float32)
    got = []
    for a, b in ((0, 96), (96, n)):
        m = b - a               # one span in slot 0, a padding span after
        tabs = [jnp.asarray(t, jnp.int32)
                for t in ([0, 1], [0, m], [m, 0], [b, 1])]
        o, bank = pk.ragged_gated_delta(q[a:b], k[a:b], v[a:b], beta[a:b],
                                        g[a:b], bank, *tabs, interpret=True)
        got.append(np.asarray(o))
        if stored == "bfloat16":
            bank = bank.astype(jnp.bfloat16).astype(jnp.float32)
        elif stored == "dropped":
            bank = jnp.zeros_like(bank)
    err = np.abs(np.concatenate(got) - np.asarray(want)).max()
    assert (err > 10 * KERNEL_TOL) if fails else (err < KERNEL_TOL)


def test_reused_slot_starts_fresh_after_a_discarded_row(model):
    """One slot, one engine.  A request that ends on EOS leaves a row of
    the launch already in flight, computed for it and dropped at booking,
    which writes its slot's state; the next request seated there starts
    from zero state all the same and serves what it served alone."""
    from paddle_tpu.observability import span_log
    rng = np.random.default_rng(3)
    a, b = rng.integers(1, 256, 40), rng.integers(1, 256, 50)
    eng = _engine(model, max_batch_size=1)
    rid = eng.add_request(b, 8)
    want = eng.run_to_completion()[rid]
    first = eng.add_request(a, 6)
    served_a = eng.run_to_completion()[first]
    eos = served_a[2]

    def discarded():
        return sum(e[5]["discarded"] for e in span_log.events()
                   if e[1] == "serving.step"
                   and e[5].get("engine") == eng.engine_id)
    before = discarded()
    ra = eng.add_request(a, 6, eos_token_id=eos)
    rb = eng.add_request(b, 8)
    out = eng.run_to_completion()
    assert out[ra] == served_a[:served_a.index(eos) + 1]
    assert discarded() - before >= 1
    assert out[rb] == want


def test_preempt_and_readmit_give_the_same_tokens(model):
    """Preemption frees the slot; re-admission prefills the prompt and
    the served tokens from position 0, so the state starts fresh."""
    rng = np.random.default_rng(4)
    p = rng.integers(1, 256, 30)
    eng = _engine(model)
    rid = eng.add_request(p, 12)
    want = eng.run_to_completion()[rid]
    rid = eng.add_request(p, 12)
    for _ in range(6):
        eng.step()
    prompt, gen = eng.preempt_request(rid)
    assert 0 < len(gen) < 12
    rid2 = eng.add_request(np.concatenate([prompt, gen]), 12 - len(gen))
    assert gen + eng.run_to_completion()[rid2] == want


@pytest.mark.parametrize("kw, word", [
    (dict(kv_dtype="int8"), "int8"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(role="prefill"), "role"),
    (dict(draft_model="same", spec_k=2), "draft"),
    (dict(mesh="tp2"), "mesh"),
])
def test_engine_refuses_what_is_not_taught_the_slot_state(model, kw, word):
    kw = dict(kw)
    if kw.get("draft_model") == "same":
        kw["draft_model"] = model
    if kw.get("mesh") == "tp2":
        from paddle_tpu.jit.spmd import tp_mesh
        kw["mesh"] = tp_mesh(2)
    with pytest.raises(ValueError, match=word) as err:
        _engine(model, **kw)
    assert "linear-attention state" in str(err.value)


def test_migration_refuses_a_hybrid_engine(model):
    eng = _engine(model)
    with pytest.raises(ValueError, match="linear-attention state"):
        eng.inject_request(np.arange(1, 5), object())
