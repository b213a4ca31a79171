"""The serving step records itself (PR 24).

Every ``ContinuousBatchingEngine.step()`` writes ONE ``serving.step``
record to the process-wide ``observability.span_log`` (unless the engine
was built with ``tracer=False``), is one ``engine.step`` profiler span
holding seven consecutive phase spans, and its requests' tracer entries
name the step that caused them.  The record counts the seconds its launch
spent building a module, and ``observability.step_clock`` joins it to
the profiler's clock.  Inside the compiled ``MixedStep`` every
part of the body sits under a ``jax.named_scope`` that the optimized
HLO keeps as metadata, so ``MixedStep.op_scopes`` can say which part a
device op belongs to; every Pallas kernel and every jitted step has a
name a trace can print.
"""
import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (STEP_SPAN,
                                          ContinuousBatchingEngine)
from paddle_tpu.jit.serving_step import STEP_SCOPES
from paddle_tpu.observability import (compile_seconds, default_registry,
                                      span_log, step_clock)

PROMPTS = [np.array([7, 9, 2], np.int64),
           np.array([3, 14, 15, 92, 65], np.int64),
           np.arange(1, 11, dtype=np.int64)]     # 10 tokens: chunked
BOUNDS = ("t_admit", "t_pack", "t_fill", "t_launch", "t_dispatch",
          "t_tokens")
PHASES = ("engine.admit", "engine.pack", "engine.fill",
          "engine.dispatch", "engine.launch", "engine.fetch",
          "engine.book")
BUILD = ("trace_s", "lower_s", "compile_s")


def _model(family):
    paddle.seed(0)
    if family == "mixtral":
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny_config)
        model = MixtralForCausalLM(mixtral_tiny_config(num_hidden_layers=2))
    elif family == "olmo":
        from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                                   olmo_hybrid_tiny_config)
        model = OlmoHybridForCausalLM(olmo_hybrid_tiny_config())
    else:
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        model = LlamaForCausalLM(llama_tiny_config(
            num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=128, intermediate_size=128))
    model.eval()
    return model


def _engine(family="llama", **kw):
    kw.setdefault("prefill_chunk_size", 4)
    return ContinuousBatchingEngine(_model(family), max_batch_size=4,
                                    num_blocks=64, block_size=4, **kw)


def _records(eng):
    """(t0, t_end, fields) of this engine's step records, in order."""
    return [(e[3], e[4], e[5]) for e in span_log.events()
            if e[1] == STEP_SPAN and e[5]["engine"] == eng.engine_id]


def _drive(eng, budget=4):
    """The staggered workload of the serving tests; returns the number
    of steps run and, per launched step, what ``_fill_mixed_pack`` was
    given as ``(req_id, q_len, kv_len)`` rows."""
    given = []
    fill = eng._fill_mixed_pack

    def spy(mx, budgets, spans, *src):
        if mx is eng.mixed:
            given.append([(r.req_id, len(toks), start + len(toks))
                          for r, toks, start, _, _, _ in spans])
        return fill(mx, budgets, spans, *src)

    eng._fill_mixed_pack = spy
    steps = 0
    rids = []
    for i, p in enumerate(PROMPTS):
        rids.append(eng.add_request(p, budget))
        if i == 0:
            eng.step()
            steps += 1
    while eng.has_work():
        eng.step()
        steps += 1
    return steps, given, rids


def _counter(kind):
    fam = default_registry().get("serving_mixed_span_tokens_total")
    return fam.labels(kind=kind).value


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_one_record_a_step(family):
    eng = _engine(family)
    before = {k: _counter(k) for k in ("decode", "prefill")}
    steps, given, rids = _drive(eng)
    recs = _records(eng)
    assert len(recs) == steps
    assert [f["step"] for _, _, f in recs] == list(range(steps))
    launched = [f for _, _, f in recs if f["budget"]]
    assert len(launched) == len(given)
    for (t0, t_end, f) in recs:
        ts = [t0] + [f[k] for k in BOUNDS] + [t_end]
        assert ts == sorted(ts), f
        assert f["tokens"] == f["n_dec"] + f["n_pre"] <= max(f["budget"], 0)
        assert f["spans"].dtype == np.int32 and f["spans"].shape[1] == 3
        assert f["spans"].nbytes < 1024
        if f["budget"]:
            assert f["budget"] in eng.token_budgets
    budgets = set()
    by_step = {f["step"]: f for _, _, f in recs}
    for f, rows in zip(launched, given):
        assert f["spans"].tolist() == [list(r) for r in rows]
        # a fresh engine traces each budget's module at its first launch
        # (``compiled`` is the launching call's, a record's budget the
        # step it booked)
        assert by_step[f["launch_step"]]["compiled"] \
            == (f["budget"] not in budgets)
        budgets.add(f["budget"])
    # the counters the engine already keeps say the same
    assert sum(f["n_dec"] for f in launched) \
        == _counter("decode") - before["decode"]
    assert sum(f["n_pre"] for f in launched) \
        == _counter("prefill") - before["prefill"]
    assert sum(f["n_pre"] for f in launched) == sum(map(len, PROMPTS))
    assert recs[-1][2]["running"] == 0 and recs[-1][2]["waiting"] == 0
    assert sorted(r for _, _, f in recs for r in f["admitted"]) == rids
    # the step's own count of the rows its experts were given: every
    # real token lands on top_k experts in each layer, the budget's
    # padding on none; a dense model counts nothing
    cfg = eng.mixed.cfg
    assert any(f["tokens"] < f["budget"] for f in launched)
    # key blocks: what the engine's own launch walks (the CPU engine
    # attends through the XLA reference, which walks none), and what
    # the TPU launch of the same model (sized here, never run) walks:
    # every tile a block at least, the diagonal's block masked
    tpu = _engine(family, use_pallas=True).mixed
    for _, _, f in recs:
        q_lens, kv_lens = f["spans"][:, 1], f["spans"][:, 2]
        assert (f["attn_blocks"], f["attn_blocks_masked"]) \
            == eng.mixed.attn_blocks(q_lens, kv_lens) == (0, 0)
        if f["budget"]:
            blocks, masked = tpu.attn_blocks(q_lens, kv_lens)
            assert 0 < masked <= blocks
        if family == "llama":
            assert f["moe_rows"] == f["moe_rows_top"] == 0
            assert f["moe_tiles"] == 0 and not eng.mixed.moe_tile_rows(16)
            continue
        k, experts = cfg.num_experts_per_tok, cfg.num_local_experts
        assert f["moe_rows"] == f["tokens"] * k * cfg.num_hidden_layers, f
        # the fullest expert: at least the mean, at most every token
        assert f["moe_rows_top"] * k <= f["moe_rows"] \
            <= f["moe_rows_top"] * experts
        # the row tiles those rows took in the grouped products (each
        # expert's rows start on a tile boundary): they hold the rows,
        # and no expert of a layer wastes a whole tile
        if not f["budget"]:
            assert f["moe_tiles"] == 0
            continue
        tile = eng.mixed.moe_tile_rows(f["budget"])
        assert tile >= 16
        assert f["moe_rows"] <= f["moe_tiles"] * tile \
            < f["moe_rows"] + tile * experts * cfg.num_hidden_layers

def test_requests_name_their_step():
    eng = _engine()
    _, _, rids = _drive(eng)
    by_step = {f["step"]: f for _, _, f in _records(eng)}
    for rid in rids:
        kinds = set()
        for _ph, kind, _ts, _te, args in eng.tracer.events(rid):
            if kind == "admit":
                assert rid in by_step[args["step"]]["admitted"]
            elif kind in ("prefill_chunk", "decode_step", "first_token"):
                assert rid in by_step[args["step"]]["spans"][:, 0]
            else:
                continue
            kinds.add(kind)
        assert kinds == {"admit", "prefill_chunk", "decode_step",
                         "first_token"}
    # the first token's step is the one whose launch held the last chunk
    first = [a for _, k, _, _, a in eng.tracer.events(rids[2])
             if k == "first_token"][0]
    rows = by_step[first["step"]]["spans"]
    row = rows[rows[:, 0] == rids[2]][0]
    assert row[2] == len(PROMPTS[2])


def test_prefix_hit_is_not_in_the_spans():
    eng = _engine(enable_prefix_cache=True)
    prompt = np.arange(1, 14, dtype=np.int64)            # 13 tokens
    eng.add_request(prompt, 2)
    eng.run_to_completion()
    rid = eng.add_request(np.concatenate([prompt[:12], [99, 98]]), 2)
    eng.run_to_completion()
    hit = eng.finished[rid].prefix_hit_tokens
    assert hit == 12
    rows = np.concatenate([f["spans"] for _, _, f in _records(eng)])
    mine = rows[rows[:, 0] == rid]
    prefill = mine[mine[:, 1] > 1] if (mine[:, 1] > 1).any() else mine[:1]
    assert prefill[:, 1].sum() == 14 - hit        # the suffix only
    assert prefill[0, 2] == hit + prefill[0, 1]   # kv_len counts the hit


def test_tracer_false_writes_no_record():
    eng = _engine(tracer=False)
    n = len(span_log)
    steps, _, _ = _drive(eng)
    assert steps > 3 and _records(eng) == [] and len(span_log) == n


def test_speculative_round_writes_the_record():
    from paddle_tpu.models.llama import llama_truncated_draft
    model = _model("llama")
    eng = ContinuousBatchingEngine(
        model, max_batch_size=4, num_blocks=64, block_size=4,
        prefill_chunk_size=4,
        draft_model=llama_truncated_draft(model, 1), spec_k=2)
    rids = [eng.add_request(p, 4) for p in PROMPTS]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    recs = _records(eng)
    assert len(recs) == steps
    for t0, t_end, f in recs:
        ts = [t0] + [f[k] for k in BOUNDS] + [t_end]
        assert ts == sorted(ts)
    rows = np.concatenate([f["spans"] for _, _, f in recs])
    for rid, p in zip(rids, PROMPTS):
        mine = rows[rows[:, 0] == rid]
        assert mine[:, 2].max() >= len(p) + 3      # reached its last token
    assert sum(f["n_pre"] for _, _, f in recs) == sum(map(len, PROMPTS))
    assert sorted(r for _, _, f in recs for r in f["admitted"]) == rids


def _profile(eng, steps, trace_dir):
    """Step ``eng`` ``steps`` times under the profiler, as the benchmark
    traces it; returns the first traced step's index."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        first = eng._step_no
        for _ in range(steps):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    return first


def test_profiler_sees_the_step_and_its_six_phases(tmp_path):
    """(Seven since the launch call became a phase of its own.)"""
    from jax.profiler import ProfileData
    eng = _engine()
    eng.add_request(PROMPTS[2], 6)
    eng.step()                                   # compiles outside the trace
    first = _profile(eng, 3, str(tmp_path))
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                  "*", "*.xplane.pb"))[0]
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    steps = sorted(e for e in events if e[0] == "engine.step")
    assert [e[3]["step_num"] for e in steps] == [first, first + 1,
                                                 first + 2]
    assert {e[3]["engine"] for e in steps} == {eng.engine_id}
    for _, s, e, _ in steps:
        inside = sorted((a, b, n) for n, a, b, _ in events
                        if n != "engine.step" and s <= a and b <= e)
        assert tuple(n for _, _, n in inside) == PHASES
        for (_, b0, _), (a1, _, _) in zip(inside, inside[1:]):
            assert b0 <= a1                       # consecutive, not nested


@pytest.mark.parametrize("round_", ["mixed", "speculative"])
def test_the_record_counts_its_own_build_seconds(round_):
    """Trace, lowering and compile seconds of the launch: above 0 on
    exactly the steps that traced a module, 0.0 on every other."""
    if round_ == "speculative":
        from paddle_tpu.models.llama import llama_truncated_draft
        model = _model("llama")
        eng = ContinuousBatchingEngine(
            model, max_batch_size=4, num_blocks=64, block_size=4,
            prefill_chunk_size=4,
            draft_model=llama_truncated_draft(model, 1), spec_k=2)
    else:
        eng = _engine()
    before = compile_seconds()
    _drive(eng)
    recs = [f for _, _, f in _records(eng)]
    assert any(f["compiled"] for f in recs)
    assert any(f["budget"] and not f["compiled"] for f in recs)
    for f in recs:
        built = [f[k] for k in BUILD]
        if f["compiled"]:
            assert min(built) >= 0.0 and sum(built) > 0, f
        else:
            assert built == [0.0, 0.0, 0.0], f
    # the process-wide counter grew by at least what the launches built
    # (the draft's modules too, which its round's record leaves out)
    after = compile_seconds()
    grown = sum(after[k] - before[k] for k in ("trace", "lower", "compile"))
    assert grown >= sum(f[k] for f in recs for k in BUILD) - 1e-9


def test_compile_seconds_counts_a_nested_trace_once():
    """A jitted function that calls another reports the inner trace and
    then the outer one that holds it: the outer's seconds are counted,
    the inner's not again."""
    @jax.jit
    def inner(x):
        return x * 2

    @jax.jit
    def outer(x):
        return inner(x) + 1

    from paddle_tpu.observability.compile_time import COMPILE_EVENTS
    seen = []

    def spy(event, duration, **_):
        if COMPILE_EVENTS.get(event) == "trace":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(spy)
    try:
        before = compile_seconds()["trace"]
        outer(np.ones(3, np.float32))
        traced = compile_seconds()["trace"] - before
    finally:
        jax.monitoring.unregister_event_duration_listener(spy)
    assert len(seen) >= 2                 # inner first, then outer
    assert traced == pytest.approx(max(seen))


def test_step_clock_joins_a_cpu_profile(tmp_path):
    """Each record pairs with the profiler's span of its engine and step;
    the offset between the two clocks holds to well under a step.  The
    CPU's trace has no device plane: no lead."""
    eng = _engine()
    for p in PROMPTS:
        eng.add_request(p, 6)
    eng.step()                                   # compiles outside the trace
    first = _profile(eng, 6, str(tmp_path))
    other = _engine()                            # the same step numbers
    other.add_request(PROMPTS[2], 12)
    other.run_to_completion()
    assert len(_records(other)) > first + 6
    from paddle_tpu.observability import load_device_trace_events
    clock = step_clock(load_device_trace_events(str(tmp_path)),
                       span_log.events())
    assert clock["steps"] == 6
    assert 0 <= clock["offset_spread_s"] < 200e-6
    assert clock["lead_s"] is None and clock["launches"] == 0
    # two captures in one directory would repeat every step: none of
    # them pairs, and nothing joins
    assert step_clock(load_device_trace_events(str(tmp_path)) * 2,
                      span_log.events()) is None


def _synthetic_trace(records, offset, lead, run=0.004):
    """A chrome trace of ``records`` as the profiler writes one on a
    chip: the host's ``engine.step`` spans ``offset`` seconds ahead of
    ``perf_counter``, and on the device one execution of the step's
    module a launched step, ``run`` seconds long, starting 0.5 ms after
    its launch call, on a clock ``lead`` seconds ahead of the host's."""
    us = 1e6
    events = [{"ph": "M", "pid": 7, "name": "process_name",
               "args": {"name": "/device:TPU:0"}},
              {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
               "args": {"name": "XLA Modules"}},
              {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name",
               "args": {"name": "XLA Ops"}},
              {"ph": "M", "pid": 9, "name": "process_name",
               "args": {"name": "/host:CPU"}}]
    for r in records:
        events.append({"ph": "X", "pid": 9, "tid": 1, "name": "engine.step",
                       "ts": (r["t0"] + offset) * us,
                       "dur": (r["t_end"] - r["t0"]) * us,
                       "args": {"step_num": str(r["step"]),
                                "engine": str(r["engine"])}})
        if r["budget"]:
            start = r["t_launch"] + 0.0005 + offset + lead
            for tid, name in ((2, "jit_mixed_step(17)"), (3, "fusion.3")):
                events.append({"ph": "X", "pid": 7, "tid": tid,
                               "name": name, "ts": start * us,
                               "dur": run * us})
    return events


def test_step_clock_bounds_a_synthetic_device_plane():
    """Built with a known offset and lead, the join returns the offset,
    and bounds on the lead that hold it: the lower from the step that
    reached the host soonest after its module ended, the upper from the
    module that started soonest after its launch call."""
    t, records = 100.0, []
    for step, (budget, wait) in enumerate([(8, 0.0045), (0, 0.0),
                                           (4, 0.0060), (8, 0.0047)]):
        r = {"engine": 3, "step": step, "budget": budget, "t0": t,
             "t_launch": t + 0.001, "t_tokens": t + 0.001 + wait,
             "t_end": t + 0.002 + wait}
        records.append(r)
        t = r["t_end"] + 0.003
    offset, lead = -12345.678, 0.0015
    clock = step_clock(_synthetic_trace(records, offset, lead), records)
    assert clock["steps"] == 4 and clock["launches"] == 3
    assert clock["offset_s"] == pytest.approx(offset, abs=1e-6)
    assert clock["offset_spread_s"] < 1e-6
    lo, hi = clock["lead_s"]
    # module end = launch + 4.5 ms + lead; the quickest fetch ended with it
    assert lo == pytest.approx(lead, abs=1e-6)
    assert hi == pytest.approx(lead + 0.0005, abs=1e-6)
    assert clock["lead_width_s"] == pytest.approx(0.0005, abs=1e-6)
    # another engine's records, or a device line the join cannot pair
    # one to one with the launches, give nothing to bound
    assert step_clock(_synthetic_trace(records, offset, lead),
                      [dict(r, engine=4) for r in records]) is None
    clock = step_clock(_synthetic_trace(records, offset, lead)[:-2],
                       records)
    assert clock["steps"] == 4 and clock["lead_s"] is None


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_compiled_step_names_its_parts(family):
    eng = _engine(family)
    T = eng.token_budgets[-1]
    lowered = eng.mixed.aot_lower(T)
    assert lowered.as_text().splitlines()[0].startswith(
        "module @jit_mixed_step")
    text = lowered.as_text(debug_info=True)
    want = {"embed", "attn.qkv", "attn.rope", "attn.kv_write",
            "attn.kernel", "attn.out", "ffn", "lm_head", "sample"}
    if family == "mixtral":
        want |= {"moe.gate", "moe.sort", "moe.experts", "moe.combine"}
    for scope in want:
        # (a scope opened inside a jitted helper of the step leads the
        # names of that helper's own function)
        assert f"/{scope}/" in text or f'"{scope}/' in text, scope
    assert want <= STEP_SCOPES
    # the ep branch's scopes stay named, and are not in a one-chip step
    assert {"moe.dispatch", "ep.all_to_all"} <= STEP_SCOPES
    assert "/moe.dispatch/" not in text and "/ep.all_to_all/" not in text
    if family == "llama":
        assert "moe." not in text
    # the optimized module: every matmul belongs to a part of the step
    scopes = eng.mixed.op_scopes(T)
    hlo = lowered.compile().as_text()
    heavy = re.findall(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ (?:dot|convolution)\(", hlo,
        re.M)
    heavy += re.findall(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = .*tpu_custom_call", hlo, re.M)
    assert heavy and all(scopes[name] for name in heavy)
    assert set(filter(None, scopes.values())) <= STEP_SCOPES
    if family == "mixtral":
        assert "moe.experts" in {scopes[n] for n in heavy}


def test_ragged_wrapper_names_its_parts():
    """The TPU path's wrapper round the ragged kernel, traced and not
    run: everything it does (the work list, the pack's padding, the
    launch) lies under ``attn.kernel``, and the scopes of the old
    operand contract are gone (tests/test_tpu_compile.py reads the v5e
    program)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (
        _ragged_paged_attention_pallas)
    T, H, Hkv, D, S, W, bs = 8, 4, 2, 16, 2, 3, 4
    pool = jnp.zeros((8, bs, Hkv, D), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, kc, vc, bt, qo, ql, kl: _ragged_paged_attention_pallas(
            q, kc, vc, bt, qo, ql, kl, 0.25, interpret=True))(
        jnp.zeros((T, H, D), jnp.float32), pool, pool,
        jnp.zeros((S, W), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), jnp.int32), jnp.ones((S,), jnp.int32))
    # the wrapper is jitted (a step traces it once a budget, not once
    # a layer): its body is the one call's jaxpr
    (call,) = jaxpr.eqns
    body = call.params["jaxpr"].jaxpr
    stacks = {str(eqn.source_info.name_stack) for eqn in body.eqns}
    assert all(s.split("/")[0] == "attn.kernel" for s in stacks), stacks
    assert not {"attn.regroup", "attn.kv_upcast", "attn.ungroup"} \
        & STEP_SCOPES
    assert [str(eqn.source_info.name_stack) for eqn in body.eqns
            if eqn.primitive.name == "pallas_call"] \
        == ["attn.kernel/ragged_paged_attention"]
    # the pools reach the launch as they are stored: no op but the
    # pallas_call takes one
    pools = body.invars[1:3]
    assert [eqn.primitive.name for eqn in body.eqns
            if any(v is p for v in eqn.invars for p in pools)] \
        == ["pallas_call"]


def test_attn_rows_follow_the_spans():
    """The record's ``attn_rows``: q rows per kv head the launch's
    attention computes.  The CPU engine attends through the XLA
    reference, which computes every row of the budget; the TPU launch
    (sized here, never run) computes whole tiles: at most one partial
    tile of slack a span."""
    from paddle_tpu.ops.pallas_kernels import (ragged_attn_rows,
                                               ragged_tile_geometry)
    eng = _engine()
    _drive(eng)
    cfg = eng.mixed.cfg
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    groups = H // Hkv
    recs = [f for _, _, f in _records(eng)]
    launched = [f for f in recs if f["budget"]]
    assert launched
    for f in recs:
        assert f["attn_rows"] == f["budget"] * groups
    tpu = _engine(use_pallas=True).mixed
    tile, _ = ragged_tile_geometry(H, Hkv, cfg.hidden_size // H, 4,
                                   tpu.bt_width, "float32", "float32")
    for f in launched:
        q_lens = f["spans"][:, 1]
        rows = tpu.attn_rows(f["budget"], q_lens)
        assert groups * f["tokens"] <= rows <= groups * (
            f["tokens"] + len(q_lens) * (tile - 1))
    # the cells' own step: 40 decode spans beside two 512-token chunks
    # at budget 1024, 32-token tiles (it was 64 spans x 512 rows x 4 =
    # 131,072)
    assert ragged_attn_rows([1] * 40 + [512, 512] + [0] * 22, 32, 4) \
        == 4 * (40 * 32 + 1024)
    assert ragged_attn_rows([131, 7, 33], 32, 4) == 4 * 32 * (5 + 1 + 2)


@pytest.mark.parametrize("family,per_span", [("llama", 0), ("olmo", 2)])
def test_attn_decode_cells_count_one_token_spans(family, per_span):
    """The record's ``attn_decode_cells``: the grouped-query launch's
    grid cells that took its decode cell, summed over the layers that
    launch it.  The CPU engine attends through the XLA reference: 0.
    The TPU launch of the same model (sized here, never run): at one
    group (the hybrid's 4 heads over 4) each one-token span times the
    full-attention layers (2 of its 8); at 2 groups (the llama's 4
    over 2) none, the tile cell takes them.
    ``serving_attn_decode_cells_total`` sums what the records hold,
    booked from the same spans."""
    fam = default_registry().get("serving_attn_decode_cells_total")
    eng = _engine(family)
    before = fam.value
    _drive(eng)
    recs = [f for _, _, f in _records(eng)]
    assert recs and all(f["attn_decode_cells"] == 0 for f in recs)
    assert fam.value == before
    tpu = _engine(family, use_pallas=True).mixed
    assert len(tpu.caches) == 2
    ones = 0
    for f in recs:
        q_lens = f["spans"][:, 1]
        assert tpu.attn_decode_cells(q_lens) \
            == per_span * int(np.sum(q_lens == 1))
        ones += int(np.sum(q_lens == 1))
    assert ones
    # an engine that books as the TPU launch would
    eng = _engine(family)
    eng.mixed.attn_decode_cells = tpu.attn_decode_cells
    before = fam.value
    _drive(eng)
    counted = sum(f["attn_decode_cells"] for _, _, f in _records(eng))
    assert counted == fam.value - before == per_span * ones


def test_scopes_are_metadata_only(monkeypatch):
    """The optimized program is the same with the scopes taken out."""
    def count(eng):
        hlo = eng.mixed.aot_lower(eng.token_budgets[-1]).compile().as_text()
        return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", hlo, re.M))

    named = count(_engine("mixtral"))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert count(_engine("mixtral")) == named


def test_jitted_steps_are_named():
    eng = _engine()
    for T in eng.token_budgets:
        assert eng.mixed.aot_lower(T).as_text().splitlines()[0].startswith(
            "module @jit_mixed_step")
    import paddle_tpu.nn as nn
    from paddle_tpu.jit.train_step import TrainStep
    paddle.seed(0)
    net = nn.Linear(4, 2)
    step = TrainStep(net, nn.MSELoss(),
                     paddle.optimizer.SGD(parameters=net.parameters()))
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 2), np.float32))
    step(x, y)
    assert step.lower(x, y).as_text().splitlines()[0].startswith(
        "module @jit_train_step")


def test_every_pallas_call_has_a_name():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu")
    names = []
    for path in glob.glob(os.path.join(root, "**", "*.py"),
                          recursive=True):
        src = open(path).read()
        # the ragged launches share one pallas_call, which takes its
        # name from each caller (``_ragged_launch(kernel, "name", ..)``)
        for m in re.finditer(r"pl\.pallas_call\(|(?<!def )_ragged_launch\(",
                             src):
            depth, i = 1, m.end()
            while depth:                          # to the matching ")"
                depth += {"(": 1, ")": -1}.get(src[i], 0)
                i += 1
            call = src[m.end():i]
            if m.group().startswith("pl.") and "name=name" in call:
                continue
            name = re.search(r'\bname="(\w+)"', call) \
                or re.match(r'\s*\w+,\s*"(\w+)"', call)
            assert name, f"{path}: pallas_call without name= at " \
                         f"line {src[:m.start()].count(chr(10)) + 1}"
            names.append(name.group(1))
    assert len(names) == len(set(names)) >= 8
    assert {"flash_attention_fwd", "flash_attention_bwd",
            "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
            "rms_norm", "ragged_paged_attention", "rope_qkv_epilogue",
            "paged_decode_attention", "ragged_latent_attention"} \
        <= set(names)
