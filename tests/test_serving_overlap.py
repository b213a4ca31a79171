"""One launch in flight across engine steps.

``ContinuousBatchingEngine.step()`` packs launch N+1 from the slots as
they will stand once launch N is booked, enqueues it, and only then
fetches and books N; a decode token N samples reaches N+1 on the device
(``MixedStep``'s src row).  What a caller sees of a request changes only
when the step that did the work is booked, one call later than the
launch.  A path that changes slots from outside settles first.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (STEP_SPAN,
                                          ContinuousBatchingEngine)
from paddle_tpu.observability import default_registry, span_log

PROMPTS = [np.array([7, 9, 2], np.int64),
           np.array([3, 14, 15, 92, 65], np.int64),
           np.arange(1, 11, dtype=np.int64),       # 10 tokens: chunked
           np.arange(20, 33, dtype=np.int64)]
N_NEW = [6, 3, 8, 1]
BOUNDS = ("t_admit", "t_pack", "t_fill", "t_launch", "t_dispatch",
          "t_tokens")


def _model(family="llama"):
    paddle.seed(0)
    if family == "mixtral":
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny_config)
        model = MixtralForCausalLM(mixtral_tiny_config(num_hidden_layers=2))
    else:
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        model = LlamaForCausalLM(llama_tiny_config(
            num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=128, intermediate_size=128))
    model.eval()
    return model


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def refs(model):
    """Eager greedy tokens of each prompt."""
    out = []
    for p, n in zip(PROMPTS, N_NEW):
        ids = model.generate(paddle.to_tensor(p[None, :]), max_new_tokens=n)
        out.append(np.asarray(ids._value)[0, len(p):].tolist())
    return out


def _engine(model, **kw):
    return ContinuousBatchingEngine(model, max_batch_size=4, num_blocks=64,
                                    block_size=4, prefill_chunk_size=4,
                                    **kw)


def _records(eng):
    return [dict(e[5], t0=e[3], t_end=e[4]) for e in span_log.events()
            if e[1] == STEP_SPAN and e[5]["engine"] == eng.engine_id]


def _counter(name, **labels):
    fam = default_registry().get(name)
    return (fam.labels(**labels) if labels else fam).value


class _Caller:
    """Drives an engine as the benchmark does and notes, after each
    ``step()``, what changed of each request: ``(n_dec, n_pre, spans)``
    with ``spans`` as sorted ``(q_len, kv_len)``.  ``settle`` books the
    launch in flight after every call: the serial engine's order."""

    def __init__(self, eng, settle=False):
        self.eng, self.settle = eng, settle
        self.seen, self.steps, self.done = {}, [], []

    def add(self, prompt, n, **kw):
        rid = self.eng.add_request(prompt, n, **kw)
        self.seen[rid] = [self.eng.waiting[-1], 0, 0]
        return rid

    def step(self):
        self.done += self.eng.step()
        if self.settle:
            self.eng._settle()
        n_dec = n_pre = 0
        spans = []
        for s in self.seen.values():
            h, pre0, out0 = s
            pre, new = h.prefill_pos - pre0, len(h.output_ids) - out0
            if pre > 0:
                spans.append((pre, h.prefill_pos))
                n_pre += pre
            elif new > 0:
                spans.append((1, len(h.prompt_ids) + len(h.output_ids) - 1))
                n_dec += 1
            s[1], s[2] = h.prefill_pos, len(h.output_ids)
        self.steps.append((n_dec, n_pre, sorted(spans)))

    def serve(self, eos=None, **kw):
        """The staggered workload: two requests, two steps, the rest."""
        rids = []
        for i, (p, n) in enumerate(zip(PROMPTS, N_NEW)):
            rids.append(self.add(p, n, eos_token_id=(eos or {}).get(i),
                                 **kw))
            if i == 1:
                self.step()
                self.step()
        while self.eng.has_work():
            self.step()
        return rids


def _eos_refs(refs):
    """An EOS for request 0 at its third token and for request 2 at its
    first (the one its last chunk samples): both end while their next
    decode is already in flight.  Returns ``(eos by index, expected
    tokens, requests that end on EOS before their budget)``."""
    eos = {0: refs[0][2], 2: refs[2][0]}
    want = [list(r) for r in refs]
    early = 0
    for i, tok in eos.items():
        want[i] = want[i][:want[i].index(tok) + 1]
        early += len(want[i]) < N_NEW[i]
    return eos, want, early


@pytest.mark.parametrize("mode", ["greedy", "eos", "lazy", "sampled"])
def test_overlapped_tokens_match_the_settled_engine(model, refs, mode):
    """Chunks, decodes, a one-token budget, EOS on a step whose next
    launch is in flight: the tokens equal eager ``generate`` (greedy)
    and the engine that settles after every call, the pool comes back
    whole, and ``discarded`` counts the rows of requests that had
    already ended."""
    kw = {"lazy_alloc": True} if mode == "lazy" else {}
    req_kw = {}
    if mode == "sampled":
        kw["sampling"] = True
        req_kw = {"temperature": 0.9, "top_k": 20, "seed": 11}
    eos, want, early = ({}, refs, 0)
    if mode == "eos":
        eos, want, early = _eos_refs(refs)
        assert early == 2
    outs = []
    for settle in (False, True):
        eng = _engine(model, **kw)
        discarded0 = _counter("serving_discarded_rows_total")
        caller = _Caller(eng, settle=settle)
        rids = caller.serve(eos, **req_kw)
        outs.append([eng.finished[r].output_ids for r in rids])
        assert sorted(caller.done) == sorted(rids)
        cache = eng.caches[0]
        assert len(cache._free) == cache.num_blocks
        recs = _records(eng)
        got = sum(f["discarded"] for f in recs)
        assert got == _counter("serving_discarded_rows_total") - discarded0
        assert got == (0 if settle else early)
        assert eng._inflight is None and not eng.mixed._unfetched
    if mode != "sampled":
        assert outs[0] == want
    assert outs[0] == outs[1]


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_one_record_a_call_of_the_step_it_booked(family):
    """One record per ``step()`` call.  Its boundaries are the call's own
    and in order; its work fields are the step it booked, which is what
    the caller saw change in that call; every launch but the first was
    enqueued ahead of the fetch."""
    eng = _engine(_model(family))
    ahead0 = _counter("serving_launches_total", mode="ahead")
    settled0 = _counter("serving_launches_total", mode="settled")
    caller = _Caller(eng)
    caller.serve()
    recs = _records(eng)
    assert len(recs) == len(caller.steps)
    assert [f["step"] for f in recs] == list(range(len(recs)))
    for f, (n_dec, n_pre, spans) in zip(recs, caller.steps):
        ts = [f["t0"]] + [f[k] for k in BOUNDS] + [f["t_end"]]
        assert ts == sorted(ts), f
        assert (f["n_dec"], f["n_pre"]) == (n_dec, n_pre)
        assert sorted((q, kv) for _, q, kv in f["spans"].tolist()) == spans
        assert f["tokens"] == n_dec + n_pre
        if f["budget"]:
            assert f["launch_step"] < f["step"]
            assert f["budget"] in eng.token_budgets
        else:
            assert f["launch_step"] == -1 and not f["spans"].size
        if family == "mixtral" and f["budget"]:
            cfg = eng.mixed.cfg
            assert f["moe_rows"] == f["tokens"] \
                * cfg.num_experts_per_tok * cfg.num_hidden_layers
    ahead = sum(f["ahead"] for f in recs)
    assert ahead == _counter("serving_launches_total", mode="ahead") - ahead0
    # the workload never leaves the engine idle: one launch with nothing
    # in flight, the first, and every other ahead of a fetch
    assert _counter("serving_launches_total", mode="settled") - settled0 == 1
    assert ahead == sum(1 for f in recs if f["budget"]) - 1
    assert not recs[0]["budget"] and recs[0]["ahead"] == 0


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_no_build_after_every_budget_ran(family):
    """The first launch reads zeros where later ones read the launch
    before's tokens: the module each budget traced at its first launch
    serves every later one, and XLA compiles nothing more."""
    eng = _engine(_model(family))
    chunk = eng.chunk_size
    for budget in eng.token_budgets:          # each budget, as warm-up
        total = min(budget, eng.max_batch_size * chunk)
        sizes = [chunk] * (total // chunk) + ([total % chunk]
                                              if total % chunk else [])
        for n in sizes:
            eng.add_request(np.ones(n, np.int64), 2)
        eng.run_to_completion()
    assert set(eng.mixed.compile_counts) == set(eng.token_budgets)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        _Caller(eng).serve()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert all(v == 1 for v in eng.mixed.compile_counts.values())


def test_preempt_settles_the_launch_in_flight(model, refs):
    """``preempt_request`` books the launch in flight first: the ids it
    returns are every token computed, and re-queued on the same engine
    the request finishes as the uninterrupted run would."""
    eng = _engine(model)
    rid = eng.add_request(PROMPTS[0], N_NEW[0])
    eng.step()                     # the prefill is launched, not fetched
    eng.step()                     # first token booked, a decode in flight
    assert eng._inflight is not None
    prompt, gen = eng.preempt_request(rid)
    assert eng._inflight is None and gen == refs[0][:2]
    rid2 = eng.add_request(np.concatenate([prompt, np.asarray(gen)]),
                           N_NEW[0] - len(gen))
    assert gen + eng.run_to_completion()[rid2] == refs[0]
    assert len(eng.caches[0]._free) == eng.caches[0].num_blocks


def test_extract_and_inject_settle(model, refs):
    """``extract_request`` books the launch in flight before it copies
    the pages, and ``inject_request`` books the target's before it
    seats the request: the migrated stream is the uninterrupted one."""
    a, b = _engine(model), _engine(model)
    rid = a.add_request(PROMPTS[2], N_NEW[2])
    other = b.add_request(PROMPTS[1], N_NEW[1])
    for _ in range(4):
        a.step()
    b.step()
    assert a._inflight is not None and b._inflight is not None
    prompt, gen, buf = a.extract_request(rid)
    assert a._inflight is None and buf is not None
    assert 0 < len(gen) < N_NEW[2] and gen == refs[2][:len(gen)]
    resume = np.concatenate([prompt, np.asarray(gen, np.int64)])
    rid2 = b.inject_request(resume, buf, max_new_tokens=N_NEW[2] - len(gen))
    assert b._inflight is None
    out = b.run_to_completion()
    assert gen + out[rid2] == refs[2] and out[other] == refs[1]
    for eng in (a, b):
        assert len(eng.caches[0]._free) == eng.caches[0].num_blocks


def test_speculative_round_never_runs_ahead(model, refs):
    """The speculative round needs every token on the host: each round
    books its own launch, and the tokens are eager ``generate``'s."""
    from paddle_tpu.models.llama import llama_truncated_draft
    eng = ContinuousBatchingEngine(
        model, max_batch_size=4, num_blocks=64, block_size=4,
        prefill_chunk_size=4, draft_model=llama_truncated_draft(model, 1),
        spec_k=2)
    ahead0 = _counter("serving_launches_total", mode="ahead")
    caller = _Caller(eng)
    rids = caller.serve()
    assert [eng.finished[r].output_ids for r in rids] == refs
    assert _counter("serving_launches_total", mode="ahead") == ahead0
    assert all(f["ahead"] == 0 and f["launch_step"] in (-1, f["step"])
               for f in _records(eng))
