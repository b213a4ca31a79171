"""Shared scaffolding for the serving/router/tracing benches.

One model-config + eager-reference contract for every round's bench:
`tools/bench_router.py` (r15) and `tools/bench_trace.py` (r16) import
these instead of keeping drifting copies — a change to the reference
model or the generate contract lands ONCE.  (`tools/bench_serving.py`
predates this module and owns a wider config matrix.)
"""
import numpy as np


def build_bench_model(on_tpu):
    """The bench model pair: tiny llama on CPU (artifact schema is
    CI-checkable), the 1.1B-ish line on TPU.  Returns (cfg, model),
    seeded and in eval mode."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=20, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16")
    else:
        cfg = llama_tiny_config()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    model.eval()
    return cfg, model


def eager_reference(model, prompt, budget):
    """The parity oracle: eager greedy `model.generate` continuation
    tokens for one prompt."""
    import paddle_tpu as paddle
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=budget)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def make_engines(model, n, knobs, tracer=None, id_base=None):
    """The router benches' pool: mixed-step + prefix-cache engines
    from the shared knob dict (slots/num_blocks/block_size/chunk).
    ``id_base`` pins explicit engine ids (omit for the process-wide
    auto sequence); ``tracer`` forwards to the engine (None = the
    default-ON tracer, False = the no-op stub)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    out = []
    for i in range(n):
        kw = {}
        if id_base is not None:
            kw["engine_id"] = id_base + i
        out.append(ContinuousBatchingEngine(
            model, max_batch_size=knobs["slots"],
            num_blocks=knobs["num_blocks"],
            block_size=knobs["block_size"],
            prefill_chunk_size=knobs["chunk"],
            enable_prefix_cache=True, tracer=tracer, **kw))
    return out


def warm_engines(engines, knobs, vocab):
    """ONE compile-warmup contract for every router-era bench: per
    engine (each owns its own MixedStep modules), run staggered
    requests shaped like the measured workload with token values from
    a DISJOINT range, so cold budget compiles land here and nothing
    registers in the measured prefix families."""
    rng = np.random.RandomState(99)
    L = knobs["prefix_len"] + knobs["suffix_len"]
    for eng in engines:
        eng.add_request(rng.randint(1, vocab, (L,)).astype(np.int64),
                        max_new_tokens=knobs["budget"])
        eng.step()
        eng.add_request(
            rng.randint(1, vocab, (knobs["suffix_len"],)).astype(np.int64),
            max_new_tokens=knobs["budget"])
        eng.run_to_completion()
