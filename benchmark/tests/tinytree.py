"""A throw-away copy of the benchmark's DATA with tiny cells added, for
tests on the CPU.  It adds files and entries and edits none: what a later
PR that brings a cell, a metric or a family does."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY = {"vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-05,
        "rope_theta": 1000000.0, "tie_word_embeddings": False,
        "dtype": "float32", "source": "test", "reduced": []}


# The family a later PR brings: layers of two kinds, layer 0 dense and the
# rest sparse, its reference a dozen lines over its siblings'.
DENSEFIRST = '''"""Throw-away family: ``first_k_dense_replace`` dense layers (as
``mistral``), then sparse ones (as ``mixtral``)."""
import importlib.util
import os


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_OF = {"dense": _sibling("mistral"), "sparse": _sibling("mixtral")}
INNER = "mixtral"
top_shapes, embed, logits = (_OF["dense"].top_shapes, _OF["dense"].embed,
                             _OF["dense"].logits)


def layer_kinds(cfg):
    k = cfg["first_k_dense_replace"]
    return ["dense"] * k + ["sparse"] * (cfg["num_hidden_layers"] - k)


def layer_shapes(cfg, kind):
    return _OF[kind].layer_shapes(cfg)


def layer(x, w, cfg, prec=None, kind=None):
    return _OF[kind].layer(x, w, cfg, prec)
'''


DATA = ("configs", "traffic", "cells", "metrics", "references")


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make(tmp: str, dtype: str = "float32") -> str:
    """Copies BENCHMARK.json and the data directories into ``tmp`` and
    adds tiny cells ``tiny-mistral.chat``, ``tiny-mixtral.docs``, a metric
    ``steps_total`` and a family of two kinds of layer, ``densefirst``,
    with its cell ``tiny-densefirst.docs``.  Returns the new root."""
    root = os.path.join(tmp, "root")
    bdir = os.path.join(root, "benchmark")
    for sub in DATA:
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bdir, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "mistral-7b-v0.3.d16.json")) as f:
        llama_prog = json.load(f)["program"]
    with open(os.path.join(BENCH, "configs",
                           "mixtral-8x7b-v0.1.d3.json")) as f:
        mix_prog = json.load(f)["program"]
    _dump(os.path.join(bdir, "configs", "tiny-mistral.json"),
          dict(TINY, dtype=dtype, num_hidden_layers=2, family="mistral",
               program=llama_prog))
    _dump(os.path.join(bdir, "configs", "tiny-mixtral.json"),
          dict(TINY, dtype=dtype, num_hidden_layers=2, family="mixtral",
               program=mix_prog, num_local_experts=4,
               num_experts_per_tok=2))
    with open(os.path.join(bdir, "references", "densefirst.py"), "w") as f:
        f.write(DENSEFIRST)
    _dump(os.path.join(bdir, "configs", "tiny-densefirst.json"),
          dict(TINY, dtype=dtype, num_hidden_layers=3, family="densefirst",
               first_k_dense_replace=1, num_local_experts=4,
               num_experts_per_tok=2, program=dict(mix_prog, layer_class={
                   "dense": "paddle_tpu.models.llama.LlamaDecoderLayer",
                   "sparse": "MixtralDecoderLayer"})))
    lens = {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 4,
            "max": 80}
    outs = {"dist": "uniform", "min": 12, "max": 24}
    _dump(os.path.join(bdir, "traffic", "tiny-chat.json"),
          {"loop": "open", "prompt_tokens": lens, "output_tokens": outs,
           "warmup_s": 0.5})
    _dump(os.path.join(bdir, "traffic", "tiny-docs.json"),
          {"loop": "closed", "pool": 8,
           "prompt_tokens": lens, "output_tokens": outs, "warmup_s": 0.5})
    engine = {"max_batch_size": 4, "prefill_chunk_size": 16,
              "block_size": 4, "num_blocks": 128, "max_seq_len": 112}
    check = {"sample": 8, "logit_gap_max": 1e-3, "logit_gap_mean": 1e-4}
    _dump(os.path.join(bdir, "cells", "tiny-mistral.chat.json"),
          {"engine": engine, "rate_per_s": 6.0,
           "trace_seconds": 0.5, "correct": check})
    # the sparse cell compares what the real one does: the share of wide
    # gaps and the capped mean, not the widest gap
    robust = {"sample": 8, "wide_gap": 1e-3, "wide_gap_share": 0.0,
              "capped_gap_mean": 1e-4}
    for name, numbers in (("tiny-mixtral.docs", robust),
                          ("tiny-densefirst.docs", check)):
        _dump(os.path.join(bdir, "cells", name + ".json"),
              {"engine": engine, "clients": 3, "trace_seconds": 0.5,
               "correct": dict(numbers, router_margin_min=1e-4,
                               undecided_share_max=0.2)})
    with open(os.path.join(bdir, "metrics", "steps_total.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.steps_in()))\n")
    bench["configs"] += [
        {"name": "tiny-mistral", "source": "test",
         "file": "benchmark/configs/tiny-mistral.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-mixtral", "source": "test",
         "file": "benchmark/configs/tiny-mixtral.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-densefirst", "source": "test",
         "file": "benchmark/configs/tiny-densefirst.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] += [
        {"name": "tiny-mistral.chat", "config": "tiny-mistral",
         "traffic": "tiny-chat", "chips": 1, "why": "test"},
        {"name": "tiny-mixtral.docs", "config": "tiny-mixtral",
         "traffic": "tiny-docs", "chips": 1, "why": "test"},
        {"name": "tiny-densefirst.docs", "config": "tiny-densefirst",
         "traffic": "tiny-docs", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            if any(w.endswith(".chat") for w in m["workloads"]):
                m["workloads"].append("tiny-mistral.chat")
            if any(w.endswith(".docs") for w in m["workloads"]):
                m["workloads"] += ["tiny-mixtral.docs",
                                   "tiny-densefirst.docs"]
    bench["per_layer"].append(
        {"name": "steps_total", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "model step",
         "moves": "setup_s"})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root
