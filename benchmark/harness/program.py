"""The only module that imports the system under test.

It builds the model through the public classes, fills it with the seeded
weights of ``weights.py``, and builds the engine the cell's file asks for.
The classes are named in the configuration's file (``program``), so a new
family is new files, not an edit here (``spec.py`` lists them).
"""
from __future__ import annotations

import copy
import dataclasses
import importlib
import sys
import time

import jax

from . import weights
from .spec import family_layers


def enable_compile_cache() -> str:
    from paddle_tpu.core.device import enable_compile_cache as enable
    return enable()


def program_config(config: dict, **overrides):
    """The system's config object for ``config`` (a configuration file)."""
    prog = config["program"]
    mod = importlib.import_module(prog["module"])
    cls = getattr(mod, prog["config_class"])
    fields = {f.name for f in dataclasses.fields(cls)}
    # the file's other keys (source, reduced, assumed, ...) are about it
    kw = {k: v for k, v in config.items() if k in fields}
    kw.update(overrides)
    return cls(**kw)


def _class(prog: dict, name: str):
    """``module.Class``, or a class of the configuration's ``module``."""
    modname, _, cls = name.rpartition(".")
    return getattr(importlib.import_module(modname or prog["module"]), cls)


def build_model(cell, seed: int):
    """The system's CausalLM at the configuration's depth, never held
    whole in float32: the top is built with no layers and cast; the FIRST
    layer of each kind (``spec.family_layers``) is built by the system
    (float32, its own initializer, whose transients are another float32
    layer's worth) and cast; every later layer of that kind is a copy of
    that one; each is filled with its seeded values and appended.  With
    one kind the float32 construction happens while the model is all but
    empty, and the build's peak (the model so far, one copied layer and
    one layer of seeded values) stays under the run's.  With more kinds
    the layers go in order, so a kind's float32 construction stands
    beside the layers before its first: for a dense layer 0 before sparse
    layers of 40 experts of 3 x 5120 x 1536, one sparse layer in float32
    (4.6 GB, and its transients) beside 0.7 GB of model."""
    config = cell.config
    prog = config["program"]
    mod = importlib.import_module(prog["module"])
    ref = cell.reference()
    kinds, shapes = family_layers(ref, config)
    cfg = program_config(config, num_hidden_layers=0)
    model = getattr(mod, prog["model_class"])(cfg)
    if config["dtype"] != "float32":
        model.to(dtype=config["dtype"])
    top = weights.make_group(seed, weights.TOP, ref.top_shapes(config),
                             config["dtype"])
    from paddle_tpu.core.tensor import Tensor
    # set_value(array) goes through numpy, i.e. through the host: 7.5 GB
    # took 11 s; set_value(Tensor) keeps the array where it is
    on_device = Tensor._from_value
    inner = getattr(model, ref.INNER)
    inner.embed_tokens.weight.set_value(on_device(top["embed_tokens.weight"]))
    inner.norm.weight.set_value(on_device(top["norm.weight"]))
    model.lm_head.weight.set_value(on_device(top["lm_head.weight"]))
    del top
    classes = prog["layer_class"]
    first = {}          # kind -> index of the first layer of that kind
    t_init = t_fill = 0.0
    for li, kind in enumerate(kinds):
        t0 = time.perf_counter()
        if kind not in first:
            first[kind] = li
            name = classes if isinstance(classes, str) else classes[kind]
            layer = _class(prog, name)(cfg)
            if config["dtype"] != "float32":
                layer.to(dtype=config["dtype"])
        else:
            layer = copy.deepcopy(inner.layers[first[kind]])
        jax.block_until_ready([p._value for p in layer.parameters()])
        t1 = time.perf_counter()
        t_init += t1 - t0
        vals = weights.make_group(seed, li, shapes[kind], config["dtype"])
        params = dict(layer.named_parameters())
        if set(params) != set(vals):
            raise RuntimeError(
                f"layer {li} (kind {kind!r}): the reference's leaves "
                f"{sorted(vals)} are not the parameters of "
                f"{type(layer).__name__}, {sorted(params)}")
        for name, p in params.items():
            p.set_value(on_device(vals[name]))
        jax.block_until_ready(list(vals.values()))
        del vals
        inner.layers.append(layer)
        t_fill += time.perf_counter() - t1
    print(f"[bench] build: the program's own float32 construction, cast "
          f"and copies {t_init:.1f}s, seeded values {t_fill:.1f}s",
          file=sys.stderr, flush=True)
    # the engine sizes its caches from the config's depth
    cfg.num_hidden_layers = len(kinds)
    return model


def build_engine(model, engine_kw: dict):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model.eval()
    return ContinuousBatchingEngine(model, mixed_step=True, **engine_kw)
