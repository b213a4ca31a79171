"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each is a file of its own, as is the cell's deployment, each
per-layer metric's reader and the configuration's plain reference:

- ``configs/<config>.json``     the model's sizes as run, source, cuts
- ``traffic/<traffic>.json``    lengths, arrivals, loop kind
- ``cells/<workload>.json``     engine settings, offered load, limits
- ``metrics/<metric>.py``       ``read(run) -> float | None``
- ``references/<family>.py``    the family's plain reference

Adding a cell, a metric or a family adds files and entries; nothing here
names one.  All five are looked for in the tree whose ``BENCHMARK.json``
was read (``Cell.dir``).

What a new FAMILY brings (the whole contract; ``PERF.md`` repeats it):

- ``configs/<config>.json``.  The harness itself reads ``family``,
  ``dtype``, ``num_hidden_layers``, ``vocab_size`` (traffic draws token
  ids from it) and ``program``: ``module``, ``config_class`` (a dataclass;
  every key of the file that is one of its fields is handed to it),
  ``model_class`` and ``layer_class``, which is one class name or a map
  from layer kind to class name, each constructed as ``cls(cfg)``; a
  name with a dot in it is ``module.Class``, any other is looked up in
  ``module``.  One chip's share of a model that several chips hold is
  written as the share: the experts held and the vocabulary slice stand
  under their own keys and under ``reduced``, the published counts under
  ``published``, the layout in ``deployment``.  The reference is given
  the same file, so the same share.
- ``references/<family>.py``, which imports nothing of the program and
  loads a sibling reference by its own directory.  Names: ``INNER`` (the
  attribute of the CausalLM that holds ``embed_tokens``, ``layers``,
  ``norm``); ``top_shapes(config)`` and ``layer_shapes(config)``,
  ``{leaf: shape}`` under the program's own parameter names, weights
  ``[in, out]``; ``embed(ids, top)``; ``layer(x, w, config, prec=None)``
  over one sequence ``[S, h]`` in float32, returning the output and
  ``None``, or for a routed layer ``(margin [S], experts chosen [S, k],
  sorted)``; ``logits(rows, top, config, prec=None)``; ``prec`` is
  ``"int8"`` (the control) or ``"bf16"`` (the witness), as
  ``mistral.mm`` spells them.  A family whose layers are of more than
  one kind also defines ``layer_kinds(config) -> [kind of each layer]``;
  the harness then calls ``layer_shapes(config, kind)`` and
  ``layer(x, w, config, prec, kind=kind)``.  A family without
  ``layer_kinds`` is never handed a kind.  A family with a leaf that is
  neither a matrix nor a norm gain (a decay's ``A_log``, a time step's
  ``dt_bias``) also defines ``leaf_draws(config, kind=None) -> {leaf:
  draw}``, handed the kind as ``layer_shapes`` is: each draw a tuple of
  ``weights.py``'s vocabulary, refused with the leaf and family named
  where it is not one or names no leaf of the shapes.  The program's
  build and the reference's forward both take it from here
  (``family_layers``); a leaf it does not name, the top's leaves and
  every leaf of a family without it keep ``weights.py``'s rule.
- ``cells/<workload>.json`` (engine, load, ``correct``'s limits; the
  optional ``kernels`` are the Pallas names the v5e rehearsal looks for),
  a traffic mix, readers for its metrics (with a counts module of its
  own beside them where ``harness/counts.py`` does not fit), and the
  entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its three data files."""

    def __init__(self, workload: str, root: str = ROOT):
        self.bench = _load_json(root, "BENCHMARK.json")
        # the benchmark's data may live in a copy of the tree (tests add
        # throw-away cells to one); its code is always this package's
        self.dir = d = os.path.join(root, self.bench["paths"][0])
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in entries:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(entries)}")
        self.entry = entries[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = _load_json(d, "configs",
                                 self.entry["config"] + ".json")
        self.traffic = _load_json(d, "traffic",
                                  self.entry["traffic"] + ".json")
        self.deploy = _load_json(d, "cells", workload + ".json")

    def reference(self):
        """The configuration's plain reference, from this cell's tree."""
        return reference_module(self.config["family"], self.dir)

    def _metrics(self, group: str):
        """Entries of ``group`` that this cell reports: those that list
        it under ``workloads``, and those with no such key."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self):
        return self._metrics("end_to_end")

    def per_layer(self):
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in e2e]


def _module(name: str, *path):
    """The module in the file ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(*path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _module(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        bench_dir, "metrics", name + ".py").read


def reference_module(family: str, bench_dir: str = BENCH_DIR):
    """``references/<family>.py``: the configuration's plain reference."""
    return _module("bench_reference_" + family,
                   bench_dir, "references", family + ".py")


def family_layers(ref, config: dict):
    """The kind of each layer, ``{kind: {leaf: shape}}`` and ``{kind:
    {leaf: draw}}`` (``weights.py``; empty where the family defines no
    ``leaf_draws``).  A family that defines no ``layer_kinds`` has one
    kind, ``None``, and its ``layer_shapes``, ``leaf_draws`` and ``layer``
    are never handed one."""
    from . import weights      # imports jax, which run.py defers
    depth = int(config["num_hidden_layers"])
    if not hasattr(ref, "layer_kinds"):
        kinds, args = [None] * depth, {None: ()}
    else:
        kinds = list(ref.layer_kinds(config))
        if len(kinds) != depth:
            raise ValueError(f"layer_kinds names {len(kinds)} layers, the "
                             f"configuration has {depth}")
        args = {k: (k,) for k in set(kinds)}
    shapes = {k: ref.layer_shapes(config, *a) for k, a in args.items()}
    draws = {k: dict(ref.leaf_draws(config, *a))
             if hasattr(ref, "leaf_draws") else {} for k, a in args.items()}
    for k, d in draws.items():
        weights.check_draws(d, shapes[k], f"references/{config['family']}"
                            f".py, layer kind {k!r}")
    return kinds, shapes, draws
