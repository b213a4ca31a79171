"""2D fsdp x tp mesh benchmark — train-to-serve, zero re-sharding
(BENCH_SPMD_r21.json).

On a forced 16-device CPU mesh, sweep the full ``fsdp x tp`` grid
{1,2,4}^2 (``spmd.mesh_2d`` via ``testing.cpu_mesh_2d``).  Per cell:

- train the tiny llama 12 steps under the 2D fused step (params, grads
  and optimizer state STORED in the composed family placement — ZeRO-3
  as the storage layout) and record the loss trajectory, per-chip
  param+opt-state bytes, the per-step fsdp/tp param-gather payload and
  the compile count;
- hand the TRAINED model straight to a ``ContinuousBatchingEngine`` on
  the SAME mesh and greedy-decode a fixed workload — asserting the
  engine adopted every param BY BUFFER IDENTITY (the round-21
  zero-re-sharding contract) and recording the serving collective
  bytes.

Every number is parity-gated against the (1,1) single-chip cell: loss
trajectories agree to <= 1e-4 and served tokens are byte-identical
across ALL NINE cells, the equal-total-degree legs called out in the
round-21 issue (fsdp2 x tp2 vs the 1D dp=4 stage-2 train step, and vs
the tp=4 serve) included; each train step must have compiled exactly
once; and the (4,4) cell's per-chip param+opt bytes must land at
~1/16 of replicated.  On any error ONE parseable failure-marker JSON
line is emitted and the process exits 1 — a crashed bench can never be
mistaken for a green one.

Writes BENCH_SPMD_r21.json next to the repo root, then regenerates
BENCH_INDEX.json (tools/bench_index.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.testing.dryrun import force_cpu_devices  # noqa: E402

N_DEV = 16
force_cpu_devices(N_DEV)

import numpy as np  # noqa: E402

GRID = (1, 2, 4)
STEPS = 12
TOL = 1e-4
BATCH, SEQ = 16, 32
PROMPTS = [[7, 9, 2], [3, 14, 15, 92, 65], [27, 18, 28, 18]]
NEW_TOKENS = 8


def _model_and_opt():
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   llama_tiny_config)
    paddle.seed(0)
    # every sharded dim divides by 4 AND by fsdp*tp=16 where composed
    cfg = llama_tiny_config(hidden_size=64, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=4,
                            intermediate_size=176, vocab_size=512)
    model = LlamaForCausalLM(cfg)
    criterion = LlamaPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    return model, criterion, opt, cfg


def _batches(cfg, n=4, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32),
             rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int64))
            for _ in range(n)]


def _per_chip_bytes(step, sd):
    """Per-chip param + optimizer-state bytes (sharded leaves count
    their shard, replicated leaves their full size)."""
    def one(v):
        if not hasattr(v, "nbytes"):
            return 0
        if hasattr(v, "sharding"):
            shard = v.sharding.shard_shape(v.shape)
            return (int(np.prod(shard)) * v.dtype.itemsize
                    if shard else v.dtype.itemsize)
        return int(v.nbytes)

    total = sum(one(t._value) for t in sd.values())
    for st in getattr(step, "_opt_states", {}).values():
        total += sum(one(v) for v in st.values())
    return total


def _train(mesh, criterion_holder):
    """Train one fresh model STEPS steps; return (result_row, model)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.jit.spmd import ShardingConfig

    model, criterion, opt, cfg = _model_and_opt()
    kw = {}
    if mesh is not None:
        kw = dict(mesh=mesh, sharding=ShardingConfig(axis="fsdp"))
    step = TrainStep(model, lambda lg, lb: criterion(lg, lb), opt,
                     clip_norm=1.0, **kw)
    batches = _batches(cfg)
    losses = []
    paddle.seed(1234)
    t0 = time.perf_counter()
    for i in range(STEPS):
        ids, labels = batches[i % len(batches)]
        loss = step(paddle.to_tensor(ids), paddle.to_tensor(labels))
        losses.append(float(np.asarray(loss._value)))
    dt = (time.perf_counter() - t0) / STEPS
    sd = model.state_dict()
    row = {
        "loss": [round(v, 8) for v in losses],
        "compile_count": step.compile_count,
        "param_opt_bytes_per_chip": _per_chip_bytes(step, sd),
        "train_allgather_bytes_per_step":
            int(getattr(step, "_gather_bytes_per_step", 0)),
        "step_ms": round(dt * 1000, 3),
    }
    return row, model


def _serve(model, mesh):
    """Greedy-decode the fixed workload off the (possibly placed) model
    tree; return (tokens, row) with the zero-re-sharding identity count
    and collective accounting."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model.eval()
    eng = ContinuousBatchingEngine(model, max_batch_size=4, num_blocks=64,
                                   block_size=4, mesh=mesh,
                                   prefill_chunk_size=4)
    rids = [eng.add_request(np.asarray(p, np.int64), NEW_TOKENS)
            for p in PROMPTS]
    eng.run_to_completion()
    toks = [eng.result(r) for r in rids]

    identical = total = 0
    if eng.tp is not None:
        placed = eng.tp._placed or {}
        for k, t in model.state_dict().items():
            total += 1
            if placed.get(k) is t._value:
                identical += 1
    row = {
        "tokens": toks,
        "fsdp_degree": eng.fsdp_degree,
        "tp_degree": eng.tp_degree,
        "params_buffer_identical": identical,
        "params_total": total,
        "serving_allgather_bytes_per_dispatch":
            int(getattr(eng, "_fsdp_gather_bytes", 0)),
        "tp_collective_bytes":
            eng.mixed.collective_bytes(eng.token_budgets[0])
            if eng.tp is not None else {},
    }
    model.train()
    return toks, row


def _run_dp4_stage2():
    """The 1D equal-total-degree train leg: dp=4, ZeRO stage 2."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.jit.spmd import ShardingConfig
    from paddle_tpu.distributed.process_mesh import ProcessMesh

    model, criterion, opt, cfg = _model_and_opt()
    mesh = ProcessMesh(shape=[4], dim_names=["dp"])
    step = TrainStep(model, lambda lg, lb: criterion(lg, lb), opt,
                     clip_norm=1.0, mesh=mesh,
                     sharding=ShardingConfig(stage=2))
    batches = _batches(cfg)
    losses = []
    paddle.seed(1234)
    for i in range(STEPS):
        ids, labels = batches[i % len(batches)]
        loss = step(paddle.to_tensor(ids), paddle.to_tensor(labels))
        losses.append(float(np.asarray(loss._value)))
    return [round(v, 8) for v in losses]


def main(out_path):
    import jax
    from paddle_tpu.jit.spmd import mesh_2d
    assert jax.device_count() >= N_DEV

    cells = {}
    tokens = {}
    gate_notes = []
    for F in GRID:
        for T in GRID:
            mesh = mesh_2d(F, T) if F * T > 1 else None
            trow, model = _train(mesh, None)
            toks, srow = _serve(model, mesh)
            key = f"fsdp{F}_tp{T}"
            cells[key] = {"fsdp": F, "tp": T, "train": trow,
                          "serve": srow}
            tokens[key] = toks
            print(f"# {key}: loss[-1]={trow['loss'][-1]:.5f} "
                  f"bytes/chip={trow['param_opt_bytes_per_chip']} "
                  f"identity={srow['params_buffer_identical']}"
                  f"/{srow['params_total']}", file=sys.stderr)

    base = cells["fsdp1_tp1"]
    base_bytes = base["train"]["param_opt_bytes_per_chip"]

    # gates ------------------------------------------------------------
    ok = True
    max_loss_diff = 0.0
    for key, cell in cells.items():
        diff = max(abs(a - b) for a, b in
                   zip(cell["train"]["loss"], base["train"]["loss"]))
        cell["train"]["max_loss_diff_vs_1x1"] = diff
        max_loss_diff = max(max_loss_diff, diff)
        if diff > TOL:
            ok = False
            gate_notes.append(f"{key}: loss diverged ({diff:.2e})")
        if tokens[key] != tokens["fsdp1_tp1"]:
            ok = False
            gate_notes.append(f"{key}: served tokens diverged")
        if cell["train"]["compile_count"] != 1:
            ok = False
            gate_notes.append(
                f"{key}: {cell['train']['compile_count']} compiles")
        s = cell["serve"]
        if s["params_total"] and \
                s["params_buffer_identical"] != s["params_total"]:
            ok = False
            gate_notes.append(
                f"{key}: only {s['params_buffer_identical']}/"
                f"{s['params_total']} params adopted by identity")
        cell["bytes_ratio_vs_1x1"] = round(
            cell["train"]["param_opt_bytes_per_chip"] / base_bytes, 4)

    # equal-total-degree legs: fsdp2xtp2 vs the 1D dp4 stage-2 train
    dp4_loss = _run_dp4_stage2()
    dp4_diff = max(abs(a - b) for a, b in
                   zip(cells["fsdp2_tp2"]["train"]["loss"], dp4_loss))
    if dp4_diff > TOL:
        ok = False
        gate_notes.append(f"fsdp2_tp2 vs dp4 stage2: {dp4_diff:.2e}")
    tp4_match = tokens["fsdp2_tp2"] == tokens["fsdp1_tp4"]
    if not tp4_match:
        ok = False
        gate_notes.append("fsdp2_tp2 vs tp4 serve tokens diverged")

    # per-chip bytes must actually shrink ~1/(fsdp*tp): the composed
    # specs leave small norm/bias vectors replicated, so allow slack
    r44 = cells["fsdp4_tp4"]["bytes_ratio_vs_1x1"]
    if not r44 <= 1.5 / 16:
        ok = False
        gate_notes.append(f"(4,4) bytes ratio {r44} > 1.5/16")

    artifact = {
        "metric": "spmd2d_per_chip_param_opt_bytes_ratio_f4t4",
        "value": r44,
        "unit": "sharded/replicated",
        "passed": bool(ok),
        "gate_notes": gate_notes,
        "n_devices": N_DEV,
        "grid": [[F, T] for F in GRID for T in GRID],
        "model": "llama_tiny(h=64,L=2,V=512)",
        "optimizer": "AdamW",
        "steps": STEPS,
        "batch": BATCH, "seq": SEQ,
        "parity": {"max_loss_diff_vs_1x1": max_loss_diff,
                   "fsdp2_tp2_vs_dp4_stage2": dp4_diff,
                   "fsdp2_tp2_vs_tp4_serve_tokens": bool(tp4_match),
                   "tol": TOL},
        "cells": cells,
        "provenance": "r20=1D (dp-only train / tp-only serve; "
                      "BENCH_SHARD_r07.json, BENCH_SERVE_r12.json); "
                      "r21=2D fsdp x tp everywhere (this file)",
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": artifact["unit"],
        "vs_baseline": round(1.0 / max(r44, 1e-9), 2),
    }), flush=True)
    print(f"# grid cells={len(cells)} max_loss_diff={max_loss_diff:.2e} "
          f"dp4_diff={dp4_diff:.2e} bytes(4,4)={r44} passed={ok}",
          file=sys.stderr)

    from tools.bench_index import main as bench_index_main
    bench_index_main()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_SPMD_r21.json")
    try:
        main(out)
    except SystemExit:
        raise
    except Exception as e:                            # noqa: BLE001
        print(json.dumps({
            "metric": "spmd2d_per_chip_param_opt_bytes_ratio_f4t4",
            "value": 1.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "error": repr(e)[:300],
        }), flush=True)
        sys.exit(1)
