"""Serving bench: modes over the continuous-batching engine (one
serving path, the fused mixed step).  CPU dry-run artifacts: parity,
counts and bytes, not speeds (ROADMAP D6 decides what stays).

Pick one mode (the modes do not combine):

- ``--tp [N]``: tensor-parallel multichip serving — the fused mixed step
  shard_map'd over a ``tp`` mesh axis (shared SPMD module jit/spmd.py)
  -> BENCH_SERVE_r12.json with a tokens/s scaling curve over tp in
  {1, 2, 4} (capped at N).  Gates: every tp degree's tokens
  BYTE-IDENTICAL to the single-chip (tp=1) engine on the same workload,
  per-chip KV-pool bytes == 1/tp of the tp=1 pool (head-sharded pages),
  and compiles <= the token-budget-set size.  On the CPU dryrun (forced
  8 virtual devices via paddle_tpu.testing.dryrun) the gate is parity +
  capacity, NOT raw speed — virtual "chips" share the same cores, so
  the curve is recorded for shape only.
- ``--quant`` -> BENCH_QUANT_r13.json, ``--speculative`` ->
  BENCH_SPEC_r14.json, ``--kernel`` -> BENCH_KERNEL_r17.json,
  ``--disagg`` -> BENCH_DISAGG_r19.json, ``--cp [N]`` ->
  BENCH_CP_r22.json, ``--moe [N]`` -> BENCH_MOE_r24.json: see each
  ``main_*`` below.

Every number is parity-gated first: engine tokens must be byte-identical
to the model's eager ``generate`` before anything is trusted
("passed").  On any error ONE parseable failure-marker JSON line is
emitted and the run exits 1.

Model: the 1.1B-param bench config (bench.py's second line) on TPU; the
tiny llama config on CPU so the artifact schema is CI-checkable.

Measurement: every engine step ends with a host fetch of the [slots]
int32 next-token array — that fetch is the synchronization barrier
(see bench.py header), and it is also genuine
per-token serving behavior (the scheduler needs the ids), so wall-clock
per step IS the served step time.  Run from the repo root.
"""
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import LlamaConfig  # noqa: E402
from paddle_tpu.models.llama import (LlamaForCausalLM,  # noqa: E402
                                     llama_tiny_config, param_count)
from paddle_tpu.inference.serving import (  # noqa: E402
    ContinuousBatchingEngine)


def build_model(on_tpu):
    if on_tpu:
        # the 1.1B line from bench.py (head_dim 128, bf16)
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


def _ref(model, prompt, budget):
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=budget)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def bench_mixed_decode(model, slots, occupancy, prompt_len, warm, steps,
                       num_blocks, block_size, chunk, mesh=None,
                       request_kw=None, **engine_kw):
    """Decode tokens/s at a given occupancy through the fused
    MixedStep; ``mesh`` shards it over the tp axis (the --tp curve);
    ``engine_kw`` passes quantization/sampling flags through,
    ``request_kw`` per-request sampling knobs (the --speculative
    sampled-throughput guard)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    vocab = model.config.vocab_size
    rng = np.random.RandomState(0)
    budget = warm + steps + 8
    eng = ContinuousBatchingEngine(model, max_batch_size=slots,
                                   num_blocks=num_blocks,
                                   block_size=block_size,
                                   prefill_chunk_size=chunk,
                                   # size the block table to the
                                   # workload: the compiled attention
                                   # gathers the full table width, so
                                   # dead width is dead work for BOTH
                                   # engines being compared
                                   max_seq_len=prompt_len + budget
                                   + block_size,
                                   mesh=mesh, **engine_kw)
    for _ in range(occupancy):
        eng.add_request(rng.randint(1, vocab, (prompt_len,))
                        .astype(np.int64), max_new_tokens=budget,
                        **(request_kw or {}))
    # drain every prefill chunk first (prompts longer than the chunk
    # size take several packed steps; the first step also runs
    # admission, so the prefilling states are visible), then the decode
    # warm window — so the measured steps are pure decode packs with
    # the all-decode budget's compile already landed
    eng.step()
    while any(r is not None and r.state == "prefilling"
              for r in eng.slots):
        eng.step()
    for _ in range(warm + 2):           # budget compiles land
        eng.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    dt = time.perf_counter() - t0
    assert eng.mixed.total_compiles <= len(eng.token_budgets), (
        "mixed step compiled past the budget-set bound mid-bench")
    return {
        "occupancy": occupancy,
        "decode_tokens_per_sec": round(occupancy * steps / dt, 1),
        "decode_step_ms": round(dt / steps * 1000, 3),
    }


SPEC_THRESHOLDS = {
    # temperature-only sampled decode tokens/s vs the r13 fp32 greedy
    # decode reference (BENCH_QUANT_r13.json): the sampling epilogue
    # skips the top-k/top-p sort pass at run time when nobody filters,
    # so it must stay close to the greedy step
    "sampled_tps_vs_r13": 0.70,
    # full top-k+top-p sampling pays a per-row sort of the vocab — an
    # overhead guard, not a perf claim (the sort is ~40% of a
    # dispatch-bound tiny-model step on CPU; negligible vs a real
    # model's layer stack)
    "filtered_tps_vs_r13": 0.30,
    # acceptance floor the TPOT gate is conditioned on: the bench pair
    # (layer-truncated self-draft against a tail-damped target — the
    # training-free stand-in for a distilled pair) must actually
    # accept, or the TPOT numbers are meaningless
    "acceptance_floor": 0.5,
    # live CPU wall-clock spec/non-spec TPOT overhead guard (see note
    # in main_spec: CPU XLA cost scales ~linearly with pack tokens, so
    # live CPU speculative decode CANNOT win wall-clock — the win gate
    # is the memory-bound model below; this guard just catches
    # pathological regressions in the round machinery)
    "cpu_live_overhead_ratio": 2.5,
}


def build_spec_pair(on_tpu):
    """Target + draft for the speculative sweep.

    TPU: the 1.1B bench target with a 5-of-20-layer truncated
    self-draft (genuine early-exit drafting; acceptance is whatever
    the model gives).  CPU dryrun: a 3-layer tiny target whose tail
    layers' output projections are damped 0.1x, drafted by its
    1-layer truncation — the TRAINING-FREE stand-in for a distilled
    draft/target pair.  Random-init models have near-tied logits, so
    an undamped truncation's argmax agreement collapses to ~0.1-0.2
    (measured; reported in the artifact as acceptance_undamped) —
    damping restores the high-agreement regime a trained pair lives
    in.  Acceptance is MEASURED either way, never assumed."""
    from paddle_tpu.models.llama import llama_truncated_draft
    if on_tpu:
        cfg, model = build_model(True)
        return cfg, model, llama_truncated_draft(model, 5)
    cfg = llama_tiny_config(num_hidden_layers=3, hidden_size=64,
                            intermediate_size=192,
                            num_attention_heads=4,
                            num_key_value_heads=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    for layer in list(model.llama.layers)[1:]:
        for lin in (layer.self_attn.o_proj, layer.mlp.down_proj):
            lin.weight._value = lin.weight._value * 0.1
    return cfg, model, llama_truncated_draft(model, 1)


def _drain_prefill(eng):
    eng.step()
    while any(r is not None and r.state == "prefilling"
              for r in eng.slots):
        eng.step()


def _spec_window(eng, rounds):
    """Run ``rounds`` engine rounds with per-launch timers wrapped
    around the draft and verify dispatches; returns live TPOT-style
    stats + median launch costs."""
    times = {"draft": [], "verify": []}
    targets = [(eng.mixed, "verify")]
    if eng.draft_step is not None:
        targets.append((eng.draft_step, "draft"))
    orig = {}
    for mx, name in targets:
        orig[name] = mx.call_packed

        def timed(pack, T, _orig=orig[name], _n=name, **kw):
            t0 = time.perf_counter()
            out = _orig(pack, T, **kw)
            times[_n].append(time.perf_counter() - t0)
            return out

        mx.call_packed = timed
    try:
        occ = sum(r is not None for r in eng.slots)
        tok0 = sum(len(r.output_ids) for r in eng.slots if r is not None)
        p0 = eng._m_spec_proposed.value
        a0 = eng._m_spec_accepted.value
        t0 = time.perf_counter()
        for _ in range(rounds):
            eng.step()
        dt = time.perf_counter() - t0
        tok1 = sum(len(r.output_ids) for r in eng.slots if r is not None)
    finally:
        for mx, name in targets:
            mx.call_packed = orig[name]
    emitted = tok1 - tok0
    proposed = eng._m_spec_proposed.value - p0
    accepted = eng._m_spec_accepted.value - a0
    med = lambda xs: statistics.median(xs) if xs else 0.0   # noqa: E731
    return {
        "rounds": rounds,
        "emitted_tokens": emitted,
        "tokens_per_round_per_slot": round(
            emitted / max(rounds * occ, 1), 4),
        "tpot_live_ms": round(dt * occ / max(emitted, 1) * 1e3, 4),
        "acceptance_rate": round(accepted / proposed, 4)
        if proposed else None,
        "proposed": int(proposed),
        "accepted": int(accepted),
        "draft_launch_ms": round(med(times["draft"]) * 1e3, 4),
        "verify_launch_ms": round(med(times["verify"]) * 1e3, 4),
    }


def _spec_engine(model, draft, k, wl, sampling=False, **kw):
    eng = ContinuousBatchingEngine(
        model, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
        block_size=wl["block_size"], max_seq_len=wl["max_seq_len"],
        prefill_chunk_size=wl["chunk"],
        draft_model=draft, spec_k=k, sampling=sampling, **kw)
    return eng


def main_spec(out_path):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, model, draft = build_spec_pair(on_tpu)
    vocab = cfg.vocab_size
    rng = np.random.RandomState(0)
    if on_tpu:
        wl = dict(slots=8, block_size=16, num_blocks=1024,
                  chunk=256, prompt_len=128, budget=400,
                  warm=4, rounds=32)
    else:
        wl = dict(slots=4, block_size=16, num_blocks=256,
                  chunk=16, prompt_len=12, budget=400,
                  warm=4, rounds=30)
    wl["max_seq_len"] = wl["prompt_len"] + wl["budget"] + 64
    prompts = [rng.randint(1, vocab, (wl["prompt_len"],))
               .astype(np.int64) for _ in range(wl["slots"])]

    # ---- greedy-parity gate: speculative greedy tokens must be
    # byte-identical to eager generate (staggered admission) ----------
    gate_prompts = [rng.randint(1, vocab, (n,)).astype(np.int64)
                    for n in (5, 3, 8)]
    gate_budgets = [6, 8, 5]
    want = [_ref(model, p, n) for p, n in zip(gate_prompts, gate_budgets)]
    eng = _spec_engine(model, draft, 2, wl)
    g0 = eng.add_request(gate_prompts[0], gate_budgets[0])
    eng.step()
    g1 = eng.add_request(gate_prompts[1], gate_budgets[1])
    g2 = eng.add_request(gate_prompts[2], gate_budgets[2])
    eng.run_to_completion()
    greedy_parity = (eng.result(g0) == want[0]
                     and eng.result(g1) == want[1]
                     and eng.result(g2) == want[2])
    leak_free = len(eng.caches[0]._free) == wl["num_blocks"]

    def warmed(k=None, sampling=False, samp_kw=None):
        e = _spec_engine(model, draft, k, wl, sampling=sampling) \
            if k else ContinuousBatchingEngine(
                model, max_batch_size=wl["slots"],
                num_blocks=wl["num_blocks"],
                block_size=wl["block_size"],
                max_seq_len=wl["max_seq_len"],
                prefill_chunk_size=wl["chunk"], sampling=sampling)
        for p in prompts:
            e.add_request(p, wl["budget"], **(samp_kw or {}))
        _drain_prefill(e)
        for _ in range(wl["warm"]):
            e.step()
        return e

    # ---- non-speculative baseline ------------------------------------
    base_eng = warmed()
    base = _spec_window(base_eng, wl["rounds"])
    c_t = base["verify_launch_ms"]          # the 1-token decode launch

    # ---- acceptance + TPOT sweep over k ------------------------------
    k_rows = []
    for k in (1, 2, 3):
        e = warmed(k=k)
        row = _spec_window(e, wl["rounds"])
        row["k"] = k
        # the memory-bound model (how a TPU prices the round): k draft
        # launches + ONE target launch whose k+1 verify tokens are
        # ~free (decode is HBM-bandwidth-bound; the weights-read
        # dominates), normalized by the measured tokens per round —
        # the standard speculative-decoding accounting evaluated AT
        # THE MEASURED acceptance rate and MEASURED launch costs
        # per-request accounting: one round costs k draft launches +
        # one target launch (shared by every slot) and hands each slot
        # ``tokens_per_round_per_slot`` tokens; the modeled baseline
        # is the measured decode launch itself (1 token/slot/round)
        tokens = max(row["tokens_per_round_per_slot"], 1e-9)
        row["tpot_modeled_memory_bound_ms"] = round(
            (k * row["draft_launch_ms"] + c_t) / tokens, 4)
        row["tpot_modeled_ratio"] = round(
            row["tpot_modeled_memory_bound_ms"] / max(c_t, 1e-9), 4)
        assert e.mixed.total_compiles <= len(e.token_budgets)
        assert e.draft_step.total_compiles <= len(e.draft_budgets)
        row["compiles"] = {
            "mixed": e.mixed.total_compiles,
            "mixed_bound": len(e.token_budgets),
            "draft": e.draft_step.total_compiles,
            "draft_bound": len(e.draft_budgets),
        }
        k_rows.append(row)
        print("# spec k=%d: acceptance %s, %.2f tok/round/slot, live "
              "TPOT %.3fms (base %.3f), modeled-mem-bound ratio %s"
              % (k, row["acceptance_rate"],
                 row["tokens_per_round_per_slot"], row["tpot_live_ms"],
                 base["tpot_live_ms"], row["tpot_modeled_ratio"]),
              file=sys.stderr)

    # undamped-truncation acceptance (the honest low number, CPU only)
    acc_undamped = None
    if not on_tpu:
        from paddle_tpu.models.llama import llama_truncated_draft
        paddle.seed(0)
        raw = LlamaForCausalLM(cfg)
        raw.eval()
        e = ContinuousBatchingEngine(
            raw, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
            block_size=wl["block_size"], max_seq_len=wl["max_seq_len"],
            prefill_chunk_size=wl["chunk"],
            draft_model=llama_truncated_draft(raw, 1), spec_k=2)
        for p in prompts:
            e.add_request(p, wl["budget"])
        _drain_prefill(e)
        for _ in range(wl["warm"]):
            e.step()
        acc_undamped = _spec_window(e, wl["rounds"])["acceptance_rate"]

    # ---- sampled throughput vs the r13 greedy decode reference -------
    # measured on the SAME model + decode config the r13 artifact used
    # (its sections.decode.fp32 row), so the comparison is
    # apples-to-apples: the only delta is the sampling epilogue
    r13_cfg, r13_model = build_model(on_tpu)
    if on_tpu:
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   steps=32, num_blocks=8 * (-(-(128 + 64) // 16) + 2),
                   block_size=16)
        dchunk = 256
    else:
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   steps=32, num_blocks=64, block_size=4)
        dchunk = 16

    def _best(fn, *a, **k):
        return max((fn(*a, **k) for _ in range(3)),
                   key=lambda r: r["decode_tokens_per_sec"])

    dargs = (r13_model, dec["slots"], dec["occupancy"],
             dec["prompt_len"], dec["warm"], dec["steps"],
             dec["num_blocks"], dec["block_size"], dchunk)
    greedy_dec = _best(bench_mixed_decode, *dargs)
    samp_dec = _best(bench_mixed_decode, *dargs, sampling=True,
                     request_kw=dict(temperature=0.8, seed=7))
    filt_dec = _best(bench_mixed_decode, *dargs, sampling=True,
                     request_kw=dict(temperature=0.8,
                                     top_k=r13_cfg.vocab_size // 8,
                                     top_p=0.9, seed=7))

    # knob/seed churn must never retrace: replay the SAME shapes with
    # different sampling parameters on one engine and demand zero new
    # compiles after the first pass
    churn_eng = ContinuousBatchingEngine(
        r13_model, max_batch_size=2, num_blocks=32,
        block_size=dec["block_size"], prefill_chunk_size=dchunk, sampling=True)
    churn_knobs = [dict(temperature=1.0, seed=1),
                   dict(temperature=2.5, top_k=3, seed=9),
                   dict(temperature=0.4, top_p=0.5, seed=77),
                   dict()]
    churn_compiles = []
    for kw in churn_knobs:
        churn_eng.add_request(gate_prompts[0], 6, **kw)
        churn_eng.run_to_completion()
        churn_compiles.append(churn_eng.mixed.total_compiles)
    knob_churn_retraced = any(c != churn_compiles[0]
                              for c in churn_compiles[1:])

    r13_decode = None
    try:
        with open("BENCH_QUANT_r13.json") as f:
            r13_decode = json.load(f)["sections"]["decode"]["fp32"][
                "decode_tokens_per_sec"]
    except Exception:
        pass
    ref_tps = r13_decode if r13_decode is not None \
        else greedy_dec["decode_tokens_per_sec"]

    best = min(k_rows, key=lambda r: r["tpot_modeled_ratio"])
    best_live = min(k_rows, key=lambda r: r["tpot_live_ms"])
    gates = {
        "greedy_spec_parity": bool(greedy_parity),
        "leak_free": bool(leak_free),
        "acceptance_floor": bool(
            max(r["acceptance_rate"] or 0 for r in k_rows)
            >= SPEC_THRESHOLDS["acceptance_floor"]),
        # THE speculative claim, at the measured acceptance rate: on
        # TPU live wall-clock, on the CPU dryrun the memory-bound
        # model with measured launch costs (live CPU wall-clock cannot
        # win — XLA-CPU cost scales ~linearly with pack tokens, so a
        # k+1-token verify pays ~(k+1)x; recorded, not gated)
        "spec_tpot_improves": bool(
            best_live["tpot_live_ms"] < base["tpot_live_ms"]) if on_tpu
        else bool(best["tpot_modeled_ratio"] < 1.0),
        "cpu_live_overhead": bool(
            best_live["tpot_live_ms"] <= SPEC_THRESHOLDS[
                "cpu_live_overhead_ratio"] * base["tpot_live_ms"]),
        "sampled_throughput": bool(
            samp_dec["decode_tokens_per_sec"]
            >= SPEC_THRESHOLDS["sampled_tps_vs_r13"] * ref_tps),
        "filtered_throughput": bool(
            filt_dec["decode_tokens_per_sec"]
            >= SPEC_THRESHOLDS["filtered_tps_vs_r13"] * ref_tps),
        "sampling_never_retraces": not knob_churn_retraced,
        "compile_bounds": all(
            r["compiles"]["mixed"] <= r["compiles"]["mixed_bound"]
            and r["compiles"]["draft"] <= r["compiles"]["draft_bound"]
            for r in k_rows),
    }
    ok = all(gates.values())
    artifact = {
        "metric": "serving_spec_accepted_tokens_per_round_per_slot",
        "value": best["tokens_per_round_per_slot"],
        "passed": ok,
        "gates": gates,
        "thresholds": SPEC_THRESHOLDS,
        "provenance": "r13 = greedy fp32 decode "
                      "(BENCH_QUANT_r13.json sections.decode.fp32); "
                      "r14 = sampled + speculative (this artifact); "
                      "acceptance rate = accepted / proposed draft "
                      "tokens over the measured window",
        "baseline_nonspec": base,
        "k_sweep": k_rows,
        "best_k": best["k"],
        "acceptance_undamped_truncation": acc_undamped,
        "sampled": {
            "greedy_live": greedy_dec,
            "r13_reference_tokens_per_sec": r13_decode,
            "temperature_only": samp_dec,
            "top_k_top_p": filt_dec,
            "ratio_temperature_only_vs_ref": round(
                samp_dec["decode_tokens_per_sec"]
                / max(ref_tps, 1e-9), 3),
            "ratio_filtered_vs_ref": round(
                filt_dec["decode_tokens_per_sec"]
                / max(ref_tps, 1e-9), 3),
        },
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "draft_layers": draft.config.num_hidden_layers,
            "target_layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "slots": wl["slots"],
            "block_size": wl["block_size"],
            "num_blocks": wl["num_blocks"],
            "chunk": wl["chunk"],
            "prompt_len": wl["prompt_len"],
            "dtype": cfg.dtype,
            "tail_damping": None if on_tpu else 0.1,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: the TPOT win gate uses the memory-bound "
                 "launch-cost model at the MEASURED acceptance rate "
                 "(XLA-CPU compute scales with pack tokens, so live "
                 "CPU speculative wall-clock regresses by design — "
                 "recorded under tpot_live_ms and bounded by the "
                 "overhead guard)" if not on_tpu
                 else "TPU: the TPOT gate is live wall-clock"),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print("# spec: best k=%d acceptance %s modeled ratio %s live "
          "%.3f/%.3fms; sampled %s/%s tok/s (ref %s); gates=%s"
          % (best["k"], best["acceptance_rate"],
             best["tpot_modeled_ratio"], best_live["tpot_live_ms"],
             base["tpot_live_ms"],
             samp_dec["decode_tokens_per_sec"],
             filt_dec["decode_tokens_per_sec"], ref_tps,
             gates), file=sys.stderr)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "tokens/round/slot",
        "vs_baseline": round(best["tokens_per_round_per_slot"], 2)
        if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


QUANT_THRESHOLDS = {
    # declared greedy token-match-rate gates vs the fp32 engine, per
    # quant config (the tolerance-gate contract: quantization is
    # allowed to flip a token only this often across the gated
    # decode-only / mixed / chunked / prefix-hit workloads)
    "kv8": 0.90,
    "w8": 0.90,
    "kv8_w8": 0.85,
    "tp2_q8_collectives": 0.90,
    # decode throughput guard (int8-KV engine / fp32 engine).  TPU:
    # 0.9 — the Pallas kernel dequantizes in-register off 1/4 the HBM
    # traffic, so int8 should never cost 10%.  CPU dryrun: 0.85 — the
    # XLA reference path pays XLA-CPU's slow int8->f32 converts on the
    # gathered pages (~12% of a dispatch-bound tiny-model step), an
    # artifact with no TPU counterpart; the guard still catches real
    # regressions (an accidental extra pool pass shows up as >15%).
    "decode_ratio_tpu": 0.90,
    "decode_ratio_cpu_dryrun": 0.85,
}


def _quant_workloads(cfg, wl):
    """The four gated workloads (token lists compared positionally)."""
    vocab = cfg.vocab_size
    rng = np.random.RandomState(7)
    dec_prompts = [rng.randint(1, vocab, (n,)).astype(np.int64)
                   for n in (5, 3, 8)]
    rng = np.random.RandomState(11)
    mixed = [rng.randint(1, vocab, (n,)).astype(np.int64)
             for n in wl["mixed_lengths"]]
    long_p = rng.randint(1, vocab, (wl["long_len"],)).astype(np.int64)
    P = rng.randint(1, vocab, (wl["prefix_len"],)).astype(np.int64)
    hit_p = np.concatenate(
        [P, rng.randint(1, vocab, (wl["suffix_len"],)).astype(np.int64)])
    return {
        "decode_only": (dec_prompts, [6, 8, 5]),
        "mixed": (mixed, [wl["budget"]] * len(mixed)),
        "chunked": ([long_p], [wl["budget"]]),
        # two requests: the first publishes the prefix pages, the
        # second admits against a warm table (hit + copy-on-write)
        "prefix_hit": ([np.concatenate([P, long_p[:wl["suffix_len"]]]),
                        hit_p], [wl["budget"]] * 2),
    }


def _run_quant_workload(model, wl, prompts, budgets, sequential,
                        mesh=None, **quant_kw):
    """One fresh mixed-step engine over one workload; returns the
    per-request token lists (and the engine, for accounting)."""
    eng = ContinuousBatchingEngine(
        model, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
        block_size=wl["block_size"],
        prefill_chunk_size=wl["chunk"], enable_prefix_cache=True,
        mesh=mesh, **quant_kw)
    rids = []
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        rids.append(eng.add_request(p, b))
        if sequential:
            eng.run_to_completion()   # prefix publisher finishes first
        elif i % 2 == 0:
            eng.step()                # staggered admission churn
    eng.run_to_completion()
    return [eng.result(r) for r in rids], eng


def _match_stats(ref, got):
    tot = sum(len(a) for a in ref)
    hit = sum(x == y for a, b in zip(ref, got) for x, y in zip(a, b))
    return hit / max(1, tot), tot - hit


def _max_logit_error(model, qtree, n_tokens=16):
    """Dense-forward probe: max |logits_fp - logits_dequant(int8 PTQ)|
    on one fixed random batch (weight-quant error in isolation)."""
    import jax.numpy as jnp
    from paddle_tpu.autograd.tape import no_grad
    from paddle_tpu.quantization.functional import dequantize_param_tree
    cfg = model.config
    rng = np.random.RandomState(23)
    ids = paddle.to_tensor(
        rng.randint(1, cfg.vocab_size, (1, n_tokens)).astype(np.int64))
    caches = [(None, None)] * cfg.num_hidden_layers
    with no_grad():
        ref, _ = model.forward(ids, caches=caches)
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        with model.bind_state(dequantize_param_tree(qtree, dt)):
            got, _ = model.forward(ids, caches=caches)
    return float(np.max(np.abs(np.asarray(ref._value, np.float32)
                               - np.asarray(got._value, np.float32))))


def main_quant(out_path):
    from paddle_tpu.testing.dryrun import force_cpu_devices
    on_tpu = _tpu_available()
    if not on_tpu:
        force_cpu_devices(8)       # the tp=2 section needs virtual chips
    dev = jax.devices()[0]
    cfg, model = build_model_tp(on_tpu)

    if on_tpu:
        wl = dict(slots=8, block_size=16, num_blocks=1024,
                  mixed_lengths=[20, 45, 70, 100, 130, 190, 250, 300],
                  long_len=600, prefix_len=192, suffix_len=32, budget=8,
                  chunk=256)
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   steps=32, num_blocks=8 * (-(-(128 + 64) // 16) + 2),
                   block_size=16)
    else:
        wl = dict(slots=4, block_size=4, num_blocks=192,
                  mixed_lengths=[3, 5, 6, 7, 9, 10, 11, 13],
                  long_len=36, prefix_len=24, suffix_len=4, budget=4,
                  chunk=16)
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   steps=32, num_blocks=64, block_size=4)
    workloads = _quant_workloads(cfg, wl)

    configs = {
        "kv8": dict(kv_dtype="int8"),
        "w8": dict(weight_quant="int8"),
        "kv8_w8": dict(kv_dtype="int8", weight_quant="int8"),
    }
    # fp32 reference tokens per workload (same engine shape, no quant)
    ref_tokens = {}
    pool_bytes_fp = None
    for name, (prompts, budgets) in workloads.items():
        toks, eng = _run_quant_workload(
            model, wl, prompts, budgets, sequential=(name == "prefix_hit"))
        ref_tokens[name] = toks
        pool_bytes_fp = eng.caches[0].per_chip_pool_bytes()

    # the r12 contract: the fp32 default path stays byte-identical to
    # eager generate (provenance: r12 = fp32, r13 = quant)
    fp32_parity = parity_gate_mixed(model, wl)

    sections = {}
    rates_all = {}
    pool_bytes_q = None
    for cname, qkw in configs.items():
        rates = {}
        mismatches = 0
        for name, (prompts, budgets) in workloads.items():
            toks, eng = _run_quant_workload(
                model, wl, prompts, budgets,
                sequential=(name == "prefix_hit"), **qkw)
            rate, miss = _match_stats(ref_tokens[name], toks)
            eng.record_token_mismatches(miss)
            rates[name] = round(rate, 4)
            mismatches += miss
            if cname == "kv8":
                pool_bytes_q = eng.caches[0].per_chip_pool_bytes()
        rates_all[cname] = rates
        sections[cname] = {"token_match_rate": rates,
                           "token_mismatches": mismatches}

    capacity_ratio = pool_bytes_fp / max(pool_bytes_q, 1)
    sections["kv8"]["kv_pool_bytes_fp32"] = pool_bytes_fp
    sections["kv8"]["kv_pool_bytes_int8_with_scales"] = pool_bytes_q
    sections["kv8"]["pages_per_hbm_byte_ratio"] = round(capacity_ratio, 3)
    qtree_probe = None
    from paddle_tpu.quantization.functional import quantize_param_tree
    qtree_probe = quantize_param_tree(
        {k: t._value for k, t in model.state_dict().items()})
    sections["w8"]["max_logit_abs_error"] = round(
        _max_logit_error(model, qtree_probe), 6)
    int8_w_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize
        for v in qtree_probe.values())
    fp_w_bytes = sum(
        int(np.prod(t._value.shape)) * t._value.dtype.itemsize
        for t in model.state_dict().values())
    sections["w8"]["weight_bytes_ratio_vs_fp"] = round(
        int8_w_bytes / fp_w_bytes, 4)

    # decode throughput: the int8-KV engine (the capacity lever) must
    # stay within 0.9x of fp32 on the standard occupancy-matched decode
    # config.  On the CPU dryrun this is an OVERHEAD GUARD — the tiny
    # model is dispatch-bound, so it bounds the quant write/dequant op
    # cost, not real-silicon speed.  Best-of-5: the per-step window is
    # sub-ms and one loaded scheduler quantum would otherwise decide
    # the gate.
    def _best(fn, *a, **k):
        return max((fn(*a, **k) for _ in range(5)),
                   key=lambda r: r["decode_tokens_per_sec"])

    dargs = (model, dec["slots"], dec["occupancy"], dec["prompt_len"],
             dec["warm"], dec["steps"], dec["num_blocks"],
             dec["block_size"], wl["chunk"])
    fp_dec = _best(bench_mixed_decode, *dargs)
    q_dec = _best(bench_mixed_decode, *dargs, kv_dtype="int8")
    qw_dec = _best(bench_mixed_decode, *dargs, kv_dtype="int8",
                   weight_quant="int8")
    fp_tps = max(fp_dec["decode_tokens_per_sec"], 1e-9)
    sections["decode"] = {
        "fp32": fp_dec, "kv8": q_dec, "kv8_w8": qw_dec,
        "ratio_kv8": round(
            q_dec["decode_tokens_per_sec"] / fp_tps, 3),
        "ratio_kv8_w8": round(
            qw_dec["decode_tokens_per_sec"] / fp_tps, 3)}

    # tp=2 + EQuARX-style int8 logits all-gather (quantized collective)
    tp2 = {"skipped": True}
    tp2_rate = 1.0
    if jax.device_count() >= 2 and cfg.num_key_value_heads % 2 == 0:
        from paddle_tpu.jit.spmd import tp_mesh
        prompts, budgets = workloads["decode_only"]
        toks, eng = _run_quant_workload(
            model, wl, prompts, budgets, sequential=False,
            mesh=tp_mesh(2), kv_dtype="int8", quant_collectives=True)
        tp2_rate, miss = _match_stats(ref_tokens["decode_only"], toks)
        eng.record_token_mismatches(miss)
        top = eng.token_budgets[-1]
        exact = eng.mixed._tp.collective_bytes(cfg, top,
                                               eng.max_batch_size)
        quant = eng.mixed.collective_bytes(top)
        tp2 = {
            "skipped": False,
            "token_match_rate_vs_fp32_tp1": round(tp2_rate, 4),
            "all_gather_bytes_exact": exact["all_gather"],
            "all_gather_bytes_quantized": quant["all_gather"],
            "all_gather_shrink": round(
                exact["all_gather"] / max(quant["all_gather"], 1), 2),
        }
    sections["tp2_q8_collectives"] = tp2

    gated = {
        "kv8": rates_all["kv8"],
        "w8": rates_all["w8"],
        "kv8_w8": rates_all["kv8_w8"],
    }
    gates = {
        "fp32_default_byte_parity": bool(fp32_parity),
        "capacity_ratio_ge_1p9": bool(capacity_ratio >= 1.9),
        "decode_within_threshold": bool(
            q_dec["decode_tokens_per_sec"]
            >= QUANT_THRESHOLDS[
                "decode_ratio_tpu" if on_tpu
                else "decode_ratio_cpu_dryrun"] * fp_tps),
        "token_match_all_workloads": all(
            r >= QUANT_THRESHOLDS[c]
            for c, rs in gated.items() for r in rs.values()),
        "tp2_quant_collectives": bool(
            tp2.get("skipped")
            or tp2_rate >= QUANT_THRESHOLDS["tp2_q8_collectives"]),
    }
    ok = all(gates.values())
    artifact = {
        "metric": "serving_quant_kv_pages_per_hbm_byte_ratio",
        "value": round(capacity_ratio, 3),
        "passed": ok,
        "gates": gates,
        "thresholds": QUANT_THRESHOLDS,
        "provenance": "r12 = fp32 serving (BENCH_SERVE_r12.json); "
                      "r13 = quantized (this artifact); fp32 default "
                      "path byte-parity re-gated live above",
        "sections": sections,
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "slots": wl["slots"],
            "block_size": wl["block_size"],
            "num_blocks": wl["num_blocks"],
            "chunk": wl["chunk"],
            "dtype": cfg.dtype,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: throughput gate is an overhead guard "
                 "(dispatch-bound); capacity + token-match gates are "
                 "platform-independent" if not on_tpu else
                 "TPU: all gates live"),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print("# quant: capacity %.2fx, decode ratio kv8 %.3f (w8 %.3f), "
          "match rates %s, tp2 %s, gates=%s"
          % (capacity_ratio, sections["decode"]["ratio_kv8"],
             sections["decode"]["ratio_kv8_w8"], rates_all,
             tp2, gates), file=sys.stderr)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "x",
        "vs_baseline": artifact["value"] if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


def build_model_tp(on_tpu):
    """The --tp model: every sharded dim must divide by the top tp
    degree (4) — the TPU 1.1B line already does (16 heads/kv); the CPU
    tiny config lifts kv heads 2 -> 4."""
    if on_tpu:
        return build_model(True)
    cfg = llama_tiny_config(num_key_value_heads=4)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _tp_workload_tokens(model, mesh, wl):
    """One staggered mixed workload (short prompts, a chunked long
    prompt, decode churn) through a fused mixed engine on ``mesh``;
    returns (token lists, engine) — the byte-parity payload."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(
        model, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
        block_size=wl["block_size"], prefill_chunk_size=wl["chunk"], mesh=mesh)
    rids = []
    for i, p in enumerate(wl["prompts"]):
        rids.append(eng.add_request(p, wl["budget"]))
        if i % 2 == 0:
            eng.step()               # stagger admission across steps
    eng.run_to_completion()
    return [eng.result(r) for r in rids], eng


def _tpu_available() -> bool:
    """TPU probe WITHOUT initializing a jax backend: forcing the host
    device count is cheapest before the CPU client first initializes,
    so this does not call jax.devices() to find out where we are.  A
    libtpu with no chip behind it then fails loudly at start-up."""
    import importlib.util
    import os
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return False
    return importlib.util.find_spec("libtpu") is not None


def main_tp(out_path, max_tp):
    from paddle_tpu.testing.dryrun import force_cpu_devices
    on_tpu = _tpu_available()
    if not on_tpu:
        # the ONE shared dryrun setup, BEFORE any jax.devices() call
        force_cpu_devices(max(8, max_tp))
    dev = jax.devices()[0]
    tp_list = [t for t in (1, 2, 4) if t <= min(max_tp,
                                                jax.device_count())]
    cfg, model = build_model_tp(on_tpu)
    vocab = cfg.vocab_size
    rng = np.random.RandomState(11)

    if on_tpu:
        wl = dict(slots=8, block_size=16, num_blocks=1024, budget=8,
                  chunk=256)
        lengths = [20, 45, 130, 300, 600]
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   steps=32, num_blocks=8 * (-(-(128 + 64) // 16) + 2),
                   block_size=16)
    else:
        wl = dict(slots=4, block_size=4, num_blocks=96, budget=4,
                  chunk=8)
        lengths = [3, 5, 9, 12, 20]
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   steps=32, num_blocks=64, block_size=4)
    wl["prompts"] = [rng.randint(1, vocab, (n,)).astype(np.int64)
                     for n in lengths]

    def mesh_for(tp):
        if tp == 1:
            return None
        from paddle_tpu.jit.spmd import tp_mesh
        return tp_mesh(tp)

    curve = []
    ref_tokens = None
    base_bytes = None
    for tp in tp_list:
        mesh = mesh_for(tp)
        tokens, eng = _tp_workload_tokens(model, mesh, wl)
        if ref_tokens is None:
            ref_tokens = tokens
        per_chip = eng.caches[0].per_chip_pool_bytes()
        if base_bytes is None:
            base_bytes = per_chip
        d = bench_mixed_decode(model, dec["slots"], dec["occupancy"],
                               dec["prompt_len"], dec["warm"],
                               dec["steps"], dec["num_blocks"],
                               dec["block_size"], wl["chunk"],
                               mesh=mesh)
        top = eng.token_budgets[-1]
        row = {
            "tp": tp,
            "decode_tokens_per_sec": d["decode_tokens_per_sec"],
            "decode_step_ms": d["decode_step_ms"],
            "parity_vs_tp1": bool(tokens == ref_tokens),
            "kv_pool_bytes_per_chip": per_chip,
            "kv_shard_ratio": round(per_chip / max(base_bytes, 1), 4),
            "mixed_step_compile_count": eng.mixed.total_compiles,
            "compile_bound": len(eng.token_budgets),
            "collective_bytes_per_top_budget_step":
                eng.mixed.collective_bytes(top),
        }
        curve.append(row)
        print("# tp=%d: %.1f decode tok/s, %.3f ms/step, kv/chip %dB "
              "(%.3fx), parity=%s, compiles %d<=%d"
              % (tp, row["decode_tokens_per_sec"],
                 row["decode_step_ms"], per_chip,
                 row["kv_shard_ratio"], row["parity_vs_tp1"],
                 row["mixed_step_compile_count"], row["compile_bound"]),
              file=sys.stderr)

    r11_decode = None
    try:
        with open("BENCH_SERVE_r11.json") as f:
            r11_decode = json.load(f)["mixed"]["decode"][
                "decode_tokens_per_sec"]
    except Exception:
        pass
    gates = {
        "parity": all(r["parity_vs_tp1"] for r in curve),
        # exact byte comparison — the rounded ratio is display-only
        "kv_pool_shard": all(
            r["kv_pool_bytes_per_chip"] * r["tp"]
            == curve[0]["kv_pool_bytes_per_chip"] for r in curve),
        "compile_bound": all(
            r["mixed_step_compile_count"] <= r["compile_bound"]
            for r in curve),
        "covers_tp2": any(r["tp"] >= 2 for r in curve),
    }
    ok = all(gates.values())
    top_row = curve[-1]
    artifact = {
        "metric": "serving_tp_decode_tokens_per_sec",
        "value": top_row["decode_tokens_per_sec"],
        "passed": ok,
        "gates": gates,
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: virtual chips share the same cores, so "
                 "the gate is byte parity + per-chip KV bytes == 1/tp "
                 "+ compile bound; the tokens/s column is recorded for "
                 "curve shape only" if not on_tpu else
                 "TPU: tokens/s is the scaling gate"),
        "scaling_curve": curve,
        "reference_r11": {
            "decode_tokens_per_sec": r11_decode,
            "provenance": "r11 = single-chip fused mixed step; "
                          "r12 = tensor-parallel (this artifact)",
        },
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "slots": wl["slots"],
            "block_size": wl["block_size"],
            "num_blocks": wl["num_blocks"],
            "chunk": wl["chunk"],
            "dtype": cfg.dtype,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "device_count": jax.device_count(),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "tokens/s",
        "vs_baseline": round(
            top_row["decode_tokens_per_sec"]
            / max(curve[0]["decode_tokens_per_sec"], 1e-9), 2)
        if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


def _cp_mesh_for(cp):
    if cp == 1:
        return None
    from paddle_tpu.jit.spmd import cp_mesh
    return cp_mesh(cp)


def _cp_prefix_tokens(model, mesh, wl):
    """The prefix-hit workload: the same long prompt twice through a
    prefix-cached engine — the second request must hit the cache (COW
    on the whole-prompt hit) and still decode byte-identically on
    slot-striped pools."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(
        model, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
        block_size=wl["block_size"],
        prefill_chunk_size=wl["chunk"], enable_prefix_cache=True,
        mesh=mesh)
    p = wl["prompts"][-1]                      # the chunked-length one
    ra = eng.add_request(p, wl["budget"])
    eng.run_to_completion()
    rb = eng.add_request(p, wl["budget"])
    eng.run_to_completion()
    hit = eng.finished[rb].prefix_hit_tokens
    return [eng.result(ra), eng.result(rb)], int(hit)


def _cp_decode_tokens(model, mesh, wl):
    """The decode-only workload: short prompts (each under one chunk,
    admitted together), long budgets — after the first step every step
    is pure ragged decode through the striped pools."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(
        model, max_batch_size=wl["slots"], num_blocks=wl["num_blocks"],
        block_size=wl["block_size"], prefill_chunk_size=wl["chunk"], mesh=mesh)
    rids = [eng.add_request(p[:3], wl["budget"] * 2)
            for p in wl["prompts"][:wl["slots"]]]
    eng.run_to_completion()
    return [eng.result(r) for r in rids]


def main_cp(out_path, max_cp):
    """--cp: context-parallel serving (round 22).  The pool stripes
    every page's SLOT dim across the cp axis, each chip runs the
    partial-softmax ragged kernels over its stripe, and one all-gather
    merges the (o, m, l) triples.  Gates: byte parity on decode-only /
    mixed+chunked / prefix-hit workloads at every cp, per-chip KV bytes
    EXACTLY 1/cp, compile count still bounded by the budget set, and
    the max-context-per-chip table growing with the chip count."""
    from paddle_tpu.testing.dryrun import force_cpu_devices
    on_tpu = _tpu_available()
    if not on_tpu:
        force_cpu_devices(max(8, max_cp))
    dev = jax.devices()[0]
    cp_list = [c for c in (1, 2, 4) if c <= min(max_cp,
                                                jax.device_count())]
    cfg, model = build_model(on_tpu)
    vocab = cfg.vocab_size
    rng = np.random.RandomState(11)

    if on_tpu:
        wl = dict(slots=8, block_size=16, num_blocks=1024, budget=8,
                  chunk=256)
        lengths = [20, 45, 130, 300, 600]
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   steps=32, num_blocks=8 * (-(-(128 + 64) // 16) + 2),
                   block_size=16)
    else:
        wl = dict(slots=4, block_size=4, num_blocks=96, budget=4,
                  chunk=8)
        lengths = [3, 5, 9, 12, 20]
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   steps=32, num_blocks=64, block_size=4)
    wl["prompts"] = [rng.randint(1, vocab, (n,)).astype(np.int64)
                     for n in lengths]

    curve = []
    context_table = []
    refs = None
    base_bytes = None
    for cp in cp_list:
        mesh = _cp_mesh_for(cp)
        mixed_toks, eng = _tp_workload_tokens(model, mesh, wl)
        dec_toks = _cp_decode_tokens(model, mesh, wl)
        pref_toks, hit = _cp_prefix_tokens(model, mesh, wl)
        if refs is None:
            refs = (mixed_toks, dec_toks, pref_toks)
        per_chip = sum(c.per_chip_pool_bytes() for c in eng.caches)
        if base_bytes is None:
            base_bytes = per_chip
        d = bench_mixed_decode(model, dec["slots"], dec["occupancy"],
                               dec["prompt_len"], dec["warm"],
                               dec["steps"], dec["num_blocks"],
                               dec["block_size"], wl["chunk"],
                               mesh=mesh)
        top = eng.token_budgets[-1]
        coll = eng.mixed.collective_bytes(top)
        # measured bytes/token/chip over the whole pool (all layers,
        # sink page included — it is real per-chip HBM)
        n_tok = (wl["num_blocks"] + 1) * wl["block_size"]
        bpt = per_chip / n_tok
        max_ctx = int((16 * 2 ** 30) // bpt)
        context_table.append({
            "chips": cp,
            "per_chip_kv_bytes_per_token": round(bpt, 2),
            "max_context_tokens_at_16gib_pool_per_chip": max_ctx,
        })
        row = {
            "cp": cp,
            "decode_tokens_per_sec": d["decode_tokens_per_sec"],
            "decode_step_ms": d["decode_step_ms"],
            "parity_mixed_vs_cp1": bool(mixed_toks == refs[0]),
            "parity_decode_vs_cp1": bool(dec_toks == refs[1]),
            "parity_prefix_vs_cp1": bool(pref_toks == refs[2]),
            "prefix_hit_tokens": hit,
            "kv_pool_bytes_per_chip": per_chip,
            "kv_stripe_ratio": round(per_chip / max(base_bytes, 1), 4),
            "mixed_step_compile_count": eng.mixed.total_compiles,
            "compile_bound": len(eng.token_budgets),
            "cp_merge_bytes_per_top_budget_step":
                coll.get("cp_merge", 0),
        }
        curve.append(row)
        print("# cp=%d: %.1f decode tok/s, %.3f ms/step, kv/chip %dB "
              "(%.3fx), parity m/d/p=%s/%s/%s, merge %dB/step, "
              "compiles %d<=%d"
              % (cp, row["decode_tokens_per_sec"],
                 row["decode_step_ms"], per_chip,
                 row["kv_stripe_ratio"], row["parity_mixed_vs_cp1"],
                 row["parity_decode_vs_cp1"],
                 row["parity_prefix_vs_cp1"],
                 row["cp_merge_bytes_per_top_budget_step"],
                 row["mixed_step_compile_count"], row["compile_bound"]),
              file=sys.stderr)

    gates = {
        "parity": all(r["parity_mixed_vs_cp1"]
                      and r["parity_decode_vs_cp1"]
                      and r["parity_prefix_vs_cp1"] for r in curve),
        # exact byte comparison — the rounded ratio is display-only
        "kv_pool_stripe": all(
            r["kv_pool_bytes_per_chip"] * r["cp"]
            == curve[0]["kv_pool_bytes_per_chip"] for r in curve),
        "compile_bound": all(
            r["mixed_step_compile_count"] <= r["compile_bound"]
            for r in curve),
        "covers_cp2": any(r["cp"] >= 2 for r in curve),
        "cp_merge_accounted": all(
            r["cp_merge_bytes_per_top_budget_step"] > 0
            for r in curve if r["cp"] > 1),
        "max_context_grows": all(
            context_table[i]["max_context_tokens_at_16gib_pool_per_chip"]
            > context_table[i - 1][
                "max_context_tokens_at_16gib_pool_per_chip"]
            for i in range(1, len(context_table))),
        "prefix_hit": all(r["prefix_hit_tokens"] > 0 for r in curve),
    }
    ok = all(gates.values())
    top_row = curve[-1]
    ctx_ratio = (context_table[-1][
        "max_context_tokens_at_16gib_pool_per_chip"]
        / max(context_table[0][
            "max_context_tokens_at_16gib_pool_per_chip"], 1))
    artifact = {
        "metric": "serving_cp_max_context_scale",
        "value": round(ctx_ratio, 2),
        "passed": ok,
        "gates": gates,
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: virtual chips share the same cores, so "
                 "the gate is byte parity on all three workloads + "
                 "per-chip KV bytes == 1/cp + compile bound; the "
                 "tokens/s column is recorded for curve shape only"
                 if not on_tpu else
                 "TPU: tokens/s and context scale are the gates"),
        "scaling_curve": curve,
        "max_context_vs_chips": context_table,
        "reference_r12": {
            "provenance": "r12/r21 = head-sharded pools (tp, 1/tp "
                          "bytes but capped by kv-head count); r22 = "
                          "slot-striped pools (cp, this artifact): "
                          "max context per chip scales with chips "
                          "past the head cap",
        },
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "slots": wl["slots"],
            "block_size": wl["block_size"],
            "num_blocks": wl["num_blocks"],
            "chunk": wl["chunk"],
            "dtype": cfg.dtype,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "device_count": jax.device_count(),
        "top_decode_tokens_per_sec": top_row["decode_tokens_per_sec"],
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "x_max_context_per_chip",
        "vs_baseline": artifact["value"] if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


# ---------------------------------------------------------------------------
# --moe (round 24): expert-parallel MoE serving
# ---------------------------------------------------------------------------
def build_model_moe(on_tpu):
    """The --moe model: tiny Mixtral (E=4, k=2) on CPU; a
    Mixtral-8-expert line over the 1.1B dense geometry on TPU (every
    sharded dim divides by the top ep degree 4)."""
    from paddle_tpu.models.mixtral import (MixtralConfig,
                                           MixtralForCausalLM,
                                           mixtral_tiny_config)
    if on_tpu:
        cfg = MixtralConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=20, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", num_local_experts=8,
            num_experts_per_tok=2)
    else:
        cfg = mixtral_tiny_config()
    paddle.seed(0)
    model = MixtralForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    model.eval()
    return cfg, model


def _ep_mesh_for(ep):
    if ep == 1:
        return None
    from paddle_tpu.jit.spmd import ep_mesh
    return ep_mesh(ep)


def _moe_expert_bytes_per_chip(model, eng):
    """Per-chip bytes of the three expert-bank families, derived from
    the engine's OWN specs (so a spec regression — an unsharded bank —
    shows up as a broken shrink ratio, not a silently-passing
    accounting)."""
    total = 0
    ep = eng.ep_degree
    specs = eng.tp.specs if eng.tp is not None else {}
    for k, t in model.state_dict().items():
        if not any(k.endswith(f) for f in ("w_gate", "w_up", "w_down")):
            continue
        v = t._value
        nbytes = v.size * v.dtype.itemsize
        spec = specs.get(k)
        sharded = spec is not None and "ep" in tuple(spec)
        total += nbytes // ep if sharded else nbytes
    return int(total)


def _moe_router_drill(moe_model, dense_model, wl):
    """The heterogeneous-pool drill: an ep=2 MoE engine, a single-chip
    MoE engine and a dense llama engine behind one round-15 router; the
    ep engine dies mid-flight and every in-flight request must requeue
    and finish its FULL budget on a survivor (zero drops), with the
    dead pool drained leak-free."""
    from paddle_tpu.inference.router import ServingRouter
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    def eng(model, mesh=None):
        return ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=wl["num_blocks"],
            block_size=wl["block_size"],
            prefill_chunk_size=wl["chunk"], mesh=mesh)

    e_moe_ep = eng(moe_model, _ep_mesh_for(2))
    pool = [e_moe_ep, eng(moe_model), eng(dense_model)]
    router = ServingRouter(pool)
    rng = np.random.RandomState(7)
    vocab = min(moe_model.config.vocab_size,
                dense_model.config.vocab_size)
    prompts = [rng.randint(1, vocab, (n,)).astype(np.int64)
               for n in (5, 7, 4, 6, 3, 8)]
    rids = [router.submit(p, max_new_tokens=wl["budget"])
            for p in prompts]
    for _ in range(2):
        router.step()
    lost = sum(1 for k in router._inflight
               if k[0] == e_moe_ep.engine_id)
    router.mark_unhealthy(e_moe_ep.engine_id)
    out = router.run_to_completion()
    c = e_moe_ep.caches[0]
    return {
        "requests": len(rids),
        "killed_in_flight": int(lost),
        "requeues": int(sum(router.finished[r].requeues for r in rids)),
        "zero_drops": bool(
            sorted(out) == sorted(rids)
            and all(len(out[r]) == wl["budget"] for r in rids)),
        "kill_hit_live_work": bool(lost >= 1),
        "dead_pool_drained": bool(len(c._free) == c.num_blocks),
    }


def main_moe(out_path, max_ep):
    """--moe: expert-parallel MoE serving (round 24).  The ep mesh axis
    shards every Mixtral expert bank's E dim; the fused MixedStep
    gates, all_to_all-dispatches, runs the grouped expert SwiGLU and
    combines inside the ONE compiled launch.  Gates: byte parity vs the
    EAGER Mixtral generate on mixed+chunked and decode-only workloads
    at every ep, per-chip expert-bank bytes EXACTLY 1/ep, compile count
    still bounded by the budget set, the ep collective accounting
    nonzero past ep=1, dropless dispatch (dropped fate stays 0), and
    the heterogeneous dense+MoE router drill with zero drops."""
    from paddle_tpu.testing.dryrun import force_cpu_devices
    on_tpu = _tpu_available()
    if not on_tpu:
        force_cpu_devices(max(8, max_ep))
    dev = jax.devices()[0]
    ep_list = [e for e in (1, 2, 4) if e <= min(max_ep,
                                                jax.device_count())]
    cfg, model = build_model_moe(on_tpu)
    vocab = cfg.vocab_size
    rng = np.random.RandomState(11)

    if on_tpu:
        wl = dict(slots=8, block_size=16, num_blocks=1024, budget=8,
                  chunk=256)
        lengths = [20, 45, 130, 300, 600]
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   steps=32, num_blocks=8 * (-(-(128 + 64) // 16) + 2),
                   block_size=16)
    else:
        wl = dict(slots=4, block_size=4, num_blocks=96, budget=4,
                  chunk=8)
        lengths = [3, 5, 9, 12, 20]
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   steps=32, num_blocks=64, block_size=4)
    wl["prompts"] = [rng.randint(1, vocab, (n,)).astype(np.int64)
                     for n in lengths]

    # the parity reference is the EAGER Mixtral generate (satellite 1
    # woke it for exactly this), not merely the ep=1 engine
    eager_mixed = [_ref(model, p, wl["budget"]) for p in wl["prompts"]]
    eager_dec = [_ref(model, p[:3], wl["budget"] * 2)
                 for p in wl["prompts"][:wl["slots"]]]

    curve = []
    base_expert = None
    for ep in ep_list:
        mesh = _ep_mesh_for(ep)
        mixed_toks, eng = _tp_workload_tokens(model, mesh, wl)
        dec_toks = _cp_decode_tokens(model, mesh, wl)
        expert_bytes = _moe_expert_bytes_per_chip(model, eng)
        if base_expert is None:
            base_expert = expert_bytes
        d = bench_mixed_decode(model, dec["slots"], dec["occupancy"],
                               dec["prompt_len"], dec["warm"],
                               dec["steps"], dec["num_blocks"],
                               dec["block_size"], wl["chunk"],
                               mesh=mesh)
        top = eng.token_budgets[-1]
        coll = eng.mixed.collective_bytes(top)
        row = {
            "ep": ep,
            "decode_tokens_per_sec": d["decode_tokens_per_sec"],
            "decode_step_ms": d["decode_step_ms"],
            "parity_mixed_vs_eager": bool(mixed_toks == eager_mixed),
            "parity_decode_vs_eager": bool(dec_toks == eager_dec),
            "expert_bank_bytes_per_chip": expert_bytes,
            "expert_shard_ratio": round(
                expert_bytes / max(base_expert, 1), 4),
            "mixed_step_compile_count": eng.mixed.total_compiles,
            "compile_bound": len(eng.token_budgets),
            "ep_all_to_all_bytes_per_top_budget_step":
                coll.get("ep_all_to_all", 0),
            "ep_all_gather_bytes_per_top_budget_step":
                coll.get("ep_all_gather", 0),
        }
        curve.append(row)
        print("# ep=%d: %.1f decode tok/s, %.3f ms/step, experts/chip "
              "%dB (%.3fx), parity m/d=%s/%s, a2a %dB/step, "
              "compiles %d<=%d"
              % (ep, row["decode_tokens_per_sec"],
                 row["decode_step_ms"], expert_bytes,
                 row["expert_shard_ratio"],
                 row["parity_mixed_vs_eager"],
                 row["parity_decode_vs_eager"],
                 row["ep_all_to_all_bytes_per_top_budget_step"],
                 row["mixed_step_compile_count"], row["compile_bound"]),
              file=sys.stderr)

    # dropless dispatch: the fate counter published by the engines
    from paddle_tpu.observability import default_registry
    disp = default_registry().get("serving_moe_dispatch_tokens_total")
    routed = disp.labels(fate="routed").value if disp else 0
    dropped = disp.labels(fate="dropped").value if disp else -1

    _, dense_model = build_model(on_tpu)
    drill = _moe_router_drill(model, dense_model, wl)

    gates = {
        "parity": all(r["parity_mixed_vs_eager"]
                      and r["parity_decode_vs_eager"] for r in curve),
        # exact byte comparison — the rounded ratio is display-only
        "expert_bank_shard": all(
            r["expert_bank_bytes_per_chip"] * r["ep"]
            == curve[0]["expert_bank_bytes_per_chip"] for r in curve),
        "compile_bound": all(
            r["mixed_step_compile_count"] <= r["compile_bound"]
            for r in curve),
        "covers_ep2": any(r["ep"] >= 2 for r in curve),
        "ep_collectives_accounted": all(
            r["ep_all_to_all_bytes_per_top_budget_step"] > 0
            and r["ep_all_gather_bytes_per_top_budget_step"] > 0
            for r in curve if r["ep"] > 1),
        "dropless_dispatch": bool(routed > 0 and dropped == 0),
        "router_drill_zero_drops": bool(
            drill["zero_drops"] and drill["kill_hit_live_work"]
            and drill["dead_pool_drained"]),
    }
    ok = all(gates.values())
    top_row = curve[-1]
    shrink = (curve[0]["expert_bank_bytes_per_chip"]
              / max(top_row["expert_bank_bytes_per_chip"], 1))
    artifact = {
        "metric": "serving_moe_expert_hbm_shrink",
        "value": round(shrink, 2),
        "passed": ok,
        "gates": gates,
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: virtual chips share the same cores, so "
                 "the gate is byte parity vs the eager Mixtral on both "
                 "workloads + per-chip expert bytes == 1/ep + compile "
                 "bound + the dropless fate counter + the router "
                 "drill; the tokens/s column is recorded for curve "
                 "shape only" if not on_tpu else
                 "TPU: tokens/s and expert HBM shrink are the gates"),
        "scaling_curve": curve,
        "moe_dispatch_tokens": {"routed": int(routed),
                                "dropped": int(dropped)},
        "router_drill": drill,
        "dispatch_math": {
            "per_layer": "topk_gate -> dropless scatter [E, tl*k, D] "
                         "-> all_to_all(ep) -> grouped SwiGLU on E/ep "
                         "banks -> all_to_all(ep) -> weighted combine "
                         "-> all_gather(tokens)",
            "ep_all_to_all_bytes":
                "2 * L * E * (T/ep * k) * hidden * item * (ep-1)/ep",
            "ep_all_gather_bytes": "L * (ep-1) * T/ep * hidden * item",
        },
        "config": {
            # real count, not the dense analytic formula — the expert
            # banks multiply the FFN params by E
            "params_m": round(sum(
                t._value.size for t in model.state_dict().values())
                / 1e6, 2),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "experts": cfg.num_local_experts,
            "top_k": cfg.num_experts_per_tok,
            "slots": wl["slots"],
            "block_size": wl["block_size"],
            "num_blocks": wl["num_blocks"],
            "chunk": wl["chunk"],
            "dtype": cfg.dtype,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "device_count": jax.device_count(),
        "top_decode_tokens_per_sec": top_row["decode_tokens_per_sec"],
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "x_expert_hbm_per_chip",
        "vs_baseline": artifact["value"] if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


def parity_gate_mixed(model, wl):
    """Decode-only byte parity: the fused mixed engine on a staggered
    3-request decode mix vs eager generate."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    vocab = model.config.vocab_size
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, vocab, (n,)).astype(np.int64)
               for n in (5, 3, 8)]
    budgets = [6, 8, 5]
    want = [_ref(model, p, n) for p, n in zip(prompts, budgets)]
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64,
                                   block_size=wl["block_size"],
                                   prefill_chunk_size=wl["chunk"])
    r0 = eng.add_request(prompts[0], budgets[0])
    eng.step()
    r1 = eng.add_request(prompts[1], budgets[1])
    eng.step()
    r2 = eng.add_request(prompts[2], budgets[2])
    eng.run_to_completion()
    return bool(eng.result(r0) == want[0] and eng.result(r1) == want[1]
                and eng.result(r2) == want[2])


# ---------------------------------------------------------------------------
# --kernel (round 17): compiled cost_analysis of the Pallas kernels,
# old (r16 sync-DMA dequant) vs new (r17 pipelined int8-MXU)
# ---------------------------------------------------------------------------
def _compiled_cost(fn, *args):
    """flops + HBM bytes-accessed of one jitted launch, from XLA's
    ``cost_analysis`` of the COMPILED module — the same source the r09
    telemetry computes MFU from.  On the CPU dryrun the kernels compile
    in interpret mode (the pallas body discharged to XLA ops), so the
    byte accounting covers exactly the DMA copies and page-dequant
    materializations the scheduling/quantization rework removes.
    Traced with x64 off: an OUTER jit around the interpret-mode kernel
    would otherwise stage i64 loop scalars against the kernel's i32
    internals (the repo default keeps x64 on for paddle int64
    semantics; every operand here is f32/i32, so nothing changes)."""
    with jax.enable_x64(False):
        c = jax.jit(fn).lower(*args).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def _kernel_pools(bs, Hkv, D, nb):
    """fp32 + int8 pools holding comparable decode-regime data, the
    int8 pool filled through the real quantize-on-write path with one
    magnitude step so the running-absmax rescale has fired."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (PagedKVCache,
                                                write_ragged_kv,
                                                write_ragged_kv_q8)
    rng = np.random.RandomState(5)
    cf = PagedKVCache(nb, bs, Hkv, D, sink_block=True)
    cq = PagedKVCache(nb, bs, Hkv, D, sink_block=True, kv_dtype="int8")
    for r in range(2):
        n = bs * nb
        k = (rng.randn(n, Hkv, D) * 2.0 ** r).astype(np.float32)
        v = (rng.randn(n, Hkv, D) * 2.0 ** r).astype(np.float32)
        blks = jnp.asarray(np.repeat(np.arange(nb, dtype=np.int32), bs))
        offs = jnp.asarray(np.tile(np.arange(bs, dtype=np.int32), nb))
        cf.key_cache, cf.value_cache = write_ragged_kv(
            jnp.asarray(k), jnp.asarray(v), cf.key_cache,
            cf.value_cache, blks, offs)
        (cq.key_cache, cq.value_cache, cq.key_scale,
         cq.value_scale) = write_ragged_kv_q8(
            jnp.asarray(k), jnp.asarray(v), cq.key_cache,
            cq.value_cache, cq.key_scale, cq.value_scale, blks, offs)
    return cf, cq


def _paired_decode_tps(model, dec, waves=21, steps=6):
    """CPU decode tokens/s, int8-KV vs fp32 mixed engines, with the
    r16 trace-bench protocol: the arms run back-to-back within a wave
    (sharing its machine-load phase) with strict alternation of who
    runs first, ``gc.collect()`` between timed windows (a gen2 pause
    is ~50ms on this heap — far above the signal), and the estimator
    is the TRIMMED MEAN of per-wave PAIRED ratios (top/bottom quarter
    dropped).  The two arms are necessarily separate engines (a pool's
    kv dtype is a construction-time shape), so the per-wave pairing is
    what absorbs machine-load drift; the int8-vs-fp32 signal (~10-15%
    on CPU) sits an order of magnitude above the protocol's ~0.2%
    A/A floor."""
    import gc
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    vocab = model.config.vocab_size
    budget = dec["warm"] + 2 + waves * steps + 8
    engines = {}
    for arm, kw in (("fp32", {}), ("int8", {"kv_dtype": "int8"})):
        rng = np.random.RandomState(0)
        eng = ContinuousBatchingEngine(
            model, max_batch_size=dec["slots"],
            num_blocks=dec["num_blocks"], block_size=dec["block_size"],
            prefill_chunk_size=dec["chunk"],
            max_seq_len=dec["prompt_len"] + budget + dec["block_size"],
            **kw)
        for _ in range(dec["occupancy"]):
            eng.add_request(
                rng.randint(1, vocab, (dec["prompt_len"],))
                .astype(np.int64), max_new_tokens=budget)
        eng.step()
        while any(r is not None and r.state == "prefilling"
                  for r in eng.slots):
            eng.step()
        for _ in range(dec["warm"] + 2):
            eng.step()
        engines[arm] = eng
    times = {"fp32": [], "int8": []}
    for w in range(waves):
        for arm in (("fp32", "int8") if w % 2 == 0
                    else ("int8", "fp32")):
            eng = engines[arm]
            gc.collect()
            t0 = time.perf_counter()
            for _ in range(steps):
                eng.step()
            times[arm].append(time.perf_counter() - t0)
    ratios = sorted(q8 / max(fp, 1e-12)
                    for q8, fp in zip(times["int8"], times["fp32"]))
    trim = len(ratios) // 4
    kept = ratios[trim:len(ratios) - trim] or ratios
    tok = dec["occupancy"] * steps * waves
    return {
        "waves": waves,
        "steps_per_wave": steps,
        "occupancy": dec["occupancy"],
        "decode_tokens_per_sec_fp32": round(
            tok / max(sum(times["fp32"]), 1e-12), 1),
        "decode_tokens_per_sec_int8": round(
            tok / max(sum(times["int8"]), 1e-12), 1),
        "int8_over_fp32_ratio_trimmed_mean": round(
            sum(kept) / len(kept), 4),
        "per_wave_ratios": [round(r, 4) for r in ratios],
        "method": "paired waves, strict first-runner alternation, "
                  "gc.collect() between windows, trimmed mean of "
                  "per-wave paired ratios (r16 protocol)",
    }


def main_kernel(out_path):
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (
        KERNEL_INT8_REL_TOL, dequant_pages, paged_attention,
        ragged_paged_attention)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    interpret = not on_tpu
    cfg, model = build_model(on_tpu)

    # the int8-KV decode regime the round-17 gate names: a pack of
    # length-1 decode spans against part-filled tables
    bs, Hkv, H, D, nb, W, S = 16, 2, 4, 64, 32, 8, 8
    cf, cq = _kernel_pools(bs, Hkv, D, nb)
    rng = np.random.RandomState(1)
    q = rng.randn(S, H, D).astype(np.float32)
    kv_lens = rng.randint(bs, W * bs + 1, (S,)).astype(np.int32)
    bt = np.full((S, W), cq.sink, np.int32)
    for i, kv in enumerate(kv_lens):
        used = -(-int(kv) // bs)
        bt[i, :used] = rng.choice(nb, used, replace=False)
    q_offsets = np.arange(S, dtype=np.int32)
    q_lens = np.ones((S,), np.int32)
    seq_lens = kv_lens - 1        # decode-kernel view: cached tokens

    def decode_fn(cache, pipelined, quant):
        def fn(qv, kc, vc, ks, vs):
            return paged_attention(
                qv, kc, vc, bt, seq_lens, interpret=interpret,
                key_scale=ks if quant else None,
                value_scale=vs if quant else None,
                pipelined=pipelined)
        return fn, (jnp.asarray(q), cache.key_cache, cache.value_cache,
                    cache.key_scale if quant else jnp.zeros(()),
                    cache.value_scale if quant else jnp.zeros(()))

    sections = {"config": {
        "block_size": bs, "kv_heads": Hkv, "q_heads": H, "head_dim": D,
        "num_blocks": nb, "table_width": W, "spans": S,
        "mode": "interpret (CPU dryrun)" if interpret else "mosaic"}}
    outs = {}
    for kname, builder in (("decode", decode_fn),):
        tbl = {}
        for qname, cache, quant in (("fp32", cf, False),
                                    ("int8", cq, True)):
            for sched, pipelined in (("sync_r16", False),
                                     ("pipelined_r17", True)):
                fn, args = builder(cache, pipelined, quant)
                tbl[f"{qname}_{sched}"] = _compiled_cost(fn, *args)
                outs[(kname, qname, sched)] = np.asarray(fn(*args))
        tbl["int8_bytes_shrink"] = round(
            tbl["int8_sync_r16"]["bytes_accessed"]
            / max(tbl["int8_pipelined_r17"]["bytes_accessed"], 1.0), 4)
        tbl["fp32_bytes_shrink"] = round(
            tbl["fp32_sync_r16"]["bytes_accessed"]
            / max(tbl["fp32_pipelined_r17"]["bytes_accessed"], 1.0), 4)
        sections[kname] = tbl

    # parity re-gate on the benched shapes: the fp32 pipelined decode
    # kernel must be byte-identical to sync; the int8 ragged launch
    # (one tiling, no sync twin) within declared tolerance of the
    # dequantizing XLA reference
    vmag = float(np.abs(np.asarray(dequant_pages(
        cq.value_cache, cq.value_scale))).max())
    parity = {"fp32_byte_identical": True, "int8_max_abs_err": 0.0}
    if not np.array_equal(outs[("decode", "fp32", "sync_r16")],
                          outs[("decode", "fp32", "pipelined_r17")]):
        parity["fp32_byte_identical"] = False
    ragged_int8 = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cq.key_cache, cq.value_cache, bt, q_offsets,
        q_lens, kv_lens, interpret=interpret, key_scale=cq.key_scale,
        value_scale=cq.value_scale))
    ref = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cq.key_cache, cq.value_cache, bt, q_offsets,
        q_lens, kv_lens, use_pallas=False, key_scale=cq.key_scale,
        value_scale=cq.value_scale))
    parity["int8_max_abs_err"] = float(np.abs(ragged_int8 - ref).max())
    parity["int8_declared_atol"] = round(KERNEL_INT8_REL_TOL * vmag, 5)
    sections["parity"] = parity

    # CPU decode throughput context, r16 paired-wave protocol
    if on_tpu:
        dec = dict(slots=8, occupancy=8, prompt_len=128, warm=4,
                   num_blocks=8 * (-(-(128 + 300) // 16) + 2),
                   block_size=16, chunk=256)
    else:
        dec = dict(slots=4, occupancy=4, prompt_len=12, warm=2,
                   num_blocks=192, block_size=4, chunk=16)
    sections["decode_tps"] = _paired_decode_tps(model, dec)

    # Gate semantics (documented in BASELINE.md "round 17"): the
    # kernels' true HBM traffic is the page DMAs, and those moved int8
    # bytes in r16 already — double buffering changes WHEN they move,
    # not how many.  The two quantities that genuinely drop and that
    # compiled cost_analysis can see are therefore gated:
    #   (1) the int8-KV decode step accesses strictly fewer HBM bytes
    #       than the SAME kernel on fp32 pools at equal config (the
    #       int8 path's per-step HBM reduction, ~3.3x here), and
    #   (2) the r17 int8 kernel executes strictly fewer flops than the
    #       r16 int8 kernel (the per-page dequant multiplies are gone
    #       — scales fold into the [g, d] accumulated products).
    # The emulated r16-vs-r17 bytes ratio is RECORDED (not gated): in
    # interpret mode the 2-slot buffers are dynamic-update-slices
    # whose full-buffer accounting adds ~3% that real DMA hardware
    # does not pay, while the dequant temporaries the int8 path
    # removes live INSIDE XLA:CPU fusions where cost_analysis cannot
    # count them.
    tbl = sections["decode"]
    shrink = tbl["int8_bytes_vs_fp32"] = round(
        tbl["fp32_pipelined_r17"]["bytes_accessed"]
        / max(tbl["int8_pipelined_r17"]["bytes_accessed"], 1.0), 3)
    gates = {
        "decode_int8_bytes_below_fp32": bool(
            sections["decode"]["int8_pipelined_r17"]["bytes_accessed"]
            < sections["decode"]["fp32_pipelined_r17"]["bytes_accessed"]
        ),
        "decode_int8_flops_below_r16": bool(
            sections["decode"]["int8_pipelined_r17"]["flops"]
            < sections["decode"]["int8_sync_r16"]["flops"]),
        "fp32_byte_parity": bool(parity["fp32_byte_identical"]),
        "int8_within_declared_tolerance": bool(
            parity["int8_max_abs_err"]
            <= parity["int8_declared_atol"]),
    }
    ok = all(gates.values())
    artifact = {
        "metric": "serving_kernel_int8_bytes_accessed_shrink",
        "value": shrink,
        "passed": ok,
        "gates": gates,
        "provenance": "r16 = sync-DMA dequant-page kernels "
                      "(pipelined=False, the BENCH_SERVE_r11/"
                      "BENCH_QUANT_r13 kernels); r17 = double-buffered "
                      "int8-MXU kernels (this artifact); decode tok/s "
                      "context measured with the BENCH_TRACE_r16 "
                      "paired trimmed-mean protocol",
        "sections": sections,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "cpu_dryrun": not on_tpu,
        "note": ("CPU dryrun: cost_analysis of the interpret-mode "
                 "kernels counts the same buffer traffic the mosaic "
                 "kernels move (pages, windows, dequant temporaries); "
                 "wall-clock is engine-level context only, the gate "
                 "is bytes + parity" if not on_tpu else
                 "TPU: all gates live"),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print("# kernel: decode int8-vs-fp32 bytes %.2fx, int8 "
          "flops r17/r16 %.0f/%.0f, emulated r16/r17 bytes ratio "
          "%.3f, ragged int8 err %.4g <= %.4g, tps ratio %s, gates=%s"
          % (shrink,
             sections["decode"]["int8_pipelined_r17"]["flops"],
             sections["decode"]["int8_sync_r16"]["flops"],
             sections["decode"]["int8_bytes_shrink"],
             parity["int8_max_abs_err"], parity["int8_declared_atol"],
             sections["decode_tps"]["int8_over_fp32_ratio_trimmed_mean"],
             gates), file=sys.stderr)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "x",
        "vs_baseline": artifact["value"] if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


# ---------------------------------------------------------------------------
# --disagg: KV page migration + host-RAM prefix tier (round 19)
# ---------------------------------------------------------------------------
def _disagg_engine(model, knobs, **kw):
    kw.setdefault("max_batch_size", knobs["slots"])
    kw.setdefault("num_blocks", knobs["num_blocks"])
    kw.setdefault("block_size", knobs["block_size"])
    kw.setdefault("max_seq_len", knobs["max_seq_len"])
    kw.setdefault("prefill_chunk_size", knobs["chunk"])
    return ContinuousBatchingEngine(model, enable_prefix_cache=True, **kw)


def _warm_resume_engine(model, knobs, resume_len, budget, kv_dtype=None):
    """A target engine with its compiles warm for BOTH resume paths:
    one request shaped like the re-prefill resume (warms every chunk /
    budget compile that prompt length touches) and the decode budget.
    Warm tokens come from a disjoint range so nothing the measured
    resume touches registers as a prefix hit."""
    eng = _disagg_engine(model, knobs, kv_dtype=kv_dtype)
    rng = np.random.RandomState(97)
    vocab = model.config.vocab_size
    warm_prompt = rng.randint(vocab - 17, vocab,
                              (resume_len,)).astype(np.int64)
    eng.add_request(warm_prompt, max_new_tokens=4)
    eng.run_to_completion()
    return eng


def _run_one(model, knobs, prompt, budget, stop_at, kv_dtype=None):
    """Run one request on a fresh source engine until it has generated
    ``stop_at`` tokens; returns the live engine + req id."""
    eng = _disagg_engine(model, knobs, kv_dtype=kv_dtype)
    rid = eng.add_request(prompt, max_new_tokens=budget)
    while True:
        eng.step()
        req = next(r for r in list(eng.slots) + list(eng.waiting)
                   if r is not None and r.req_id == rid)
        if len(req.output_ids) >= stop_at:
            return eng, rid
        assert req.state != "done", "source finished before the preempt"


def _resume_ttft_pair(model, knobs, prompt, budget, stop_at,
                      kv_dtype=None):
    """One paired measurement: the SAME preempted state resumed via
    page migration (extract→inject→decode step) vs via re-prefill
    (r15: resume prompt through add_request).  Both windows cover the
    full resume bill, starting at the preempt and ending when the
    first post-resume token exists.  Targets are pre-warmed; the two
    arms run back-to-back off identical source states (greedy decode
    makes the two source runs byte-identical)."""
    resume_len = len(prompt) + stop_at
    remaining = budget - stop_at

    # --- migrated arm ---------------------------------------------------
    tgt = _warm_resume_engine(model, knobs, resume_len, budget, kv_dtype)
    src, rid = _run_one(model, knobs, prompt, budget, stop_at, kv_dtype)
    t0 = time.perf_counter()
    p, gen, buf = src.extract_request(rid)
    resume = np.concatenate([p, np.asarray(gen, np.int64)])
    rid2 = tgt.inject_request(resume, buf, max_new_tokens=remaining)
    req = next(r for r in tgt.slots if r is not None
               and r.req_id == rid2)
    while not req.output_ids:
        tgt.step()
    t_mig = time.perf_counter() - t0
    tgt.run_to_completion()
    mig_tokens = gen + tgt.finished[rid2].output_ids

    # --- re-prefill arm -------------------------------------------------
    tgt2 = _warm_resume_engine(model, knobs, resume_len, budget,
                               kv_dtype)
    src2, rid = _run_one(model, knobs, prompt, budget, stop_at,
                         kv_dtype)
    t0 = time.perf_counter()
    p, gen2 = src2.preempt_request(rid)
    resume2 = np.concatenate([p, np.asarray(gen2, np.int64)])
    rid3 = tgt2.add_request(resume2, max_new_tokens=remaining)
    while rid3 not in tgt2.finished and not any(
            r is not None and r.req_id == rid3 and r.output_ids
            for r in tgt2.slots):
        tgt2.step()
    t_pre = time.perf_counter() - t0
    tgt2.run_to_completion()
    pre_tokens = gen2 + tgt2.finished[rid3].output_ids

    leak_free = all(
        len(e.caches[0]._free) + len(e.prefix_cache.cached_blocks())
        == e.caches[0].num_blocks
        for e in (src, tgt, src2, tgt2))
    return t_mig, t_pre, mig_tokens, pre_tokens, leak_free, buf


def bench_migrated_resume(model, knobs, kv_dtype=None, reps=3):
    """The tentpole gate: migrated-resume TTFT strictly beats
    re-prefill TTFT at a >=64-token generation, streams byte-identical
    to the uninterrupted single-engine reference."""
    vocab = model.config.vocab_size
    rng = np.random.RandomState(41)
    prompt = rng.randint(1, vocab,
                         (knobs["prompt_len"],)).astype(np.int64)
    budget, stop_at = knobs["budget"], knobs["gen_before_move"]

    ref_eng = _disagg_engine(model, knobs, kv_dtype=kv_dtype)
    rr = ref_eng.add_request(prompt, max_new_tokens=budget)
    ref = ref_eng.run_to_completion()[rr]

    mig_ts, pre_ts = [], []
    parity = True
    leaks = True
    buf_bytes = 0
    for _ in range(reps):
        t_mig, t_pre, mig_tokens, pre_tokens, leak_free, buf = \
            _resume_ttft_pair(model, knobs, prompt, budget, stop_at,
                              kv_dtype)
        mig_ts.append(t_mig)
        pre_ts.append(t_pre)
        parity = parity and mig_tokens == ref and pre_tokens == ref
        leaks = leaks and leak_free
        buf_bytes = buf.nbytes
    mig, pre = statistics.median(mig_ts), statistics.median(pre_ts)
    return {
        "kv_dtype": kv_dtype or "float32",
        "generated_before_move": stop_at,
        "migrated_resume_ttft_ms": round(mig * 1e3, 3),
        "reprefill_resume_ttft_ms": round(pre * 1e3, 3),
        "speedup": round(pre / max(1e-9, mig), 3),
        "stream_parity_vs_unmigrated": bool(parity),
        "pools_leak_free": bool(leaks),
        "buffer_bytes": int(buf_bytes),
    }


def bench_transfer_count(model, knobs):
    """The one-transfer rule on the wire: host payload copies per
    migration must be O(1) — identical for a small and a large page
    count."""
    from paddle_tpu.jit.serving_step import migration_transfers
    vocab = model.config.vocab_size
    rng = np.random.RandomState(43)
    counts = {}
    for tag, gen_n in (("small", 2), ("large", knobs["gen_before_move"])):
        prompt = rng.randint(1, vocab,
                             (knobs["prompt_len"],)).astype(np.int64)
        src, rid = _run_one(model, knobs, prompt, knobs["budget"], gen_n)
        tgt = _disagg_engine(model, knobs)
        t0 = migration_transfers()
        p, gen, buf = src.extract_request(rid)
        resume = np.concatenate([p, np.asarray(gen, np.int64)])
        tgt.inject_request(resume, buf,
                           max_new_tokens=knobs["budget"] - gen_n)
        t1 = migration_transfers()
        counts[tag] = {
            "pages": buf.n_pages,
            "d2h": t1["d2h"] - t0["d2h"],
            "h2d": t1["h2d"] - t0["h2d"],
        }
    small, large = counts["small"], counts["large"]
    return {
        **counts,
        "transfer_count_o1": bool(
            small["d2h"] == large["d2h"]
            and small["h2d"] == large["h2d"]
            and large["pages"] > small["pages"]),
    }


def bench_host_tier(model, knobs):
    """Prefix hit-rate under memory pressure, host tier vs none: the
    same two-wave shared-prefix workload on the same (deliberately
    tiny) HBM page budget."""
    vocab = model.config.vocab_size
    rng = np.random.RandomState(47)
    hk = knobs["host_tier"]
    families = [rng.randint(1, vocab,
                            (hk["prefix_len"],)).astype(np.int64)
                for _ in range(hk["families"])]
    suffixes = [
        [rng.randint(1, vocab, (hk["suffix_len"],)).astype(np.int64)
         for _ in range(hk["families"])] for _ in range(2)]

    def run_wave(eng, wave):
        outs = []
        for i, fam in enumerate(families):
            prompt = np.concatenate([fam, suffixes[wave][i]])
            rid = eng.add_request(prompt, max_new_tokens=hk["budget"])
            eng.run_to_completion()
            outs.append((prompt, eng.finished[rid].output_ids))
        return outs

    arms = {}
    parity = True
    for tag, tier in (("with_tier", hk["tier_bytes"]), ("no_tier", 0)):
        eng = ContinuousBatchingEngine(
            model, max_batch_size=knobs["slots"],
            num_blocks=hk["num_blocks"],
            block_size=knobs["block_size"],
            max_seq_len=hk["max_seq_len"],
            prefill_chunk_size=knobs["chunk"],
            enable_prefix_cache=True, host_tier_bytes=tier)
        run_wave(eng, 0)
        h0, m0 = eng.prefix_cache.hits, eng.prefix_cache.misses
        outs = run_wave(eng, 1)
        h1, m1 = eng.prefix_cache.hits, eng.prefix_cache.misses
        hits, misses = h1 - h0, m1 - m0
        for prompt, out in outs:
            parity = parity and out == _ref(model, prompt, hk["budget"])
        arms[tag] = {
            "hit_rate": round(hits / max(1, hits + misses), 4),
            "hits": hits, "misses": misses,
            "spills": eng.prefix_cache.spills,
            "host_hits": eng.prefix_cache.host_hits,
            "restores": eng.prefix_cache.restores,
            "skipped_pinned": eng.prefix_cache.skipped_pinned,
            "tier_bytes_end": (eng.host_tier.bytes
                               if eng.host_tier else 0),
            "leak_free": bool(
                len(eng.caches[0]._free)
                + len(eng.prefix_cache.cached_blocks())
                == eng.caches[0].num_blocks),
        }
    arms["parity_vs_eager"] = bool(parity)
    return arms


def bench_disagg_roles(model, knobs):
    """The prefill→decode disaggregation drill through the router:
    fresh prompts land on the prefill specialist, pages migrate to the
    decode specialist after the first token, streams byte-identical."""
    from paddle_tpu.inference.router import ServingRouter
    from paddle_tpu.observability.request_trace import validate_span_chain
    vocab = model.config.vocab_size
    rng = np.random.RandomState(53)
    pe = _disagg_engine(model, knobs, role="prefill", engine_id=1930)
    de = _disagg_engine(model, knobs, role="decode", engine_id=1931,
                        max_batch_size=knobs["slots"] * 2)
    router = ServingRouter([pe, de])
    n_req = knobs["disagg_requests"]
    prompts = [rng.randint(1, vocab,
                           (knobs["prompt_len"],)).astype(np.int64)
               for _ in range(n_req)]
    budget = knobs["disagg_budget"]
    rids = [router.submit(p, max_new_tokens=budget) for p in prompts]
    out = router.run_to_completion()
    parity = all(out[rid] == _ref(model, p, budget)
                 for rid, p in zip(rids, prompts))
    started_prefill = [r for r in rids
                       if router.finished[r].engines_visited()
                       and router.finished[r].engines_visited()[0]
                       == 1930]
    migrated = [r for r in started_prefill
                if router.finished[r].migrations >= 1
                and router.finished[r].engines_visited()[-1] == 1931]
    chains_ok = all(validate_span_chain(router.tracer.events(r))[0]
                    for r in rids)
    leak_free = all(
        len(e.caches[0]._free) + len(e.prefix_cache.cached_blocks())
        == e.caches[0].num_blocks for e in (pe, de))
    return {
        "requests": n_req,
        "started_on_prefill_tier": len(started_prefill),
        "migrated_to_decode_tier": len(migrated),
        "parity_vs_eager": bool(parity),
        "span_chains_valid": bool(chains_ok),
        "pools_leak_free": bool(leak_free),
        "disagg_ok": bool(started_prefill
                          and len(migrated) == len(started_prefill)),
    }


def main_disagg(out_path):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, model = build_model(on_tpu)
    if on_tpu:
        knobs = dict(slots=4, num_blocks=1024, block_size=16,
                     max_seq_len=512, chunk=64, prompt_len=128,
                     budget=96, gen_before_move=64,
                     disagg_requests=8, disagg_budget=16,
                     host_tier=dict(num_blocks=48, max_seq_len=256,
                                    prefix_len=128, suffix_len=32,
                                    families=6, budget=8,
                                    tier_bytes=1 << 28))
    else:
        knobs = dict(slots=2, num_blocks=128, block_size=4,
                     max_seq_len=128, chunk=8, prompt_len=9,
                     budget=72, gen_before_move=64,
                     disagg_requests=3, disagg_budget=8,
                     host_tier=dict(num_blocks=6, max_seq_len=16,
                                    prefix_len=8, suffix_len=3,
                                    families=4, budget=4,
                                    tier_bytes=1 << 22))

    ok = True
    gate_notes = []

    # an engine with zero migration/host-tier config: the staggered
    # parity gate must hold
    defaults_ok = parity_gate_mixed(model, knobs)
    if not defaults_ok:
        ok = False
        gate_notes.append("default-engine parity vs eager failed")
    print("# defaults parity: %s" % defaults_ok, file=sys.stderr)

    resume_arms = []
    for kv_dtype in (None, "int8"):
        arm = bench_migrated_resume(model, knobs, kv_dtype=kv_dtype)
        resume_arms.append(arm)
        print("# resume[%s]: migrated %.2fms vs re-prefill %.2fms "
              "(%.2fx) parity=%s" % (
                  arm["kv_dtype"], arm["migrated_resume_ttft_ms"],
                  arm["reprefill_resume_ttft_ms"], arm["speedup"],
                  arm["stream_parity_vs_unmigrated"]), file=sys.stderr)
        if not arm["stream_parity_vs_unmigrated"]:
            ok = False
            gate_notes.append("stream parity failed (%s)"
                              % arm["kv_dtype"])
        if not (arm["migrated_resume_ttft_ms"]
                < arm["reprefill_resume_ttft_ms"]):
            ok = False
            gate_notes.append(
                "migrated TTFT did not beat re-prefill (%s)"
                % arm["kv_dtype"])
        if not arm["pools_leak_free"]:
            ok = False
            gate_notes.append("pool leak (%s)" % arm["kv_dtype"])

    transfers = bench_transfer_count(model, knobs)
    print("# transfers: small=%r large=%r o1=%s" % (
        transfers["small"], transfers["large"],
        transfers["transfer_count_o1"]), file=sys.stderr)
    if not transfers["transfer_count_o1"]:
        ok = False
        gate_notes.append("host-transfer count not O(1) in pages")

    tier = bench_host_tier(model, knobs)
    print("# host tier: with=%.2f no=%.2f spills=%d restores=%d "
          "parity=%s" % (
              tier["with_tier"]["hit_rate"], tier["no_tier"]["hit_rate"],
              tier["with_tier"]["spills"],
              tier["with_tier"]["restores"],
              tier["parity_vs_eager"]), file=sys.stderr)
    if not (tier["with_tier"]["hit_rate"]
            > tier["no_tier"]["hit_rate"]):
        ok = False
        gate_notes.append(
            "host-tier hit rate not strictly above the no-tier arm")
    if not (tier["parity_vs_eager"]
            and tier["with_tier"]["leak_free"]
            and tier["no_tier"]["leak_free"]
            and tier["with_tier"]["restores"] > 0):
        ok = False
        gate_notes.append("host-tier arm failed: %r" % (tier,))

    disagg = bench_disagg_roles(model, knobs)
    print("# disagg: started_prefill=%d migrated=%d parity=%s "
          "chains=%s" % (
              disagg["started_on_prefill_tier"],
              disagg["migrated_to_decode_tier"],
              disagg["parity_vs_eager"], disagg["span_chains_valid"]),
          file=sys.stderr)
    if not (disagg["disagg_ok"] and disagg["parity_vs_eager"]
            and disagg["span_chains_valid"]
            and disagg["pools_leak_free"]):
        ok = False
        gate_notes.append("disagg role drill failed: %r" % (disagg,))

    fp_arm = resume_arms[0]
    artifact = {
        "metric": "serving_migrated_resume_ttft_speedup",
        "value": fp_arm["speedup"],
        "passed": ok,
        "gate_notes": gate_notes,
        "defaults_parity_vs_eager": bool(defaults_ok),
        "migrated_resume": resume_arms,
        "transfer_count": transfers,
        "host_tier": tier,
        "disagg_roles": disagg,
        "provenance": {
            "r15": "request routing only — a preempted/lost request "
                   "re-prefills every generated token on the target "
                   "engine (BENCH_ROUTER_r15.json)",
            "r19": "page migration — the same preemption resumes via "
                   "extract_blocks/inject_blocks with zero re-prefill "
                   "(this artifact)",
        },
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "dtype": cfg.dtype,
            **{k: v for k, v in knobs.items() if k != "host_tier"},
            "host_tier_knobs": knobs["host_tier"],
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "x",
        "vs_baseline": artifact["value"] if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


def main():
    if "--disagg" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--disagg"]
        stray = [a for a in argv if a.startswith("-")]
        if stray:
            print("bench_serving: --disagg cannot combine with %s — "
                  "run the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = argv[0] if argv else "BENCH_DISAGG_r19.json"
        try:
            main_disagg(out_path)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_migrated_resume_ttft_speedup",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--kernel" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--kernel"]
        stray = [a for a in argv if a.startswith("-")]
        if stray:
            print("bench_serving: --kernel cannot combine with %s — "
                  "run the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = argv[0] if argv else "BENCH_KERNEL_r17.json"
        try:
            main_kernel(out_path)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_kernel_int8_bytes_accessed_shrink",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--quant" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--quant"]
        stray = [a for a in argv if a.startswith("-")]
        if stray:
            print("bench_serving: --quant cannot combine with %s — run "
                  "the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = argv[0] if argv else "BENCH_QUANT_r13.json"
        try:
            main_quant(out_path)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_quant_kv_pages_per_hbm_byte_ratio",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--speculative" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--speculative"]
        stray = [a for a in argv if a.startswith("-")]
        if stray:
            print("bench_serving: --speculative cannot combine with %s "
                  "— run the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = argv[0] if argv else "BENCH_SPEC_r14.json"
        try:
            main_spec(out_path)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_spec_accepted_tokens_per_round_per_slot",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--cp" in sys.argv[1:]:
        args = sys.argv[1:]
        i = args.index("--cp")
        max_cp = 4
        if i + 1 < len(args):
            nxt = args[i + 1]
            if nxt.isdigit():
                max_cp = int(args.pop(i + 1))
            elif not nxt.endswith(".json"):
                # a typo'd degree must fail loudly, not become the
                # artifact path of a silent default-degree run
                print("bench_serving: --cp expects a number (or a "
                      ".json output path next), got %r" % nxt,
                      file=sys.stderr)
                sys.exit(2)
        args.remove("--cp")
        stray = [a for a in args if a.startswith("-")]
        if stray:
            print("bench_serving: --cp cannot combine with %s — run "
                  "the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = args[0] if args else "BENCH_CP_r22.json"
        try:
            main_cp(out_path, max_cp)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_cp_max_context_scale",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--moe" in sys.argv[1:]:
        args = sys.argv[1:]
        i = args.index("--moe")
        max_ep = 4
        if i + 1 < len(args):
            nxt = args[i + 1]
            if nxt.isdigit():
                max_ep = int(args.pop(i + 1))
            elif not nxt.endswith(".json"):
                # a typo'd degree must fail loudly, not become the
                # artifact path of a silent default-degree run
                print("bench_serving: --moe expects a number (or a "
                      ".json output path next), got %r" % nxt,
                      file=sys.stderr)
                sys.exit(2)
        args.remove("--moe")
        stray = [a for a in args if a.startswith("-")]
        if stray:
            print("bench_serving: --moe cannot combine with %s — run "
                  "the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = args[0] if args else "BENCH_MOE_r24.json"
        try:
            main_moe(out_path, max_ep)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_moe_expert_hbm_shrink",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    if "--tp" in sys.argv[1:]:
        args = sys.argv[1:]
        i = args.index("--tp")
        max_tp = 4
        if i + 1 < len(args):
            nxt = args[i + 1]
            if nxt.isdigit():
                max_tp = int(args.pop(i + 1))
            elif not nxt.endswith(".json"):
                # a typo'd degree must fail loudly, not become the
                # artifact path of a silent default-degree run
                print("bench_serving: --tp expects a number (or a "
                      ".json output path next), got %r" % nxt,
                      file=sys.stderr)
                sys.exit(2)
        args.remove("--tp")
        stray = [a for a in args if a.startswith("-")]
        if stray:
            print("bench_serving: --tp cannot combine with %s — run "
                  "the modes separately" % ", ".join(stray),
                  file=sys.stderr)
            sys.exit(2)
        out_path = args[0] if args else "BENCH_SERVE_r12.json"
        try:
            main_tp(out_path, max_tp)
        except SystemExit:
            raise
        except Exception as e:                        # noqa: BLE001
            print(json.dumps({
                "metric": "serving_tp_decode_tokens_per_sec",
                "value": 0.0,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": repr(e)[:300],
            }), flush=True)
            sys.exit(1)
        return
    print("bench_serving: pick a mode: --tp [N] | --quant | --speculative "
          "| --kernel | --disagg | --cp [N] | --moe [N]", file=sys.stderr)
    sys.exit(2)


if __name__ == "__main__":
    main()
