"""Capacity-plane bench: monitor overhead + saturation drill +
serving-step efficiency + defaults parity.

Round-20 tentpole artifact (BENCH_CAP_r20.json):

1. **Monitor+planner overhead** on the r15 router bench workload
   (shared-prefix families over a 2-engine mixed+prefix pool): ONE
   warmed pool, ``router.capacity`` TOGGLED between a live
   ``FleetCapacityMonitor`` and ``None`` (the r19 default path) across
   interleaved waves — the full r16 protocol (same-pool toggle,
   pre-seeded prefix families with fresh per-run suffixes,
   ``gc.collect()`` between timed windows, strict within-wave
   alternation of who runs first).  The gated estimator is the MEDIAN
   of the per-wave paired ratios (this box's bursty neighbors push
   wave outliers past the r16 quarter-trim budget; the trimmed mean
   is recorded for comparability), plus a deterministic secondary: the
   amortized ``observe_router`` microbench must stay under
   ``OBSERVE_US_GATE`` per router step.  Gates: median overhead < 2%,
   observe < 100 µs/step (measured ~7 µs at ``sample_every=4``).

2. **Saturation drill**: 12 requests onto 4 fleet slots drive the
   fleet saturation EWMA through the high watermark -> the planner
   must commit ``scale_up``; draining the pool and idling it must
   commit ``scale_down``; across the WHOLE transition each action
   commits at most once (ZERO flaps at the declared hysteresis bands
   + min_dwell), and ``router_capacity_transitions_total`` agrees
   with the planner's committed history.

3. **Serving-step efficiency**: with ``PADDLE_TPU_MFU_COST_ANALYSIS``
   enabled, per-engine ``flops_per_token`` / ``hbm_bytes_per_token``
   come off the COMPILED step's cost_analysis and the MFU gauge is
   published (> 0 under a declared peak override).  Consistency with
   the BENCH_KERNEL_r17 tables: an int8-KV engine's step-level HBM
   bytes/token must sit BELOW an equal-config fp32 engine's (same
   direction as r17's kernel-level ``int8_bytes_vs_fp32`` = 3.38; the
   step-level ratio is smaller because fp weights/activations ride
   every launch), and flops/token must sit within a sane band of the
   analytic 2N-per-token model-flops count.  Honesty note (BASELINE
   round 17): these numbers describe the compiled XLA step — on CPU
   the XLA reference attention, NOT the interpret-mode Pallas kernel.

4. **Defaults parity**: a router built WITHOUT ``capacity=`` serves
   the same prompts byte-identically to eager ``model.generate`` and
   exposes no ``capacity`` payload block — the r19 surface, untouched.

Model: tiny llama on CPU (artifact schema CI-checkable); the 1.1B
line on TPU.  Artifact path in argv[1] (default BENCH_CAP_r20.json).
On any error ONE parseable failure-marker JSON line is emitted and
the run exits 1.  After a successful run, ``tools/bench_index.py``
refreshes BENCH_INDEX.json so the trajectory includes this round.
"""
import gc
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402

from paddle_tpu.models.llama import param_count  # noqa: E402
from paddle_tpu.inference.router import ServingRouter  # noqa: E402
from paddle_tpu.observability.capacity import (  # noqa: E402
    CapacityConfig, FleetCapacityMonitor)
from tools.bench_common import (build_bench_model,  # noqa: E402
                                eager_reference, make_engines,
                                warm_engines)
from tools.bench_trace import (prefix_families,  # noqa: E402
                               shared_prefix_wave)

OVERHEAD_GATE = 0.02
OVERHEAD_BUDGET = 32          # decode tokens/request in the overhead arm
OBSERVE_US_GATE = 100.0       # amortized observe_router budget per step
PEAK_OVERRIDE = 1.0e12        # declared CPU peak for the MFU gate


# ---------------------------------------------------------------------------
# 1. overhead (the r16 same-pool paired trimmed-mean protocol)
# ---------------------------------------------------------------------------
def bench_overhead(model, knobs, waves=21):
    """ONE warmed 2-engine pool; ``router.capacity`` toggles between a
    live monitor and None across interleaved waves.  The off arm is
    the EXACT r19 step loop (one ``is not None`` check per step); the
    on arm pays per-engine window sampling + the planner tick + gauge
    refreshes every router round."""
    vocab = model.config.vocab_size
    engines = make_engines(model, 2, knobs, id_base=0)
    warm_engines(engines, knobs, vocab)
    monitor = FleetCapacityMonitor(CapacityConfig())
    router = ServingRouter(engines)

    def set_arm(on: bool):
        router.capacity = monitor if on else None

    fams = prefix_families(knobs, vocab, knobs["families"])
    for p in shared_prefix_wave(knobs, vocab, knobs["families"], 1,
                                seed=39, fams=fams):
        router.submit(p, max_new_tokens=knobs["budget"])
    router.run_to_completion()
    for rid in list(router.finished):
        router.pop_record(rid)
    per_family = 2 * knobs["per_family"]
    times = {"on": [], "off": []}
    for w in range(waves):
        for pos, arm in enumerate(("on", "off") if w % 2 == 0
                                  else ("off", "on")):
            prompts = shared_prefix_wave(
                knobs, vocab, knobs["families"], per_family,
                seed=100 + 2 * w + pos, fams=fams)
            set_arm(arm == "on")
            gc.collect()
            t0 = time.perf_counter()
            rids = [router.submit(p, max_new_tokens=OVERHEAD_BUDGET)
                    for p in prompts]
            router.run_to_completion()
            times[arm].append(time.perf_counter() - t0)
            for rid in rids:
                router.pop_record(rid)
    set_arm(True)
    ratios = sorted(a / max(1e-12, b)
                    for a, b in zip(times["on"], times["off"]))
    trim = len(ratios) // 4
    kept = ratios[trim:len(ratios) - trim] or ratios
    trimmed_mean = sum(kept) / len(kept) - 1.0
    # the GATED estimator is the MEDIAN of the paired ratios, not the
    # r16 trimmed mean: this box's bursty neighbors produce per-wave
    # ratio outliers past the quarter-trim budget (observed spread
    # -58%..+23% in one run while the amortized per-step microbench
    # below reads a steady ~7us), and the median tolerates up to half
    # the waves being contaminated.  The trimmed mean is recorded for
    # r16 comparability.
    overhead = statistics.median(ratios) - 1.0
    # deterministic secondary: amortized observe_router cost per
    # router step on the warmed (idle) pool — load-insensitive, and
    # the number the <2% gate is made of (cost/step over step wall)
    router._probe_all()
    n_calls = 20000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        monitor.observe_router(router)
    observe_us = (time.perf_counter() - t0) / n_calls * 1e6
    return {
        "waves": waves,
        "budget": OVERHEAD_BUDGET,
        "requests_per_wave": knobs["families"] * per_family,
        "median_wall_on_s": round(statistics.median(times["on"]), 4),
        "median_wall_off_s": round(statistics.median(times["off"]), 4),
        "per_wave_ratios": [round(r - 1.0, 4) for r in ratios],
        "overhead_ratio": round(overhead, 4),
        "trimmed_mean_ratio": round(trimmed_mean, 4),
        "observe_us_per_step": round(observe_us, 2),
        "observe_us_gate": OBSERVE_US_GATE,
        "overhead_gate": OVERHEAD_GATE,
        "monitored_steps": monitor.planner.evaluations,
        "method": "same-pool capacity toggle, waves interleaved; gate "
                  "on MEDIAN of per-wave paired ratios (r16 protocol "
                  "with a contamination-robust estimator) + amortized "
                  "observe_router microbench",
    }


# ---------------------------------------------------------------------------
# 2. saturation drill: overload -> scale_up, drain -> scale_down
# ---------------------------------------------------------------------------
def bench_saturation_drill(model, knobs):
    vocab = model.config.vocab_size
    engines = make_engines(model, 2, knobs, id_base=20)
    warm_engines(engines, knobs, vocab)
    ccfg = CapacityConfig(min_dwell=2, halflife_s=0.05,
                          sample_every=1)
    router = ServingRouter(engines, capacity=ccfg)
    rng = np.random.RandomState(7)
    L = knobs["prefix_len"] + knobs["suffix_len"]
    n_req = 6 * knobs["slots"]            # 3x the fleet's slot count
    rids = [router.submit(
        rng.randint(1, vocab, (L,)).astype(np.int64),
        max_new_tokens=2 * knobs["budget"]) for _ in range(n_req)]
    sat_peak = 0.0
    while router.has_work():
        router.step()
        sat_peak = max(sat_peak,
                       router.capacity.fleet_signals()["saturation"])
    loaded_actions = list(router.capacity.planner.actions)
    # drain phase: idle steps until the EWMA decays through the low
    # band (bounded — fail the gate rather than spin forever)
    drained = False
    for _ in range(200):
        router.step()
        time.sleep(0.01)
        if router.capacity.planner.action == "scale_down":
            drained = True
            break
    actions = list(router.capacity.planner.actions)
    plan = router.capacity_plan()
    # transitions counter must agree with the committed history
    from paddle_tpu.observability import default_registry
    snap = default_registry().snapshot()
    trans_total = sum(
        s["value"]
        for s in snap["router_capacity_transitions_total"]["series"])
    return {
        "requests": n_req,
        "fleet_slots": 2 * knobs["slots"],
        "saturation_peak": round(sat_peak, 4),
        "scale_up_committed": "scale_up" in loaded_actions,
        "scale_down_committed": drained
        and actions[-1] == "scale_down",
        "zero_flaps": len(actions) == len(set(actions)),
        "committed_actions": actions,
        "transitions_counter_consistent":
            trans_total >= len(actions),  # counter is process-wide:
        # the overhead arm's monitor contributes too, so >= not ==
        "transitions_counter_this_process": trans_total,
        "final_plan_action": plan["action"],
        "bands": plan["bands"],
        "full_budgets": all(
            len(router.finished[r].output_ids) == 2 * knobs["budget"]
            for r in rids),
    }


# ---------------------------------------------------------------------------
# 3. serving-step efficiency: cost_analysis gauges + r17 consistency
# ---------------------------------------------------------------------------
def bench_efficiency(model, knobs):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    os.environ.pop("PADDLE_TPU_MFU_COST_ANALYSIS", None)  # default ON
    vocab = model.config.vocab_size

    def build(kv_dtype, eid):
        return ContinuousBatchingEngine(
            model, max_batch_size=knobs["slots"],
            num_blocks=knobs["num_blocks"],
            block_size=knobs["block_size"], prefill_chunk_size=knobs["chunk"],
            enable_prefix_cache=True, kv_dtype=kv_dtype,
            engine_id=eid)

    fp32 = build(None, 40)
    int8 = build("int8", 41)
    monitor = FleetCapacityMonitor(CapacityConfig(halflife_s=0.5),
                                   peak_flops=PEAK_OVERRIDE)
    router = ServingRouter([fp32, int8], capacity=monitor)
    rng = np.random.RandomState(11)
    L = knobs["prefix_len"] + knobs["suffix_len"]
    for _ in range(6):
        router.submit(rng.randint(1, vocab, (L,)).astype(np.int64),
                      max_new_tokens=knobs["budget"])
    router.run_to_completion()
    eff = monitor.refresh_efficiency(compute=True)
    plan = monitor.evaluate()             # publishes the gauges
    e_fp, e_q8 = eff.get("40"), eff.get("41")
    # gauge surface: the per-engine series must exist on the scrape
    from paddle_tpu.observability import default_registry, generate_latest
    text = generate_latest(default_registry()).decode()
    gauges_published = all(
        f'{name}{{engine="{eid}"}}' in text
        for name in ("serving_step_mfu", "serving_hbm_bytes_per_token",
                     "serving_model_flops_per_token")
        for eid in ("40", "41"))
    # analytic band: per-token forward flops ~ 2N (N = param count);
    # cost_analysis folds attention + softmax + sampling on top, and
    # the tiny config's vocab head skews it — band kept wide, value
    # recorded for the trajectory
    n_params = param_count(model.config)
    flops_vs_2n = (e_fp["flops_per_token"] / (2.0 * n_params)
                   if e_fp else 0.0)
    # r17 consistency: the kernel tables put int8 page traffic 3.38x
    # under fp32 at equal config; at STEP level weights/activations
    # dilute it, but the direction must hold
    r17_ratio = None
    try:
        with open("BENCH_KERNEL_r17.json") as f:
            r17_ratio = json.load(f)["sections"]["ragged"][
                "int8_bytes_vs_fp32"]
    except Exception:                                 # noqa: BLE001
        pass
    # both sides must have REAL bytes numbers — a backend that stops
    # reporting 'bytes accessed' must fail this gate, not divide by a
    # clamp and pass on no data
    fp_bytes = e_fp["hbm_bytes_per_token"] if e_fp else 0.0
    q8_bytes = e_q8["hbm_bytes_per_token"] if e_q8 else 0.0
    step_ratio = (fp_bytes / q8_bytes
                  if fp_bytes > 0 and q8_bytes > 0 else 0.0)
    mfu_ok = bool(e_fp and e_fp["mfu"] > 0.0
                  and abs(e_fp["mfu"] - e_fp["tokens_per_s"]
                          * e_fp["flops_per_token"] / PEAK_OVERRIDE)
                  < 1e-12)
    return {
        "peak_flops_override": PEAK_OVERRIDE,
        "fp32": e_fp, "int8": e_q8,
        "gauges_published": bool(gauges_published),
        "mfu_arithmetic_ok": mfu_ok,
        "flops_per_token_vs_2n_params": round(flops_vs_2n, 3),
        "flops_band_ok": bool(e_fp) and 0.25 <= flops_vs_2n <= 10.0,
        "step_hbm_fp32_over_int8": round(step_ratio, 4),
        "int8_step_bytes_below_fp32": step_ratio > 1.0,
        "kernel_r17_int8_bytes_vs_fp32": r17_ratio,
        "payload_carries_efficiency":
            "efficiency" in fp32.health_payload(),
        "plan_carries_efficiency":
            "efficiency" in plan["engines"]["40"],
        "note": "cost_analysis of the compiled XLA step (CPU = XLA "
                "reference attention, not interpret-mode Pallas; "
                "BASELINE r17 honesty note); step-level fp32/int8 "
                "byte ratio is diluted vs the kernel-level 3.38x by "
                "fp weights riding every launch",
    }


# ---------------------------------------------------------------------------
# 4. defaults parity: no monitor => the r19 surface
# ---------------------------------------------------------------------------
def bench_defaults_parity(model, knobs):
    vocab = model.config.vocab_size
    engines = make_engines(model, 2, knobs, id_base=60)
    warm_engines(engines, knobs, vocab)
    router = ServingRouter(engines)       # capacity unset
    rng = np.random.RandomState(13)
    L = knobs["prefix_len"] + knobs["suffix_len"]
    prompts = [rng.randint(1, vocab, (L,)).astype(np.int64)
               for _ in range(6)]
    rids = [router.submit(p, max_new_tokens=knobs["budget"])
            for p in prompts]
    out = router.run_to_completion()
    parity = all(out[rid] == eager_reference(model, p, knobs["budget"])
                 for rid, p in zip(rids, prompts))
    plan_raises = False
    try:
        router.capacity_plan()
    except ValueError:
        plan_raises = True
    return {
        "token_parity_vs_eager": bool(parity),
        "no_capacity_payload_key":
            "capacity" not in router.health_payload(),
        "capacity_plan_raises": plan_raises,
    }


def main(out_path):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, model = build_bench_model(on_tpu)
    if on_tpu:
        knobs = dict(slots=4, num_blocks=512, block_size=16, chunk=64,
                     prefix_len=192, suffix_len=32, families=6,
                     per_family=4, budget=16)
        waves = 21
    else:
        knobs = dict(slots=2, num_blocks=96, block_size=4, chunk=8,
                     prefix_len=24, suffix_len=4, families=5,
                     per_family=3, budget=4)
        # 31 (vs the tracer bench's 21): the monitor's true cost
        # (~0.5-1%) sits closer to its 2% gate than the tracer's did,
        # so the trimmed mean gets more central waves to average
        waves = 31

    ok = True
    gate_notes = []

    overhead = bench_overhead(model, knobs, waves=waves)
    print("# overhead: on=%.3fs off=%.3fs median_ratio=%.4f "
          "(trimmed %.4f; gate < %.2f) observe=%.1fus/step"
          % (overhead["median_wall_on_s"],
             overhead["median_wall_off_s"],
             overhead["overhead_ratio"],
             overhead["trimmed_mean_ratio"], OVERHEAD_GATE,
             overhead["observe_us_per_step"]),
          file=sys.stderr)
    if overhead["overhead_ratio"] >= OVERHEAD_GATE:
        ok = False
        gate_notes.append("capacity overhead %.4f >= %.2f"
                          % (overhead["overhead_ratio"], OVERHEAD_GATE))
    if overhead["observe_us_per_step"] >= OBSERVE_US_GATE:
        ok = False
        gate_notes.append("observe_router %.1fus/step >= %.0fus"
                          % (overhead["observe_us_per_step"],
                             OBSERVE_US_GATE))

    drill = bench_saturation_drill(model, knobs)
    print("# drill: peak_sat=%.2f actions=%r flaps=%s"
          % (drill["saturation_peak"], drill["committed_actions"],
             not drill["zero_flaps"]), file=sys.stderr)
    for gate in ("scale_up_committed", "scale_down_committed",
                 "zero_flaps", "transitions_counter_consistent",
                 "full_budgets"):
        if not drill[gate]:
            ok = False
            gate_notes.append("saturation drill failed: %s" % gate)

    eff = bench_efficiency(model, knobs)
    print("# efficiency: fp32 flops/tok=%.3g hbm/tok=%.3g mfu=%.3g "
          "fp32/int8 bytes=%.3f"
          % (eff["fp32"]["flops_per_token"] if eff["fp32"] else 0,
             eff["fp32"]["hbm_bytes_per_token"] if eff["fp32"] else 0,
             eff["fp32"]["mfu"] if eff["fp32"] else 0,
             eff["step_hbm_fp32_over_int8"]), file=sys.stderr)
    for gate in ("gauges_published", "mfu_arithmetic_ok",
                 "flops_band_ok", "int8_step_bytes_below_fp32",
                 "payload_carries_efficiency",
                 "plan_carries_efficiency"):
        if not eff[gate]:
            ok = False
            gate_notes.append("efficiency gate failed: %s" % gate)

    parity = bench_defaults_parity(model, knobs)
    for gate, val in parity.items():
        if not val:
            ok = False
            gate_notes.append("defaults parity failed: %s" % gate)

    artifact = {
        "metric": "router_capacity_monitor_overhead_ratio",
        "value": overhead["overhead_ratio"],
        "passed": ok,
        "gate_notes": gate_notes,
        "overhead": overhead,
        "saturation_drill": drill,
        "efficiency": eff,
        "defaults_parity": parity,
        "provenance": "r19 = unmonitored router (BENCH_DISAGG_r19); "
                      "r20 = capacity plane (this artifact); overhead "
                      "via the r16 same-pool paired trimmed-mean "
                      "protocol (BENCH_TRACE_r16); efficiency "
                      "consistency vs BENCH_KERNEL_r17 cost_analysis "
                      "tables",
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "dtype": cfg.dtype,
            **knobs,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "overhead_ratio",
        "vs_baseline": (OVERHEAD_GATE - overhead["overhead_ratio"]
                        if ok else 0.0),
    }), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_CAP_r20.json"
    try:
        main(out)
    except SystemExit:
        raise
    except Exception as e:                            # noqa: BLE001
        print(json.dumps({
            "metric": "router_capacity_monitor_overhead_ratio",
            "value": 1.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "error": repr(e)[:300],
        }), flush=True)
        sys.exit(1)
