"""Elastic-actuator bench: closed-loop pool scaling + live mesh reshape.

Round-25 tentpole artifact (BENCH_ELASTIC_r25.json):

1. **Closed-loop drill** (segment A): a 2-engine mixed+prefix pool with
   ONE warmed standby engine and an :class:`ElasticController` riding
   ``router.capacity_plan()``.  An overload wave drives the fleet
   saturation EWMA through the high watermark -> the planner commits
   ``scale_up`` and the controller ACTS: the standby is admitted (pool
   2 -> 3), its host tier warmed from the hottest peers' spilled prefix
   pages, and decode work shed onto its empty slots.  Draining the pool
   to idle commits ``scale_down`` and the controller retires the
   least-saturated engine back to standby.  Gates: the pool size
   actually changes in BOTH directions through planner-driven
   actuation, zero capacity-band flaps, zero drops (every request
   finishes its full budget), and byte-identical streams vs eager
   ``model.generate``.

2. **Mid-load drain** (segment B): with fresh requests mid-decode on
   every engine, a scale_down is driven through the controller's own
   actuator (the planner's scale_down band only clears at idle, so the
   under-load drain is invoked directly — the remove_engine/extract/
   requeue path is byte-for-byte the planner-driven one).  Gates: every
   extractable in-flight request drains with ``fate="migrated"`` (KV
   pages travel, ZERO re-prefill), none degrade to ``re_prefilled``,
   and the migrated requests still finish byte-identically on the
   surviving engine.

3. **Live mesh reshape**: a ZeRO-2 sharded TrainStep runs K steps on a
   dp=8 mesh, then moves to dp=4 two ways — :func:`live_reshape`
   (device-to-device redistribution, arXiv:2112.01075) vs the r08
   checkpoint round trip (host-numpy params + ``opt_state_arrays``
   into a fresh dp=4 step).  Gates: bit-exact loss trajectory across
   BOTH arms for all K+N steps, moved bytes < 0.5x the full-gather
   equivalent, and the per-chip staging peak bounded below the
   full-tensor peak the naive restore pays.

Defaults parity (no controller attached == r24 byte-identical) is
bench_capacity's gate and is not repeated here.  Model: tiny llama on
CPU (artifact schema CI-checkable); the 1.1B line on TPU.  Artifact
path in argv[1] (default BENCH_ELASTIC_r25.json).  On any error ONE
parseable failure-marker JSON line is emitted and the run exits 1.
After a successful run, ``tools/bench_index.py`` refreshes
BENCH_INDEX.json so the trajectory includes this round.
"""
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tpu_available() -> bool:
    """TPU probe WITHOUT initializing a jax backend (the forced CPU
    device count only applies before the CPU client first initializes,
    so jax.devices() must not be the probe)."""
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return False
    return importlib.util.find_spec("libtpu") is not None


ON_TPU = _tpu_available()
if not ON_TPU:
    # the ONE shared dryrun setup, BEFORE any jax.devices() call: the
    # reshape arm needs an 8-device dp mesh
    from paddle_tpu.testing.dryrun import force_cpu_devices
    force_cpu_devices(8)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from paddle_tpu.inference.elastic import ElasticController  # noqa: E402
from paddle_tpu.inference.router import ServingRouter  # noqa: E402
from paddle_tpu.models.llama import param_count  # noqa: E402
from paddle_tpu.observability.capacity import CapacityConfig  # noqa: E402
from tools.bench_common import (build_bench_model,  # noqa: E402
                                eager_reference, warm_engines)
from tools.bench_trace import (prefix_families,  # noqa: E402
                               shared_prefix_wave)

MOVED_RATIO_GATE = 0.5        # redistribution bytes vs full-gather


def _make_engines(model, n, knobs, id_base):
    """bench_common.make_engines plus the r19 host tier (the warmup
    path restores spilled prefix pages into the admitted engine)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return [ContinuousBatchingEngine(
        model, max_batch_size=knobs["slots"],
        num_blocks=knobs["num_blocks"], block_size=knobs["block_size"],
        prefill_chunk_size=knobs["chunk"],
        enable_prefix_cache=True,
        host_tier_bytes=knobs["host_tier_bytes"],
        engine_id=id_base + i) for i in range(n)]


# ---------------------------------------------------------------------------
# 1+2. the elastic drill
# ---------------------------------------------------------------------------
def bench_elastic_drill(model, knobs):
    vocab = model.config.vocab_size
    engines = _make_engines(model, 3, knobs, id_base=40)
    warm_engines(engines, knobs, vocab)
    cold = engines.pop()                  # compile-warm but NOT pooled
    router = ServingRouter(engines, capacity=CapacityConfig(
        min_dwell=2, halflife_s=0.05, sample_every=1))
    ctl = ElasticController(router, standby=[cold], min_engines=1,
                            max_engines=3, cooldown_steps=4,
                            warm_pages=16)
    fams = prefix_families(knobs, vocab, knobs["families"])
    budgets, prompts = {}, {}

    def submit(p, budget):
        rid = router.submit(p, max_new_tokens=budget)
        budgets[rid] = budget
        prompts[rid] = p
        return rid

    # seed the prefix caches past eviction BEFORE the drill so the
    # host tiers hold spilled pages by the time scale_up warms the
    # newcomer (an overload alone scales up before anything spills)
    for p in shared_prefix_wave(knobs, vocab, knobs["families"], 1,
                                seed=10, fams=fams):
        submit(p, knobs["budget"])
    router.run_to_completion()
    # the seed wave is its own load cycle: a second scale_up commit in
    # the drill proper is a fresh transition, not a flap
    seed_actions = len(router.capacity.planner.actions)

    # ---- segment A: overload -> scale_up, idle drain -> scale_down
    for p in shared_prefix_wave(knobs, vocab, knobs["families"],
                                knobs["per_family"], seed=11,
                                fams=fams):
        submit(p, 2 * knobs["budget"])
    pool_sizes = [len(router.handles)]
    sat_peak = 0.0
    while router.has_work():
        router.step()
        ctl.step()
        pool_sizes.append(len(router.handles))
        sat_peak = max(
            sat_peak, router.capacity.fleet_signals()["saturation"])
    planner_down = False
    for _ in range(300):                  # bounded: fail, don't spin
        router.step()
        ctl.step()
        pool_sizes.append(len(router.handles))
        if any(a[1] == "scale_down" for a in ctl.actions):
            planner_down = True
            break
        time.sleep(0.01)
    actions_a = list(router.capacity.planner.actions)[seed_actions:]
    up_detail = next(
        (a[2] for a in ctl.actions if a[1] == "scale_up"), None)

    # ---- segment B: forced drain with work mid-decode everywhere
    rids2 = [submit(p, 4 * knobs["budget"])
             for p in shared_prefix_wave(knobs, vocab, 2, 2, seed=12,
                                         fams=fams[:2])]
    for _ in range(80):                   # until all 4 are extractable
        router.step()
        live = [router._inflight[k] for k in list(router._inflight)]
        if len(live) == len(rids2) and all(
                rr.engine_req is not None
                and getattr(rr.engine_req, "state", "") == "running"
                and rr.engine_req.output_ids for rr in live):
            break
    pool_before_drain = len(router.handles)
    forced = ctl._scale_down()
    drain = ctl.actions[-1][2] if forced == "scale_down" else {}
    while router.has_work():
        router.step()
        pool_sizes.append(len(router.handles))

    parity = all(
        list(router.finished[rid].output_ids)
        == eager_reference(model, prompts[rid], budgets[rid])
        for rid in budgets)
    fates = drain.get("fates", {})
    # capacity oscillations only — a repeated rebalance commit is a
    # within-band move, not a flap (recorded in planner_actions)
    scale_a = [a for a in actions_a if a != "rebalance"]
    from paddle_tpu.observability import default_registry
    snap = default_registry().snapshot()
    elastic_actions = {
        s["labels"]["action"]: s["value"]
        for s in snap["elastic_actions_total"]["series"]}
    drained_total = {
        s["labels"]["fate"]: s["value"]
        for s in snap["elastic_drained_requests_total"]["series"]}
    return {
        "requests": len(budgets),
        "fleet_slots_initial": 2 * knobs["slots"],
        "saturation_peak": round(sat_peak, 4),
        "pool_size_min": min(pool_sizes),
        "pool_size_max": max(pool_sizes),
        "pool_size_final": len(router.handles),
        "pool_scaled_up": max(pool_sizes) == 3,
        "pool_scaled_down_by_planner": planner_down,
        "zero_flaps": len(scale_a) == len(set(scale_a)),
        "planner_actions": actions_a,
        "controller_actions": [(a[1], a[2]) for a in ctl.actions],
        "warmup_restored_pages":
            up_detail.get("warmed_pages", 0) if up_detail else 0,
        "scale_up_shed": up_detail.get("shed", 0) if up_detail else 0,
        "forced_drain_pool_before": pool_before_drain,
        "forced_drain_fates": fates,
        "drain_all_migrated":
            fates.get("migrated", 0) >= 1
            and fates.get("re_prefilled", 1) == 0,
        "zero_drops": all(
            len(router.finished[rid].output_ids) == budgets[rid]
            for rid in budgets),
        "byte_identical_streams": bool(parity),
        "elastic_actions_total": elastic_actions,
        "elastic_drained_requests_total": drained_total,
        "pool_gauge_final": next(
            (s["value"]
             for s in snap["router_engine_pool_size"]["series"]), None),
    }


# ---------------------------------------------------------------------------
# 3. live dp=8 -> 4 reshape vs the checkpoint round trip
# ---------------------------------------------------------------------------
def bench_reshape(k_before=3, n_after=4):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.process_mesh import ProcessMesh
    from paddle_tpu.jit.redistribute import live_reshape
    from paddle_tpu.jit.train_step import ShardingConfig, TrainStep
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    w_true = rng.randn(8, 2).astype(np.float32)
    batches = []
    for _ in range(k_before + n_after):
        x = rng.randn(16, 8).astype(np.float32)
        batches.append((x, (x @ w_true).astype(np.float32)))

    def make():
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                            nn.Linear(32, 2))
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        return net, opt

    def run(ts, i):
        x, y = batches[i]
        return float(np.asarray(ts(paddle.to_tensor(x),
                                   paddle.to_tensor(y))._value))

    mesh8 = ProcessMesh(shape=[8, 1], dim_names=["dp", "mp"])
    mesh4 = ProcessMesh(shape=[4, 1], dim_names=["dp", "mp"])

    # live arm: K steps on dp=8, redistribute in place, N on dp=4
    net, opt = make()
    ts = TrainStep(net, nn.MSELoss(), opt, clip_norm=1.0, mesh=mesh8,
                   sharding=ShardingConfig(stage=2))
    live = [run(ts, i) for i in range(k_before)]
    t0 = time.perf_counter()
    ts_live, plan = live_reshape(ts, mesh4)
    live_reshape_s = time.perf_counter() - t0    # placement only: both
    # arms pay the new mesh's first-step compile identically below
    live += [run(ts_live, i)
             for i in range(k_before, k_before + n_after)]

    # reference arm: the r08 restart — every byte through host RAM
    net, opt = make()
    ts_a = TrainStep(net, nn.MSELoss(), opt, clip_norm=1.0, mesh=mesh8,
                     sharding=ShardingConfig(stage=2))
    ref = [run(ts_a, i) for i in range(k_before)]
    t0 = time.perf_counter()
    host_params = {k: np.asarray(v._value)
                   for k, v in net.state_dict().items()}
    host_opt = {k: np.asarray(v)
                for k, v in ts_a.opt_state_arrays().items()}
    for k, v in net.state_dict().items():
        v._value = jnp.asarray(host_params[k])
    ts_ref = TrainStep(net, nn.MSELoss(), opt, clip_norm=1.0,
                       mesh=mesh4, sharding=ShardingConfig(stage=2))
    ts_ref.load_opt_state_arrays(host_opt)
    ckpt_roundtrip_s = time.perf_counter() - t0
    ref += [run(ts_ref, i)
            for i in range(k_before, k_before + n_after)]

    s = plan.summary()
    from paddle_tpu.observability import default_registry
    snap = default_registry().snapshot()
    moved_by_kind = {
        ser["labels"]["kind"]: ser["value"]
        for ser in snap["redistribute_bytes_total"]["series"]}
    return {
        "steps_before": k_before,
        "steps_after": n_after,
        "losses_live": live,
        "losses_checkpoint_restart": ref,
        "bit_exact_losses": live == ref,
        "moved_bytes": s["moved_bytes"],
        "adopted_bytes": s["adopted_bytes"],
        "full_gather_equiv_bytes": s["full_gather_equiv_bytes"],
        "moved_over_full_gather": round(s["moved_over_full_gather"], 4),
        "moved_ratio_gate": MOVED_RATIO_GATE,
        "per_chip_peak_bytes": s["per_chip_peak_bytes"],
        "full_gather_peak_bytes": s["full_gather_peak_bytes"],
        "peak_bounded":
            s["per_chip_peak_bytes"] < s["full_gather_peak_bytes"],
        "leaves": s["leaves"],
        "live_reshape_s": round(live_reshape_s, 4),
        "ckpt_roundtrip_s": round(ckpt_roundtrip_s, 4),
        "redistribute_bytes_total": moved_by_kind,
    }


def main(out_path):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, model = build_bench_model(on_tpu)
    if on_tpu:
        knobs = dict(slots=4, num_blocks=512, block_size=16, chunk=64,
                     prefix_len=192, suffix_len=32, families=8,
                     per_family=2, budget=16,
                     host_tier_bytes=1 << 30)
    else:
        # num_blocks=64 (vs bench_capacity's 96) + 16 families is
        # deliberate: each engine sees ~8 families, enough that the
        # prefix cache EVICTS and the host tier holds spilled pages
        # for the scale_up warmup path to restore
        knobs = dict(slots=2, num_blocks=64, block_size=4, chunk=8,
                     prefix_len=24, suffix_len=4, families=16,
                     per_family=2, budget=4, host_tier_bytes=1 << 20)

    ok = True
    gate_notes = []

    drill = bench_elastic_drill(model, knobs)
    print("# drill: pool %d->%d->%d sat_peak=%.2f warmed=%d "
          "fates=%r planner=%r"
          % (drill["fleet_slots_initial"] // knobs["slots"],
             drill["pool_size_max"], drill["pool_size_final"],
             drill["saturation_peak"],
             drill["warmup_restored_pages"],
             drill["forced_drain_fates"], drill["planner_actions"]),
          file=sys.stderr)
    for gate in ("pool_scaled_up", "pool_scaled_down_by_planner",
                 "zero_flaps", "zero_drops", "byte_identical_streams",
                 "drain_all_migrated"):
        if not drill[gate]:
            ok = False
            gate_notes.append("elastic drill failed: %s" % gate)

    reshape = bench_reshape()
    print("# reshape: moved/fg=%.4f peak=%d/%d bit_exact=%s "
          "live=%.3fs ckpt=%.3fs"
          % (reshape["moved_over_full_gather"],
             reshape["per_chip_peak_bytes"],
             reshape["full_gather_peak_bytes"],
             reshape["bit_exact_losses"], reshape["live_reshape_s"],
             reshape["ckpt_roundtrip_s"]), file=sys.stderr)
    if not reshape["bit_exact_losses"]:
        ok = False
        gate_notes.append("reshape losses not bit-exact vs "
                          "checkpoint restart")
    if not (reshape["moved_over_full_gather"] < MOVED_RATIO_GATE):
        ok = False
        gate_notes.append("moved/full-gather %.4f >= %.2f"
                          % (reshape["moved_over_full_gather"],
                             MOVED_RATIO_GATE))
    if not reshape["peak_bounded"]:
        ok = False
        gate_notes.append("per-chip staging peak not below the "
                          "full-gather peak")

    artifact = {
        "metric": "elastic_reshape_moved_over_full_gather",
        "value": reshape["moved_over_full_gather"],
        "passed": ok,
        "gate_notes": gate_notes,
        "elastic_drill": drill,
        "live_reshape": reshape,
        "provenance": "r20 recommended (BENCH_CAP_r20); r25 actuates "
                      "(this artifact).  Drain speed vs re-prefill "
                      "measured in BENCH_DISAGG_r19 (7.3-8.4x); "
                      "redistribution model per arXiv:2112.01075",
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
        "unit": "byte_ratio",
        "vs_baseline": (MOVED_RATIO_GATE
                        - reshape["moved_over_full_gather"])
        if ok else 0.0,
    }), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_ELASTIC_r25.json"
    try:
        main(out)
    except SystemExit:
        raise
    except Exception as e:                            # noqa: BLE001
        print(json.dumps({
            "metric": "elastic_reshape_moved_over_full_gather",
            "value": 1.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "error": repr(e)[:300],
        }), flush=True)
        sys.exit(1)
