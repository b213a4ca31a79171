"""Engine-server entrypoint: one process, one
``ContinuousBatchingEngine``, served over the fleet wire protocol
(``paddle_tpu.inference.fleet``).

Launched by ``EngineProcess`` (or by hand)::

    python tools/engine_server.py --config cfg.json --port-file port

The config JSON builds the engine deterministically —
``build_engine_from_config`` is also imported by the fleet bench/tests
to build the byte-parity in-process reference with IDENTICAL weights
(same ``paddle.seed``) and knobs::

    {
      "platform": "cpu",          // REQUIRED, no default: "cpu" forces
                                  // JAX onto CPU (test/bench rigs);
                                  // "tpu" serves from the chip (and
                                  // fails without one); "inherit"
                                  // leaves an already-set-up process
                                  // alone (in-process reference builds)
      "host": "127.0.0.1", "port": 0,
      "engine_id": 0, "role": "mixed",
      "seed": 0,                  // paddle.seed before model build
      "slots": 4, "num_blocks": 64, "block_size": 4, "chunk": null,
      "enable_prefix_cache": true,
      "kv_dtype": null, "sampling": false,
      "warm": {"prompt_len": 12, "budget": 4},   // optional precompile
      "fault_spec": "hang:rpc.recv:ms=2000"      // optional, in-process
    }

The listening address is published by WRITING ``host:port`` to
``--port-file`` via rename (the parent polls for it), AFTER the
optional warmup — so a client's first step RPC never eats the cold
compile under its deadline.  The process serves until a ``shutdown``
RPC, SIGTERM, or being killed.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_engine_from_config(cfg: dict):
    """Deterministic engine from the config dict (shared with
    tools/bench_fleet.py and the slow-lane fleet tests: the same config
    builds byte-identical weights in any process)."""
    platform = cfg.get("platform")
    if platform == "cpu":
        from paddle_tpu.testing.dryrun import force_cpu_devices
        force_cpu_devices(int(cfg.get("cpu_devices", 1)))
    elif platform == "tpu":
        # one process per chip: this server owns it, so whoever spawned
        # it must have stayed off JAX (or the start-up below fails)
        from paddle_tpu.core.device import enable_compile_cache, on_tpu
        enable_compile_cache()
        if not on_tpu():
            raise RuntimeError('config asks for "platform": "tpu" but '
                               'JAX found no TPU')
    elif platform != "inherit":
        # a missing key used to mean "cpu": a fleet started for the
        # chip would then quietly serve from the CPU
        raise ValueError('engine config needs "platform": "cpu", "tpu" '
                         'or "inherit"; got %r' % (platform,))
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model_cfg = llama_tiny_config()
    paddle.seed(int(cfg.get("seed", 0)))
    model = LlamaForCausalLM(model_cfg)
    model.eval()
    kw = {}
    if cfg.get("engine_id") is not None:
        kw["engine_id"] = int(cfg["engine_id"])
    engine = ContinuousBatchingEngine(
        model,
        max_batch_size=int(cfg.get("slots", 4)),
        num_blocks=int(cfg.get("num_blocks", 64)),
        block_size=int(cfg.get("block_size", 4)),
        prefill_chunk_size=cfg.get("chunk"),
        enable_prefix_cache=bool(cfg.get("enable_prefix_cache", True)),
        kv_dtype=cfg.get("kv_dtype"),
        sampling=bool(cfg.get("sampling", False)),
        role=cfg.get("role", "mixed"),
        **kw)
    return model_cfg, engine


def warm_engine(engine, warm: dict, vocab: int):
    """Optional cold-compile warmup before the port publishes: one
    throwaway request shaped like the workload, tokens from the top of
    the vocab so nothing registers in measured prefix families."""
    import numpy as np
    rng = np.random.RandomState(97)
    L = int(warm.get("prompt_len", 12))
    prompt = rng.randint(max(1, vocab - 50), vocab, (L,)).astype(np.int64)
    engine.add_request(prompt, max_new_tokens=int(warm.get("budget", 4)))
    engine.run_to_completion()
    engine.finished.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True,
                    help="engine config JSON path")
    ap.add_argument("--port-file", required=True,
                    help="file to publish host:port into (via rename)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)

    if cfg.get("fault_spec"):
        # in-process server-side faults (the env var works too — this
        # keeps bench/test configs in one JSON)
        from paddle_tpu.testing import faults
        faults.configure(cfg["fault_spec"])

    from paddle_tpu.inference.fleet import EngineServer
    model_cfg, engine = build_engine_from_config(cfg)
    if cfg.get("warm"):
        warm_engine(engine, cfg["warm"], int(model_cfg.vocab_size))

    server = EngineServer(engine, host=cfg.get("host", "127.0.0.1"),
                          port=int(cfg.get("port", 0))).start()
    host, port = server.address
    tmp = args.port_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(f"{host}:{port}\n")
    os.replace(tmp, args.port_file)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
