"""paddle_tpu.observability — unified runtime metrics + telemetry.

The production-observability layer SURVEY §5.5 notes the reference
lacks in-repo: a process-wide :class:`MetricsRegistry` of labeled
Counter/Gauge/Histogram instruments, per-step :class:`StepTelemetry`
(wall time, tokens/s, MFU from the compiled step's ``cost_analysis()``,
live/peak HBM, NaN/Inf loss sentinel), Prometheus text / HTTP
(`/metrics`, `/healthz`) / JSON exporters, and a merger folding host
``RecordEvent`` spans, runtime step/checkpoint/comm markers and the
``jax.profiler`` device trace into one chrome://tracing JSON (on the
profiler's clock where the serving steps join the two:
:func:`step_clock`), and the seconds the process spent building XLA
programs (:func:`compile_seconds`).

Every built-in subsystem records into :func:`default_registry`:

========================  =================================================
subsystem                 metric families
========================  =================================================
Engine.fit                train_steps_total, train_step_duration_seconds,
                          train_tokens_per_second, train_mfu_ratio,
                          train_checkpoint_stall_seconds,
                          train_resume_total, hbm_in_use_bytes
ContinuousBatchingEngine  serving_queue_depth, serving_slot_occupancy_ratio,
                          serving_kv_page_utilization_ratio,
                          serving_prefill_duration_seconds,
                          serving_decode_step_duration_seconds,
                          serving_ttft_seconds, serving_tpot_seconds,
                          serving_requests_total, serving_tokens_total,
                          serving_truncated_victims_total
ServingRouter             router_requests_total, router_pending_depth,
                          router_prefix_route_hits_total,
                          router_requeues_total, router_engine_healthy,
                          router_slo_attained_total,
                          router_latency_quantile_seconds
RequestTracer             request_trace_spans_total,
                          request_trace_dropped_spans_total
CheckpointManager         checkpoint_save_duration_seconds,
                          checkpoint_written_bytes_total,
                          checkpoint_commits_total,
                          checkpoint_gc_removed_total,
                          checkpoint_failures_total
DataLoader                dataloader_queue_wait_seconds
comm_watchdog             comm_timeouts_total, comm_aborts_total
========================  =================================================
"""
from .metrics import (MetricsRegistry, Counter, Gauge, Histogram,
                      MetricError, DEFAULT_BUCKETS, default_registry,
                      counter, gauge, histogram)
from .exporters import (generate_latest, json_snapshot, dump_json,
                        MetricsServer, start_metrics_server,
                        METRICS_PORT_ENV, set_health_provider,
                        healthz_payload)
from .telemetry import (StepTelemetry, device_peak_flops,
                        PEAK_FLOPS_BY_KIND, CHECK_NAN_ENV,
                        PEAK_FLOPS_ENV)
from .trace_merge import (SpanLog, span_log, record_span, record_instant,
                          merge_chrome_trace, load_device_trace_events,
                          step_clock)
from .compile_time import compile_seconds
from .request_trace import (RequestTracer, NullRequestTracer,
                            NULL_TRACER, resolve_tracer,
                            LatencyReservoir, validate_span_chain,
                            fleet_trace)
from .capacity import (SignalWindow, EngineCapacityMonitor,
                       CapacityConfig, CapacityPlanner,
                       FleetCapacityMonitor, resolve_capacity_monitor,
                       CAPACITY_ACTIONS)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "MetricError",
    "DEFAULT_BUCKETS", "default_registry", "counter", "gauge",
    "histogram",
    "generate_latest", "json_snapshot", "dump_json", "MetricsServer",
    "start_metrics_server", "METRICS_PORT_ENV",
    "set_health_provider", "healthz_payload",
    "StepTelemetry", "device_peak_flops", "PEAK_FLOPS_BY_KIND",
    "CHECK_NAN_ENV", "PEAK_FLOPS_ENV",
    "SpanLog", "span_log", "record_span", "record_instant",
    "merge_chrome_trace", "load_device_trace_events", "step_clock",
    "compile_seconds",
    "RequestTracer", "NullRequestTracer", "NULL_TRACER",
    "resolve_tracer", "LatencyReservoir", "validate_span_chain",
    "fleet_trace",
    "SignalWindow", "EngineCapacityMonitor", "CapacityConfig",
    "CapacityPlanner", "FleetCapacityMonitor",
    "resolve_capacity_monitor", "CAPACITY_ACTIONS",
]
