"""Unified telemetry: in-graph accumulators, step tracing, anomaly detection.

The observability layer for the async hot loop (ROADMAP: production-scale
serving with zero added steady-state syncs). Four pieces:

  accumulators — cumulative device counters in the donated ``state
                 ["telemetry"]`` leaf, advanced inside the jitted step and
                 drained through ``engine._log_step``'s ONE batched
                 device_get; windows are host-side snapshot diffs
  tracing      — ``span``: the one host-span primitive (a
                 ``jax.profiler.TraceAnnotation`` named ``ds:<layer>.<phase>``
                 plus the elapsed seconds), always on in both engines; the
                 span recorder around the dispatch/prefetch/block phases of
                 ``engine.train_batches`` (Chrome-trace export), the
                 windowed ``jax.profiler`` capture, and the build log: ONE
                 record a program built (``BuildLog``, ``build_log()``) from
                 JAX's own compile events under ``ds:setup.program`` spans
  anomaly      — structured-severity events (loss spikes, grad-norm drift,
                 overflow bursts, dispatch-stall regressions) from the
                 drained window stats
  join         — graft-lint's static collective census and XLA's compiled
                 flops priced by the observed step rate: modeled comms
                 bytes/sec and per-window MFU as monitor events

The serving fleet (PR 18) adds two request-tier pieces on the same rules:

  request_trace — per-request host-clock spans across the whole lifecycle
                  (admission → prefill chunks → decode quanta → drain/
                  migration), stitched across replicas through drain-state
                  v3 and merged into one Chrome trace (replica = process)
  exposition    — mergeable fixed-edge histograms + Prometheus text
                  format for the router's ``fleet_stats()`` rollup

The robustness subsystem (``deepspeed_tpu/robustness``) publishes its
recovery decisions on the same record stream: ``ckpt_fallback``,
``fault_recovered``, ``ckpt_save_failed``, ``preempted`` and
``fault_injected`` records are drained from ``robustness.events`` by
``engine._log_step`` at the SAME window boundary (and into the same JSONL
sink) as the telemetry records — fault handling is observable with zero
added steady-state syncs.

Enable with config ``{"telemetry": {"enabled": true}}``; see the README
"Observability" and "Fault tolerance" sections for the full reference.
"""

from deepspeed_tpu.telemetry.accumulators import (HIST_BUCKETS, HIST_LOG2_MIN,
                                                  HostWindow, accumulate,
                                                  init_leaf,
                                                  update_to_param_ratio,
                                                  window_stats)
from deepspeed_tpu.telemetry.anomaly import (SEVERITY_NUM, AnomalyDetector,
                                             severity_num)
from deepspeed_tpu.telemetry.exposition import (Histogram, parse_exposition,
                                                render_prometheus)
from deepspeed_tpu.telemetry.join import joined_rates, static_step_cost
from deepspeed_tpu.telemetry.request_trace import (RequestTracer,
                                                   merge_chrome_trace)
from deepspeed_tpu.telemetry.tracing import (BuildLog, StepTracer, build_log,
                                             span)

__all__ = [
    "BuildLog", "HIST_BUCKETS", "HIST_LOG2_MIN", "AnomalyDetector",
    "Histogram",
    "HostWindow", "RequestTracer", "SEVERITY_NUM", "StepTracer", "accumulate",
    "build_log", "init_leaf", "joined_rates", "merge_chrome_trace",
    "parse_exposition",
    "render_prometheus", "severity_num", "span", "static_step_cost",
    "update_to_param_ratio", "window_stats",
]
