"""Observability subsystem — the ``Stat.h``/``REGISTER_TIMER`` successor
for the fused hot loop (ISSUE 2) plus structured tracing and the
anomaly-triggered flight recorder (ISSUE 4).

The layers:

- :mod:`~paddle_tpu.obs.sinks` — pluggable record consumers (in-memory,
  JSONL file, logging); ``emit`` is thread-safe (stager/fill threads
  write too).
- :mod:`~paddle_tpu.obs.health` — device-side training-health scalars
  (grad/param/update norms, update ratio, NaN/Inf sentinel) traced into
  the compiled step.
- :mod:`~paddle_tpu.obs.telemetry` — the :class:`Telemetry` object the
  Trainer drives: per-call step-time breakdown (host stack / shard /
  dispatch / fenced device / events-replay), retrace+compile tracking
  keyed by step fingerprint with HLO cost-analysis FLOPs, MFU and
  tokens/sec accounting, and device-memory peak sampling.
- :mod:`~paddle_tpu.obs.trace` — :class:`Tracer`: thread-aware spans
  emitted as Chrome Trace Event Format JSON (Perfetto-viewable), with
  flow events linking a group's stager-thread staging to its main-thread
  dispatch and drain, and programmatic ``jax.profiler`` capture windows.
  ONE rule decides whether the program's spans (trainer loop,
  scheduler, engine) are recorded, and there is no knob: a tracer is
  attached to the object, or a ``jax.profiler`` session is active in
  the process (:func:`~paddle_tpu.obs.trace.live`). In the second case
  they go to the process-wide :func:`session_tracer` and, as
  ``TraceAnnotation``s named ``paddle_tpu:<name>``, into the profiler's
  own trace, on the device operations' clock:
  ``jax.profiler.start_trace`` round any entry point is all an operator
  needs to get host and device on one timeline. :func:`self_times`
  gives each span's duration less its children's.
- :mod:`~paddle_tpu.obs.xla_cache` — where the persistent compilation
  cache lives, and ``compile_log()``: every trace, lowering and backend
  compile JAX reports (``jax.monitoring``), with its function's name,
  seconds, time and whether the cache answered. Always recorded.
- :mod:`~paddle_tpu.obs.anomaly` — :class:`AnomalyDetector`: rolling
  robust statistics over the telemetry stream (slow-step outliers,
  retrace bursts, drain stalls, memory high-water, the NaN sentinel);
  on trigger, a one-shot forensics bundle (telemetry ring + trace tail +
  config/env/mesh snapshot + verdict) lands on disk.
- :mod:`~paddle_tpu.obs.report` — ``python -m paddle_tpu.obs.report
  run.jsonl``: run-summary table (throughput, MFU, retraces, overlap,
  anomalies) from a telemetry JSONL.
- The fleet observability plane (ISSUE 17):
  :mod:`~paddle_tpu.obs.fleet_trace` merges router + per-replica span
  batches (shipped back on tick replies, stamped with the one fleet
  clock) into a single Chrome/Perfetto timeline with per-replica lanes
  and rid-keyed flows; :mod:`~paddle_tpu.obs.slo` streams rolling
  P²-estimated p50/p95/p99 TTFT/TPOT, goodput, and error-budget burn
  rate over terminal request records (``fleet.slo_report()``);
  ``python -m paddle_tpu.obs.top`` is the live text dashboard over
  heartbeat files + telemetry JSONL; and
  :class:`~paddle_tpu.obs.anomaly.ServingAnomalyDetector` extends the
  flight recorder with per-replica serving kinds (tick stall, accept/
  prefix-hit collapse, retransmit burst, queue divergence).

:mod:`~paddle_tpu.obs.xla_flags` holds the compile options that make a
data-parallel step's gradient all-reduces asynchronous on the TPU, and
the rule by which ``Trainer`` gives them to its step programs.

Attach with ``Trainer(..., telemetry=Telemetry(sinks=[JsonlSink(path)]),
tracer=Tracer(), anomaly=AnomalyDetector(out_dir))``. With none attached
(and no profiler session) the hot loop is unchanged: same traced step,
same dispatch count, same donation, zero extra device fetches.
"""

from . import xla_cache, xla_flags
from .anomaly import (ANOMALY_KINDS, SERVING_ANOMALY_KINDS,
                      AnomalyDetector, ServingAnomalyDetector, Verdict)
from .fleet_trace import (flow_connected, flow_summary, lane_monotonic,
                          merge_fleet_trace, save_fleet_trace)
from .health import (HEALTH_KEYS, health_scalars, tree_l2_norm,
                     tree_nonfinite_count)
from .metrics import (Counter, Gauge, Histogram, MetricsHub,
                      log_buckets, parse_exposition)
from .percentiles import (GOODPUT_REASONS, P2Quantile, percentile,
                          summarize_handoffs, summarize_requests,
                          summarize_scale)
from .sinks import InMemorySink, JsonlSink, LoggingSink, Sink
from .slo import SLOMonitor, SLOTargets
from .telemetry import (PEAK_FLOPS, Telemetry, device_memory_stats,
                        device_peak_flops, lowered_hlo_flops)
from .trace import (Tracer, jax_profile, live, self_times, session_tracer,
                    tspan)

__all__ = [
    "Telemetry", "Sink", "InMemorySink", "JsonlSink", "LoggingSink",
    "HEALTH_KEYS", "health_scalars", "tree_l2_norm", "tree_nonfinite_count",
    "PEAK_FLOPS", "device_peak_flops", "lowered_hlo_flops",
    "device_memory_stats",
    "Tracer", "tspan", "jax_profile", "live", "session_tracer", "self_times",
    "AnomalyDetector", "ServingAnomalyDetector", "Verdict",
    "ANOMALY_KINDS", "SERVING_ANOMALY_KINDS",
    "xla_flags",
    "percentile", "P2Quantile", "summarize_requests", "summarize_scale",
    "summarize_handoffs", "GOODPUT_REASONS",
    "SLOMonitor", "SLOTargets",
    "MetricsHub", "Counter", "Gauge", "Histogram", "log_buckets",
    "parse_exposition",
    "merge_fleet_trace", "save_fleet_trace", "flow_summary",
    "flow_connected", "lane_monotonic",
]
