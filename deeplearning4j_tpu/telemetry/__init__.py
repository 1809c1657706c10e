"""Unified training telemetry (ISSUE 2).

Three layers, one subsystem:

- **in-graph** (telemetry/metrics.py): grad/param global norms, update
  ratio, router load — dicts of device scalars computed inside the jitted
  step, parity-safe (0 ulp vs the unthreaded step);
- **host** (registry.py / step_log.py / session.py): labeled
  counters/gauges/histograms, the JSONL step-event log, and TrainTelemetry
  which buffers device metrics and syncs once per N steps;
- **export** (prometheus.py + ui/server.py routes): Prometheus text format
  at ``/metrics``, JSON snapshot at ``/api/telemetry``, device memory at
  ``/api/memory``, live trace spans at ``/api/trace``;
- **tracing** (trace.py, ISSUE 7): span-based distributed tracing across
  the elastic control plane (context propagation over the tracker frame
  protocol and blob metas) + a per-process crash flight recorder dumped
  on error/SIGTERM and checkpointed write-ahead at round boundaries —
  merged into round timelines by tools/trace_report.py; and the serving
  tick's phases (``phase`` / ``phases_between``, ISSUE 25): each a
  ``TraceAnnotation`` on the profiler's clock and a record in an
  always-on process-global ring;
- **watch** (history.py + alerts.py, ISSUE 15): a bounded time-series
  history sampled from the registry (range/rate/delta queries, windowed
  histogram-delta percentiles, crash-readable JSONL spill) and a
  declarative alert engine over it (threshold / rate-of-change /
  absence-staleness / burn-rate SLO rules with for_s hysteresis) whose
  firing verdicts bump ``alerts_firing``, dump flight-recorder
  forensics, and publish into the tracker KV for the cluster alert view
  — served at ``/api/history`` and ``/api/alerts``, reported by
  tools/alert_report.py;
- **federation** (federation.py, ISSUE 12): per-process registries
  pushed as versioned JSON snapshots through the StateTracker KV map and
  merged into one cluster view (counters sum, gauges per-process,
  histograms bucket-merge, lapsed pushers marked stale) served at
  ``/api/cluster`` and ``/metrics?scope=cluster``;
- **performance attribution** (xprofile.py, ISSUE 9): compile-time
  introspection of every jitted step behind the ``profile=`` seam —
  XLA cost/memory analysis, HLO collective inventory, measured-MFU /
  roofline attribution, live memory watermarks, served at
  ``/api/profile`` and reported by tools/profile_report.py;
- **runtime profiling** (runprof.py, ISSUE 17): measured step-phase
  timelines behind the ``runprof=`` seam — ring-buffered host/dispatch/
  device/comm-wait breakdowns, streaming ``runprof_*`` gauges (steps/s,
  measured MFU, host + input-wait fractions), and on-demand N-step
  capture sessions (write-ahead JSONL + atomic JSON + Chrome trace
  events on the span-tree trace ids) controlled at ``/api/profiling``
  or ``DL4J_TPU_RUNPROF``, rendered by
  ``tools/profile_report.py --runtime``.

The listener chain bridges in via optimize/listeners.MetricsIterationListener
and the scaleout counters via the statetracker registry mirror.
"""

from deeplearning4j_tpu.telemetry.metrics import (
    global_norm,
    train_step_metrics,
    update_metrics,
)
from deeplearning4j_tpu.telemetry.federation import (
    ClusterAggregator,
    MetricsPusher,
    merge_snapshots,
)
from deeplearning4j_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    Watchtower,
    arm_watchtower,
    default_rules,
    get_engine,
    set_engine,
)
from deeplearning4j_tpu.telemetry.history import (
    MetricsHistory,
    get_history,
    read_spill,
    replay_spill,
    set_history,
)
from deeplearning4j_tpu.telemetry.prometheus import (
    CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE,
    render_prometheus,
    render_snapshot,
    sanitize_name,
)
from deeplearning4j_tpu.telemetry.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    flat_record,
)
from deeplearning4j_tpu.telemetry.session import (
    DEFAULT_INTERVAL,
    TrainTelemetry,
)
from deeplearning4j_tpu.telemetry.trace import (
    Span,
    Tracer,
    current_trace_context,
    format_traceparent,
    get_tracer,
    maybe_span,
    parse_traceparent,
    phase,
    phases_between,
    set_tracer,
)
from deeplearning4j_tpu.telemetry.step_log import (
    StepLogWriter,
    read_step_log,
    summarize_step_log,
)
from deeplearning4j_tpu.telemetry.runprof import (
    RunProfiledStep,
    RunProfiler,
    StepTiming,
    chrome_trace_events,
    default_runprof,
    find_sessions,
    get_runprof,
    load_session,
    maybe_runprof,
    resolve_runprof,
    set_runprof,
    summarize_session,
)
from deeplearning4j_tpu.telemetry.xprofile import (
    MemoryWatermarkSampler,
    ProfiledStep,
    ProfileStore,
    StepProfile,
    attribute,
    default_profile_store,
    profile_compiled,
    profile_lowered,
)

__all__ = [
    "AlertEngine",
    "AlertRule",
    "ClusterAggregator",
    "Counter",
    "DEFAULT_BUCKETS",
    "DEFAULT_INTERVAL",
    "Gauge",
    "Histogram",
    "MemoryWatermarkSampler",
    "MetricsHistory",
    "MetricsPusher",
    "MetricsRegistry",
    "PROMETHEUS_CONTENT_TYPE",
    "ProfileStore",
    "ProfiledStep",
    "RunProfiledStep",
    "RunProfiler",
    "Span",
    "StepLogWriter",
    "StepProfile",
    "StepTiming",
    "Tracer",
    "TrainTelemetry",
    "Watchtower",
    "arm_watchtower",
    "attribute",
    "chrome_trace_events",
    "default_profile_store",
    "default_runprof",
    "find_sessions",
    "load_session",
    "maybe_runprof",
    "profile_compiled",
    "profile_lowered",
    "current_trace_context",
    "default_registry",
    "default_rules",
    "flat_record",
    "format_traceparent",
    "get_engine",
    "get_history",
    "get_runprof",
    "get_tracer",
    "maybe_span",
    "merge_snapshots",
    "parse_traceparent",
    "phase",
    "phases_between",
    "read_spill",
    "render_snapshot",
    "replay_spill",
    "resolve_runprof",
    "set_engine",
    "set_history",
    "set_runprof",
    "set_tracer",
    "summarize_session",
    "global_norm",
    "read_step_log",
    "render_prometheus",
    "sanitize_name",
    "summarize_step_log",
    "train_step_metrics",
    "update_metrics",
]
