"""Observability subsystem: spans, metrics, per-worker event logs, and
XLA profiler orchestration.

One coherent data model for everything the trainer used to print as
free-form text: ``EventLog`` writes schema-versioned JSONL per worker,
``Tracer`` times nested scopes without device syncs, ``MetricsRegistry``
holds the counters/gauges/histograms every subsystem registers into,
and ``ProfilerOrchestrator`` captures XLA traces on a step window or on
the first anomaly.  ``merge_timeline`` folds the per-worker files into
one gang timeline; ``AlertEngine`` watches window boundaries for SLO
breaks, ``to_trace_events`` exports the timeline for Perfetto, and
``baseline`` keeps the longitudinal run store the perf gate compares
against.

Everything here is import-light (no jax at module scope): the chaos
injector, the launcher supervisor, and ``scripts/check_events.py`` all
import from this package in contexts where jax must not load.
"""

from .alerts import AlertEngine, default_rules, parse_alert_spec
from .baseline import (
    GATE_METRICS,
    RunSummaryBuilder,
    append_run,
    compare_to_baseline,
    load_baseline,
    read_runs,
    run_summary_from_timeline,
    save_baseline,
)
from .cost_model import (
    MFUMeter,
    mlp_fwd_flops,
    peak_flops_for,
    simple_cnn_fwd_flops,
    train_step_flops,
    transformer_fwd_flops,
    xla_cost_analysis,
)
from .critical_path import (
    check_lineage,
    critical_path_of,
    request_decompositions,
    tier_rollups,
    ttft_rollup,
)
from .events import (
    EventLog,
    events_path,
    load_timeline,
    merge_timeline,
    read_events,
)
from .httpmetrics import (
    MetricsHTTPServer,
    parse_prometheus_text,
    prometheus_text,
    scrape,
)
from .goodput import GoodputLedger, goodput_from_timeline
from .memory import MemoryTelemetry, live_array_bytes
from .profiler import ProfilerOrchestrator, parse_profile_steps, profile_trace
from .registry import (
    Counter,
    Gauge,
    Histogram,
    JsonlExporter,
    MetricsRegistry,
    TextExporter,
)
from .schema import (
    ENVELOPE,
    EVENT_KINDS,
    SCHEMA_VERSION,
    json_safe,
    validate_file,
    validate_record,
)
from .straggler import straggler_report
from .trace import Tracer, get_tracer, set_tracer
from .trace_export import to_trace_events, validate_trace, write_trace
from .tracecontext import (
    SpanContext,
    derive_span_id,
    derive_trace_id,
    from_fields,
    from_traceparent,
    root_context,
)

__all__ = [
    "ENVELOPE",
    "EVENT_KINDS",
    "GATE_METRICS",
    "SCHEMA_VERSION",
    "AlertEngine",
    "Counter",
    "EventLog",
    "Gauge",
    "GoodputLedger",
    "Histogram",
    "JsonlExporter",
    "MFUMeter",
    "MemoryTelemetry",
    "MetricsHTTPServer",
    "MetricsRegistry",
    "ProfilerOrchestrator",
    "RunSummaryBuilder",
    "SpanContext",
    "TextExporter",
    "Tracer",
    "append_run",
    "check_lineage",
    "compare_to_baseline",
    "critical_path_of",
    "default_rules",
    "derive_span_id",
    "derive_trace_id",
    "events_path",
    "from_fields",
    "from_traceparent",
    "get_tracer",
    "goodput_from_timeline",
    "json_safe",
    "live_array_bytes",
    "load_baseline",
    "load_timeline",
    "merge_timeline",
    "mlp_fwd_flops",
    "parse_alert_spec",
    "parse_profile_steps",
    "parse_prometheus_text",
    "peak_flops_for",
    "profile_trace",
    "prometheus_text",
    "read_events",
    "read_runs",
    "request_decompositions",
    "root_context",
    "run_summary_from_timeline",
    "save_baseline",
    "scrape",
    "set_tracer",
    "simple_cnn_fwd_flops",
    "straggler_report",
    "tier_rollups",
    "to_trace_events",
    "ttft_rollup",
    "train_step_flops",
    "transformer_fwd_flops",
    "validate_file",
    "validate_record",
    "validate_trace",
    "write_trace",
    "xla_cost_analysis",
]
