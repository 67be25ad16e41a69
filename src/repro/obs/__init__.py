"""Telemetry: metrics registry, span tracer, Chrome-trace/JSON export.

Three dependency-free layers (stdlib only; jax touched lazily in
``obs.trace`` spans and ``provenance``):

  * ``obs.metrics``  — counters / gauges / fixed-bucket histograms under
    stable dotted names, with mergeable snapshots and bridges from the
    engine stats families (``FusedScanStats`` etc.) to the four
    accounting-regime counters.
  * ``obs.trace``    — explicit begin/end spans at host wave boundaries,
    written into any running profiler capture unfenced; the recording
    tracer also keeps them (fenced) for Chrome-trace export, and the
    default null tracer keeps instrumented code free of conditionals.
  * ``obs.export``   — Perfetto-loadable Chrome-trace JSON, the
    schema-versioned metrics envelope, and run provenance.

Catalogue and worked examples: ``docs/OBSERVABILITY.md``.
"""

from repro.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, merge_snapshots,
    LATENCY_BUCKETS_MS, record_fused_scan, record_graph_scan,
    record_graph_sharded, record_flat_exchange, record_fused_serve_totals,
    record_mutations, record_drift, record_dco_method, DCO_METHODS,
)
from repro.obs.trace import (  # noqa: F401
    Tracer, NullTracer, NULL_TRACER, current_tracer, set_tracer, use_tracer,
)
from repro.obs.export import (  # noqa: F401
    SCHEMA_VERSION, provenance, chrome_trace, write_chrome_trace,
    metrics_envelope, write_metrics_json, span_totals,
)
