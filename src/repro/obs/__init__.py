"""Serving telemetry: metrics registry, span sink, pipeline instrumentation.

Quickstart::

    from repro.obs import Telemetry

    tel = Telemetry()
    idx.attach_telemetry(tel)                   # stage spans from the executor
    fe = AnnFrontend(idx, telemetry=tel)        # queue/exec decomposition
    ...serve...
    print(tel.registry.expose_text())           # Prometheus text exposition
    tel.spans.dump_jsonl("events.jsonl")        # bounded JSONL event log

Attached, each executor stage is also a ``jax.profiler.TraceAnnotation``
on the device trace's clock: ``lanns.route``, ``lanns.candidates``,
``lanns.rerank``, ``lanns.merge``, and per routed partition of the fp32
scan ``lanns.scan.upload`` and ``lanns.scan.wait``;
``lanns_transfer_bytes_total{direction="h2d"|"d2h"}`` counts the bytes
that scan moves and ``lanns_scan_calls_total{path=...}`` its calls by the
path ``ops.distance_topk`` took.  Instrumentation-off (no attach, ``telemetry=None``) and
-on paths return bit-identical results — the hooks only observe.  On one
TPU v5e serving batches of 1024 a span costs about 2.4 µs of host time,
profiler on or off; the upload span's wait for the copy costs a traced
batch about 0.1 s of 12.7 s (README "Observability").
"""

from repro.obs.metrics import (
    BATCH_SIZE_BUCKETS,
    DEFAULT_LATENCY_BUCKETS_S,
    Counter,
    CounterFamily,
    Gauge,
    GaugeFamily,
    Histogram,
    HistogramFamily,
    MetricsRegistry,
)
from repro.obs.spans import (
    STAGES,
    SpanSink,
    format_stage_table,
    percentiles_ms,
    stage_breakdown,
)
from repro.obs.telemetry import Telemetry

__all__ = [
    "BATCH_SIZE_BUCKETS",
    "DEFAULT_LATENCY_BUCKETS_S",
    "Counter",
    "CounterFamily",
    "Gauge",
    "GaugeFamily",
    "Histogram",
    "HistogramFamily",
    "MetricsRegistry",
    "STAGES",
    "SpanSink",
    "Telemetry",
    "format_stage_table",
    "percentiles_ms",
    "stage_breakdown",
]
