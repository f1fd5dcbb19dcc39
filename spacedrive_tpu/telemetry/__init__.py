"""Telemetry — metrics registry, pipeline spans, snapshot/scrape APIs.

The observability layer for the TPU dispatch path: queue waits, batch
occupancy, H2D byte counts, and per-phase durations are first-class
series, so a gap between device and end-to-end rates needs no ad-hoc
prints to explain.

Surface:

- ``REGISTRY`` / ``counter`` / ``gauge`` / ``histogram`` — the
  process-wide metrics registry (Prometheus text via ``render()``);
- ``metrics`` — every predeclared family for the hot paths;
- ``span(stage, nbytes=0)`` — sync/async context manager recording
  per-stage wall time and bytes;
- ``snapshot()`` — the JSON read path (rspc ``telemetry.snapshot``);
- ``render()`` — Prometheus exposition text (the ``/metrics`` route);
- ``trace`` / ``trace_export()`` — distributed trace ids on every span,
  exported as Chrome-trace JSON (the ``/trace`` route);
- ``events`` — flight-recorder rings; ``debug_bundle()`` — the redacted
  support artifact (docs/observability.md);
- ``reset()`` — test isolation across metrics, spans, traces, rings.
"""

from . import (
    attrib,
    events,
    federation,
    health,
    history,
    metrics,
    resources,
    sampler,
    slo,
    tenants,
    trace,
)
from .registry import (
    BYTE_BUCKETS,
    MAX_SERIES_PER_FAMILY,
    OVERFLOW_LABEL,
    RATIO_BUCKETS,
    REGISTRY,
    TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .snapshot import counter_value, gauge_value, histogram_recent, snapshot
from .spans import Span, clear_recent, current_span, recent_spans, span


def render() -> str:
    return REGISTRY.render()


def reset() -> None:
    """Test/bench isolation: zero every metric series AND clear the
    span ring (`trace`'s, the one there is), every flight-recorder ring, the
    attribution report cache + pass markers, SLO evaluation state, the
    host profiler's accumulators + capture-window ring + trigger
    state, the resource sampler's last-sample state + planted test
    leaks, the tenant plane's heavy-hitter sketches, and every
    history writer's in-memory tail (durable history
    segments are data-dir state and deliberately survive)."""
    REGISTRY.reset()
    trace.clear()
    events.clear_all()
    attrib.reset()
    slo.reset()
    sampler.reset()
    resources.reset()
    tenants.reset()
    history.reset_tails()
    # the index journal's per-location runtime counters + stats cache
    # live like registry series (lazy import: journal imports metrics)
    from ..location.indexer.journal import reset_runtime

    reset_runtime()
    # the execution continuum's per-stage throughput EWMAs and the
    # Controller's derived lease targets are registry-like state too
    from ..parallel import scheduler as _scheduler

    _scheduler.reset()


def trace_export(trace_id=None):
    """Chrome-trace-event JSON of the completed-span ring, with the
    host profiler's capture-window samples merged onto a dedicated
    ``host-profile`` lane (the ``GET /trace`` + ``telemetry.trace_export``
    payload — Perfetto shows what Python was doing beside the spans).
    With a ``trace_id`` filter, profiler events are clipped to the
    filtered spans' time range — captures from unrelated incidents
    must not stretch one trace's timeline into a sliver."""
    doc = trace.export(trace_id)
    profile_events = sampler.SAMPLER.chrome_events()
    if trace_id is not None and profile_events:
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        if not spans:
            return doc
        lo = min(e["ts"] for e in spans)
        hi = max(e["ts"] + e.get("dur", 0) for e in spans)
        profile_events = [
            e for e in profile_events
            if e.get("ph") == "M" or lo <= e.get("ts", 0) <= hi
        ]
        if all(e.get("ph") == "M" for e in profile_events):
            profile_events = []  # nothing landed in-window: no lane
    doc["traceEvents"].extend(profile_events)
    return doc


def debug_bundle(node=None, data_dir=None):
    """The redacted debug bundle dict (see telemetry.bundle)."""
    from .bundle import build_bundle

    return build_bundle(node, data_dir)


def counter(name: str, help: str = "", labels=()):
    return REGISTRY.counter(name, help, labels)


def gauge(name: str, help: str = "", labels=()):
    return REGISTRY.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels=(), buckets=TIME_BUCKETS):
    return REGISTRY.histogram(name, help, labels, buckets)


__all__ = [
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TIME_BUCKETS", "RATIO_BUCKETS", "BYTE_BUCKETS",
    "MAX_SERIES_PER_FAMILY", "OVERFLOW_LABEL",
    "metrics", "span", "Span", "current_span", "recent_spans",
    "clear_recent", "snapshot", "histogram_recent", "gauge_value",
    "counter_value", "render", "counter", "gauge", "histogram",
    "trace", "events", "reset", "trace_export", "debug_bundle",
    "health", "federation", "attrib", "history", "slo", "sampler",
    "resources", "tenants",
]
