"""Telemetry subsystem: spans, metrics, device-counter export, events
(DESIGN.md §9).

Every instrumented call site in the engine, shard dispatch, lifecycle,
fault, and serving layers goes through this surface. Spans always emit a
``jax.profiler.TraceAnnotation`` (free unless a profiler is capturing);
histograms, counters, events and the device-counter drains (a host sync
per op) run only while collection is enabled, which is off by default.
Nothing obs-related ever enters a jitted program, so compiled HLO and op
outputs are bit-identical either way (pinned by ``tests/test_obs.py``).
Enable with :func:`enable` or ``REPRO_OBS=1``.

Quick tour::

    from repro import obs

    obs.enable()
    with obs.span("wave", n=4):                 # TraceAnnotation "wave"
        vals, rep = lookup_batch(tree, qb, ql)  #   + histogram span.wave
    obs.histogram("serve.request_latency_s").observe(dt)
    obs.counter("shard.retries", op="lookup").inc()
    obs.event("publish", label="compact", version=1, ok=True,
              reason="", duration_s=0.12)
    print(obs.console_summary())
    obs.export_events_jsonl("out/obs/events.jsonl")

Stable public surface — import from here, not from the submodules.
"""
from .bridge import drain_op_report
from .events import (EVENT_TYPES, event, event_summary, events,
                     validate_event)
from .export import console_summary, export_events_jsonl, prometheus_text
from .registry import (HIST_BOUNDS, Counter, Gauge, Histogram, all_metrics,
                       counter, disable, enable, enabled, gauge, get_metric,
                       histogram, reset)
from .trace import current_path, span

__all__ = [
    # state
    "enabled", "enable", "disable", "reset",
    # spans
    "span", "current_path",
    # metrics
    "Counter", "Gauge", "Histogram", "HIST_BOUNDS",
    "counter", "gauge", "histogram", "get_metric", "all_metrics",
    # device-counter bridge
    "drain_op_report",
    # events
    "EVENT_TYPES", "event", "events", "event_summary", "validate_event",
    # exporters
    "export_events_jsonl", "prometheus_text", "console_summary",
]
