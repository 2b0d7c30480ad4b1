"""Observability plane for the serving stack and the trainer:

- ``trace.py``  spans on two clocks. ``span(name)`` opens a host span on
                the profiler's clock (JAX's ``TraceAnnotation``),
                which places the host's work against the device's
                operations in a profiler trace; ``SPANS`` names every
                such span. ``Tracer`` records submit -> queue -> flush
                -> gather -> dispatch -> scatter -> reply per request on
                its own epoch-anchored ``perf_counter`` clock in a
                bounded ring, with cross-process stitching over the
                socket transport (frames carry trace id + parent span);
- ``export.py`` metrics export — Prometheus text exposition, JSONL
                ``EventLog``, and the ``MetricsServer`` stdlib HTTP
                endpoint (``--metrics-port`` on the launch CLIs).

Dispatch accounting (assert "one fused dispatch per flush" instead of
trusting comments) lives with the dispatch decision in
``repro.kernels.dispatch`` (``counting()``); the sampled telemetry time
series lives with the counters in ``repro.serving.telemetry``
(``Telemetry.history``).
"""

from repro.obs.export import EventLog, MetricsServer, render_prometheus
from repro.obs.trace import (SPANS, FlushSpans, Span, Trace, TraceContext,
                             Tracer, finish_all, now, span)

__all__ = [
    "EventLog",
    "FlushSpans",
    "MetricsServer",
    "SPANS",
    "Span",
    "Trace",
    "TraceContext",
    "Tracer",
    "finish_all",
    "now",
    "render_prometheus",
    "span",
]
