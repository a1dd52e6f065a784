"""Serving observability layer (docs/observability.md).

Three independent pieces, stdlib-only so every layer of the stack can
depend on them without import cycles:

* ``trace``    — a low-overhead ring-buffer event tracer (spans, instants,
                 counters) the scheduler and block pool emit structured
                 events into.
* ``metrics``  — a process-wide registry of counters / gauges / histograms
                 with Prometheus text-format and JSON export.
* ``timeline`` — export of the event stream as Chrome trace-event JSON,
                 viewable in Perfetto (https://ui.perfetto.dev), one track
                 per pool slot plus scheduler / pool tracks.
"""
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import NULL_TRACER, Event, Tracer
from repro.obs.timeline import to_chrome_trace, write_chrome_trace

__all__ = ["Event", "Tracer", "NULL_TRACER", "MetricsRegistry", "REGISTRY",
           "to_chrome_trace", "write_chrome_trace"]
