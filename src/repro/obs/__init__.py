"""Fleet-grade observability for the RANGE-LSH serving stack.

Dependency-free tracker/span/sink subsystem (DESIGN.md §13) plus the
performance-intelligence layer on top (DESIGN.md §14): SLO monitoring
over request classes, Chrome trace export with per-shard pids,
analytic device-cost attribution, and tracker/histogram merge for
per-shard -> fleet rollups. Everything is host-side python recorded
after explicit device-sync boundaries, so attaching a tracker never
changes traced programs or query results.

Every span is also a ``jax.profiler.TraceAnnotation`` of its name, with
or without a tracker (without one it is nothing else: no clock, no
sync). Under ``jax.profiler.trace`` the stages therefore appear on the
host timeline beside the device ops, on one clock, with the program
launches each stage issued nested inside it. ``jax.named_scope`` cannot
name these stages on the device: the query path runs eagerly, and an
eager primitive is one cached program shared by every stage calling it.

Typical wiring::

    from repro import obs
    tracker = obs.Tracker(sinks=[obs.RingBufferSink(),
                                 obs.JsonlSink("metrics.jsonl",
                                               max_bytes=1 << 24)])
    eng = QueryEngine(index, tracker=tracker)      # explicit
    obs.set_default_tracker(tracker)               # or ambient
    ...
    obs.export_chrome_trace(tracker, "trace.json")  # load in Perfetto
"""

from repro.obs.audit import RecallAuditor
from repro.obs.cost import query_stage_costs, xla_cost
from repro.obs.export import (chrome_trace_events, export_chrome_trace,
                              validate_chrome_trace)
from repro.obs.sinks import (JsonlSink, RingBufferSink, StdoutTableSink,
                             format_table, read_jsonl)
from repro.obs.slo import RequestClass, SloMonitor
from repro.obs.trace import Span, Tracer, span_or_null
from repro.obs.tracker import (DEFAULT_QUANTILES, HIST_GROWTH, HIST_HI,
                               HIST_LO, LogHistogram, Tracker,
                               default_tracker, resolve_tracker,
                               set_default_tracker)

__all__ = [
    "Tracker", "LogHistogram", "HIST_GROWTH", "HIST_LO", "HIST_HI",
    "DEFAULT_QUANTILES",
    "Span", "Tracer", "span_or_null",
    "RingBufferSink", "JsonlSink", "StdoutTableSink", "read_jsonl",
    "format_table",
    "RecallAuditor",
    "RequestClass", "SloMonitor",
    "chrome_trace_events", "export_chrome_trace", "validate_chrome_trace",
    "query_stage_costs", "xla_cost",
    "set_default_tracker", "default_tracker", "resolve_tracker",
]
