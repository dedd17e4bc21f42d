"""Span-based tracing of the query hot path (DESIGN.md §13).

A span names one stage — ``hash_encode``, ``plan``, ``directory_match``,
``segmented_gather``, ``planned_take``, ``re_rank``, ``top_k`` — and is
always a ``jax.profiler.TraceAnnotation`` of that name around its body,
whether or not a tracker is attached. Under ``jax.profiler.trace`` the
stage therefore shows on the host timeline of the device trace, on the
device ops' clock, with the program launches it issued nested inside it;
that is what charges device time to a stage. ``jax.named_scope`` cannot:
the query path runs eagerly, and an eager primitive is compiled once per
shape and shared by every stage that calls it, so a scope never reaches
its device program. Outside a profiler trace an annotation costs about a
microsecond.

With no tracker (``span_or_null(None, name)``) the annotation is all a
span does: no clock, no sync, no record; ``sync`` is the identity and
``set_attrs`` a no-op. With a tracker a span also times the stage with an
*explicit device-sync boundary*: jax dispatch is asynchronous, so a
wall-clock reading after an un-synced call measures dispatch latency, not
the stage. Registering a sync value (``span(name, sync=x)`` or
``sp.sync(x)`` in the body) makes the span ``jax.block_until_ready`` it,
inside the annotation, before reading the clock. Instrumentation never
goes *inside* jitted code and never touches values — enabling tracing
cannot change query results (parity-tested).

Spans nest: the tracer keeps a stack and emits each span with its full
``path`` (``/``-joined ancestry), so the per-stage breakdown of a
``repro.engine.query`` parent is reconstructable from the record stream.
Durations also land in the tracker histogram named by the span, giving
p50/p90/p99 stage timings for free (``benchmarks/roofline_report.py
--obs`` consumes exactly these).

Span records carry ``t0`` (start, seconds since tracker start) alongside
``dur_s``, so ``repro.obs.export`` can rebuild exact begin/end pairs for
Chrome ``trace_event`` output, and an optional ``attrs`` dict —
``sp.set_attrs(...)`` — that the exporter forwards as trace-event args
(DESIGN.md §14). A span whose body OR sync raises emits nothing: a
failed device computation has no meaningful duration, and recording one
would poison the stage histograms.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation


class Span:
    """One timed stage; use via ``with tracker.span(name) as sp:``."""

    __slots__ = ("name", "tracer", "_sync", "t_start", "duration", "path",
                 "depth", "attrs", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, sync: Any = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.tracer = tracer
        self.name = name
        self._sync = sync
        self.t_start: Optional[float] = None
        self.duration: Optional[float] = None
        self.path: Optional[str] = None
        self.depth: Optional[int] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self._annotation = TraceAnnotation(name)

    def sync(self, value: Any) -> Any:
        """Register the value whose device completion ends this span;
        returns it unchanged so it can wrap the producing expression."""
        self._sync = value
        return value

    def set_attrs(self, **attrs: Any) -> None:
        """Attach structured attributes (predicted flops/bytes, shapes,
        ...) to this span's record; merged over earlier values."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self.tracer._push(self)
        self.t_start = self.tracer.tracker.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        failed = exc_type is not None
        try:
            if not failed and self._sync is not None:
                import jax
                # the one sanctioned device sync: repro-lint rule R6
                # confines block_until_ready to this module, and rule R2 /
                # contract C3 keep spans out of traced code entirely
                jax.block_until_ready(self._sync)
        except BaseException:
            # a sync that raises mid-block_until_ready is a failed span:
            # the duration would measure time-to-error, not the stage
            failed = True
            raise
        finally:
            self.duration = self.tracer.tracker.clock() - self.t_start
            self.tracer._pop(self, failed=failed)
            self._annotation.__exit__(exc_type, exc, tb)


class Tracer:
    """Span factory + nesting stack for one tracker."""

    def __init__(self, tracker):
        self.tracker = tracker
        self._stack: List[Span] = []

    def span(self, name: str, *, sync: Any = None,
             attrs: Optional[Dict[str, Any]] = None) -> Span:
        return Span(self, name, sync=sync, attrs=attrs)

    def _push(self, span: Span) -> None:
        span.depth = len(self._stack)
        span.path = "/".join([s.name for s in self._stack] + [span.name])
        self._stack.append(span)

    def _pop(self, span: Span, *, failed: bool) -> None:
        # unwind even on exceptions; tolerate out-of-order exits from
        # misuse rather than corrupting the stack
        while self._stack and self._stack[-1] is not span:
            self._stack.pop()
        if self._stack:
            self._stack.pop()
        if failed:
            return
        tr = self.tracker
        h = tr.hists.get(span.name)
        if h is None:
            from repro.obs.tracker import LogHistogram
            h = tr.hists[span.name] = LogHistogram()
        h.record(span.duration)
        rec = {"type": "span", "name": span.name, "path": span.path,
               "depth": span.depth, "t0": span.t_start - tr._t0,
               "dur_s": span.duration}
        if span.attrs:
            rec["attrs"] = dict(span.attrs)
        tr._emit(rec)


class _NullSpan:
    """No-tracker path: the stage's profiler annotation and nothing else;
    ``sync`` is the identity and ``set_attrs`` a no-op."""

    __slots__ = ("_annotation",)

    def __init__(self, name: str):
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> "_NullSpan":
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)

    @staticmethod
    def sync(value):
        return value

    @staticmethod
    def set_attrs(**attrs):
        return None


def span_or_null(tracker, name: str, *, sync: Any = None):
    """``tracker.span(name)`` when a tracker is attached, else an
    annotation-only span of the same name — the instrumentation idiom
    for hot paths where ``tracker`` is usually None."""
    if tracker is None:
        return _NullSpan(name)
    return tracker.span(name, sync=sync)
