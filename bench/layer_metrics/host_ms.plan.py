"""Planner layer (core/planner.py): host milliseconds per batch inside
the engine's ``repro.engine.plan`` span, ``resolve_budgets`` as the query
itself runs it, before any device work of the batch, over the tracked
segment. The span registers no sync (planning is host code), so the
tracker adds only its own bookkeeping. Moves ``qps``."""

SPANS = ("repro.engine.plan",)


def read(ctx):
    return ctx.span_ms_per_batch(SPANS)
