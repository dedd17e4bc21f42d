"""Planner layer (core/planner.py): candidates probed per query under the
recall contract, the mean of the engine's ``repro.engine.probe_width``
observation over the tracked segment. A wider plan means more gather and
re-rank work per query, so it moves ``qps``."""


def read(ctx):
    return ctx.mean("repro.engine.probe_width")
