"""Planner layer (core/planner.py): milliseconds per call of
``resolve_budgets(calibration, recall_target, k)``, the greedy budget
allocation every planned query runs on the host before it dispatches
device work. Timed by the benchmark on the host clock, over enough calls
to span at least a quarter of a second. Moves ``qps``."""

CALL = "planner.resolve_budgets"


def read(ctx):
    c = ctx.host_calls.get(CALL)
    if not c or c["count"] <= 0:
        return None
    return 1e3 * c["total_s"] / c["count"]
