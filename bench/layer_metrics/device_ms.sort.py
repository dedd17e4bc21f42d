"""XLA glue between kernels: device milliseconds per batch of sort ops in
the profiled segment (the directory argsort, the dense engine's (batch,
N) argsorts, the re-rank's duplicate mask). Moves ``qps``."""

from bench.lib.devtrace import op_kind


def is_sort(op):
    return op_kind(op[1]) == "sort" or op[2] == "sort"


def read(ctx):
    rec = ctx.recording
    if rec is None or ctx.traced_batches <= 0:
        return None
    secs = rec.op_seconds(is_sort)
    if secs <= 0:
        return None
    return 1e3 * secs / ctx.traced_batches
