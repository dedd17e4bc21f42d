"""Merge layer (core/distributed.py): device milliseconds per profiled
batch of the all-gather collectives of the Algorithm-2 merge, the
``all_gather`` of every shard's local top-k values and ids: the ops whose
HLO instruction is an all-gather (synchronous, or its ``-start`` /
``-done`` pair), summed on each chip and averaged over the chips, over
the profiled segment with no tracker. Moves ``qps``."""

from bench.lib.devtrace import op_kind

KINDS = ("all-gather", "all-gather-start", "all-gather-done")


def is_all_gather(op):
    return op_kind(op[1]) in KINDS


def read(ctx):
    rec = ctx.recording
    if rec is None or ctx.traced_batches <= 0:
        return None
    secs = rec.op_seconds(is_all_gather)
    if secs <= 0:
        return None
    return 1e3 * secs / ctx.traced_batches
