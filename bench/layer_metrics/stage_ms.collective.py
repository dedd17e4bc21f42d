"""Sharded engine layer (core/distributed.py): milliseconds per tracked
batch of the ``repro.engine.distributed.collective`` span, the one jitted
program that every chip runs for a batch (directory traversal, owned
gather and re-rank, all_gather merge). The span syncs the device at its
end, so this is wall time of finished device work plus its dispatch, over
the tracked segment. Moves ``qps``."""

SPANS = ("repro.engine.distributed.collective",)


def read(ctx):
    return ctx.span_ms_per_batch(SPANS)
