"""Engine layer (core/topk.py): milliseconds per batch of the exact
re-rank, the ``re_rank`` (candidate row gather and float32 inner
products) and ``top_k`` (duplicate mask and top-k) spans over the tracked
segment. Moves ``qps``."""

SPANS = ("repro.engine.re_rank", "repro.engine.top_k")


def read(ctx):
    return ctx.span_ms_per_batch(SPANS)
