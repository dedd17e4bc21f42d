"""Engine stages layer (core/topk.py): device milliseconds per batch
charged to the exact re-rank, ``re_rank`` (candidate row gather and
float32 inner products) and ``top_k`` (duplicate mask and top-k), over the
profiled segment with no tracker (``bench/lib/stages.py``); the unsynced
device counterpart of ``stage_ms.rerank``. Moves ``qps``."""

from bench.lib.stages import device_ms_per_batch

STAGES = ("re_rank", "top_k")


def read(ctx):
    return device_ms_per_batch(ctx, STAGES)
