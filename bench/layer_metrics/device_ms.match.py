"""Engine stages layer (core/engine.py): device milliseconds per batch
charged to the match stage, ``directory_match`` (bucket engine) or
``dense_match`` (dense engine): every device program launched inside the
stage's annotation, linked to its launch (``bench/lib/stages.py``), over
the profiled segment with no tracker. Unsynced device time, unlike the
synced wall time of ``stage_ms.traverse``. Moves ``qps``."""

from bench.lib.stages import device_ms_per_batch

STAGES = ("directory_match", "dense_match")


def read(ctx):
    return device_ms_per_batch(ctx, STAGES)
