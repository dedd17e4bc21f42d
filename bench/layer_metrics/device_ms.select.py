"""Engine stages layer (core/engine.py): device milliseconds per batch
charged to candidate selection, ``segmented_gather`` (bucket engine) or
``dense_select`` (dense engine), their child ``planned_take`` included
(``bench/lib/stages.py``), over the profiled segment with no tracker.
Moves ``qps``."""

from bench.lib.stages import device_ms_per_batch

STAGES = ("segmented_gather", "dense_select", "planned_take")


def read(ctx):
    return device_ms_per_batch(ctx, STAGES)
