"""Engine stages layer (core/engine.py): device milliseconds per batch
charged to ``planned_take``, the per-range budget walk over the
probe-ordered directory (the eager loop over ranges of
``range_cum_before``), over the profiled segment with no tracker
(``bench/lib/stages.py``). Moves ``qps``."""

from bench.lib.stages import device_ms_per_batch

STAGES = ("planned_take",)


def read(ctx):
    return device_ms_per_batch(ctx, STAGES)
