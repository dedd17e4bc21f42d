"""Engine layer (core/engine.py): milliseconds per batch of candidate
generation after the query encode, from the program's stage spans over
the tracked segment: ``directory_match`` + ``segmented_gather`` in the
bucket engine, ``dense_match`` + ``dense_select`` in the dense engine.
Each span syncs the device at its end, so this is wall time of finished
device work plus its dispatch. Moves ``qps``."""

SPANS = ("repro.engine.directory_match", "repro.engine.segmented_gather",
         "repro.engine.dense_match", "repro.engine.dense_select")


def read(ctx):
    return ctx.span_ms_per_batch(SPANS)
