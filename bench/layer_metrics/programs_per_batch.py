"""XLA glue between kernels: device program executions per batch in the
profiled segment (the ``XLA Modules`` line of the trace, per device). The
query path runs eagerly, one program per primitive, so this counts the
launches a batch pays for; it repeats exactly from batch to batch.
Moves ``qps``."""


def read(ctx):
    rec = ctx.recording
    if rec is None or ctx.traced_batches <= 0:
        return None
    w0, w1 = rec.window
    devs = [d for d in rec.devices.values() if d["modules"]]
    n = sum(sum(1 for m in d["modules"] if w0 <= m[1] < w1) for d in devs)
    if n <= 0:
        return None
    return n / len(devs) / ctx.traced_batches
