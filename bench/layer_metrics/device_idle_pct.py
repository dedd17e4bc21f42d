"""Device layer: per cent of the profiled window in which no op ran on
the chip (1 - busy / window), over a segment with no tracker attached, so
no span sync adds idle time. Moves ``qps``."""


def read(ctx):
    rec = ctx.recording
    if rec is None or rec.window_s <= 0 or rec.busy_s() <= 0:
        return None
    return 100.0 * (1.0 - rec.busy_s() / rec.window_s)
