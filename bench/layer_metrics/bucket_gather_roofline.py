"""Kernel layer (kernels/bucket_probe.py): per cent of the HBM roofline
reached by the segmented candidate gather of the bucket engine. Bytes per
call: the (batch, runs + 1) ``cum`` and (batch, runs) ``starts`` read and
the (batch, probe width) CSR positions written, whatever the kernel loops
over (``bench/lib/roofline.py``); on the planned path the runs are every
bucket. Time: device time of the ``bucket_gather_pallas`` program's ops
in the trace, over its executions. Bounded by bytes: no peak is published
for the VPU's integer work. Moves ``qps``."""

from bench.lib import roofline

PROGRAM = "bucket_gather_pallas"


def read(ctx):
    rec, s = ctx.recording, ctx.shapes
    if rec is None or ctx.peaks is None or not s:
        return None
    secs, calls = rec.module_calls(lambda m: PROGRAM in m)
    if calls <= 0 or secs <= 0:
        return None
    nbytes = calls * roofline.bucket_gather_bytes(s["batch"], s["runs"],
                                                  s["probe_width"])
    return roofline.share_pct(nbytes, secs, ctx.peaks["hbm_bytes_per_s"])
