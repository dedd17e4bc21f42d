"""Kernel layer (kernels/hamming.py): per cent of the HBM roofline
reached by the packed Hamming scan, the query codes against the bucket
directory (bucket engine) or against every item code (dense engine).
Bytes per call: both code tables read once and the (batch, rows) int32
counts written (``bench/lib/roofline.py``); time: device time of the
``hamming_pallas`` program's ops in the trace, over its executions.
Bounded by bytes: no peak is published for the VPU's integer work.
Moves ``qps``."""

from bench.lib import roofline

PROGRAM = "hamming_pallas"


def read(ctx):
    rec, s = ctx.recording, ctx.shapes
    if rec is None or ctx.peaks is None or not s:
        return None
    secs, calls = rec.module_calls(lambda m: PROGRAM in m)
    if calls <= 0 or secs <= 0:
        return None
    rows = s["num_buckets"] if s["engine"] == "bucket" else s["num_items"]
    nbytes = calls * roofline.hamming_scan_bytes(s["batch"], rows,
                                                 s["code_words"])
    return roofline.share_pct(nbytes, secs, ctx.peaks["hbm_bytes_per_s"])
