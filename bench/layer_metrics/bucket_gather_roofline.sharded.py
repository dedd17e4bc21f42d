"""Kernel layer (kernels/bucket_probe.py) inside the sharded collective:
per cent of the HBM roofline reached by the segmented candidate gather
that a shard's re-rank loop calls once per block of ``gather_slots``
probed slots. Bytes per call: the (batch, runs + 1) ``cum`` and (batch,
runs) ``starts`` read and the (batch, gather_slots) CSR positions
written (``bench/lib/roofline.py``); the runs are every bucket of the
global directory and one covering run. Time: device time of the
``bucket_gather_pallas`` ops in the trace, over their count, summed over
the chips (a shard that owns no planned slot makes no call). Bounded by
bytes, as ``bucket_gather_roofline``. Moves ``qps``."""

from bench.lib import roofline
from bench.lib.devtrace import op_kind

KERNEL = "bucket_gather_pallas"


def read(ctx):
    rec, s = ctx.recording, ctx.shapes
    if rec is None or ctx.peaks is None or "gather_slots" not in s:
        return None
    ns = [o[4] for dev in rec.devices.values() for o in dev["ops"]
          if op_kind(o[1]) == KERNEL]
    if not ns or sum(ns) <= 0:
        return None
    nbytes = len(ns) * roofline.bucket_gather_bytes(
        s["batch"], s["runs"], s["gather_slots"])
    return roofline.share_pct(nbytes, sum(ns) / 1e9,
                              ctx.peaks["hbm_bytes_per_s"])
