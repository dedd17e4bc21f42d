"""Sharded layout layer (core/distributed.py, ``build_sharded``): how
unevenly the planned probes fall on the shards. Each shard reports the
planned slots it owns per query of a batch (the tracker observations
``repro.engine.distributed.owned_probes.shard<s>``); this is the largest
shard's mean over the mean across shards, over the tracked segment. 1.0
is balanced; with S shards, S means one shard owns every probe and
re-ranks alone while the others wait at the merge. Moves ``qps``."""

PREFIX = "repro.engine.distributed.owned_probes.shard"


def read(ctx):
    means = [ctx.mean(name) for name in ctx.tracked
             if name.startswith(PREFIX)]
    means = [m for m in means if m is not None]
    if not means or sum(means) <= 0:
        return None
    return max(means) / (sum(means) / len(means))
