"""Device layer: GiB in use by the chip's allocator once set-up is over:
the arrays of ``index_hbm_gib`` and the runtime's own allocations that no
array owns (programs, buffers it keeps). Moves ``index_hbm_gib``: state a
program keeps on the chip outside its arrays shows here alone."""

GIB = float(1 << 30)


def read(ctx):
    b = ctx.memory.get("bytes_in_use")
    return b / GIB if b else None
