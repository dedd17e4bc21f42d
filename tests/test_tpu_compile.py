"""Compile the main-path kernels for a described TPU v5e, at the widths
``chip_smoke.py`` runs (N = 2^20 items, d = 128, Q = 128 queries), and
the sharded query for a described four-chip host.

Interpret mode accepts what the TPU kernel compiler (Mosaic) refuses:
unsigned reductions, lane-axis dynamic slices, per-row gathers, more VMEM
than a kernel may use. Each case lowers an ops wrapper with
``impl="pallas"`` as it runs on the chip, the XLA padding and layout
around the kernel included, and compiles it for one described chip; the
sharded cases compile the distributed engine's programs for four.
Nothing runs: no result and no time comes from here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distributed as dist
from repro.core.family import SimpleLSHFamily
from repro.core.index import IndexSpec
from repro.kernels import ops

N = 1 << 20            # smoke catalog size
D = 128                # imagenet-profile width
Q = 128                # smoke query batch
CAL_Q = 256            # planner calibration batch (planner.DEFAULT_CAL_QUERIES)
B = 1 << 16            # bucket directory bound at code_len=16, m=32
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # entries compiled for an absent chip cannot be read back: keep
        # them out of any persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        # the wrappers pick the compiled kernel (not interpret mode) when
        # they see a TPU; steer them to it while lowering
        mp.setattr(ops, "_on_tpu", lambda: True)
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",))


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit HBM"
    return compiled


def test_hash_encode_items(one_chip):
    """Index build: every item encoded at code_len=16, m=32 (11 bits)."""
    fam = SimpleLSHFamily()
    fn = functools.partial(fam.encode_items, impl="pallas")
    _compile(lambda A, x, u: fn(A, x, u), one_chip,
             ((D + 1, 11), jnp.float32), ((N, D), jnp.float32),
             ((N,), jnp.float32))


@pytest.mark.parametrize("q", [Q, CAL_Q])
def test_hamming_scan_items(one_chip, q):
    """Dense match over every item code (queries, and calibration)."""
    _compile(functools.partial(ops.hamming_scan, impl="pallas"), one_chip,
             ((q, 1), jnp.uint32), ((N, 1), jnp.uint32))


def test_bucket_match_directory(one_chip):
    _compile(functools.partial(ops.bucket_match, hash_bits=11,
                               impl="pallas"), one_chip,
             ((Q, 1), jnp.uint32), ((B, 1), jnp.uint32))


@pytest.mark.parametrize("runs,slots", [
    (N // 100, N // 100),      # num_probe = N/100: S = min(B, P) runs
    (B, 1 << 15),              # planned walk, every range budgeted: S = B
    (38, 1 << 15),             # planned walk, one range budgeted: S' runs
])
def test_bucket_gather(one_chip, runs, slots):
    _compile(functools.partial(ops.bucket_gather, num_probe=slots,
                               impl="pallas"), one_chip,
             ((Q, runs + 1), jnp.int32), ((Q, runs), jnp.int32))


def test_mips_topk_items(one_chip):
    _compile(functools.partial(ops.mips_topk, k=10, impl="pallas"),
             one_chip, ((Q, D), jnp.float32), ((N, D), jnp.float32))


@pytest.mark.parametrize("engine", ["bucket", "dense"])
def test_sharded_query(four_chips, engine):
    """The sharded query on four chips, laid out as ``shard_index`` places
    it. A kernel called on arrays that span the mesh outside a shard_map
    is refused ("Mosaic kernels cannot be automatically partitioned"), so
    the query encode and the per-shard traversal must both be manual.
    Shapes are cut to N = 2^16: partitioning does not depend on size."""
    n, b, hb, m = 1 << 16, 4000, 11, 32
    rep, row, row2 = (NamedSharding(four_chips, s)
                      for s in (P(), P("data"), P("data", None)))

    def s(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    idx = dist.ShardedIndex(
        spec=IndexSpec(family="simple", code_len=16, m=m),
        params=s((D + 1, hb), f32, rep), rank=s((m, hb + 1), i32, rep),
        dir_code=s((b, 1), u32, rep), dir_rid=s((b,), i32, rep),
        dir_size=s((b,), i32, rep), dir_shard=s((b,), i32, rep),
        dir_local_start=s((b,), i32, rep), items=s((n, D), f32, row2),
        codes=s((n, 1), u32, row2), range_id=s((n,), i32, row),
        bucket_of=s((n,), i32, row), bucket_off=s((n,), i32, row),
        perm=s((n,), i32, row), valid=s((n,), jnp.bool_, row),
        num_shards=4, rows_per_shard=n // 4, num_items=n, hash_bits=hb)
    eng = dist.DistributedEngine(idx, four_chips, engine=engine)
    queries = s((Q, D), f32, rep)
    assert "tpu_custom_call" in eng._encode.lower(
        idx.params, queries).compile().as_text()
    mapped = eng._mapped(n // 100, 10).lower(
        s((Q, 1), u32, rep), queries, idx.params, idx.dir_code,
        idx.dir_rid, idx.dir_size, idx.dir_shard, idx.dir_local_start,
        idx.rank, idx.items, idx.codes, idx.range_id, idx.bucket_of,
        idx.bucket_off, idx.perm, idx.valid)
    assert "tpu_custom_call" in mapped.compile().as_text()
