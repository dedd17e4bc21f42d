"""Pallas TPU kernels for the bucket-store query engine (DESIGN.md §5).

The bucketed realization of Algorithm 2 replaces the dense (Q, N) Hamming
scan + O(N log N) argsort with work proportional to the *bucket directory*
(B = #occupied (range_id, code) buckets, B <= N and typically B << N for the
paper's short codes):

  * :func:`bucket_match_pallas` — XOR + popcount the query codes against the
    (B, W) bucket directory and emit *match counts* ``l = hash_bits - ham``
    (the quantity eq. 12 consumes). Same VPU tiling as the dense Hamming
    kernel, just over the directory instead of the item table.
  * :func:`bucket_gather_pallas` — the segmented candidate gather: given the
    per-query probe-ordered bucket runs as CSR (cum, starts) arrays, compute
    for every output slot ``p`` the CSR position of the p-th probed item.
    This is the ragged "walk buckets until the budget is met" loop expressed
    as a dense VPU computation: one pass over the selected buckets with a
    (BQ, P) membership mask per bucket — O(S * P) VPU ops per query block,
    no dynamic gathers inside the kernel (the final ``item_ids[csr_pos]``
    lookup is one XLA take outside).

TPU mapping (DESIGN.md §7): match = (BQ, BB, W) XOR/popcount tile in VMEM;
gather = int32 (BQ, P) accumulator updated by a fori_loop over the S
selected buckets (S <= num_probe, both static).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.annotations import KernelAnnotation

LANES = 128        # queries per gather block (the lane width)
GATHER_BS = 1024   # runs per gather grid step
GATHER_BP = 512    # probed slots per gather grid step

# kernelcheck model claims (DESIGN.md §16). Both kernels partition their
# output grid bijectively (no revisiting); both wrappers slice every padded
# row/column off before returning. Transient peaks: the match kernel
# broadcasts a (BQ, BB, W) XOR tile + int32 popcount tile and reduces to a
# (BQ, BB) block; the gather kernel keeps its (BP, 128) loop carry, slot
# iota and compare/select tile live, plus one reduced copy of the run
# block for the skip tests. Its run grid dimension revisits the output
# block on purpose (the sequential overwrite across run blocks).
MATCH_ANNOTATION = KernelAnnotation(
    name="bucket_match",
    grid_names=("queries", "buckets"),
    extra_vmem=lambda ins, outs: (
        2 * ins[0][0] * ins[1][0] * ins[0][1] * 4
        + ins[0][0] * ins[1][0] * 4),
    pad_contained=True,
)
GATHER_ANNOTATION = KernelAnnotation(
    name="bucket_gather",
    grid_names=("queries", "slots", "runs"),
    revisit_dims=(2,),
    extra_vmem=lambda ins, outs: (3 * outs[0][0] * outs[0][1] * 4
                                  + ins[0][0] * ins[0][1] * 4),
    pad_contained=True,
    note="padded query lanes carry a single covering run and padded runs "
         "start past every slot, so the walk stays in-contract; both are "
         "sliced off",
)


def _match_kernel(q_ref, db_ref, out_ref, *, hash_bits: int):
    q = q_ref[...]                     # (BQ, W) uint32
    db = db_ref[...]                   # (BB, W) uint32
    x = jnp.bitwise_xor(q[:, None, :], db[None, :, :])
    pop = jax.lax.population_count(x).astype(jnp.int32)
    out_ref[...] = hash_bits - jnp.sum(pop, axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("hash_bits", "bq", "bb", "interpret"))
def bucket_match_pallas(q_codes: jax.Array, bucket_codes: jax.Array, *,
                        hash_bits: int, bq: int = 64, bb: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Match counts of queries against the bucket directory.

    Args:
      q_codes:      (Q, W) uint32, Q % bq == 0.
      bucket_codes: (B, W) uint32, B % bb == 0.

    Returns: (Q, B) int32 — ``hash_bits - hamming`` per (query, bucket).
    """
    Q, W = q_codes.shape
    B, W2 = bucket_codes.shape
    if W != W2 or Q % bq or B % bb:
        raise ValueError(
            f"bucket_match_pallas precondition: codes (Q={Q}, W={W}) vs "
            f"directory (B={B}, W={W2}) must share W with Q % {bq} == 0 "
            f"and B % {bb} == 0 (pad in kernels/ops.py)")
    grid = (Q // bq, B // bb)
    return pl.pallas_call(
        functools.partial(_match_kernel, hash_bits=hash_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, W), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, B), jnp.int32),
        interpret=interpret,
    )(q_codes, bucket_codes)


def _gather_kernel(lo_ref, off_ref, out_ref, *, bp: int, n_s: int):
    """Transposed layout: slots on sublanes, queries on lanes.

    out[p, q] = p + off[j, q] for the last run j with lo[j, q] <= p, where
    off = start - lo. The runs are contiguous and ascending, so that run
    is the one holding slot p (an empty run shares its lo with the next
    run, which comes later and wins). The run axis is the minor grid axis:
    the output block accumulates the overwrite across run blocks.
    """
    pi = pl.program_id(1)
    s = pl.program_id(2)
    bs = lo_ref.shape[0]

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = pi * bp + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    p_lo = pi * bp

    # whole-block shortcuts: a run block that starts after this slot
    # chunk changes nothing; one that lies wholly at or before its first
    # slot leaves every slot on the block's last run.
    @pl.when(jnp.logical_and(jnp.min(lo_ref[...]) < p_lo + bp,
                             jnp.max(lo_ref[...]) > p_lo))
    def _walk():
        def body(t, acc):
            lo = lo_ref[pl.ds(t, 1), :]                            # (1, BQ)
            off = off_ref[pl.ds(t, 1), :]
            return jnp.where(p >= lo, off, acc)

        out_ref[...] = jax.lax.fori_loop(0, bs, body, out_ref[...])

    @pl.when(jnp.max(lo_ref[...]) <= p_lo)
    def _before():
        out_ref[...] = jnp.broadcast_to(off_ref[pl.ds(bs - 1, 1), :],
                                        out_ref.shape)

    @pl.when(s == n_s - 1)
    def _finish():
        out_ref[...] = out_ref[...] + p


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "bp", "interpret"))
def bucket_gather_pallas(lo: jax.Array, off: jax.Array, num_slots: int, *,
                         bp: int, interpret: bool = False) -> jax.Array:
    """Segmented candidate gather in the transposed layout.

    Args:
      lo:  (S, Q) int32 — first probed slot of each run (``cum[:, :-1]``
           transposed); padded runs carry a value past every slot.
      off: (S, Q) int32 — CSR start minus first slot of each run.
      num_slots: output rows, a multiple of ``bp``.

    Returns: (num_slots, Q) int32 — the CSR position of each probed slot.
    Pre-padded shapes required: S % 8 == 0, Q % 128 == 0 (pad in
    kernels/ops.py). Runs stream in blocks of up to ``GATHER_BS``.
    """
    S, Q = lo.shape
    bs = min(GATHER_BS, S)
    if off.shape != (S, Q) or S % bs or Q % LANES or num_slots % bp:
        raise ValueError(
            f"bucket_gather_pallas precondition: lo {lo.shape} == off "
            f"{off.shape} with S % {bs} == 0, Q % {LANES} == 0 and "
            f"num_slots={num_slots} % {bp} == 0 (pad in kernels/ops.py)")
    n_s = S // bs
    grid = (Q // LANES, num_slots // bp, n_s)    # runs minor: sequential
    return pl.pallas_call(
        functools.partial(_gather_kernel, bp=bp, n_s=n_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, LANES), lambda i, j, s: (s, i)),
            pl.BlockSpec((bs, LANES), lambda i, j, s: (s, i)),
        ],
        out_specs=pl.BlockSpec((bp, LANES), lambda i, j, s: (j, i)),
        out_shape=jax.ShapeDtypeStruct((num_slots, Q), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lo, off)
