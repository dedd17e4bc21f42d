"""Pallas TPU kernel: fused ``sign(x @ A [+ tail * a_tail])`` + bit-pack.

Index build cost is dominated by encoding: an (N, d) x (d, L) matmul whose
output is immediately collapsed to N*L bits. Materializing the f32 projection
in HBM wastes 32x the bytes actually needed; this kernel keeps the projection
block in VMEM, applies sign, packs 32 bits per uint32 word, and writes only
the packed codes to HBM.

TPU mapping (DESIGN.md §7):
  * grid = (N/BN, L/BL, d/BD), K-dim innermost so the f32 accumulator block
    (BN, BL) lives in VMEM scratch across the K loop (MXU-friendly matmul).
  * BN=128 rows, BL=128 bits (both multiples of the 128-lane MXU),
    BD<=512 K-slab.
  * the SIMPLE-LSH augmentation [x; sqrt(1-||x||^2)] is folded in as a rank-1
    update ``tail * a_tail`` on the last K step — the augmented vector never
    exists in HBM.
  * on the last K step the block is sign-ed and packed: (BN, BL) bits ->
    (BN, BL/32) uint32 (LSB-first within a word, matching
    ``repro.core.hashing.pack_bits``).

The ops.py wrapper pads N/L/d to block multiples (zero padding is sign-safe:
padded rows/cols are sliced away, padded K contributes 0 to the dot).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.annotations import KernelAnnotation, SentinelSpec

WORD = 32

# kernelcheck model claims (DESIGN.md §16): the K-slab grid dimension
# deliberately revisits the (i, j) output block (the f32 accumulator lives
# in scratch across the K loop — sequential-grid accumulate, NOT safe under
# "arbitrary" grid semantics); the transient peak is on the final K step:
# the (BN, BL) projection plus its power-of-two bit terms. Row/column padding
# is sliced off by the wrapper; in-word bit padding is masked to 0 in the
# last uint32 word (sign(0) = 1 would otherwise pollute Hamming distances).
ANNOTATION = KernelAnnotation(
    name="hash_encode",
    grid_names=("rows", "code_bits", "k_slab"),
    revisit_dims=(2,),
    extra_vmem=lambda ins, outs: 2 * ins[0][0] * ins[1][1] * 4,
    sentinel=SentinelSpec(
        kind="bits", value=0,
        note="padding bits of the final packed word are masked to 0"),
    pad_contained=True,
)


def _encode_kernel(x_ref, a_ref, tail_ref, atail_ref, out_ref, acc_ref, *,
                   n_k: int):
    """One (BN, BL) output block; K-loop accumulates into acc_ref."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == n_k - 1)
    def _finish():
        proj = acc_ref[...] + tail_ref[...] * atail_ref[...]
        bl = proj.shape[1]
        # bit b of word w sits in lane 32w + b. Words are summed in int32
        # (the TPU kernel compiler has no unsigned reduction); the terms
        # are distinct powers of two, so no sum carries and the int32
        # pattern is the packed word.
        shift = jax.lax.broadcasted_iota(jnp.int32, (1, bl), 1) % WORD
        terms = jnp.where(proj >= 0.0, jnp.left_shift(1, shift), 0)
        for w in range(bl // WORD):
            out_ref[:, w:w + 1] = jax.lax.bitcast_convert_type(
                jnp.sum(terms[:, w * WORD:(w + 1) * WORD], axis=1,
                        keepdims=True), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bn", "bl", "bd", "interpret"))
def hash_encode_pallas(x: jax.Array, A: jax.Array, tail: jax.Array,
                       a_tail: jax.Array, *, bn: int = 128, bl: int = 128,
                       bd: int = 512, interpret: bool = False) -> jax.Array:
    """Fused encode. Shapes must be pre-padded: N%bn == L%bl == d%bd == 0.

    Args:
      x:      (N, d) f32 — items, already normalized by their range's U_j.
      A:      (d, L) f32 — random projections (the first d rows of the
              (d+1, L) SIMPLE-LSH projection matrix).
      tail:   (N, 1) f32 — ``sqrt(1 - ||x||^2)`` augmentation coordinate
              (zeros to disable the fold).
      a_tail: (1, L) f32 — last projection row.

    Returns: (N, L//32) uint32 packed codes.
    """
    N, d = x.shape
    L = A.shape[1]
    if N % bn or L % bl or d % bd or bl % WORD:
        raise ValueError(
            f"hash_encode_pallas precondition: N={N} % {bn}, L={L} % "
            f"{bl}, d={d} % {bd} and bl={bl} % {WORD} must all be 0 "
            f"(pad in kernels/ops.py)")
    n_k = d // bd
    grid = (N // bn, L // bl, n_k)

    return pl.pallas_call(
        functools.partial(_encode_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bd, bl), lambda i, j, k: (k, j)),   # A
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, 0)),    # tail
            pl.BlockSpec((1, bl), lambda i, j, k: (0, j)),    # a_tail
        ],
        out_specs=pl.BlockSpec((bn, bl // WORD), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, L // WORD), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, bl), jnp.float32)],
        interpret=interpret,
    )(x, A, tail, a_tail)
