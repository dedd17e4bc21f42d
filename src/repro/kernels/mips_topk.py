"""Pallas TPU kernel: blocked exact-MIPS with streaming top-k.

The re-rank stage of the query pipeline (and the exact-MIPS baseline) scores
a query block against the full item matrix and keeps a running top-k. A
naive matmul materializes (Q, N) scores in HBM; for N in the millions that
is the dominant byte cost. This kernel streams item blocks through VMEM and
maintains the running (vals, ids) top-k buffer in the *output* blocks, which
map to the same (0, j)-block for every item step — the TPU "output
revisiting" pattern keeps them resident in VMEM across the whole item loop.

  * grid = (N/BN, Q/BQ) with the item axis OUTER-most-minor (sequential on
    TPU) so each query block finishes its full item sweep before moving on.
  * block-local top-k is K rounds of (max, mask) on the (BQ, BN) score tile —
    K is static and small (<=64), avoiding lax.top_k inside the kernel
    (unsupported lowering on TPU Pallas).
  * merge = same iterative max over the concatenated (BQ, K + BN) tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.annotations import KernelAnnotation, SentinelSpec

NEG = -3.0e38

# kernelcheck model claims (DESIGN.md §16): the item grid dimension
# deliberately revisits the (i, 0) output blocks — the running top-k
# buffer is the canonical TPU output-revisiting accumulate, safe only
# because the minor grid axis is sequential. Transient peak: the (BQ, BN)
# score tile plus five (BQ, K + BN) merge tiles (vals f32, ids i32, the
# column iota, the masked scores and one select). Padded item rows are
# the shard-padding-leak surface: the wrapper's sentinel feature column
# makes them score real_dot - 1e30 so they rank strictly last even
# against negative real scores.
ANNOTATION = KernelAnnotation(
    name="mips_topk",
    grid_names=("queries", "items"),
    revisit_dims=(1,),
    extra_vmem=lambda ins, outs: (
        ins[0][0] * ins[1][0] * 4
        + 5 * ins[0][0] * (ins[1][0] + outs[0][1]) * 4),
    sentinel=SentinelSpec(
        kind="vals", value=-1e30,
        note="padded item rows score real_dot - 1e30 via the appended "
             "sentinel feature column; ids of padded rows must never "
             "surface in the returned top-k"),
)


def _iter_topk(scores: jax.Array, ids: jax.Array, k: int):
    """K rounds of (row max, mask) over the last axis. scores (BQ, M).

    Ties pick the lowest column (argmax's first occurrence). Built from
    row reductions and iota masks only: the TPU kernel compiler has no
    lowering for a per-row gather or scatter.
    """
    bq, m = scores.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    vals = jnp.zeros((bq, k), scores.dtype)
    out_ids = jnp.zeros((bq, k), ids.dtype)
    s = scores
    for r in range(k):
        top = jnp.max(s, axis=-1, keepdims=True)                  # (BQ, 1)
        pos = jnp.min(jnp.where(s == top, col, m), axis=-1,
                      keepdims=True)
        hit = col == pos
        sel = jnp.sum(jnp.where(hit, ids, 0), axis=-1, keepdims=True)
        vals = jnp.where(out_col == r, top, vals)
        out_ids = jnp.where(out_col == r, sel, out_ids)
        s = jnp.where(hit, NEG, s)
    return vals, out_ids


def _topk_kernel(q_ref, it_ref, vals_ref, ids_ref, *, k: int, bn: int,
                 n_blocks: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG)
        ids_ref[...] = jnp.zeros_like(ids_ref)

    q = q_ref[...].astype(jnp.float32)                    # (BQ, d)
    it = it_ref[...].astype(jnp.float32)                  # (BN, d)
    scores = jax.lax.dot_general(
        q, it, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)              # (BQ, BN)
    blk_ids = nb * bn + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)

    all_vals = jnp.concatenate([vals_ref[...], scores], axis=-1)
    all_ids = jnp.concatenate([ids_ref[...], blk_ids], axis=-1)
    vals, ids = _iter_topk(all_vals, all_ids, k)
    vals_ref[...] = vals
    ids_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def mips_topk_pallas(queries: jax.Array, items: jax.Array, k: int, *,
                     bq: int = 8, bn: int = 256,
                     interpret: bool = False):
    """Exact top-k MIPS: (Q, d) x (N, d) -> vals (Q, k) f32, ids (Q, k) i32.

    Pre-padded shapes required: Q % bq == 0, N % bn == 0; k <= bn.
    """
    Q, d = queries.shape
    N, d2 = items.shape
    if d != d2 or Q % bq or N % bn or k > bn:
        raise ValueError(
            f"mips_topk_pallas precondition: queries (Q={Q}, d={d}) vs "
            f"items (N={N}, d={d2}) must share d with Q % {bq} == 0, "
            f"N % {bn} == 0 and k={k} <= {bn} (pad in kernels/ops.py)")
    n_blocks = N // bn
    grid = (Q // bq, n_blocks)          # item axis minor => sequential sweep
    vals, ids = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, bn=bn, n_blocks=n_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, items)
    return vals, ids
