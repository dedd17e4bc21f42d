"""Pallas TPU kernel: fused single-pass planned-budget query (DESIGN.md
§17).

One kernel replaces the staged relay (segmented gather -> dense re-rank ->
top_k) on the bucket-traversal hot path: per grid step a block of queries
walks its probe-ordered CSR take-runs (the bucket_probe expansion), scores
one chunk of candidate rows from a *resident* item payload against the
query block, and folds the chunk into a running phase-1 top-k' buffer held
in the revisited output blocks — candidate rows never round-trip through
HBM between stages. On the final chunk the k' survivors alone are rescored
against the resident f32 item rows and the outputs are rewritten in
rescored order; the wrapper slices the leading k columns.

Two-precision split: phase-1 scoring reads ``payload`` (int8 rows + per-
item f32 dequant ``scale``, or the f32 rows themselves with unit scales —
the parity arm), while the rescore always reads the f32 ``items``. With an
f32 payload the phase-1 and rescore scores are identical dots, which makes
the emitted ids bit-identical to the staged planned path (tested).

Padding discipline: candidate slots past a query's take total (chunk-grid
padding, and whole padded query rows whose ``cum`` rows are zero) score
the ``NEG`` sentinel with id -1, so ``_iter_topk``'s masking keeps them
behind every real candidate (kernelcheck K4). The candidate-chunk grid
axis is minor — sequential on TPU — so the output blocks accumulate
safely; declared via ``revisit_dims=(1,)`` (kernelcheck K3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.annotations import KernelAnnotation, SentinelSpec
from repro.kernels.mips_topk import _iter_topk

NEG = -3e38       # sentinel score for padded candidate slots (id -1)

_BP = 128         # candidate chunk width (the wrapper's fixed bp)

# kernelcheck model claims (DESIGN.md §17): the chunk grid dimension
# deliberately revisits the (i, 0) output blocks — the running phase-1
# top-k' buffer is the TPU output-revisiting accumulate, safe only
# because the minor grid axis is sequential. Transient peak: the gathered
# int8 chunk + its f32 dequant + scale/score/position lanes, the run
# expansion's lane iota and masked column tile over (BQ, S+1), five
# (BQ, K' + BP) merge tiles, and the (BQ, K', d) f32 survivor gather of
# the final rescore.
ANNOTATION = KernelAnnotation(
    name="fused_query",
    grid_names=("queries", "cand_chunks"),
    revisit_dims=(1,),
    extra_vmem=lambda ins, outs: (
        ins[0][0] * _BP * (5 * ins[0][1] + 16)
        + 2 * ins[1][0] * ins[1][1] * 4
        + 5 * ins[0][0] * (outs[0][1] + _BP) * 4
        + ins[0][0] * outs[0][1] * (4 * ins[0][1] + 8)),
    sentinel=SentinelSpec(
        kind="vals", value=NEG,
        note="candidate slots past a query's take total (and every slot "
             "of padded query rows) carry score NEG with id -1; the "
             "iterative top-k masks them behind any real candidate"),
    note="payload/scale/items blocks are whole-array resident: the fused "
         "kernel serves shards up to N*d*(1+4+4/d) bytes of half the VMEM "
         "budget; shard (distributed engine) beyond that",
)


def _expand_chunk(cum: jax.Array, starts: jax.Array, base: jax.Array,
                  total: int):
    """CSR run expansion for one chunk of candidate slots.

    cum (BQ, S+1): exclusive prefix of per-run take sizes; starts (BQ, S):
    CSR start of each run's take; base (BQ, BP): global candidate-slot
    index per chunk column. Returns (pos, valid): CSR positions (garbage
    where invalid) and the in-range mask — a slot is valid when it is
    below both the query's runtime take total (masks padded query rows)
    and the static planned width (masks chunk-grid padding).
    """
    S = starts.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)

    def column(x, i):
        # run i's value per query, by a masked row sum: the TPU kernel
        # compiler has no lane-axis dynamic slice
        return jnp.sum(jnp.where(lane[:, :x.shape[1]] == i, x, 0), axis=1,
                       keepdims=True)

    def body(i, off):
        # the last run starting at or before a slot holds it (runs are
        # contiguous and ascending; an empty run yields to the next one)
        lo = column(cum, i)
        return jnp.where(base >= lo, column(starts, i) - lo, off)

    off = jax.lax.fori_loop(0, S, body, jnp.zeros(base.shape, jnp.int32))
    tot = cum[:, S:S + 1]
    valid = (base < tot) & (base < total)
    return base + off, valid


def _fused_kernel(q_ref, cum_ref, st_ref, pay_ref, sc_ref, it_ref,
                  vals_ref, pos_ref, *, kprime: int, bp: int,
                  total: int, n_chunks: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG)
        pos_ref[...] = jnp.full_like(pos_ref, -1)

    q = q_ref[...].astype(jnp.float32)                    # (BQ, d)
    bq = q.shape[0]
    base = nb * bp + jax.lax.broadcasted_iota(jnp.int32, (bq, bp), 1)
    pos, valid = _expand_chunk(cum_ref[...], st_ref[...], base, total)
    safe = jnp.where(valid, pos, 0)
    rows = pay_ref[...][safe].astype(jnp.float32)         # (BQ, BP, d)
    scale = sc_ref[...][:, 0][safe]                       # (BQ, BP)
    scores = jnp.einsum("qd,qpd->qp", q, rows * scale[..., None])
    scores = jnp.where(valid, scores, NEG)

    # buffer columns first: on score ties the earlier chunk (lower CSR
    # position) wins, preserving canonical candidate order
    all_vals = jnp.concatenate([vals_ref[...], scores], axis=-1)
    all_pos = jnp.concatenate([pos_ref[...],
                               jnp.where(valid, pos, -1)], axis=-1)
    mv, mp = _iter_topk(all_vals, all_pos, kprime)
    vals_ref[...] = mv
    pos_ref[...] = mp

    @pl.when(nb == n_chunks - 1)
    def _rescore():
        sp = pos_ref[...]                                 # (BQ, K')
        ok = sp >= 0
        rows = it_ref[...][jnp.where(ok, sp, 0)]          # (BQ, K', d) f32
        rescored = jnp.einsum("qd,qpd->qp", q, rows)
        rescored = jnp.where(ok, rescored, NEG)
        fv, fp = _iter_topk(rescored, sp, kprime)
        vals_ref[...] = fv
        pos_ref[...] = fp


@functools.partial(jax.jit, static_argnames=(
    "k", "kprime", "total", "bq", "bp", "interpret"))
def fused_query_pallas(queries: jax.Array, cum: jax.Array,
                       starts: jax.Array, payload: jax.Array,
                       scale: jax.Array, items: jax.Array, k: int, *,
                       kprime: int, total: int, bq: int = 8,
                       bp: int = _BP, interpret: bool = False):
    """Fused planned-budget query: vals (Q, k') f32, pos (Q, k') i32 CSR
    positions, in rescored order — slice the leading k columns.

    queries (Q, d) f32; cum (Q, S+1) / starts (Q, S) i32 probe-ordered
    take runs (padded query rows carry all-zero cum rows); payload (N, d)
    int8|f32 phase-1 rows; scale (N, 1) f32 dequant scales; items (N, d)
    f32 rescore rows. ``total`` is the static planned width every real
    query's takes sum to. Pre-padded shapes required: Q % bq == 0
    (pad in kernels/ops.py).
    """
    Q, d = queries.shape
    S = starts.shape[1]
    N = items.shape[0]
    if (Q % bq or cum.shape != (Q, S + 1) or payload.shape != (N, d)
            or scale.shape != (N, 1) or kprime < k):
        raise ValueError(
            f"fused_query_pallas precondition: Q={Q} % bq={bq} == 0, cum "
            f"{cum.shape} == (Q, S+1={S + 1}), payload {payload.shape} == "
            f"items {(N, d)}, scale {scale.shape} == ({N}, 1), k={k} <= "
            f"kprime={kprime} (pad in kernels/ops.py)")
    n_chunks = -(-total // bp)
    grid = (Q // bq, n_chunks)      # chunk axis minor => sequential sweep
    vals, pos = pl.pallas_call(
        functools.partial(_fused_kernel, kprime=kprime, bp=bp,
                          total=total, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, S + 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, S), lambda i, j: (i, 0)),
            pl.BlockSpec((N, d), lambda i, j: (0, 0)),
            pl.BlockSpec((N, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((N, d), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, kprime), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kprime), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, kprime), jnp.float32),
            jax.ShapeDtypeStruct((Q, kprime), jnp.int32),
        ],
        interpret=interpret,
    )(queries, cum, starts, payload, scale, items)
    return vals, pos
