"""Exact MIPS oracles, candidate re-ranking, and recall metrics."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import span_or_null

# f32 products and sums throughout: the TPU's default matmul precision
# rounds f32 inputs to bf16, which is not an exact inner product
EXACT = jax.lax.Precision.HIGHEST


def exact_mips(queries: jax.Array, items: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Brute-force top-k MIPS: (Q, d) x (N, d) -> values (Q, k), ids (Q, k)."""
    scores = jnp.matmul(queries, items.T, precision=EXACT)
    return jax.lax.top_k(scores, k)


def rerank(queries: jax.Array, items: jax.Array, cand_ids: jax.Array, k: int,
           *, tracker=None) -> Tuple[jax.Array, jax.Array]:
    """Exact re-rank of per-query candidates.

    ``cand_ids``: (Q, P) item indices (may repeat — bucket padding/fill
    duplicates). Repeated ids are masked down to their first occurrence
    before the top-k, so one item can never claim two result slots (the
    exact_mips oracle scores each item once; unmasked repeats silently
    diverged from it). Returns top-k values and *item* ids (Q, k) by true
    inner product. ``tracker`` adds re_rank/top_k stage spans (host-side
    sync points — only pass one from eager callers, never from inside
    jitted code).
    """
    Q = cand_ids.shape[0]
    with span_or_null(tracker, "repro.engine.re_rank") as sp:
        cand = items[cand_ids]                              # (Q, P, d)
        scores = sp.sync(jnp.einsum("qd,qpd->qp", queries, cand,
                                    precision=EXACT))
    with span_or_null(tracker, "repro.engine.top_k") as sp:
        # first-occurrence duplicate mask without the (Q, P, P) blowup:
        # stable-sort ids per row, flag equal neighbors, scatter back.
        # Unique rows (every engine path) are left bit-identical.
        order = jnp.argsort(cand_ids, axis=1, stable=True)
        sorted_ids = jnp.take_along_axis(cand_ids, order, axis=1)
        dup_sorted = jnp.concatenate(
            [jnp.zeros((Q, 1), jnp.bool_),
             sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=1)
        dup = jnp.zeros_like(dup_sorted).at[
            jnp.arange(Q)[:, None], order].set(dup_sorted)
        scores = jnp.where(dup, jnp.finfo(scores.dtype).min, scores)
        vals, pos = jax.lax.top_k(scores, k)
        ids = sp.sync(jnp.take_along_axis(cand_ids, pos, axis=1))
    return vals, ids


def recall_at(retrieved: jax.Array, truth: jax.Array) -> jax.Array:
    """Mean fraction of ``truth`` ids (Q, k) present in ``retrieved`` (Q, P)."""
    hit = (retrieved[:, :, None] == truth[:, None, :]).any(axis=1)  # (Q, k)
    return jnp.mean(hit.astype(jnp.float32))


def probed_recall_curve(probe_order: jax.Array, truth: jax.Array,
                        probe_counts: jax.Array) -> jax.Array:
    """Recall@T of the *probing order* for each T in ``probe_counts``.

    ``probe_order``: (Q, N) item ids sorted by descending probe priority —
    the first T entries are "the items probed after T probes". Used to draw
    the paper's Fig 2 probed item-recall curves.

    Returns (len(probe_counts),) mean recall of the top-k truth set.
    """
    q, n = probe_order.shape
    k = truth.shape[1]
    # rank position of every item for every query
    pos = jnp.zeros((q, n), jnp.int32)
    pos = pos.at[jnp.arange(q)[:, None], probe_order].set(
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (q, n)))
    truth_pos = jnp.take_along_axis(pos, truth, axis=1)       # (Q, k)
    # recall@T = fraction of truth with rank < T
    return jnp.stack([
        jnp.mean((truth_pos < t).astype(jnp.float32)) for t in probe_counts])
