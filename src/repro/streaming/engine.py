"""Merged candidate generation: base bucket store + delta buffer, exact
(DESIGN.md §9).

The contract is bit-parity with a from-scratch rebuild: for any interleaving
of inserts and deletes, the merged candidate sequence equals the canonical
``(rank[j, l], CSR position)`` sequence of a bucket store rebuilt over the
mutated dataset (frozen hash functions / current ``U_j``). Three pieces make
one stable sort sufficient:

  * base arm — the normal bucket traversal (or dense scan), over-probed to
    ``probe_base = min(N_csr, num_probe + max_tombstones)`` so that after
    masking at most ``max_tombstones`` dead rows, at least ``num_probe``
    live base candidates survive in canonical order;
  * delta arm — one ``delta_scan`` over the buffer; dead slots come back as
    ``-1`` and rank as ``RANK_SENTINEL`` (sorted last). Columns are
    pre-arranged by the buffer's canonical ``perm``;
  * merge — two-pass LSD stable sort by ``(rank, ord)`` where ``ord`` is
    the directory-position ordinal (base bucket ``b`` -> ``2b``; delta
    slots carry their host-computed placement). Ties in ``(rank, ord)``
    mean "same bucket" (or distinct new delta buckets in one directory
    gap), and the pre-arranged column order — base CSR order first, then
    delta slots in ``(range_id, code, id)`` order — is exactly the
    canonical tie order, so stability finishes the job.

Everything is jit-static in shape: delta capacity, ``probe_base`` and the
bucket count only change at structural events (compaction, repartition),
so steady-state insert/delete/query traffic never recompiles.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.topk import EXACT
from repro.kernels import ops

RANK_SENTINEL = jnp.iinfo(jnp.int32).max


def _base_arm(arrs: Dict[str, jax.Array], q_codes: jax.Array,
              probe_base: int, hash_bits: int, engine: str, impl: str
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(rank, ord, id) of the first ``probe_base`` base-store candidates in
    canonical order; dead (tombstoned) rows carry RANK_SENTINEL."""
    if engine == "bucket":
        matches = ops.bucket_match(q_codes, arrs["bucket_code"], hash_bits,
                                   impl=impl)                       # (Q, B)
        brank = arrs["rank"][arrs["bucket_rid"][None, :], matches]
        order = jnp.argsort(brank, axis=-1, stable=True)
        B = arrs["bucket_rid"].shape[0]
        sel = order[:, :min(B, probe_base)]
        sizes = (arrs["bucket_start"][1:] - arrs["bucket_start"][:-1])[sel]
        starts = arrs["bucket_start"][:-1][sel]
        cum = jnp.concatenate(
            [jnp.zeros((sel.shape[0], 1), jnp.int32),
             jnp.cumsum(sizes, axis=-1, dtype=jnp.int32)], axis=-1)
        csr_pos = ops.bucket_gather(cum, starts, probe_base, impl=impl)
        bucket_of = arrs["csr_bucket"][csr_pos]
        base_rank = jnp.take_along_axis(brank, bucket_of, axis=1)
    else:  # dense scan over the CSR-ordered code table
        m_csr = ops.bucket_match(q_codes, arrs["csr_codes"], hash_bits,
                                 impl=impl)                         # (Q, N)
        rank_csr = arrs["rank"][arrs["csr_rid"][None, :], m_csr]
        order = jnp.argsort(rank_csr, axis=-1, stable=True)
        csr_pos = order[:, :probe_base]
        base_rank = jnp.take_along_axis(rank_csr, csr_pos, axis=1)
        bucket_of = arrs["csr_bucket"][csr_pos]
    base_ids = arrs["item_ids"][csr_pos]
    dead = ~arrs["live"][base_ids]
    base_rank = jnp.where(dead, RANK_SENTINEL, base_rank)
    return base_rank, 2 * bucket_of, base_ids


def _delta_arm(arrs: Dict[str, jax.Array], q_codes: jax.Array,
               hash_bits: int, impl: str
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(rank, ord, id) of every delta slot, columns in canonical ``perm``
    order; dead slots carry RANK_SENTINEL."""
    dm = ops.delta_scan(q_codes, arrs["d_codes"], arrs["d_live"], hash_bits,
                        impl=impl)                                  # (Q, C)
    d_rank = arrs["rank"][arrs["d_rid"][None, :], jnp.maximum(dm, 0)]
    d_rank = jnp.where(dm < 0, RANK_SENTINEL, d_rank)
    perm = arrs["d_perm"]
    Q, C = dm.shape
    d_rank = d_rank[:, perm]
    d_ord = jnp.broadcast_to(arrs["d_ord"][perm][None, :], (Q, C))
    d_ids = jnp.broadcast_to(arrs["d_ids"][perm][None, :], (Q, C))
    return d_rank, d_ord, d_ids


@functools.partial(jax.jit, static_argnames=(
    "num_probe", "probe_base", "hash_bits", "engine", "impl"))
def merged_candidates(arrs: Dict[str, jax.Array], q_codes: jax.Array, *,
                      num_probe: int, probe_base: int, hash_bits: int,
                      engine: str, impl: str) -> jax.Array:
    """(Q, num_probe) global item ids over base + delta, bit-identical to a
    from-scratch rebuild on the mutated dataset (host wrapper guarantees
    ``num_probe`` <= live item count)."""
    if probe_base > 0:
        b_rank, b_ord, b_ids = _base_arm(arrs, q_codes, probe_base,
                                         hash_bits, engine, impl)
        d_rank, d_ord, d_ids = _delta_arm(arrs, q_codes, hash_bits, impl)
        rank_all = jnp.concatenate([b_rank, d_rank], axis=1)
        ord_all = jnp.concatenate([b_ord, d_ord], axis=1)
        ids_all = jnp.concatenate([b_ids, d_ids], axis=1)
    else:  # base store empty (everything lives in the delta)
        rank_all, ord_all, ids_all = _delta_arm(arrs, q_codes, hash_bits,
                                                impl)
    # LSD two-pass stable sort: secondary key ord, then primary key rank.
    o1 = jnp.argsort(ord_all, axis=-1, stable=True)
    r1 = jnp.take_along_axis(rank_all, o1, axis=1)
    o2 = jnp.argsort(r1, axis=-1, stable=True)
    morder = jnp.take_along_axis(o1, o2, axis=1)
    return jnp.take_along_axis(ids_all, morder[:, :num_probe], axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
def merged_rerank(store_items: jax.Array, delta_items: jax.Array,
                  store_live: jax.Array, delta_live: jax.Array,
                  queries: jax.Array, cand: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Exact re-rank with the two-source gather: global id < N_store reads
    the base store, otherwise delta slot ``id - N_store``. Dead candidates
    score ``-inf`` — a probe budget past the live count pads the candidate
    tail with tombstoned rows (they sort last), and masking here keeps the
    budget a *structural* shape (it never tracks the live count)."""
    n_store = store_items.shape[0]
    in_base = cand < n_store
    base_pos = jnp.clip(cand, 0, n_store - 1)
    slot = jnp.clip(cand - n_store, 0, delta_items.shape[0] - 1)
    vecs = jnp.where(in_base[..., None], store_items[base_pos],
                     delta_items[slot])
    live = jnp.where(in_base, store_live[base_pos], delta_live[slot])
    scores = jnp.einsum("qd,qpd->qp", queries, vecs, precision=EXACT)
    scores = jnp.where(live, scores, -jnp.inf)
    vals, pos = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(cand, pos, axis=1)
