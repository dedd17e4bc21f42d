"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references: each kernel test sweeps shapes/dtypes
and asserts the Pallas output (interpret mode on CPU, compiled on TPU)
matches these functions exactly (integer outputs) or to fp tolerance.

They are also the production fallback on non-TPU backends — XLA compiles
them well on CPU/GPU, while the Pallas versions are TPU-tiled.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.hashing import pack_bits


def hash_encode_ref(x: jax.Array, A: jax.Array,
                    tail: Optional[jax.Array] = None,
                    a_tail: Optional[jax.Array] = None) -> jax.Array:
    """Oracle for the fused encode kernel.

    ``x``: (N, d) items (already divided by their range's U_j),
    ``A``: (d, L) projections. If ``tail`` (N,) and ``a_tail`` (L,) are given,
    the SIMPLE-LSH augmentation ``tail * a_tail`` is added to the projection
    (eq. 8 folded, DESIGN.md §3). Returns packed (N, ceil(L/32)) uint32.
    """
    proj = jnp.matmul(x.astype(jnp.float32), A.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    if tail is not None:
        proj = proj + tail.astype(jnp.float32)[:, None] * a_tail[None, :]
    return pack_bits((proj >= 0.0).astype(jnp.uint8))


def hamming_ref(q_codes: jax.Array, db_codes: jax.Array) -> jax.Array:
    """Oracle for the Hamming-scan kernel: (Q, W) x (N, W) -> (Q, N) int32."""
    x = jnp.bitwise_xor(q_codes[:, None, :], db_codes[None, :, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def mips_topk_ref(queries: jax.Array, items: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the streaming top-k kernel: exact matmul + lax.top_k."""
    scores = jnp.matmul(queries.astype(jnp.float32),
                        items.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
    vals, ids = jax.lax.top_k(scores, k)
    return vals, ids.astype(jnp.int32)


def bucket_match_ref(q_codes: jax.Array, bucket_codes: jax.Array,
                     hash_bits: int) -> jax.Array:
    """Oracle for the bucket-directory match kernel: (Q, B) match counts
    ``l = hash_bits - hamming``."""
    return hash_bits - hamming_ref(q_codes, bucket_codes)


def delta_scan_ref(q_codes: jax.Array, delta_codes: jax.Array,
                   live: jax.Array, hash_bits: int) -> jax.Array:
    """Oracle for the delta-buffer scan kernel: (Q, C) match counts
    ``l = hash_bits - hamming`` for live slots, ``-1`` for dead slots."""
    matches = hash_bits - hamming_ref(q_codes, delta_codes)
    return jnp.where(live[None, :].astype(jnp.int32) > 0, matches, -1)


def fused_query_ref(queries: jax.Array, cum: jax.Array, starts: jax.Array,
                    items: jax.Array, total: int, k: int, *,
                    kprime: Optional[int] = None,
                    payload: Optional[jax.Array] = None,
                    scale: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the fused single-pass query kernel (DESIGN.md §17).

    Staged realization of the same contract: CSR run expansion
    (:func:`bucket_gather_ref`) -> dequantized phase-1 scores -> top-k'
    survivors -> f32 rescore -> top-k. Returns vals (Q, k) f32 and CSR
    positions (Q, k) i32. With an f32 payload (``payload=None``) the
    phase-1 and rescore scores are the same dots, so the emitted
    positions are bit-identical to ``lax.top_k`` over the full staged
    candidate scores.
    """
    NEG = -3e38
    if kprime is None:
        kprime = max(k, min(max(4 * k, 32), total))
    if payload is None:
        payload = items
        scale = jnp.ones((items.shape[0], 1), jnp.float32)
    pos = bucket_gather_ref(cum, starts, total)             # (Q, total)
    valid = jnp.arange(total, dtype=jnp.int32)[None, :] < cum[:, -1:]
    # dequantize the gathered rows, not the whole payload (total << N on
    # the planned path), in the kernel's op order: row * scale, then dot
    deq = payload[pos].astype(jnp.float32) * scale[pos][..., 0][..., None]
    s1 = jnp.einsum("qd,qpd->qp", queries.astype(jnp.float32), deq)
    s1 = jnp.where(valid, s1, NEG)
    kp = min(int(kprime), total)
    sv, si = jax.lax.top_k(s1, kp)
    spos = jnp.take_along_axis(pos, si, axis=1)             # (Q, kp)
    ok = jnp.take_along_axis(valid, si, axis=1)
    rescored = jnp.einsum("qd,qpd->qp", queries.astype(jnp.float32),
                          items.astype(jnp.float32)[spos])
    rescored = jnp.where(ok, rescored, NEG)
    fv, fi = jax.lax.top_k(rescored, k)
    return fv, jnp.take_along_axis(spos, fi, axis=1).astype(jnp.int32)


def bucket_gather_ref(cum: jax.Array, starts: jax.Array,
                      num_probe: int) -> jax.Array:
    """Oracle for the segmented candidate gather: CSR position of the p-th
    probed item per query.

    ``cum``: (Q, S+1) exclusive prefix sizes of the probe-ordered selected
    buckets; ``starts``: (Q, S) their CSR start offsets. The selected runs
    must cover >= num_probe items. Returns (Q, num_probe) int32.
    """
    S = starts.shape[1]
    p = jnp.arange(num_probe, dtype=jnp.int32)
    # j[q, p] = #{i : cum[q, i+1] <= p} — the run containing output slot p
    j = jax.vmap(lambda c: jnp.searchsorted(c, p, side="right"))(cum[:, 1:])
    j = jnp.minimum(j, S - 1).astype(jnp.int32)
    base = jnp.take_along_axis(starts, j, axis=1)
    lo = jnp.take_along_axis(cum, j, axis=1)
    return base + (p[None, :] - lo)
