"""Unified query engine: dense-scan and bucket-traversal candidate
generation behind one front-end (DESIGN.md §5).

Both engines realize Algorithm 2's probe order — the eq.-12 ranking of
``(range, match count)`` pairs — but with different cost shapes:

  * ``engine="dense"`` — one Hamming scan over all N codes taken in CSR
    order, where each range is a contiguous segment; each segment's
    ranks by a select on the match count; one O(N log N) stable sort on
    the key ``rank * R + range``, which also gives each slot's range, so
    per-range budgets are selects too. No lookup gathers over the (Q, N)
    slots. Best for small N or when the bucket directory is nearly as
    large as the item table.
  * ``engine="bucket"`` — scan only the B-entry bucket directory
    (core/bucket_index.py), sort B bucket ranks, and gather the first
    ``num_probe`` items by walking the probe-ordered bucket runs
    (kernels/bucket_probe.py). Work is O(B log B + num_probe) per query —
    sublinear in N whenever buckets collide (the paper's short-code
    regime), which is where the proven query complexity comes from.

Canonical candidate order (shared by both engines): ascending
``(rank[j, l], CSR position)``. All items in a bucket share a rank; the
CSR position — items sorted by (range_id, code, id) — breaks every tie
deterministically, so for a fixed ``(index, queries, num_probe)`` the two
engines return *identical* candidate id sequences (tested).

``QueryEngine`` wraps an index (a spec-built ComposedIndex of any hash
family, or a legacy RangeLSH / SimpleLSH / VocabIndex tuple) plus an
optional prebuilt :class:`BucketIndex`, exposes batched ``candidates`` /
``query``, and is what ``ComposedIndex.query``, the legacy module shims
and the LSH-decode serving head dispatch through. Query encoding and
match counting dispatch through the index's family when it has one, so
integer-hash families (L2-ALSH) traverse buckets too.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import hashing
from repro.core.bucket_index import BucketIndex, build_bucket_index
from repro.core.topk import rerank
from repro.kernels import ops
from repro.obs.trace import span_or_null
from repro.obs.tracker import resolve_tracker

ENGINES = ("auto", "dense", "bucket", "fused")

# engine="auto" break-even (BENCH_0001, N=100k CPU): at L=16 the directory
# collapses items (B/N ~ 0.33) and bucket traversal is ~3x faster; at L=32
# nearly every bucket is a singleton (B/N ~ 0.99) and the directory scan IS
# the dense scan plus sort overhead (dense ~1.04x faster). The ratio splits
# the two measured arms; bucket wins exactly when the directory is
# meaningfully smaller than the item table.
AUTO_DENSE_RATIO = 0.75


def select_engine(num_buckets: int, num_items: int) -> str:
    """Resolve ``engine="auto"``: bucket traversal when the directory is
    meaningfully smaller than the item table, dense scan otherwise."""
    return "bucket" if num_buckets < AUTO_DENSE_RATIO * num_items else "dense"


def encode_queries(index, queries: jax.Array, *,
                   impl: str = "auto") -> jax.Array:
    """Hash queries against the index's hash parameters.

    Spec-built indexes carry their family (core/family.py) and dispatch to
    its asymmetric query transform; legacy indexes share the ``(d+1, L)``
    projection layout with the augmentation row last (``P(q) = [q; 0]``).
    """
    fam = getattr(index, "family", None)
    if fam is not None:
        return fam.encode_queries(index.params, queries, impl=impl)
    q = hashing.normalize(queries.astype(jnp.float32))
    zeros = jnp.zeros((q.shape[0],), q.dtype)
    return ops.hash_encode(q, index.A[:-1], zeros, index.A[-1], impl=impl)


def _default_match(buckets: BucketIndex, impl: str):
    """Packed-code match counter (legacy indexes): ``l = L - hamming``."""
    return lambda q_codes, codes: ops.bucket_match(
        q_codes, codes, buckets.hash_bits, impl=impl)


def _directory_order(buckets: BucketIndex, q_codes: jax.Array,
                     match_fn, tracker) -> jax.Array:
    """(Q, B) probe-ordered bucket indices: directory match -> per-bucket
    rank -> stable argsort (ties break by CSR bucket position). The shared
    front half of every bucket-store traversal (staged, planned, fused)."""
    with span_or_null(tracker, "repro.engine.directory_match") as sp:
        matches = match_fn(q_codes, buckets.bucket_code)         # (Q, B)
        bucket_rank = buckets.rank[buckets.bucket_rid[None, :], matches]
        return sp.sync(
            jnp.argsort(bucket_rank, axis=-1, stable=True))      # (Q, B)


def _probe_runs(buckets: BucketIndex, order: jax.Array,
                num_probe: int) -> Tuple[jax.Array, jax.Array]:
    """(cum (Q, S+1), starts (Q, S)) CSR runs of the first ``num_probe``
    probed items. Every bucket holds >= 1 item, so the first min(B, P)
    buckets cover the budget."""
    sel = order[:, :min(buckets.num_buckets, num_probe)]         # (Q, S)
    sizes = (buckets.bucket_start[1:] - buckets.bucket_start[:-1])[sel]
    starts = buckets.bucket_start[:-1][sel]
    cum = jnp.concatenate(
        [jnp.zeros((sel.shape[0], 1), jnp.int32),
         jnp.cumsum(sizes, axis=-1, dtype=jnp.int32)],
        axis=-1)                                                 # (Q, S+1)
    return cum, starts


def _planned_runs(buckets: BucketIndex, order: jax.Array,
                  budgets: Sequence[int], tracker=None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(cum (Q, B+1), starts (Q, B)) CSR runs realizing per-range budgets:
    each probe-ordered bucket takes what is left of its range's budget
    (zero-take buckets contribute empty runs)."""
    sizes_o = (buckets.bucket_start[1:] - buckets.bucket_start[:-1])[order]
    starts = buckets.bucket_start[:-1][order]
    rid_o = buckets.bucket_rid[order]
    with span_or_null(tracker, "repro.engine.planned_take") as sp:
        take = sp.sync(planned_take(rid_o, sizes_o, budgets))
    cum = jnp.concatenate(
        [jnp.zeros((order.shape[0], 1), jnp.int32),
         jnp.cumsum(take, axis=-1, dtype=jnp.int32)], axis=-1)
    return cum, starts


def bucket_candidates(buckets: BucketIndex, q_codes: jax.Array,
                      num_probe: int, *, impl: str = "auto",
                      match_fn=None, tracker=None) -> jax.Array:
    """(Q, num_probe) candidate item ids via bucket traversal.

    Directory match -> per-bucket probe rank -> stable sort of B ranks ->
    segmented gather of the first ``num_probe`` items. ``num_probe`` must
    not exceed the item count. ``match_fn`` overrides the packed-Hamming
    match counter (family-specific codes). ``tracker`` adds
    directory_match / segmented_gather stage spans (device-synced, values
    untouched).
    """
    num_probe = int(num_probe)
    if not 0 < num_probe <= buckets.num_items:
        # ValueError, not assert: the check must survive ``python -O``
        # and match QueryEngine.candidates.
        raise ValueError(f"num_probe={num_probe} outside "
                         f"(0, N={buckets.num_items}]")
    if match_fn is None:
        match_fn = _default_match(buckets, impl)
    order = _directory_order(buckets, q_codes, match_fn, tracker)
    with span_or_null(tracker, "repro.engine.segmented_gather") as sp:
        cum, starts = _probe_runs(buckets, order, num_probe)
        csr_pos = ops.bucket_gather(cum, starts, num_probe, impl=impl)
        return sp.sync(buckets.item_ids[csr_pos])


def check_budgets(budgets: Sequence[int], range_counts: np.ndarray
                  ) -> Tuple[Tuple[int, ...], int]:
    """Validate a per-range budget vector against the store's per-range
    item counts; returns (clipped budgets, total planned width)."""
    budgets = tuple(int(b) for b in budgets)
    if len(budgets) != range_counts.shape[0]:
        raise ValueError(f"{len(budgets)} budgets for "
                         f"{range_counts.shape[0]} ranges")
    if any(b < 0 for b in budgets):
        raise ValueError(f"budgets must be >= 0, got {budgets}")
    eff = tuple(min(b, int(c)) for b, c in zip(budgets, range_counts))
    total = sum(eff)
    if total <= 0:
        raise ValueError("planned budgets probe zero items")
    return eff, total


def bucket_range_counts(buckets: BucketIndex) -> np.ndarray:
    """(R,) per-range item counts from the bucket directory (host).

    device_get *before* any jnp op: inside a jit trace the directory
    arrays are closed-over constants, and slicing them with jnp would
    stage tracers that cannot come back to host.
    """
    start = np.asarray(jax.device_get(buckets.bucket_start))
    return np.bincount(
        np.asarray(jax.device_get(buckets.bucket_rid)),
        weights=(start[1:] - start[:-1]),
        minlength=buckets.rank.shape[0]).astype(np.int64)


def range_cum_before(rid_o: jax.Array, sizes_o: jax.Array,
                     num_ranges: int) -> jax.Array:
    """(Q, B) cumulative same-range sizes before each probe-ordered slot
    — THE within-range-position primitive every planned arm derives from
    (one implementation, so bucket/dense/distributed cannot drift out of
    the bit-identical contract). With unit sizes it is the within-range
    probe position itself; an item at in-bucket offset ``o`` of the
    bucket at slot ``s`` sits at within-range position
    ``range_cum_before[s] + o``."""
    crb = jnp.zeros_like(sizes_o)
    for j in range(num_ranges):
        mask = rid_o == j
        sz_j = jnp.where(mask, sizes_o, 0)
        crb = crb + jnp.where(
            mask, jnp.cumsum(sz_j, axis=-1, dtype=jnp.int32) - sz_j, 0)
    return crb


def planned_take(rid_o: jax.Array, sizes_o: jax.Array,
                 budgets: Sequence[int]) -> jax.Array:
    """(Q, B) per-bucket take realizing per-range budgets over a
    probe-ordered directory (the planner contract, DESIGN.md §12): each
    bucket takes what is left of its range's budget after the same-range
    buckets probed before it. Shared by the single-device bucket arm and
    the distributed traversal."""
    crb = range_cum_before(rid_o, sizes_o, len(budgets))
    caps = jnp.asarray(budgets, jnp.int32)[rid_o]
    return jnp.clip(caps - crb, 0, sizes_o)


def planned_bucket_candidates(buckets: BucketIndex, q_codes: jax.Array,
                              budgets: Sequence[int], *,
                              impl: str = "auto", match_fn=None,
                              range_counts: Optional[np.ndarray] = None,
                              tracker=None) -> jax.Array:
    """(Q, sum_j min(b_j, n_j)) candidates under per-range probe budgets
    (DESIGN.md §12): for each range j, the first ``min(b_j, n_j)`` items
    of range j in canonical ``(rank, CSR position)`` order, emitted in
    global canonical order. The directory walk computes, per bucket, how
    much of its range's budget is left — zero-take buckets cost nothing
    in the segmented gather. Pass ``range_counts`` (see
    :func:`bucket_range_counts`) to skip the per-call host sync."""
    if range_counts is None:
        range_counts = bucket_range_counts(buckets)
    budgets, total = check_budgets(budgets, range_counts)
    if match_fn is None:
        match_fn = _default_match(buckets, impl)
    order = _directory_order(buckets, q_codes, match_fn, tracker)
    with span_or_null(tracker, "repro.engine.segmented_gather") as sp:
        # every query's takes sum to exactly ``total`` (each range always
        # contributes its full effective budget), so no covering run is
        # needed
        cum, starts = _planned_runs(buckets, order, budgets, tracker)
        csr_pos = ops.bucket_gather(cum, starts, total, impl=impl)
        return sp.sync(buckets.item_ids[csr_pos])


def fused_bucket_query(buckets: BucketIndex, q_codes: jax.Array,
                       queries: jax.Array, items_csr: jax.Array, k: int, *,
                       num_probe: Optional[int] = None,
                       budgets: Optional[Sequence[int]] = None,
                       payload: Optional[jax.Array] = None,
                       scale: Optional[jax.Array] = None,
                       impl: str = "auto", match_fn=None,
                       range_counts: Optional[np.ndarray] = None,
                       tracker=None) -> Tuple[jax.Array, jax.Array, int]:
    """Single-pass fused traversal + re-rank (DESIGN.md §17): directory
    match, then ONE kernel dispatch covering run expansion, phase-1
    scoring, survivor selection and f32 rescore. Returns (vals, ids,
    probed width). ``items_csr`` holds the item rows in CSR order
    (``items[buckets.item_ids]``); optional ``payload``/``scale`` select
    the int8 phase-1 arm. With the default f32 payload the returned ids
    are bit-identical to the staged planned path (conformance-tested).
    """
    if (num_probe is None) == (budgets is None):
        raise ValueError("pass exactly one of num_probe/budgets")
    if match_fn is None:
        match_fn = _default_match(buckets, impl)
    if budgets is not None:
        if range_counts is None:
            range_counts = bucket_range_counts(buckets)
        budgets, total = check_budgets(budgets, range_counts)
    else:
        total = int(num_probe)
        if not 0 < total <= buckets.num_items:
            raise ValueError(f"num_probe={total} outside "
                             f"(0, N={buckets.num_items}]")
    order = _directory_order(buckets, q_codes, match_fn, tracker)
    with span_or_null(tracker, "repro.engine.fused_query") as sp:
        if budgets is not None:
            cum, starts = _planned_runs(buckets, order, budgets, tracker)
        else:
            cum, starts = _probe_runs(buckets, order, total)
        vals, pos = ops.fused_query(queries, cum, starts, items_csr,
                                    total, k, payload=payload,
                                    scale=scale, impl=impl)
        ids = sp.sync(buckets.item_ids[pos])
    return vals, ids, total


def quantize_payload(items_csr: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-item int8 quantization of the CSR payload: returns
    (payload (N, d) int8, scale (N, 1) f32) with
    ``rows ~= payload * scale`` and scale = max|row| / 127."""
    mx = jnp.max(jnp.abs(items_csr), axis=1, keepdims=True)
    scale = jnp.maximum(mx, jnp.finfo(jnp.float32).tiny) / 127.0
    payload = jnp.clip(jnp.round(items_csr / scale), -127, 127
                       ).astype(jnp.int8)
    return payload, scale.astype(jnp.float32)


def item_range_counts(range_id: jax.Array, num_ranges: int) -> np.ndarray:
    """(R,) per-range item counts from the items' range ids (host)."""
    return np.bincount(np.asarray(jax.device_get(range_id)),
                       minlength=num_ranges).astype(np.int64)


def _segment_bounds(range_counts: np.ndarray) -> Tuple[int, ...]:
    """(R+1,) static CSR offsets of each range's segment: the store sorts
    items by range first, so range j owns CSR positions
    ``[bounds[j], bounds[j+1])``."""
    return (0,) + tuple(int(c) for c in np.cumsum(range_counts))


@functools.partial(jax.jit, static_argnames=("bounds",))
def _dense_sort(matches_csr: jax.Array, rank: jax.Array,
                bounds: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
    """(sorted_key, order), both (Q, N): the CSR positions in canonical
    ``(rank, CSR position)`` order and their keys ``rank * R + range``.

    Each range's segment takes its rank by a (K+1)-way select on the
    match count, so no (Q, N) gather touches the slots. The rank table is
    a permutation of the ``(range, l)`` pairs, so a rank fixes its range:
    the composite key sorts exactly as the rank does, and ``key % R``
    recovers each slot's range after the sort."""
    num_ranges = len(bounds) - 1
    key_table = rank * num_ranges + jnp.arange(
        num_ranges, dtype=jnp.int32)[:, None]                    # (R, K+1)
    parts = []
    for j in range(num_ranges):
        m = matches_csr[:, bounds[j]:bounds[j + 1]]
        if m.shape[1] == 0:
            continue
        key = jnp.full(m.shape, key_table[j, 0], jnp.int32)
        for l in range(1, key_table.shape[1]):
            key = jnp.where(m == l, key_table[j, l], key)
        parts.append(key)
    key = jnp.concatenate(parts, axis=1)
    pos = lax.broadcasted_iota(jnp.int32, key.shape, 1)
    return lax.sort_key_val(key, pos, dimension=1, is_stable=True)


def _dense_order(buckets: BucketIndex, q_codes: jax.Array,
                 db_codes: jax.Array, range_counts: np.ndarray, match_fn,
                 tracker) -> Tuple[jax.Array, jax.Array]:
    """The dense arms' shared match-and-rank stage: hash-match the
    queries against the codes in CSR order (an (N, W) row gather, not
    kept), then :func:`_dense_sort`. Returns (sorted_key, order)."""
    bounds = _segment_bounds(range_counts)
    if bounds[-1] != buckets.num_items:
        raise ValueError(f"range counts cover {bounds[-1]} items, the "
                         f"store holds {buckets.num_items}")
    with span_or_null(tracker, "repro.engine.dense_match") as sp:
        codes_csr = jnp.take(db_codes, buckets.item_ids, axis=0)
        matches = match_fn(q_codes, codes_csr)                   # (Q, N)
        return sp.sync(_dense_sort(matches, buckets.rank, bounds))


# Query rows per step of _dense_keep's loop. The compiler keeps up to R of
# range_cum_before's (rows, N) cumsums live at once, so a step's scratch
# grows with its rows. On a v5e at N = 2^20, R = 32: 16 rows took 216 ms
# and 2.1 GiB per 128 queries, 8 rows 1,098 ms, 32 rows 376 ms and 4.4
# GiB; all 128 rows at once need more than the chip's 16 GB.
_KEEP_ROWS = 16


@functools.partial(jax.jit, static_argnames=("budgets",))
def _dense_keep(sorted_key: jax.Array, budgets: Tuple[int, ...]
                ) -> jax.Array:
    """(Q, N) bool: the probe-ordered slots within their range's budget.
    Each slot's range is its sort key mod R and its cap a select over the
    R budgets; unit sizes make :func:`range_cum_before` the within-range
    probe position."""
    num_ranges = len(budgets)

    def keep_row(key):
        rid_o = key % num_ranges
        wpos = range_cum_before(rid_o, jnp.ones_like(rid_o), num_ranges)
        caps = jnp.zeros_like(rid_o)
        for j, b in enumerate(budgets):
            caps = jnp.where(rid_o == j, b, caps)
        return wpos < caps

    return lax.map(keep_row, sorted_key, batch_size=_KEEP_ROWS)


@functools.partial(jax.jit, static_argnames=("total",))
def _dense_take(keep: jax.Array, order: jax.Array, item_ids: jax.Array,
                total: int) -> jax.Array:
    """(Q, total) ids of the kept slots: exactly ``total`` per query, and
    the stable sort pulls them to the front in canonical order."""
    sel = jnp.argsort(~keep, axis=-1, stable=True)[:, :total]
    return item_ids[jnp.take_along_axis(order, sel, axis=-1)]


def planned_dense_candidates(buckets: BucketIndex, q_codes: jax.Array,
                             db_codes: jax.Array, range_id: jax.Array,
                             budgets: Sequence[int], *,
                             impl: str = "auto", match_fn=None,
                             range_counts: Optional[np.ndarray] = None,
                             tracker=None) -> jax.Array:
    """Dense-scan realization of the same per-range-budget contract as
    :func:`planned_bucket_candidates` — identical candidate id sequences
    (tested by the conformance suite)."""
    if range_counts is None:
        range_counts = item_range_counts(range_id, buckets.num_ranges)
    budgets, total = check_budgets(budgets, range_counts)
    if match_fn is None:
        match_fn = _default_match(buckets, impl)
    sorted_key, order = _dense_order(buckets, q_codes, db_codes,
                                     range_counts, match_fn, tracker)
    with span_or_null(tracker, "repro.engine.dense_select") as sp:
        with span_or_null(tracker, "repro.engine.planned_take") as sp_take:
            keep = sp_take.sync(_dense_keep(sorted_key, budgets))
        del sorted_key  # free its (Q, N) before the take's sort
        return sp.sync(_dense_take(keep, order, buckets.item_ids, total))


def dense_candidates(buckets: BucketIndex, q_codes: jax.Array,
                     db_codes: jax.Array, range_id: jax.Array,
                     num_probe: int, *, impl: str = "auto",
                     match_fn=None,
                     range_counts: Optional[np.ndarray] = None,
                     tracker=None) -> jax.Array:
    """(Q, num_probe) candidate ids via the dense scan, in the same
    canonical ``(rank, CSR position)`` order as :func:`bucket_candidates`.

    Scores every item (O(Q N) match + O(N log N) sort); the bucket store is
    used only for the rank table and the CSR tie-break layout.
    ``range_counts`` (host) skips the per-call sync for the segment bounds.
    """
    num_probe = int(num_probe)
    if range_counts is None:
        range_counts = item_range_counts(range_id, buckets.num_ranges)
    if match_fn is None:
        match_fn = _default_match(buckets, impl)
    _, order = _dense_order(buckets, q_codes, db_codes, range_counts,
                            match_fn, tracker)
    with span_or_null(tracker, "repro.engine.dense_select") as sp:
        return sp.sync(buckets.item_ids[order[:, :num_probe]])


# bounded LRU engine memo for the convenience surface (ComposedIndex.query /
# candidates dispatch): repeat calls over the same index reuse the host-built
# bucket store instead of paying the O(N log N) rebuild per call — the
# recall-contract default path goes through here every query. The entry
# holds a strong ref to the index, so the id() key can't be a stale reuse
# (same pattern as distributed._shim_engine). The cap bounds the memo under
# per-request trackers in a serving loop (each request resolving a fresh
# tracker used to grow the memo without bound — PR 10 bugfix); the
# ``repro.engine.memo_size`` gauge makes the occupancy observable.
_ENGINE_MEMO_CAP = 8
_engine_memo: OrderedDict = OrderedDict()


def engine_for(index, *, engine: str, buckets=None,
               impl: str = "auto", tracker=None) -> "QueryEngine":
    """A :class:`QueryEngine` over ``index``, memoized in a bounded LRU
    when no prebuilt ``buckets`` are supplied. The memo key includes the
    tracker identity (the entry holds strong refs, so id() keys cannot
    alias collected objects); the ambient default tracker is resolved
    *here* so installing one redirects even already-memoized convenience
    paths."""
    tracker = resolve_tracker(tracker)
    if buckets is not None:
        return QueryEngine(index, engine=engine, buckets=buckets,
                           impl=impl, tracker=tracker)
    key = (id(index), engine, impl, id(tracker))
    ent = _engine_memo.get(key)
    if ent is None:
        eng = QueryEngine(index, engine=engine, impl=impl, tracker=tracker)
        _engine_memo[key] = (index, tracker, eng)
        while len(_engine_memo) > _ENGINE_MEMO_CAP:
            _engine_memo.popitem(last=False)
    else:
        _engine_memo.move_to_end(key)
        eng = ent[-1]
    if tracker is not None:
        tracker.gauge("repro.engine.memo_size", len(_engine_memo))
    return eng


class QueryEngine:
    """Batched candidate generation + exact re-rank over one index.

    Args:
      index:   spec-built ComposedIndex (any family, DESIGN.md §10) or a
               legacy RangeLSHIndex / SimpleLSHIndex / VocabIndex.
      engine:  "dense" | "bucket" | "fused" | "auto" (:func:`select_engine`
               picks dense/bucket by directory size vs item count; "fused"
               — the single-pass kernel, DESIGN.md §17 — is opt-in because
               it requires the item payload resident per shard). All
               engines need the store (dense uses its rank table + CSR
               tie-break layout), so construction always has one.
      buckets: optional prebuilt BucketIndex; when None, one is built
               here — a host-side O(N log N) one-time cost, so reuse the
               engine (or pass ``buckets``) across query batches.
      impl:    kernel dispatch ("auto" | "pallas" | "ref").
      quantized: fused engine only — score phase 1 against the int8
               payload (per-item scales) instead of the f32 rows; the
               f32 rescore of the k' survivors bounds the recall delta
               (conformance-tested).
      tracker: optional :class:`repro.obs.Tracker`; None falls back to the
               ambient default (resolved once, at construction). Attaching
               one adds stage spans + query counters, all recorded
               host-side after device sync — results stay bit-identical
               (parity-tested).
    """

    def __init__(self, index, *, engine: str = "auto",
                 buckets: Optional[BucketIndex] = None, impl: str = "auto",
                 tracker=None, quantized: bool = False):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine: {engine!r}")
        if quantized and engine != "fused":
            raise ValueError("quantized phase-1 scoring is a fused-engine "
                             "arm; pass engine=\"fused\"")
        if engine == "fused" and impl != "ref" \
                and jax.default_backend() == "tpu":
            raise NotImplementedError(
                "engine=\"fused\" does not compile for TPU: its kernel "
                "gathers candidate rows in-kernel and keeps the whole item "
                "payload resident in VMEM (DESIGN.md §17). Use "
                "engine=\"bucket\" or \"dense\", or impl=\"ref\".")
        if buckets is None:
            buckets = build_bucket_index(index)
        if engine == "auto":
            engine = select_engine(buckets.num_buckets, buckets.num_items)
        self.index = index
        self.engine = engine
        self.buckets = buckets
        self.impl = impl
        self.quantized = quantized
        self.tracker = resolve_tracker(tracker)
        self._range_counts_cache = None
        self._fused_cache = None

    @property
    def _fused_arrays(self):
        """(items_csr, payload, scale) for the fused kernel — item rows
        reordered to CSR layout once per engine (device-resident), plus
        the int8 payload + per-item scales when ``quantized``."""
        if self._fused_cache is None:
            items_csr = jnp.take(
                self.index.items.astype(jnp.float32),
                self.buckets.item_ids, axis=0)
            payload = scale = None
            if self.quantized:
                payload, scale = quantize_payload(items_csr)
            self._fused_cache = (items_csr, payload, scale)
        return self._fused_cache

    @property
    def _range_id(self) -> jax.Array:
        if hasattr(self.index, "range_id"):
            return self.index.range_id
        return jnp.zeros((self.index.codes.shape[0],), jnp.int32)

    @property
    def _range_counts(self) -> np.ndarray:
        """Per-range item counts (host, computed once — the planned
        paths validate budgets against them on every call)."""
        if self._range_counts_cache is None:
            self._range_counts_cache = bucket_range_counts(self.buckets)
        return self._range_counts_cache

    @property
    def _match_fn(self):
        """Family-aware match counter; None keeps the packed default."""
        fam = getattr(self.index, "family", None)
        if fam is None:
            return None
        return lambda q_codes, codes: fam.match_counts(
            self.index.params, q_codes, codes, self.index.hash_bits,
            impl=self.impl)

    def candidates(self, queries: jax.Array,
                   num_probe: Optional[int] = None, *,
                   budgets: Optional[Sequence[int]] = None) -> jax.Array:
        """(Q, P) item ids in canonical probe order. ``num_probe`` probes
        the global canonical prefix; ``budgets`` probes per-range prefixes
        (the planner contract, DESIGN.md §12) with
        ``P = sum_j min(b_j, n_j)``."""
        if (num_probe is None) == (budgets is None):
            raise ValueError("pass exactly one of num_probe/budgets")
        tr = self.tracker
        with span_or_null(tr, "repro.engine.hash_encode") as sp:
            q_codes = sp.sync(
                encode_queries(self.index, queries, impl=self.impl))
        if budgets is not None:
            if self.engine in ("bucket", "fused"):
                # the fused engine's candidate *set* is the bucket
                # traversal's (the kernel only fuses scoring onto it), so
                # candidate-level callers get the staged walk
                return planned_bucket_candidates(
                    self.buckets, q_codes, budgets, impl=self.impl,
                    match_fn=self._match_fn,
                    range_counts=self._range_counts, tracker=tr)
            return planned_dense_candidates(
                self.buckets, q_codes, self.index.codes, self._range_id,
                budgets, impl=self.impl, match_fn=self._match_fn,
                range_counts=self._range_counts, tracker=tr)
        num_probe = int(num_probe)
        if not 0 < num_probe <= self.buckets.num_items:
            raise ValueError(f"num_probe={num_probe} outside "
                             f"(0, N={self.buckets.num_items}]")
        if self.engine in ("bucket", "fused"):
            return bucket_candidates(self.buckets, q_codes, num_probe,
                                     impl=self.impl,
                                     match_fn=self._match_fn, tracker=tr)
        return dense_candidates(self.buckets, q_codes, self.index.codes,
                                self._range_id, num_probe, impl=self.impl,
                                match_fn=self._match_fn,
                                range_counts=self._range_counts, tracker=tr)

    def query(self, queries: jax.Array, k: int,
              num_probe: Optional[int] = None, *,
              recall_target: Optional[float] = None,
              budgets: Optional[Sequence[int]] = None
              ) -> Tuple[jax.Array, jax.Array]:
        """Algorithm 2 end-to-end: probe, exact re-rank, return (vals,
        ids) (Q, k). Exactly one of ``num_probe`` (static global budget),
        ``budgets`` (per-range budgets) or ``recall_target`` (resolved to
        budgets through the index's calibration table — the recall
        contract) selects the probe set."""
        if recall_target is not None and (num_probe is not None
                                          or budgets is not None):
            raise ValueError("pass one of num_probe/budgets/recall_target")
        tr = self.tracker
        with span_or_null(tr, "repro.engine.query"):
            if recall_target is not None:
                from repro.core.planner import resolve_budgets
                with span_or_null(tr, "repro.engine.plan"):
                    budgets = resolve_budgets(
                        getattr(self.index, "calib", None), recall_target,
                        k=k).budgets
            if self.engine == "fused":
                if (num_probe is None) == (budgets is None):
                    raise ValueError("pass exactly one of "
                                     "num_probe/budgets")
                with span_or_null(tr, "repro.engine.hash_encode") as sp:
                    q_codes = sp.sync(encode_queries(
                        self.index, queries, impl=self.impl))
                items_csr, payload, scale = self._fused_arrays
                vals, ids, width = fused_bucket_query(
                    self.buckets, q_codes, queries, items_csr, int(k),
                    num_probe=num_probe, budgets=budgets,
                    payload=payload, scale=scale, impl=self.impl,
                    match_fn=self._match_fn,
                    range_counts=self._range_counts, tracker=tr)
            else:
                cand = self.candidates(queries, num_probe, budgets=budgets)
                if not 0 < int(k) <= cand.shape[1]:
                    raise ValueError(f"k={k} outside (0, probed width "
                                     f"{cand.shape[1]}]")
                vals, ids = rerank(queries, self.index.items, cand, int(k),
                                   tracker=tr)
                width = cand.shape[1]
        if tr is not None:
            tr.count("repro.engine.queries", queries.shape[0])
            tr.observe("repro.engine.probe_width", width)
            if budgets is not None:
                for j, b in enumerate(budgets):
                    tr.observe(f"repro.engine.probes_used.range{j}", b)
        return vals, ids
