"""Distributed serving on the composable spec API (DESIGN.md §11).

Algorithm 2 of the paper ("take the best across sub-datasets") is exactly
a distributed merge, and the norm-range partition composes with any base
hash (§10) — so the distributed layer is built on the same two pieces as
the single-device path:

  * **shard-aligned layout** (:func:`build_sharded`): the spec-built index
    is materialized in its *global CSR bucket order* — items sorted by
    ``(range_id, code, id)`` — and split into ``num_shards`` contiguous
    spans whose boundaries land on bucket starts (``align="range"``
    restricts them to range starts). Every shard therefore owns whole
    buckets and, since the CSR is range-major, a contiguous run of norm
    ranges. Per-shard rows are padded to a common length and masked by
    ``valid`` / ``perm == -1``.
  * **replicated directory**: the bucket directory — ``(rid, code, size)``
    plus each bucket's owning shard and local CSR offset — is O(B) and
    rides replicated; the O(N) item payload (vectors, codes, ids) is what
    shards.
  * **per-shard traversal** (:class:`DistributedEngine`): inside
    ``shard_map`` every shard computes the *global* bucket probe order
    from the replicated directory (family ``match_counts`` + rank table,
    ``impl`` kernel dispatch), derives how many items of each bucket the
    global ``num_probe`` budget takes, and gathers/re-ranks only the
    probed items it owns — the probed union across shards is exactly the
    first ``num_probe`` items of the single-device canonical order, which
    is what makes the merged answer bit-identical to
    ``QueryEngine.query`` (tested). ``engine="dense"`` scans the local
    codes instead of walking runs (same probed set, dense cost shape).
  * **merge**: per-shard exact top-k, one ``all_gather`` of
    ``(vals, ids)`` — O(k * shards) bytes on the interconnect — and a
    replicated re-top-k. Shards whose probed count falls short of ``k``
    pad with ``(-inf, -1)``, which can never displace a real candidate in
    the merge.

``query_axis`` keeps the PR-era 2-D decomposition: queries shard over a
second mesh axis, the Algorithm-2 merge all-gathers only across the item
axes, and a final gather over the query axis restores the replicated
(Q, k) answer.

The legacy seed-era surface (``build`` / ``shard_index`` / ``query`` over
a dense-only RANGE-LSH layout) is kept as thin shims over this path,
mirroring the PR3 migration; ``num_probe_per_shard`` maps onto the global
budget ``min(N, num_probe_per_shard * num_shards)``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bucket_index import build_bucket_index, rank_from_scores
from repro.core.engine import planned_take, range_cum_before, select_engine
from repro.core.index import ComposedMultiTable, IndexSpec, _check_probe
from repro.core.index import build as build_spec
from repro.core.probe import DEFAULT_EPS
from repro.core.topk import EXACT
from repro.kernels import ops
from repro.obs.trace import span_or_null
from repro.obs.tracker import resolve_tracker

ALIGNMENTS = ("bucket", "range")


class ShardedIndex(NamedTuple):
    """Spec-built index in shard-aligned global CSR layout.

    Replicated (small): ``params`` (family hash parameters), ``rank``
    (probe rank per ``(range, match count)``), and the bucket directory
    ``dir_*`` — per bucket its code, range, item count, owning shard and
    start offset *within the owner's local rows*.

    Sharded (O(N)): all ``(num_shards * rows_per_shard, ...)`` arrays.
    Shard ``s`` owns rows ``[s * rows_per_shard, (s+1) * rows_per_shard)``
    — its contiguous global-CSR span first, then padding (``valid``
    False, ``perm`` -1). ``bucket_of`` / ``bucket_off`` place each row in
    its (global) bucket, which is how the dense arm recovers the item's
    global canonical probe position without the directory walk.
    """

    spec: IndexSpec
    params: Any
    rank: jax.Array             # (R, n_hashes+1) int32
    dir_code: jax.Array         # (B, W) uint32 | (B, K) int32
    dir_rid: jax.Array          # (B,)  int32
    dir_size: jax.Array         # (B,)  int32
    dir_shard: jax.Array        # (B,)  int32 owning shard
    dir_local_start: jax.Array  # (B,)  int32 offset within the owner rows
    items: jax.Array            # (S*rows, d) f32
    codes: jax.Array            # (S*rows, W|K)
    range_id: jax.Array         # (S*rows,) int32
    bucket_of: jax.Array        # (S*rows,) int32
    bucket_off: jax.Array       # (S*rows,) int32
    perm: jax.Array             # (S*rows,) int32 original item id (-1 pad)
    valid: jax.Array            # (S*rows,) bool
    num_shards: int
    rows_per_shard: int
    num_items: int
    hash_bits: int
    calib: Optional[object] = None  # planner CalibrationTable (host-side)

    @property
    def num_buckets(self) -> int:
        return self.dir_rid.shape[0]

    @property
    def family(self):
        return self.spec.resolve_family()


def _split_offsets(bounds: np.ndarray, n: int, num_shards: int
                   ) -> np.ndarray:
    """(S+1,) non-decreasing item offsets: each interior cut is the legal
    boundary nearest the ideal equal-item split."""
    cut = np.zeros((num_shards + 1,), np.int64)
    cut[-1] = n
    for s in range(1, num_shards):
        ideal = int(round(s * n / num_shards))
        j = int(np.searchsorted(bounds, ideal))
        cands = [int(bounds[i]) for i in (j - 1, j)
                 if 0 <= i < bounds.size]
        best = min(cands, key=lambda b: abs(b - ideal)) if cands else 0
        cut[s] = max(best, cut[s - 1])
    return cut


def build_sharded(spec: IndexSpec, items: jax.Array, key: jax.Array,
                  num_shards: int, *, align: str = "bucket",
                  strict: bool = True,
                  calibration_queries: Optional[jax.Array] = None,
                  calibration_k: Optional[int] = None) -> ShardedIndex:
    """Build the shard-aligned index for any spec (DESIGN.md §11).

    ``align="bucket"`` (default) splits at bucket boundaries balancing
    item counts; ``align="range"`` restricts cuts to norm-range
    boundaries (whole ranges per shard, possibly less balanced). Planner
    calibration (a spec ``recall_target`` or explicit calibration
    kwargs, DESIGN.md §12) happens on the pre-layout index — the
    calibrated canonical order is what every shard traverses — and the
    table rides replicated on the result.
    """
    num_shards = int(num_shards)
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if align not in ALIGNMENTS:
        raise ValueError(f"unknown align {align!r}; "
                         f"expected one of {ALIGNMENTS}")
    cidx = build_spec(spec, items, key, strict=strict,
                      calibration_queries=calibration_queries,
                      calibration_k=calibration_k)
    if isinstance(cidx, ComposedMultiTable):
        raise ValueError("multi-table single-probe has no sharded path")
    buckets = build_bucket_index(cidx)

    bstart = np.asarray(jax.device_get(buckets.bucket_start)).astype(
        np.int64)                                          # (B+1,)
    brid = np.asarray(jax.device_get(buckets.bucket_rid))
    item_ids = np.asarray(jax.device_get(buckets.item_ids))
    n, num_b = item_ids.shape[0], brid.shape[0]

    if align == "range":
        new_range = np.ones((num_b,), bool)
        if num_b > 1:
            new_range[1:] = brid[1:] != brid[:-1]
        bounds = bstart[:-1][new_range]
    else:
        bounds = bstart[:-1]
    cut = _split_offsets(bounds, n, num_shards)
    rows = max(int(np.max(np.diff(cut))), 1)

    sizes = np.diff(bstart)
    bucket_of_g = np.repeat(np.arange(num_b, dtype=np.int64), sizes)
    off_g = np.arange(n, dtype=np.int64) - bstart[bucket_of_g]

    total = num_shards * rows
    src = np.zeros((total,), np.int64)        # global item id per slot
    perm = np.full((total,), -1, np.int32)
    valid = np.zeros((total,), bool)
    bof = np.zeros((total,), np.int32)
    boff = np.zeros((total,), np.int32)
    for s in range(num_shards):
        a, b = int(cut[s]), int(cut[s + 1])
        sl = slice(s * rows, s * rows + (b - a))
        src[sl] = item_ids[a:b]
        perm[sl] = item_ids[a:b]
        valid[sl] = True
        bof[sl] = bucket_of_g[a:b]
        boff[sl] = off_g[a:b]

    items_np = np.asarray(jax.device_get(cidx.items))
    codes_np = np.asarray(jax.device_get(cidx.codes))
    rid_np = np.asarray(jax.device_get(cidx.range_id))
    items_sh = items_np[src]
    codes_sh = codes_np[src]
    rid_sh = rid_np[src].astype(np.int32)
    items_sh[~valid] = 0
    codes_sh[~valid] = 0
    rid_sh[~valid] = 0

    dir_shard = (np.searchsorted(cut, bstart[:-1], side="right") - 1)
    dir_shard = np.clip(dir_shard, 0, num_shards - 1).astype(np.int32)
    dir_local_start = (bstart[:-1] - cut[dir_shard]).astype(np.int32)

    return ShardedIndex(
        spec=spec,
        params=cidx.params,
        rank=rank_from_scores(cidx.table),
        dir_code=buckets.bucket_code,
        dir_rid=buckets.bucket_rid,
        dir_size=jnp.asarray(sizes.astype(np.int32)),
        dir_shard=jnp.asarray(dir_shard),
        dir_local_start=jnp.asarray(dir_local_start),
        items=jnp.asarray(items_sh),
        codes=jnp.asarray(codes_sh),
        range_id=jnp.asarray(rid_sh),
        bucket_of=jnp.asarray(bof),
        bucket_off=jnp.asarray(boff),
        perm=jnp.asarray(perm),
        valid=jnp.asarray(valid),
        num_shards=num_shards,
        rows_per_shard=rows,
        num_items=n,
        hash_bits=cidx.hash_bits,
        calib=cidx.calib,
    )


def _axis_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _mesh_shards(mesh: Mesh, axis: Tuple[str, ...]) -> int:
    shards = 1
    for a in axis:
        shards *= mesh.shape[a]
    return shards


def shard_index(index: ShardedIndex, mesh: Mesh, axis="data"
                ) -> ShardedIndex:
    """Place the index on ``mesh``: per-item arrays sharded over ``axis``
    (one or a tuple of mesh axis names), directory/params replicated."""
    axis = _axis_tuple(axis)
    if _mesh_shards(mesh, axis) != index.num_shards:
        raise ValueError(
            f"index was built for {index.num_shards} shards but mesh axis "
            f"{axis} has {_mesh_shards(mesh, axis)} devices")
    row = NamedSharding(mesh, P(axis))
    row2 = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    put = jax.device_put
    return index._replace(
        params=jax.tree.map(lambda x: put(x, rep), index.params),
        rank=put(index.rank, rep),
        dir_code=put(index.dir_code, rep),
        dir_rid=put(index.dir_rid, rep),
        dir_size=put(index.dir_size, rep),
        dir_shard=put(index.dir_shard, rep),
        dir_local_start=put(index.dir_local_start, rep),
        items=put(index.items, row2),
        codes=put(index.codes, row2),
        range_id=put(index.range_id, row),
        bucket_of=put(index.bucket_of, row),
        bucket_off=put(index.bucket_off, row),
        perm=put(index.perm, row),
        valid=put(index.valid, row),
    )


def _shard_query(q_codes, queries, params, dir_code, dir_rid, dir_size,
                 dir_shard, dir_lstart, rank, items, codes, range_id,
                 bucket_of, bucket_off, perm, valid, *, family, hash_bits,
                 num_probe, k, engine, impl, axis, axis_sizes, query_axis,
                 budgets=None):
    """Per-shard body: global directory traversal -> local probe of the
    owned slice of the canonical first-``num_probe`` items (or, with
    ``budgets``, of the planner's per-range prefixes totalling
    ``num_probe``) -> exact local top-k -> Algorithm-2 all_gather merge."""
    my = jnp.int32(0)
    for a, s in zip(axis, axis_sizes):
        my = my * s + jax.lax.axis_index(a)

    # global bucket probe order, identical on every shard (replicated
    # inputs): matches -> rank -> stable argsort -> per-bucket take under
    # the global budget.
    matches = family.match_counts(params, q_codes, dir_code, hash_bits,
                                  impl=impl)                  # (Q, B)
    brank = rank[dir_rid[None, :], matches]
    order = jnp.argsort(brank, axis=-1, stable=True)          # (Q, B)
    q_local = q_codes.shape[0]
    # a shard re-ranks at most its own rows, whatever the global budget
    width = min(num_probe, codes.shape[0])

    if engine == "bucket":
        # walk only the owned buckets' runs: O(B log B) directory work +
        # O(num_probe) gather, never the O(rows) item table. Every bucket
        # holds >= 1 item, so the first min(B, P) probe-ordered buckets
        # cover a global budget (the single-device slice, engine.py);
        # per-range budgets can land anywhere, so they walk the full
        # directory.
        if budgets is not None:
            sel = order
            sizes_o = dir_size[sel]
            take = planned_take(dir_rid[order], sizes_o, budgets)
        else:
            sel = order[:, :min(order.shape[1], num_probe)]
            sizes_o = dir_size[sel]
            cum = jnp.cumsum(sizes_o, axis=-1, dtype=jnp.int32)
            take = jnp.clip(num_probe - (cum - sizes_o), 0, sizes_o)
        owned = dir_shard[sel] == my
        ltake = jnp.where(owned, take, 0)
        lcum = jnp.cumsum(ltake, axis=-1, dtype=jnp.int32)
        total = lcum[:, -1]                                   # (Q,)
        starts_o = dir_lstart[sel]
        # a covering run keeps the gather in-contract past ``total``;
        # its slots are masked below.
        cum2 = jnp.concatenate(
            [jnp.zeros((q_local, 1), jnp.int32), lcum,
             lcum[:, -1:] + jnp.int32(width)], axis=1)
        starts2 = jnp.concatenate(
            [starts_o, jnp.zeros((q_local, 1), jnp.int32)], axis=1)
        pos = ops.bucket_gather(cum2, starts2, width, impl=impl)
    else:
        # dense arm: score every local row, keep rows whose canonical
        # position (items before its bucket + in-bucket offset — global
        # under a scalar budget, within-range under planned budgets) is
        # under the budget — the same probed set as the bucket arm.
        # The position scatter needs the cumulative sizes of ALL buckets.
        md = family.match_counts(params, q_codes, codes, hash_bits,
                                 impl=impl)                   # (Q, rows)
        irank = rank[range_id[None, :], md]
        if budgets is not None:
            crb = range_cum_before(dir_rid[order], dir_size[order],
                                   len(budgets))
            cpb = jnp.zeros_like(crb).at[
                jnp.arange(q_local)[:, None], order].set(crb)
            wpos = cpb[:, bucket_of] + bucket_off[None, :]
            cap = jnp.asarray(budgets, jnp.int32)[range_id]
            probed = valid[None, :] & (wpos < cap[None, :])
        else:
            sizes_o = dir_size[order]
            cum = jnp.cumsum(sizes_o, axis=-1, dtype=jnp.int32)
            cum_prev = cum - sizes_o
            cpb = jnp.zeros_like(cum_prev).at[
                jnp.arange(q_local)[:, None], order].set(cum_prev)
            gpos = cpb[:, bucket_of] + bucket_off[None, :]
            probed = valid[None, :] & (gpos < num_probe)
        key = jnp.where(probed, irank, jnp.iinfo(jnp.int32).max)
        order_l = jnp.argsort(key, axis=-1, stable=True)
        pos = order_l[:, :width]
        total = jnp.sum(probed.astype(jnp.int32), axis=-1)

    slot_ok = jnp.arange(width, dtype=jnp.int32)[None, :] < total[:, None]
    cand = items[pos]                                         # (Q, P, d)
    ip = jnp.einsum("qd,qpd->qp", queries, cand, precision=EXACT)
    ip = jnp.where(slot_ok, ip, -jnp.inf)
    if width < k:        # a shard smaller than k still merges cleanly
        ip = jnp.concatenate(
            [ip, jnp.full((q_local, k - width), -jnp.inf, ip.dtype)],
            axis=1)
        pos = jnp.concatenate(
            [pos, jnp.zeros((q_local, k - width), pos.dtype)], axis=1)
    lvals, lpos = jax.lax.top_k(ip, k)
    lids = perm[jnp.take_along_axis(pos, lpos, axis=1)]
    # padded/tombstone slots must not leak ids into the merge
    lids = jnp.where(lvals == -jnp.inf, -1, lids)

    av = jax.lax.all_gather(lvals, axis)                      # (S, Q, k)
    ai = jax.lax.all_gather(lids, axis)
    s_all, q_all, kk = av.shape
    fv = jnp.transpose(av, (1, 0, 2)).reshape(q_all, s_all * kk)
    fi = jnp.transpose(ai, (1, 0, 2)).reshape(q_all, s_all * kk)
    bv, bp = jax.lax.top_k(fv, k)
    bi = jnp.take_along_axis(fi, bp, axis=1)
    bi = jnp.where(bv == -jnp.inf, -1, bi)
    if query_axis is not None:   # restore the full replicated (Q, k)
        gv = jax.lax.all_gather(bv, query_axis)
        gi = jax.lax.all_gather(bi, query_axis)
        bv = gv.reshape(-1, k)
        bi = gi.reshape(-1, k)
    return bv, bi


class DistributedEngine:
    """Batched distributed MIPS over a placed :class:`ShardedIndex`.

    Args:
      index:  a ``build_sharded`` index, already placed via
              :func:`shard_index` (or abstract, for dry-runs).
      mesh:   the mesh the index was placed on.
      axis:   item mesh axis name (or tuple — multi-pod shards items over
              ``('pod', 'data')``); product must equal
              ``index.num_shards``.
      engine: "bucket" | "dense" | "auto" (directory-size break-even,
              :func:`repro.core.engine.select_engine`); None takes the
              spec's engine.
      impl:   kernel dispatch; None takes the spec's.
      query_axis: optional second mesh axis sharding the query batch
              (2-D decomposition; merge traffic drops by its size).
      tracker: optional :class:`repro.obs.Tracker` (None = ambient
              default). Records encode/collective spans, query counters,
              and jitted-collective cache hit/miss + trace-count — all
              host-side, outside the shard_map, so results stay
              bit-identical (parity-tested). Stage timings inside the
              collective are not separable (one jitted program); the
              collective span measures it end-to-end.
    """

    def __init__(self, index: ShardedIndex, mesh: Mesh, *,
                 axis="data", engine: Optional[str] = None,
                 impl: Optional[str] = None,
                 query_axis: Optional[str] = None, tracker=None):
        self.axis = _axis_tuple(axis)
        if _mesh_shards(mesh, self.axis) != index.num_shards:
            raise ValueError(
                f"index has {index.num_shards} shards but mesh axis "
                f"{self.axis} has {_mesh_shards(mesh, self.axis)} devices")
        engine = index.spec.engine if engine is None else engine
        if engine not in ("auto", "dense", "bucket"):
            raise ValueError(f"unknown engine: {engine!r}")
        if engine == "auto":
            engine = select_engine(index.num_buckets, index.num_items)
        self.index = index
        self.mesh = mesh
        self.engine = engine
        self.impl = index.spec.impl if impl is None else impl
        self.query_axis = query_axis
        self.family = index.spec.resolve_family()
        self.tracker = resolve_tracker(tracker)
        # the params are replicated over the mesh, so the encode is a
        # program over every device; the TPU kernel compiler cannot
        # partition a kernel, so each device encodes the whole batch
        self._encode = jax.jit(jax.shard_map(
            functools.partial(self.family.encode_queries, impl=self.impl),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), index.params), P()),
            out_specs=P(), check_vma=False))
        self._mapped_cache = {}
        self._range_counts_cache = None

    @property
    def _range_counts(self) -> np.ndarray:
        """Global per-range item counts from the replicated directory —
        computed lazily: only the planned-budget path needs concrete
        values, and dry-runs construct the engine from abstract arrays."""
        if self._range_counts_cache is None:
            idx = self.index
            self._range_counts_cache = np.bincount(
                np.asarray(jax.device_get(idx.dir_rid)),
                weights=np.asarray(jax.device_get(idx.dir_size)),
                minlength=idx.rank.shape[0]).astype(np.int64)
        return self._range_counts_cache

    def _mapped(self, num_probe: int, k: int, budgets=None):
        """Jitted shard_map per (num_probe, k, budgets) — repeat traffic
        (decode steps, fixed-budget batches) hits the executable cache
        instead of re-tracing the collective."""
        key = (num_probe, k, budgets)
        fn = self._mapped_cache.get(key)
        tr = self.tracker
        if fn is not None:
            if tr is not None:
                tr.count("repro.engine.distributed.jit_cache.hit")
            return fn
        if tr is not None:
            tr.count("repro.engine.distributed.jit_cache.miss")
        idx = self.index
        axis_sizes = tuple(self.mesh.shape[a] for a in self.axis)
        body = functools.partial(
            _shard_query, family=self.family, hash_bits=idx.hash_bits,
            num_probe=num_probe, k=k, engine=self.engine,
            impl=self.impl, axis=self.axis, axis_sizes=axis_sizes,
            query_axis=self.query_axis, budgets=budgets)
        q2 = P(self.query_axis, None) if self.query_axis \
            else P(None, None)
        row = P(self.axis)
        row2 = P(self.axis, None)
        params_spec = jax.tree.map(lambda _: P(), idx.params)
        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(q2, q2, params_spec, P(), P(), P(), P(), P(), P(),
                      row2, row2, row, row, row, row, row),
            out_specs=(P(), P()),
            check_vma=False,
        ))
        self._mapped_cache[key] = fn
        if tr is not None:
            # trace count == distinct jitted collectives alive; a steady
            # gauge under repeat traffic is the "no re-trace" regression
            # signal (tests/test_distributed.py).
            tr.gauge("repro.engine.distributed.trace_count",
                     len(self._mapped_cache))
        return fn

    def query(self, queries: jax.Array, k: int,
              num_probe: Optional[int] = None, *,
              recall_target: Optional[float] = None,
              budgets=None) -> Tuple[jax.Array, jax.Array]:
        """Distributed Algorithm 2 under a *global* probe budget: the
        probed union across shards is exactly the first ``num_probe``
        items of the single-device canonical order, so (vals, ids) —
        each (Q, k), replicated — are bit-identical to
        ``QueryEngine.query`` on the same spec.

        ``budgets`` / ``recall_target`` select the planner's per-range
        contract instead (DESIGN.md §12): every shard derives the same
        per-range takes from the replicated directory, so the probed
        union is exactly the single-device *planned* candidate set and
        the merge stays bit-identical to ``QueryEngine.query`` with the
        same budgets."""
        idx = self.index
        if recall_target is not None:
            if num_probe is not None or budgets is not None:
                raise ValueError(
                    "pass one of num_probe/budgets/recall_target")
            from repro.core.planner import resolve_budgets
            budgets = resolve_budgets(idx.calib, recall_target,
                                      k=k).budgets
        if budgets is not None:
            if num_probe is not None:
                raise ValueError("pass one of num_probe/budgets")
            from repro.core.engine import check_budgets
            budgets, num_probe = check_budgets(budgets,
                                               self._range_counts)
            if not 0 < int(k) <= num_probe:
                raise ValueError(f"k={k} outside (0, planned width "
                                 f"{num_probe}]")
        else:
            if num_probe is None:
                raise ValueError(
                    "pass num_probe, budgets or recall_target")
            num_probe = _check_probe(num_probe, k, idx.num_items)
        tr = self.tracker
        with span_or_null(tr, "repro.engine.hash_encode") as sp:
            q_codes = sp.sync(self._encode(idx.params, queries))
        mapped = self._mapped(num_probe, int(k), budgets)
        # NOTE: re-rank uses the ORIGINAL queries (true inner products);
        # the family transform only affects the hash codes.
        with span_or_null(tr, "repro.engine.distributed.collective") as sp:
            vals, ids = sp.sync(mapped(
                q_codes, queries, idx.params, idx.dir_code,
                idx.dir_rid, idx.dir_size, idx.dir_shard,
                idx.dir_local_start, idx.rank, idx.items, idx.codes,
                idx.range_id, idx.bucket_of, idx.bucket_off,
                idx.perm, idx.valid))
        if tr is not None:
            tr.count("repro.engine.queries", queries.shape[0])
            tr.observe("repro.engine.probe_width", num_probe)
            if budgets is not None:
                for j, b in enumerate(budgets):
                    tr.observe(f"repro.engine.probes_used.range{j}", b)
        return vals, ids


# -- legacy shims (seed-era dense RANGE-LSH surface) --------------------------


def build(items: jax.Array, key: jax.Array, code_len: int, num_ranges: int,
          num_shards: int, *, eps: float = DEFAULT_EPS, impl: str = "auto"
          ) -> ShardedIndex:
    """Legacy entry point: RANGE-LSH == ``IndexSpec(family="simple")``
    through :func:`build_sharded` (strict=False, as the old kwargs
    surface allowed any ``num_ranges``)."""
    spec = IndexSpec(family="simple", code_len=code_len, m=num_ranges,
                     engine="dense", eps=eps, impl=impl)
    return build_sharded(spec, items, key, num_shards, strict=False)


# one-slot engine memo for the legacy shim: repeat calls over the same
# (index, mesh) reuse the jitted collective instead of re-tracing it.
# The entry holds strong refs to index/mesh, so the id() key can't be a
# stale reuse.
_shim_engine: dict = {}


def query(index: ShardedIndex, queries: jax.Array, k: int,
          num_probe_per_shard: int, mesh: Mesh, axis="data",
          query_axis: Optional[str] = None, *,
          engine: Optional[str] = None, impl: Optional[str] = None,
          ) -> Tuple[jax.Array, jax.Array]:
    """Legacy entry point over :class:`DistributedEngine` (construct the
    engine directly for serving loops — it caches the jitted collective).

    The seed-era ``num_probe_per_shard`` bounded re-rank work per device
    with a per-shard local scan; the engine's budget is global and
    exact, so the shim maps it to
    ``num_probe = min(N, num_probe_per_shard * num_shards)`` — identical
    at full budget, and the same per-device probe ceiling otherwise.
    """
    shards = _mesh_shards(mesh, _axis_tuple(axis))
    num_probe = min(index.num_items, int(num_probe_per_shard) * shards)
    key = (id(index), id(mesh), _axis_tuple(axis), query_axis, engine,
           impl)
    ent = _shim_engine.get(key)
    if ent is None:
        eng = DistributedEngine(index, mesh, axis=axis, engine=engine,
                                impl=impl, query_axis=query_axis)
        _shim_engine.clear()
        _shim_engine[key] = (index, mesh, eng)
    else:
        eng = ent[2]
    return eng.query(queries, k, num_probe)
