"""Recall-contract query planner (DESIGN.md §12).

The paper's headline claim is a speedup *at fixed recall*, yet a static
``num_probe`` never sees recall at all — it is a proxy the operator tunes
offline against one dataset snapshot. This module closes the loop by
making the recall target itself the query parameter:

  * **calibrate offline** (:func:`calibrate`) — sample held-out queries,
    compute brute-force ground truth, and measure where the truth items
    land in the index's *canonical probe order* (the eq.-12 rank table of
    whatever :class:`~repro.core.family.HashFamily` the index was built
    with — calibration never touches family internals, only the order the
    score table induces). The result is a :class:`CalibrationTable`:
    per-range recall curves ``r_j(b)`` ("a truth item in range j is found
    within the first ``b`` probed items of range j"), the truth mass per
    range, and a global curve for scalar-budget surfaces.
  * **plan online** (:func:`plan`) — turn a target (e.g. 0.95@k=10) into
    per-range probe budgets by greedy marginal-gain allocation over the
    calibrated curves: repeatedly grow the budget of the range with the
    best Δrecall/Δprobes until the predicted recall meets the target. The
    follow-up paper's observation that per-range ρ varies with the norm
    cap is exactly why this beats one global budget: ranges that never
    hold truth items get ~0 probes instead of riding along in the eq.-12
    interleave. The greedy path is deterministic, so plans are *nested*:
    a lower target's budgets are an elementwise prefix of a higher
    target's (the conformance suite's prefix-superset invariant).
  * **adapt per query** (:func:`adaptive_query`) — walk the planned
    candidates grouped by descending range cap, re-ranking in chunks, and
    stop a query once its running top-k lower bound (exact inner
    products) beats the best score any remaining bucket could have —
    the full-match score-table entry of its range, ``U_j`` for sign
    families, so the bound is ``q.x <= ||q|| ||x|| <= ||q|| U_j``:
    provable, not the eq.-12 estimate. Early-terminated queries return
    the *same* top-k as the full planned re-rank; only the provably
    futile tail of the budget is skipped.

Execution of a per-range budget vector is the engines' job
(``repro.core.engine.planned_*_candidates`` and the ``budgets=`` arm of
``repro.core.distributed._shard_query``); the shared contract is:

    probe, for each range j, the first ``min(b_j, n_j)`` items of range j
    in canonical (rank, CSR position) order.

Because every range contributes exactly ``min(b_j, n_j)`` items for every
query, the candidate count ``sum_j min(b_j, n_j)`` is static — planned
queries stay on the jit cache like static-budget ones.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, topk

DEFAULT_CAL_QUERIES = 256
DEFAULT_CAL_K = 10
GRID_FACTOR = 1.3


class CalibrationTable(NamedTuple):
    """Measured recall curves in canonical probe order (all numpy, host).

    Attributes:
      probe_grid:    (G,) int64 ascending probe counts; grid[0] == 0 and
                     grid[-1] >= N, so every target is reachable.
      recall_range:  (R, G) f32 — P(truth item of range j is within the
                     first ``min(grid[g], n_j)`` probed items of range j).
      recall_global: (G,) f32 — recall of the *global* canonical prefix
                     (the curve scalar-``num_probe`` surfaces plan from).
      truth_mass:    (R,) f32 — fraction of all truth items in range j.
      range_counts:  (R,) int64 items per range at calibration time (clips
                     budgets; doubles as a partition fingerprint).
      k:             top-k the curves were measured at.
      num_queries:   calibration sample size.
    """

    probe_grid: np.ndarray
    recall_range: np.ndarray
    recall_global: np.ndarray
    truth_mass: np.ndarray
    range_counts: np.ndarray
    k: int
    num_queries: int

    @property
    def num_ranges(self) -> int:
        return int(self.range_counts.shape[0])

    @property
    def num_items(self) -> int:
        return int(self.range_counts.sum())


class Plan(NamedTuple):
    """A resolved recall contract: per-range budgets + predicted recall.

    ``budgets[j]`` is already clipped to the range's item count, so
    ``num_probe == sum(budgets)`` is the exact planned candidate width.
    """

    budgets: Tuple[int, ...]
    num_probe: int
    predicted_recall: float
    recall_target: float


def default_grid(n: int, factor: float = GRID_FACTOR) -> np.ndarray:
    """Geometric probe-count grid {0, 1, ..., n}: dense where the curves
    move, sparse in the tail."""
    vals = {0, int(n)}
    v = 1.0
    while v < n:
        vals.add(int(round(v)))
        v *= factor
    return np.asarray(sorted(vals), np.int64)


def check_target(recall_target: float) -> float:
    recall_target = float(recall_target)
    if not 0.0 < recall_target <= 1.0:
        raise ValueError(
            f"recall_target must be in (0, 1], got {recall_target}")
    return recall_target


# -- calibration --------------------------------------------------------------


def calibrate_from_order(order_ids: np.ndarray, range_id: np.ndarray,
                         truth_ids: np.ndarray, *,
                         num_ranges: Optional[int] = None,
                         grid: Optional[np.ndarray] = None
                         ) -> CalibrationTable:
    """Fit the table from an explicit probe order (the family-agnostic
    core: any surface that can enumerate its canonical order calibrates
    through here).

    order_ids:  (Q, N) item ids, canonical probe order per query.
    range_id:   (N,) range of each item id.
    truth_ids:  (Q, k) brute-force ground-truth ids.
    num_ranges: R of the index's rank table — pass it when empty top
                ranges are possible (uniform bins), so budget vectors
                keep the engines' expected length.
    """
    order_ids = np.asarray(order_ids)
    range_id = np.asarray(range_id, np.int64)
    truth_ids = np.asarray(truth_ids)
    q, n = order_ids.shape
    k = truth_ids.shape[1]
    if num_ranges is None:
        num_ranges = int(range_id.max()) + 1 if range_id.size else 1
    m = int(num_ranges)
    counts = np.bincount(range_id, minlength=m).astype(np.int64)
    if grid is None:
        grid = default_grid(n)
    grid = np.asarray(grid, np.int64)

    # global position of every id, and its position within its range's
    # probe order (cumulative count of same-range items before it)
    gpos = np.empty((q, n), np.int64)
    rows = np.arange(q)[:, None]
    gpos[rows, order_ids] = np.arange(n, dtype=np.int64)[None, :]
    t_gpos = np.take_along_axis(gpos, truth_ids, axis=1)          # (Q, k)
    # a stable sort of each probe-ordered row by range (numpy radix-sorts
    # 16-bit keys in linear time) lists every range's items in probe
    # order; an item's place within its range's group is its position
    key_t = np.int16 if m <= np.iinfo(np.int16).max else np.int64
    sorted_rid = range_id[order_ids].astype(key_t)           # (Q, N)
    by_range = np.argsort(sorted_rid, axis=1, kind="stable")
    rid_by = np.take_along_axis(sorted_rid, by_range, axis=1)
    idx = np.arange(n, dtype=np.int64)[None, :]
    first = np.ones((q, n), bool)
    first[:, 1:] = rid_by[:, 1:] != rid_by[:, :-1]
    group_start = np.maximum.accumulate(np.where(first, idx, 0), axis=1)
    wpos_sorted = np.empty((q, n), np.int64)
    wpos_sorted[rows, by_range] = idx - group_start
    t_wpos = np.take_along_axis(wpos_sorted, t_gpos, axis=1).reshape(-1)
    t_gpos = t_gpos.reshape(-1)
    t_rid = range_id[truth_ids.reshape(-1)]
    total = t_rid.size

    recall_global = (t_gpos[None, :] < grid[:, None]).mean(
        axis=1).astype(np.float32)
    recall_range = np.zeros((m, grid.size), np.float32)
    mass = np.zeros((m,), np.float32)
    for j in range(m):
        sel = t_rid == j
        mass[j] = sel.sum() / total
        eff = np.minimum(grid, counts[j])
        if sel.any():
            recall_range[j] = (t_wpos[sel][None, :]
                               < eff[:, None]).mean(axis=1)
        # the full range always contains all its truth items (also pins
        # empty-truth ranges so predicted recall reaches 1.0 at full)
        recall_range[j, eff >= counts[j]] = 1.0
    return CalibrationTable(grid, recall_range, recall_global, mass,
                            counts, int(k), int(q))


def canonical_order(index, queries: jax.Array, *, buckets=None
                    ) -> np.ndarray:
    """(Q, N) item ids in the engines' canonical ``(rank, CSR position)``
    probe order — the order both query engines and the distributed
    traversal realize (core/engine.py)."""
    from repro.core.bucket_index import build_bucket_index

    if buckets is None:
        buckets = build_bucket_index(index)
    fam = index.family
    q_codes = fam.encode_queries(index.params, queries,
                                 impl=index.spec.impl)
    matches = fam.match_counts(index.params, q_codes, index.codes,
                               index.hash_bits, impl=index.spec.impl)
    item_rank = buckets.rank[index.range_id[None, :], matches]
    rank_csr = np.asarray(jax.device_get(item_rank))[
        :, np.asarray(jax.device_get(buckets.item_ids))]
    order = np.argsort(rank_csr, axis=1, kind="stable")
    return np.asarray(jax.device_get(buckets.item_ids))[order]


def calibrate(index, queries: Optional[jax.Array] = None, *,
              k: int = DEFAULT_CAL_K, key: Optional[jax.Array] = None,
              num_queries: int = DEFAULT_CAL_QUERIES,
              grid: Optional[np.ndarray] = None,
              buckets=None) -> CalibrationTable:
    """Calibrate a spec-built :class:`~repro.core.index.ComposedIndex`.

    ``queries`` should be held-out samples of the serving distribution;
    when absent, standard-normal queries are drawn from ``key`` (the
    synthetic-dataset query model — override for real workloads). Ground
    truth is brute force, so this is an offline O(Q N) step.
    """
    if queries is None:
        if key is None:
            raise ValueError("pass calibration queries or a key to "
                             "sample them")
        queries = jax.random.normal(key,
                                    (num_queries, index.items.shape[-1]))
    queries = jnp.asarray(queries, jnp.float32)
    n = int(index.items.shape[0])
    if not 0 < int(k) <= n:
        raise ValueError(f"calibration k={k} outside (0, N={n}]")
    order_ids = canonical_order(index, queries, buckets=buckets)
    _, truth = topk.exact_mips(queries, index.items, k)
    return calibrate_from_order(
        order_ids, np.asarray(jax.device_get(index.range_id)),
        np.asarray(jax.device_get(truth)),
        num_ranges=int(index.table.shape[0]), grid=grid)


def calibrate_streaming(mindex, queries: jax.Array, *,
                        k: int = DEFAULT_CAL_K,
                        grid: Optional[np.ndarray] = None
                        ) -> CalibrationTable:
    """Calibrate a :class:`repro.streaming.MutableIndex` over its live
    set (merged base+delta canonical order). Attach with
    ``mindex.set_calibration(table)``; structural events that move range
    boundaries flag it stale."""
    queries = jnp.asarray(queries, jnp.float32)
    live = mindex.live_count
    if not 0 < int(k) <= live:
        raise ValueError(f"calibration k={k} outside (0, live={live}]")
    order_gids = np.asarray(jax.device_get(
        mindex.candidates(queries, live)))             # (Q, live) globals
    vecs, gids = mindex.live_vectors()
    _, truth_pos = topk.exact_mips(queries, vecs, k)
    truth_gids = gids[np.asarray(jax.device_get(truth_pos))]
    # compact global ids to [0, live) so calibrate_from_order's scatters
    # stay dense
    remap = np.full((mindex.store_size + mindex.delta.capacity,), -1,
                    np.int64)
    remap[gids] = np.arange(gids.size)
    rid_all = np.concatenate([
        mindex._rid, mindex.delta._rid[:mindex.delta.count]])
    # remap[gids] == arange(live), so rid_all[gids] is already indexed by
    # compact id
    return calibrate_from_order(remap[order_gids], rid_all[gids],
                                remap[truth_gids],
                                num_ranges=mindex.num_ranges, grid=grid)


# -- planning -----------------------------------------------------------------


def plan(calib: CalibrationTable, recall_target: float) -> Plan:
    """Per-range budgets predicted to meet the target at near-minimal
    total candidate count.

    Greedy marginal-gain allocation over the calibrated grid: advance the
    range with the highest Δrecall/Δprobes (ties: cheaper step, then lower
    range id) until ``sum_j mass_j r_j(b_j) >= target``. Greedy is exact
    for concave curves and near-minimal on the empirical step curves
    measured here (a non-concave jump can make it overshoot the true
    minimum); deterministic and incremental, so plans for increasing
    targets are nested.
    """
    recall_target = check_target(recall_target)
    grid = calib.probe_grid
    counts = calib.range_counts
    m, g_max = calib.recall_range.shape
    level = np.zeros((m,), np.int64)     # grid index per range
    eff = np.minimum(grid[None, :], counts[:, None])     # (R, G)
    contrib = calib.truth_mass[:, None] * calib.recall_range
    predicted = float(contrib[np.arange(m), level].sum())
    while predicted < recall_target:
        best, best_key = -1, None
        for j in range(m):
            lv = level[j]
            if lv + 1 >= g_max or eff[j, lv + 1] <= eff[j, lv]:
                continue                 # range exhausted
            dcost = int(eff[j, lv + 1] - eff[j, lv])
            dgain = float(contrib[j, lv + 1] - contrib[j, lv])
            key = (-dgain / dcost, dcost, j)
            if best_key is None or key < best_key:
                best, best_key = j, key
        if best < 0:                     # every range at full coverage
            break
        level[best] += 1
        predicted = float(contrib[np.arange(m), level].sum())
    budgets = tuple(int(eff[j, level[j]]) for j in range(m))
    return Plan(budgets, int(sum(budgets)), predicted, recall_target)


def plan_global(calib: CalibrationTable, recall_target: float) -> Plan:
    """Scalar-budget fallback for surfaces without per-range probing
    (streaming merged engine, the lm_head dense arm): the smallest grid
    ``num_probe`` whose measured *global-prefix* recall meets the target.
    ``budgets`` is empty — the budget is the global prefix itself."""
    recall_target = check_target(recall_target)
    ok = np.flatnonzero(calib.recall_global >= recall_target)
    g = int(ok[0]) if ok.size else int(calib.probe_grid.size - 1)
    num_probe = int(min(calib.probe_grid[g], calib.num_items))
    return Plan((), max(num_probe, 1),
                float(calib.recall_global[g]), recall_target)


def check_contract_k(calib: CalibrationTable, k) -> None:
    """The curves measure recall@``calib.k``; a deeper query k would
    silently under-deliver, so refuse it (smaller k is conservative —
    the top of the truth set is found earliest in probe order)."""
    if k is not None and int(k) > calib.k:
        raise ValueError(
            f"recall contract was calibrated at k={calib.k} but queried "
            f"at k={k} — recalibrate with calibration_k >= {k}")


def resolve_budgets(calib: Optional[CalibrationTable],
                    recall_target: float, k=None) -> Plan:
    """Shared entry used by the engines: validate calibration presence
    and that the query k is covered by the calibrated curves."""
    if calib is None:
        raise ValueError(
            "recall_target needs a calibrated index — build with "
            "IndexSpec(recall_target=...) or attach planner.calibrate()")
    check_contract_k(calib, k)
    return plan(calib, recall_target)


# -- adaptive early termination ----------------------------------------------


def adaptive_query(engine, queries: jax.Array, k: int, *,
                   recall_target: Optional[float] = None,
                   budgets: Optional[Sequence[int]] = None,
                   num_probe: Optional[int] = None,
                   chunk: int = 32, tracker=None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Planned probing with provable per-query early termination.

    The planned candidate set is re-walked grouped by *descending range
    cap* (canonical order within a cap) in ``chunk``-sized exact re-rank
    steps. The best score any unprobed bucket could possibly reach is its
    range's full-match score-table entry — ``U_j`` for sign families, so
    ``q.x <= ||q|| U_j`` is a hard bound, and the cap-descending walk
    makes it the suffix maximum for free. A query stops as soon as its
    running k-th exact inner product meets the next candidate's bound:
    everything skipped provably cannot displace the top-k, so
    ``(vals, ids)`` equal the full planned re-rank (up to exact-tie
    order) while ``probes_used`` records the work actually done.

    Returns ``(vals, ids, probes_used)`` — (Q, k), (Q, k), (Q,).

    ``tracker`` (default: the engine's) records per-query ``probes_used``
    and adaptive-termination savings host-side after the loop completes —
    the returned arrays are untouched.
    """
    index = engine.index
    if recall_target is not None:
        if budgets is not None or num_probe is not None:
            raise ValueError("pass one of recall_target/budgets/num_probe")
        budgets = resolve_budgets(getattr(index, "calib", None),
                                  recall_target, k=k).budgets
    if (budgets is None) == (num_probe is None):
        raise ValueError("pass exactly one of budgets/num_probe "
                         "(or recall_target)")
    queries = jnp.asarray(queries, jnp.float32)
    if budgets is not None:
        cand = engine.candidates(queries, budgets=budgets)
    else:
        cand = engine.candidates(queries, num_probe)
    P = int(cand.shape[1])
    k = int(k)
    if not 0 < k <= P:
        raise ValueError(f"k={k} outside (0, planned width {P}]")

    # hard per-candidate bound: full-match score-table entry of its range
    # (strictly increasing in l, so the last column), times ||q||
    cap = index.table[:, -1][engine._range_id[cand]]          # (Q, P)
    reorder = jnp.argsort(-cap, axis=-1, stable=True)
    cand = jnp.take_along_axis(cand, reorder, axis=-1)
    cap = jnp.take_along_axis(cap, reorder, axis=-1)          # descending
    qnorm = hashing.l2_norm(queries)                          # (Q,)

    n_chunks = -(-P // chunk)
    pad = n_chunks * chunk - P
    cand_p = jnp.pad(cand, ((0, 0), (0, pad)))
    valid_p = jnp.pad(jnp.ones(cand.shape, bool), ((0, 0), (0, pad)))
    # padded slots: -inf bound (never extends probing), ip masked anyway
    bound_p = jnp.where(
        valid_p,
        jnp.pad(cap.astype(jnp.float32), ((0, 0), (0, pad)))
        * qnorm[:, None], -jnp.inf)

    q = queries.shape[0]
    items = index.items

    def body(state):
        c, vals, ids, used, active = state
        sl = jax.lax.dynamic_slice_in_dim(cand_p, c * chunk, chunk, axis=1)
        ok = jax.lax.dynamic_slice_in_dim(valid_p, c * chunk, chunk,
                                          axis=1)
        ip = jnp.einsum("qd,qpd->qp", queries, items[sl],
                        precision=topk.EXACT)
        ip = jnp.where(ok & active[:, None], ip, -jnp.inf)
        av = jnp.concatenate([vals, ip], axis=1)
        ai = jnp.concatenate([ids, sl], axis=1)
        vals, pos = jax.lax.top_k(av, k)
        ids = jnp.take_along_axis(ai, pos, axis=1)
        used = used + jnp.where(active,
                                jnp.sum(ok, axis=1, dtype=jnp.int32), 0)
        nxt = jnp.minimum((c + 1) * chunk, P - 1)
        next_bound = jax.lax.dynamic_index_in_dim(
            bound_p.T, nxt, axis=0, keepdims=False)           # (Q,)
        exhausted = (c + 1) * chunk >= P
        active = active & ~exhausted & (vals[:, k - 1] < next_bound)
        return c + 1, vals, ids, used, active

    state = (jnp.int32(0),
             jnp.full((q, k), -jnp.inf, jnp.float32),
             jnp.full((q, k), -1, jnp.int32),
             jnp.zeros((q,), jnp.int32),
             jnp.ones((q,), bool))
    state = jax.lax.while_loop(
        lambda s: jnp.logical_and(s[0] < n_chunks, s[4].any()), body,
        state)
    _, vals, ids, used, _ = state
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    tr = tracker if tracker is not None else getattr(engine, "tracker",
                                                     None)
    if tr is not None:
        used_host = np.asarray(jax.device_get(used))
        for u in used_host:
            tr.observe("repro.planner.probes_used", float(u))
            tr.observe("repro.planner.adaptive_savings",
                       float(P - u) / float(P))
        tr.count("repro.planner.adaptive_queries", q)
        tr.gauge("repro.planner.planned_width", P)
    return vals, ids, used
