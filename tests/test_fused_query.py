"""Fused single-pass query kernel + PR 10 hot-path regressions
(DESIGN.md §17).

Coverage map:
  * engine-level parity matrix — fused ids bit-identical to the staged
    planned path across hash families x shard slices x degenerate shapes
    (the acceptance contract; ref dispatch, the CPU production path);
  * interpret-mode kernel parity — ``ops.fused_query(impl="pallas")``
    against the jnp oracle, f32 and int8 arms (the repro-lint R3 hook);
  * int8 arm recall-delta bound on the conformance long-tail mixture;
  * the PR 10 bugfixes — duplicate-candidate re-rank masking and the
    bounded ``engine_for`` memo.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import topk
from repro.core.engine import QueryEngine, engine_for, quantize_payload
from repro.core.index import IndexSpec, build
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.obs.tracker import Tracker

KEY = jax.random.PRNGKey(7)

FAMILIES = ("simple", "l2_alsh", "sign_alsh")


def _longtail_items(n, d, key):
    """Items with a long-tail norm profile (norm ranging has to matter)."""
    k1, k2 = jax.random.split(key)
    base = jax.random.normal(k1, (n, d))
    scales = jnp.exp(1.2 * jax.random.normal(k2, (n, 1)))
    return (base * scales).astype(jnp.float32)


def _build(items, family, m=4, engine="bucket"):
    spec = IndexSpec(family=family, code_len=16, m=m, engine=engine)
    return build(spec, items, KEY)


def _assert_fused_matches_staged(idx, queries, k, *, num_probe=None,
                                 budgets=None, impl="auto"):
    staged = QueryEngine(idx, engine="bucket")
    fused = QueryEngine(idx, engine="fused", impl=impl)
    sv, si = staged.query(queries, k, num_probe, budgets=budgets)
    fv, fi = fused.query(queries, k, num_probe, budgets=budgets)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(si))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(sv),
                               atol=1e-4, rtol=1e-5)


# -- engine-level parity matrix ----------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("shards", (1, 8))
def test_fused_matches_staged_planned(family, shards):
    """Fused == staged planned path, bit-identical ids, on every
    contiguous shard slice of a long-tail dataset (the per-shard layout
    the distributed engine hands each device)."""
    items = _longtail_items(256, 8, jax.random.PRNGKey(11))
    queries = jax.random.normal(jax.random.PRNGKey(12), (6, 8))
    per = items.shape[0] // shards
    # first and last slice bracket the norm layout; the middle adds one
    # interior boundary without 8x-ing the runtime
    test_slices = (0,) if shards == 1 else (0, 3, 7)
    for s in test_slices:
        idx = _build(items[s * per:(s + 1) * per], family)
        _assert_fused_matches_staged(idx, queries, 4,
                                     budgets=[12, 8, 5, 3])


@pytest.mark.parametrize("family", FAMILIES)
def test_fused_matches_staged_unplanned(family):
    items = _longtail_items(200, 8, jax.random.PRNGKey(13))
    queries = jax.random.normal(jax.random.PRNGKey(14), (5, 8))
    idx = _build(items, family)
    _assert_fused_matches_staged(idx, queries, 6, num_probe=48)


def test_fused_degenerate_shapes():
    items = _longtail_items(64, 8, jax.random.PRNGKey(15))
    queries = jax.random.normal(jax.random.PRNGKey(16), (4, 8))
    # Q=1 (sub-block query row)
    idx = _build(items, "simple")
    _assert_fused_matches_staged(idx, queries[:1], 3, num_probe=16)
    # single range (m=1: the SIMPLE-LSH degenerate, one budget entry)
    flat = _build(items, "simple", m=1)
    _assert_fused_matches_staged(flat, queries, 3, budgets=[20])
    # k exceeding every bucket size (survivors must merge across runs)
    _assert_fused_matches_staged(idx, queries, 16, num_probe=32)


def test_fused_full_probe_is_exact():
    """At full probe budget the fused engine IS exact MIPS."""
    items = _longtail_items(96, 8, jax.random.PRNGKey(17))
    queries = jax.random.normal(jax.random.PRNGKey(18), (4, 8))
    idx = _build(items, "simple")
    eng = QueryEngine(idx, engine="fused")
    fv, fi = eng.query(queries, 5, items.shape[0])
    tv, ti = topk.exact_mips(queries, items, 5)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ti))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(tv),
                               atol=1e-4, rtol=1e-5)


def test_composed_index_fused_engine_routes():
    """ComposedIndex.query(engine="fused") == the staged bucket engine;
    the spec-level engine default routes the same way."""
    items = _longtail_items(128, 8, jax.random.PRNGKey(19))
    queries = jax.random.normal(jax.random.PRNGKey(20), (4, 8))
    idx = _build(items, "simple")
    sv, si = idx.query(queries, 4, 32, engine="bucket")
    fv, fi = idx.query(queries, 4, 32, engine="fused")
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(si))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(sv), atol=1e-4)
    spec_fused = build(IndexSpec(family="simple", code_len=16, m=4,
                                 engine="fused"), items, KEY)
    fv2, fi2 = spec_fused.query(queries, 4, 32)
    np.testing.assert_array_equal(np.asarray(fi2), np.asarray(si))


def test_fused_candidates_are_staged_candidates():
    items = _longtail_items(128, 8, jax.random.PRNGKey(21))
    queries = jax.random.normal(jax.random.PRNGKey(22), (3, 8))
    idx = _build(items, "simple")
    c_b = idx.candidates(queries, 40, engine="bucket")
    c_f = idx.candidates(queries, 40, engine="fused")
    np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_b))


def test_multi_table_rejects_fused_engine():
    with pytest.raises(ValueError, match="multi-table"):
        IndexSpec(family="simple", code_len=16, num_tables=4,
                  engine="fused").validate()


# -- interpret-mode kernel parity (repro-lint R3 hook) ------------------------


def _runs(key, q, s, n, total):
    """Random CSR runs whose per-query takes sum to exactly ``total``."""
    k1, k2 = jax.random.split(key)
    cuts = jnp.sort(jax.random.randint(k1, (q, s - 1), 0, total + 1), axis=1)
    cum = jnp.concatenate(
        [jnp.zeros((q, 1), jnp.int32), cuts.astype(jnp.int32),
         jnp.full((q, 1), total, jnp.int32)], axis=1)
    sizes = cum[:, 1:] - cum[:, :-1]
    starts = jax.random.randint(k2, (q, s), 0, n - total).astype(jnp.int32)
    del sizes
    return cum, starts


@pytest.mark.parametrize("q,s,n,d,total,k", [
    (3, 4, 64, 8, 24, 5),      # unaligned Q (pads 3 -> 8)
    (1, 1, 32, 4, 8, 8),       # Q=1, single run, k == total
    (8, 3, 300, 16, 160, 10),  # multi-chunk candidate axis (total > 128)
])
def test_fused_query_pallas_matches_ref(q, s, n, d, total, k):
    key = jax.random.PRNGKey(q * 100 + s)
    queries = jax.random.normal(key, (q, d), jnp.float32)
    items = jax.random.normal(jax.random.fold_in(key, 1), (n, d),
                              jnp.float32)
    cum, starts = _runs(jax.random.fold_in(key, 2), q, s, n, total)
    gv, gp = ops.fused_query(queries, cum, starts, items, total, k,
                             impl="pallas")
    wv, wp = ops.fused_query(queries, cum, starts, items, total, k,
                             impl="ref")
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               atol=1e-4, rtol=1e-5)


def test_fused_query_pallas_int8_matches_ref():
    q, s, n, d, total, k = 4, 3, 80, 8, 40, 6
    key = jax.random.PRNGKey(23)
    queries = jax.random.normal(key, (q, d), jnp.float32)
    items = _longtail_items(n, d, jax.random.fold_in(key, 1))
    payload, scale = quantize_payload(items)
    cum, starts = _runs(jax.random.fold_in(key, 2), q, s, n, total)
    gv, gp = ops.fused_query(queries, cum, starts, items, total, k,
                             payload=payload, scale=scale, impl="pallas")
    wv, wp = ops.fused_query(queries, cum, starts, items, total, k,
                             payload=payload, scale=scale, impl="ref")
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               atol=1e-4, rtol=1e-5)


def test_fused_engine_pallas_end_to_end():
    """The whole fused engine in interpret mode: ids bit-identical to the
    staged ref path (the acceptance criterion, end to end)."""
    items = _longtail_items(128, 8, jax.random.PRNGKey(24))
    queries = jax.random.normal(jax.random.PRNGKey(25), (4, 8))
    idx = _build(items, "simple")
    _assert_fused_matches_staged(idx, queries, 4, budgets=[10, 8, 6, 4],
                                 impl="pallas")


def test_fused_query_validation():
    queries = jnp.ones((2, 4))
    cum = jnp.asarray([[0, 4], [0, 4]], jnp.int32)
    starts = jnp.zeros((2, 1), jnp.int32)
    items = jnp.ones((16, 4))
    for impl in ("ref", "pallas"):
        with pytest.raises(ValueError, match="must not exceed"):
            ops.fused_query(queries, cum, starts, items, 4, 5, impl=impl)
    with pytest.raises(ValueError, match="kprime"):
        ops.fused_query(queries, cum, starts, items, 4, 3, kprime=2)
    with pytest.raises(ValueError, match="payload and scale together"):
        ops.fused_query(queries, cum, starts, items, 4, 2,
                        payload=jnp.zeros((16, 4), jnp.int8))


# -- int8 arm: recall delta on the long-tail mixture --------------------------


def test_fused_int8_recall_delta_bounded(longtail_ds):
    """Quantized phase-1 scoring with the f32 rescore of k' survivors
    stays within the calibrated recall tolerance of the f32 engine."""
    items, queries = longtail_ds.items, longtail_ds.queries
    idx = build(IndexSpec(family="simple", code_len=16, m=8,
                          engine="bucket"), items, KEY)
    k, probe = 10, 800
    _, truth = topk.exact_mips(queries, items, k)
    _, ids_f32 = QueryEngine(idx, engine="fused").query(queries, k, probe)
    _, ids_int8 = QueryEngine(idx, engine="fused", quantized=True).query(
        queries, k, probe)
    rec_f32 = float(topk.recall_at(ids_f32, truth))
    rec_int8 = float(topk.recall_at(ids_int8, truth))
    assert rec_f32 - rec_int8 <= 0.03, (rec_f32, rec_int8)


def test_quantized_requires_fused_engine():
    items = _longtail_items(64, 8, jax.random.PRNGKey(26))
    idx = _build(items, "simple")
    with pytest.raises(ValueError, match="fused"):
        QueryEngine(idx, engine="bucket", quantized=True)


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_fused_engine_refuses_tpu_at_construction(monkeypatch, impl):
    """On a TPU the fused kernel does not lower: construction says so,
    instead of a compiler error surfacing mid-query. The jnp reference
    arm and the other engines still construct."""
    items = _longtail_items(64, 8, jax.random.PRNGKey(27))
    idx = _build(items, "simple")
    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="does not compile"):
        QueryEngine(idx, engine="fused", impl=impl)
    assert QueryEngine(idx, engine="fused", impl="ref").engine == "fused"
    assert QueryEngine(idx, engine="bucket", impl=impl).engine == "bucket"


def test_quantize_payload_roundtrip():
    items = _longtail_items(50, 8, jax.random.PRNGKey(27))
    payload, scale = quantize_payload(items)
    assert payload.dtype == jnp.int8 and scale.shape == (50, 1)
    deq = payload.astype(jnp.float32) * scale
    err = jnp.max(jnp.abs(deq - items) / jnp.maximum(scale, 1e-30))
    assert float(err) <= 0.5 + 1e-3       # half-ulp rounding in int8 grid
    # all-zero rows must not divide by zero
    p0, s0 = quantize_payload(jnp.zeros((3, 8), jnp.float32))
    assert bool(jnp.all(p0 == 0)) and bool(jnp.all(jnp.isfinite(s0)))


# -- PR 10 bugfix: duplicate-candidate re-rank --------------------------------


def test_rerank_masks_duplicate_candidates():
    """Repeated candidate ids must not claim multiple result slots."""
    items = _longtail_items(32, 8, jax.random.PRNGKey(28))
    queries = jax.random.normal(jax.random.PRNGKey(29), (2, 8))
    cand = jnp.asarray([[3, 5, 3, 3, 7, 5, 1, 0],
                        [9, 9, 9, 9, 2, 4, 6, 8]], jnp.int32)
    k = 4
    vals, ids = topk.rerank(queries, items, cand, k)
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == k, row
    # parity with exact MIPS over the de-duplicated candidate set
    for qi in range(queries.shape[0]):
        uniq = jnp.asarray(sorted(set(np.asarray(cand[qi]).tolist())),
                           jnp.int32)
        sc = items[uniq] @ queries[qi]
        tv, ti = jax.lax.top_k(sc, k)
        np.testing.assert_array_equal(np.asarray(ids[qi]),
                                      np.asarray(uniq[ti]))
        np.testing.assert_allclose(np.asarray(vals[qi]), np.asarray(tv),
                                   atol=1e-5)


def test_rerank_unique_rows_unchanged():
    """The duplicate mask must leave repeat-free rows bit-identical to
    plain score + top_k (every engine path)."""
    items = _longtail_items(64, 8, jax.random.PRNGKey(30))
    queries = jax.random.normal(jax.random.PRNGKey(31), (3, 8))
    cand = jnp.tile(jnp.arange(20, dtype=jnp.int32)[None, :], (3, 1))
    vals, ids = topk.rerank(queries, items, cand, 6)
    scores = jnp.einsum("qd,qpd->qp", queries, items[cand])
    tv, tp = jax.lax.top_k(scores, 6)
    np.testing.assert_array_equal(
        np.asarray(ids), np.asarray(jnp.take_along_axis(cand, tp, axis=1)))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(tv))


# -- PR 10 bugfix: bounded engine memo ----------------------------------------


def test_engine_memo_lru_bounded_and_observable():
    items = _longtail_items(64, 8, jax.random.PRNGKey(32))
    idx = _build(items, "simple")
    engine_mod._engine_memo.clear()
    trackers = [Tracker() for _ in range(engine_mod._ENGINE_MEMO_CAP + 4)]
    engines = [engine_for(idx, engine="bucket", tracker=t)
               for t in trackers]
    assert len(engine_mod._engine_memo) <= engine_mod._ENGINE_MEMO_CAP
    # the gauge reports occupancy on every resolution
    snap = trackers[-1].snapshot()
    assert snap["gauges"]["repro.engine.memo_size"] \
        <= engine_mod._ENGINE_MEMO_CAP
    # most-recent entries still hit (LRU, not clear-on-insert)
    again = engine_for(idx, engine="bucket", tracker=trackers[-1])
    assert again is engines[-1]
    # evicted entries rebuild without error
    rebuilt = engine_for(idx, engine="bucket", tracker=trackers[0])
    assert rebuilt is not engines[0]
    engine_mod._engine_memo.clear()
