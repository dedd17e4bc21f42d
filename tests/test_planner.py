"""Recall-contract planner unit tests (DESIGN.md §12): greedy budget
solve, calibration plumbing through build/spec, per-surface threading
(engine, streaming, lm_head), and the adaptive arm's bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import streaming
from repro.core import planner, topk
from repro.core.engine import QueryEngine, check_budgets
from repro.core.index import IndexSpec, build
from repro.data.synthetic import make_dataset

KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def calibrated(longtail_ds):
    spec = IndexSpec(family="simple", code_len=16, m=8,
                     recall_target=0.9)
    return build(spec, longtail_ds.items, KEY)


# -- greedy solve over a hand-built table -------------------------------------


def hand_table():
    """Two ranges: range 0 holds 80% of the truth and saturates fast;
    range 1 holds 20% and needs deep probing."""
    grid = np.array([0, 10, 100, 1000], np.int64)
    recall_range = np.array([[0.0, 0.9, 1.0, 1.0],
                             [0.0, 0.1, 0.5, 1.0]], np.float32)
    return planner.CalibrationTable(
        probe_grid=grid, recall_range=recall_range,
        recall_global=np.array([0.0, 0.3, 0.8, 1.0], np.float32),
        truth_mass=np.array([0.8, 0.2], np.float32),
        range_counts=np.array([1000, 1000], np.int64),
        k=10, num_queries=64)


def test_plan_greedy_prefers_high_mass_range():
    pl = planner.plan(hand_table(), 0.7)
    # 10 probes of range 0 give 0.72 recall; range 1 untouched
    assert pl.budgets == (10, 0)
    assert pl.num_probe == 10
    assert pl.predicted_recall >= 0.7


def test_plan_nests_and_reaches_one():
    prev = (0, 0)
    for target in (0.3, 0.7, 0.9, 1.0):
        pl = planner.plan(hand_table(), target)
        assert all(a <= b for a, b in zip(prev, pl.budgets))
        assert pl.predicted_recall >= target - 1e-6
        prev = pl.budgets
    assert planner.plan(hand_table(), 1.0).predicted_recall == 1.0


def test_plan_global_picks_smallest_grid_point():
    pl = planner.plan_global(hand_table(), 0.75)
    assert pl.num_probe == 100
    assert pl.budgets == ()
    assert planner.plan_global(hand_table(), 0.2).num_probe == 10


def _within_range_positions_loop(order_ids, range_id, truth_ids, m):
    """Reference for calibrate_from_order: each truth item's position
    within its range's probe order, by one cumulative count per range."""
    q, n = order_ids.shape
    rows = np.arange(q)[:, None]
    sorted_rid = range_id[order_ids]
    wpos_sorted = np.empty((q, n), np.int64)
    for j in range(m):
        mask = sorted_rid == j
        wpos_sorted[mask] = (np.cumsum(mask, axis=1) - 1)[mask]
    wpos = np.empty((q, n), np.int64)
    wpos[rows, order_ids] = wpos_sorted
    return np.take_along_axis(wpos, truth_ids, axis=1).reshape(-1)


@pytest.mark.parametrize("q,n,m", [(3, 7, 1), (5, 50, 3), (17, 1000, 8),
                                   (8, 4000, 32)])
def test_calibrate_from_order_matches_per_range_loop(q, n, m):
    rng = np.random.default_rng(q * n + m)
    order = np.stack([rng.permutation(n) for _ in range(q)])
    rid = rng.integers(0, m, n)
    truth = np.stack([rng.choice(n, min(10, n), replace=False)
                      for _ in range(q)])
    grid = np.arange(n + 1)
    table = planner.calibrate_from_order(order, rid, truth, num_ranges=m,
                                         grid=grid)
    wpos = _within_range_positions_loop(order, rid, truth, m)
    counts = np.bincount(rid, minlength=m)
    t_rid = rid[truth.reshape(-1)]
    want = np.zeros((m, grid.size), np.float32)
    for j in range(m):
        sel = t_rid == j
        eff = np.minimum(grid, counts[j])
        if sel.any():
            want[j] = (wpos[sel][None, :] < eff[:, None]).mean(axis=1)
        want[j, eff >= counts[j]] = 1.0
    np.testing.assert_array_equal(table.recall_range, want)


@pytest.mark.parametrize("block", [None, 1, 24, 64])
def test_blocked_calibration_equals_the_host_canonical_order(
        calibrated, longtail_ds, monkeypatch, block):
    """Device-counted truth positions, in any block of queries, fit the
    table the unblocked host canonical order fits, bit for bit."""
    if block is not None:  # None keeps the default block
        monkeypatch.setattr(planner, "CAL_BLOCK", block)
    queries = longtail_ds.queries[:64]
    _, truth = topk.exact_mips(queries, calibrated.items, 10)
    want = planner.calibrate_from_order(
        planner.canonical_order(calibrated, queries),
        np.asarray(calibrated.range_id), np.asarray(truth),
        num_ranges=int(calibrated.table.shape[0]))
    got = planner.calibrate(calibrated, queries, k=10)
    for f in want._fields:
        a, b = np.asarray(getattr(want, f)), np.asarray(getattr(got, f))
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def test_target_validation():
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="recall_target"):
            planner.plan(hand_table(), bad)
    with pytest.raises(ValueError, match="recall_target"):
        IndexSpec(recall_target=1.5).validate()
    with pytest.raises(ValueError, match="recall_target"):
        IndexSpec(recall_target=0.9, num_tables=2,
                  engine="dense").validate()
    with pytest.raises(ValueError, match="calibration"):
        build(IndexSpec(num_tables=2, engine="dense", code_len=8),
              jax.random.normal(KEY, (50, 8)), KEY,
              calibration_queries=jax.random.normal(KEY, (4, 8)))


def test_check_budgets_validation():
    counts = np.array([5, 5], np.int64)
    assert check_budgets((3, 9), counts) == ((3, 5), 8)
    with pytest.raises(ValueError, match="budgets"):
        check_budgets((1, 2, 3), counts)
    with pytest.raises(ValueError, match=">= 0"):
        check_budgets((-1, 2), counts)
    with pytest.raises(ValueError, match="zero"):
        check_budgets((0, 0), counts)


# -- calibration through build/spec -------------------------------------------


def test_build_attaches_calibration(calibrated):
    cal = calibrated.calib
    assert cal is not None
    assert cal.probe_grid[0] == 0
    assert cal.probe_grid[-1] >= calibrated.items.shape[0]
    assert cal.num_ranges == 8
    np.testing.assert_allclose(cal.truth_mass.sum(), 1.0, atol=1e-6)
    # curves are monotone in the budget
    assert (np.diff(cal.recall_range, axis=1) >= -1e-6).all()
    assert (np.diff(cal.recall_global) >= -1e-6).all()
    assert float(cal.recall_global[-1]) == 1.0


def test_spec_recall_target_is_query_default(calibrated, longtail_ds):
    """query() with no budget runs the spec's recall contract."""
    q = longtail_ds.queries[:8]
    vals, ids = calibrated.query(q, 5)
    pl = planner.plan(calibrated.calib, 0.9)
    want_v, want_i = calibrated.query(q, 5, budgets=pl.budgets)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v),
                               rtol=1e-6)


def test_recall_target_requires_calibration(longtail_ds):
    spec = IndexSpec(family="simple", code_len=16, m=8)
    cidx = build(spec, longtail_ds.items, KEY)
    with pytest.raises(ValueError, match="calibrat"):
        cidx.query(longtail_ds.queries[:2], 5, recall_target=0.9)
    # no selector and no spec recall_target: clear error, not a TypeError
    with pytest.raises(ValueError, match="num_probe"):
        cidx.query(longtail_ds.queries[:2], 5)
    with pytest.raises(ValueError, match="num_probe"):
        cidx.candidates(longtail_ds.queries[:2])
    eng = QueryEngine(cidx)
    with pytest.raises(ValueError, match="calibrat"):
        eng.query(longtail_ds.queries[:2], 5, recall_target=0.9)
    with pytest.raises(ValueError, match="exactly one"):
        eng.candidates(longtail_ds.queries[:2])
    with pytest.raises(ValueError, match="one of"):
        eng.query(longtail_ds.queries[:2], 5, 100, recall_target=0.9)


def test_contract_refuses_deeper_k_than_calibrated(calibrated,
                                                   longtail_ds):
    """The curves measure recall@calib.k; querying deeper under the
    contract would silently under-deliver, so it must refuse."""
    assert calibrated.calib.k == 10
    with pytest.raises(ValueError, match="calibrated at k=10"):
        calibrated.query(longtail_ds.queries[:2], 20, recall_target=0.9)
    calibrated.query(longtail_ds.queries[:2], 5, recall_target=0.9)


@pytest.mark.slow
def test_planned_beats_static_at_same_recall(calibrated, longtail_ds):
    """The acceptance direction at test scale: the planned budget meets
    its target with fewer probed candidates than the smallest static
    global budget that does."""
    q = longtail_ds.queries
    k = calibrated.calib.k
    _, truth = topk.exact_mips(q, calibrated.items, k)
    target = 0.9
    pl = planner.plan(calibrated.calib, target)
    eng = QueryEngine(calibrated, engine="bucket")
    got = float(topk.recall_at(eng.candidates(q, budgets=pl.budgets),
                               truth))
    assert got >= target - 0.05
    static = next(
        npb for npb in sorted({int(v) for v in calibrated.calib.probe_grid
                               if v > 0})
        if float(topk.recall_at(eng.candidates(q, npb), truth)) >= got)
    assert pl.num_probe <= static


# -- streaming threading ------------------------------------------------------


def test_streaming_recall_target_and_staleness(longtail_ds):
    mi = streaming.build(longtail_ds.items[:600], KEY, 16, 8,
                         capacity=128)
    with pytest.raises(ValueError, match="num_probe or recall_target"):
        mi.query(longtail_ds.queries[:2], 5)
    with pytest.raises(ValueError, match="calibrat"):
        mi.query(longtail_ds.queries[:2], 5, recall_target=0.9)
    cal = planner.calibrate_streaming(mi, longtail_ds.queries, k=5)
    mi.set_calibration(cal)
    vals, ids = mi.query(longtail_ds.queries[:4], 5, recall_target=0.9)
    want = mi.query(longtail_ds.queries[:4], 5,
                    planner.plan_global(cal, 0.9).num_probe)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want[1]))
    # an overflow insert moves a range boundary -> contract unenforceable
    hi = float(mi.upper.max()) * 2.0
    v = np.zeros((1, mi.items.shape[1]), np.float32)
    v[0, 0] = hi
    mi.insert(jnp.asarray(v))
    assert mi.calib_stale
    with pytest.raises(ValueError, match="stale"):
        mi.query(longtail_ds.queries[:2], 5, recall_target=0.9)
    mi.set_calibration(planner.calibrate_streaming(
        mi, longtail_ds.queries, k=5))
    assert not mi.calib_stale
    mi.query(longtail_ds.queries[:2], 5, recall_target=0.9)


# -- lm_head threading --------------------------------------------------------


def test_vocab_index_recall_target():
    from repro.models import lm_head
    d, V = 24, 512
    unembed = jax.random.normal(KEY, (d, V)) * \
        jnp.exp(0.7 * jax.random.normal(jax.random.PRNGKey(1), (1, V)))
    index = lm_head.build_vocab_index(unembed, jax.random.PRNGKey(2),
                                      code_len=32, num_ranges=8)
    hidden = jax.random.normal(jax.random.PRNGKey(3), (32, d))
    with pytest.raises(ValueError, match="calibrat"):
        lm_head.lsh_topk_tokens(index, hidden, unembed, k=5,
                                recall_target=0.9)
    cal = lm_head.calibrate_vocab_index(index, unembed, hidden, k=5)
    index = index._replace(calib=cal)
    vals, ids = lm_head.lsh_topk_tokens(index, hidden[:4], unembed, k=5,
                                        recall_target=0.9)
    want_np = planner.plan_global(cal, 0.9).num_probe
    wv, wi = lm_head.lsh_topk_tokens(index, hidden[:4], unembed, k=5,
                                     num_probe=want_np)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))


def test_planned_candidates_pallas_parity(longtail_ds):
    """Planned per-range budgets reach the Pallas kernels (interpret mode
    on CPU) through the same ops dispatch as static budgets."""
    spec = IndexSpec(family="simple", code_len=16, m=8)
    cidx = build(spec, longtail_ds.items[:500], KEY)
    budgets = (0, 0, 0, 5, 5, 10, 20, 40)
    outs = {}
    for impl in ("ref", "pallas"):
        eng = QueryEngine(cidx, engine="bucket", impl=impl)
        outs[impl] = np.asarray(
            eng.candidates(longtail_ds.queries[:4], budgets=budgets))
    np.testing.assert_array_equal(outs["ref"], outs["pallas"])


# -- adaptive arm -------------------------------------------------------------


def test_adaptive_argument_validation(calibrated, longtail_ds):
    eng = QueryEngine(calibrated, engine="bucket")
    q = longtail_ds.queries[:2]
    with pytest.raises(ValueError, match="exactly one"):
        planner.adaptive_query(eng, q, 5)
    with pytest.raises(ValueError, match="one of"):
        planner.adaptive_query(eng, q, 5, recall_target=0.9,
                               num_probe=50)
    with pytest.raises(ValueError, match="k="):
        planner.adaptive_query(eng, q, 500, num_probe=50)


def test_adaptive_early_termination_saves_probes(longtail_ds):
    """At a high target on the long-tail profile the plan spans small-cap
    ranges whose probes the bound provably skips."""
    spec = IndexSpec(family="simple", code_len=16, m=32,
                     charge_index_bits=False)
    cidx = build(spec, longtail_ds.items, KEY,
                 calibration_queries=jax.random.normal(
                     jax.random.PRNGKey(7), (128, 32)))
    pl = planner.plan(cidx.calib, 0.999)
    eng = QueryEngine(cidx, engine="bucket")
    q = longtail_ds.queries[:32]
    want_v, _ = eng.query(q, 10, budgets=pl.budgets)
    got_v, got_i, used = planner.adaptive_query(eng, q, 10,
                                               budgets=pl.budgets,
                                               chunk=16)
    np.testing.assert_allclose(np.sort(np.asarray(got_v), axis=1),
                               np.sort(np.asarray(want_v), axis=1),
                               rtol=1e-5, atol=1e-6)
    used = np.asarray(used)
    assert (used <= pl.num_probe).all()
    assert used.mean() < pl.num_probe, \
        "early termination never fired on the long-tail profile"
