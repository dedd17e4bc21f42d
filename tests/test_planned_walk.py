"""The planned bucket walk (core/engine.py) orders, takes and gathers only
the directory buckets of ranges with a nonzero budget. Its ids and values
are bit-identical to the planned dense arm (the contract's reference) and
to the walk over the whole directory, and a structural guard keeps every
(Q, B) array but the match out of the walk when one range is budgeted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import engine
from repro.core.engine import (QueryEngine, bucket_range_bounds,
                               bucket_range_counts, check_budgets,
                               planned_bucket_candidates,
                               planned_dense_candidates, planned_take)
from repro.core.index import IndexSpec, build
from repro.kernels import ops
from repro.obs import Tracker

R, K, Q = 8, 10, 16
TOP, EMPTY = R - 1, 3
MATCH_SCOPE = "guard_directory_match"


@pytest.fixture(scope="module")
def case():
    """A forced-small R = 8 index with uniform-width norm ranges over a
    catalog whose norms leave a gap, so ranges 3 and 4 hold no item."""
    rng = np.random.default_rng(0)
    n, d = 600, 12
    dirs = rng.standard_normal((n, d))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    norms = np.where(rng.random(n) < 0.6, rng.uniform(0.05, 0.3, n),
                     rng.uniform(0.75, 1.0, n))
    items = jnp.asarray(dirs * norms[:, None], jnp.float32)
    idx = build(IndexSpec(family="simple", code_len=8, m=R,
                          scheme="uniform", engine="bucket"),
                items, jax.random.PRNGKey(0))
    bucket = QueryEngine(idx, engine="bucket")
    dense = QueryEngine(idx, engine="dense", buckets=bucket.buckets)
    queries = jnp.asarray(rng.standard_normal((Q, d)), jnp.float32)
    counts = bucket_range_counts(bucket.buckets)
    assert counts[EMPTY] == 0 and counts[TOP] > 2 * K
    return bucket, dense, queries, counts


def _budgets(name, counts):
    b = [0] * R
    if name == "one_whole":
        b[TOP] = int(counts[TOP])
    elif name == "one_partial":
        b[TOP] = int(counts[TOP]) // 2
    elif name == "two_apart":
        # ranges 5 and 7 budgeted, range 6 (which holds items) at 0
        assert counts[5] > 0 and counts[6] > 0
        b[5], b[TOP] = max(1, int(counts[5]) // 2), int(counts[TOP]) // 3
    elif name == "every":
        b = [max(1, int(c) // 3) for c in counts]
    elif name == "empty_range":
        b[EMPTY], b[TOP] = 50, int(counts[TOP]) // 2
    return tuple(b)


def _full_walk(eng, q_codes, budgets):
    """The planned walk over every directory bucket: the whole (Q, B)
    order, every range's take, all B runs to the gather."""
    b = eng.buckets
    budgets, total = check_budgets(budgets, eng._range_counts)
    order = engine._directory_order(b, q_codes, eng._match_fn, None)
    sizes = (b.bucket_start[1:] - b.bucket_start[:-1])[order]
    take = planned_take(b.bucket_rid[order], sizes, budgets)
    cum = jnp.concatenate([jnp.zeros((order.shape[0], 1), jnp.int32),
                           jnp.cumsum(take, axis=-1, dtype=jnp.int32)], -1)
    csr_pos = ops.bucket_gather(cum, b.bucket_start[:-1][order], total)
    return b.item_ids[csr_pos]


CASES = ["one_whole", "one_partial", "two_apart", "every", "empty_range"]


@pytest.mark.parametrize("name", CASES)
def test_planned_walk_equals_the_dense_arm(case, name):
    bucket, dense, queries, counts = case
    budgets = _budgets(name, counts)
    v_b, i_b = bucket.query(queries, K, budgets=budgets)
    v_d, i_d = dense.query(queries, K, budgets=budgets)
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_d))
    np.testing.assert_array_equal(np.asarray(v_b), np.asarray(v_d))

    q_codes = engine.encode_queries(bucket.index, queries)
    cand = planned_bucket_candidates(
        bucket.buckets, q_codes, budgets, match_fn=bucket._match_fn)
    want = planned_dense_candidates(
        bucket.buckets, q_codes, bucket.index.codes, bucket._range_id,
        budgets, match_fn=bucket._match_fn)
    np.testing.assert_array_equal(np.asarray(cand), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(cand), np.asarray(_full_walk(bucket, q_codes, budgets)))
    assert cand.shape[1] == check_budgets(budgets, counts)[1]


@pytest.mark.parametrize("name", CASES)
def test_walked_buckets_counter(case, name):
    bucket, _, queries, counts = case
    budgets = _budgets(name, counts)
    bounds = bucket_range_bounds(bucket.buckets)
    t = Tracker()
    QueryEngine(bucket.index, engine="bucket", buckets=bucket.buckets,
                tracker=t).query(queries, K, budgets=budgets)
    h = t.hists["repro.engine.walked_buckets"]
    want = sum(bounds[j + 1] - bounds[j] for j, b in enumerate(budgets)
               if b > 0)
    assert h.count == 1 and h.max == want
    if name == "every":
        assert want == bucket.buckets.num_buckets
    else:
        assert want < bucket.buckets.num_buckets


def test_range_bounds_follow_the_directory(case):
    b = case[0].buckets
    bounds = bucket_range_bounds(b)
    rid = np.asarray(b.bucket_rid)
    assert bounds[0] == 0 and bounds[-1] == b.num_buckets
    for j in range(R):
        assert np.all(rid[bounds[j]:bounds[j + 1]] == j)
    assert bounds[EMPTY] == bounds[EMPTY + 1]


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


def _trace(fn, q_codes, monkeypatch):
    """(equations of ``fn``'s jaxpr, the (cum, starts) shapes each
    ``ops.bucket_gather`` call received)."""
    calls, orig = [], ops.bucket_gather

    def recording(cum, starts, num_probe, **kw):
        calls.append((cum.shape, starts.shape))
        return orig(cum, starts, num_probe, **kw)

    monkeypatch.setattr(ops, "bucket_gather", recording)
    return list(_eqns(jax.make_jaxpr(fn)(q_codes).jaxpr)), calls


def test_one_budgeted_range_walks_no_directory_wide_array(case, monkeypatch):
    """With one budgeted range, no sort and no bucket_gather operand is B
    wide, and no array with a B-wide axis is made outside the match (the
    full walk's sort and gather are, which the last assertions check)."""
    bucket, _, queries, counts = case
    b = bucket.buckets
    big = b.num_buckets
    budgets = _budgets("one_partial", counts)
    bounds = bucket_range_bounds(b)
    walked = bounds[TOP + 1] - bounds[TOP]
    assert 0 < walked < big

    def match(q_codes, codes):
        with jax.named_scope(MATCH_SCOPE):
            return bucket._match_fn(q_codes, codes)

    def planned(q):
        return planned_bucket_candidates(
            b, q, budgets, match_fn=match, range_counts=counts,
            bucket_bounds=bounds)

    q_codes = engine.encode_queries(bucket.index, queries)
    eqns, calls = _trace(planned, q_codes, monkeypatch)
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    assert sorts and all(big not in v.aval.shape
                         for e in sorts for v in e.invars)
    assert calls == [((Q, walked + 1), (Q, walked))]
    outside = [e for e in eqns
               if MATCH_SCOPE not in str(e.source_info.name_stack)]
    assert all(big not in v.aval.shape for e in outside for v in e.outvars)
    assert any((Q, big) == v.aval.shape for e in eqns for v in e.outvars)

    eqns, calls = _trace(lambda q: _full_walk(bucket, q, budgets), q_codes,
                         monkeypatch)
    assert any(big in v.aval.shape for e in eqns
               if e.primitive.name == "sort" for v in e.invars)
    assert calls == [((Q, big + 1), (Q, big))]
