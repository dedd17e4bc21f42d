"""The dense arm's gather-free match, rank and budget stages
(core/engine.py) against the gather form they replace, and a structural
guard that no (Q, N) gather comes back into the dense arm."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import engine
from repro.core.bucket_index import build_buckets, rank_from_scores
from repro.core.engine import (_dense_sort, _segment_bounds,
                               dense_candidates, item_range_counts,
                               planned_dense_candidates, range_cum_before)
from repro.core.family import L2ALSHFamily
from repro.kernels import ref

HASH_BITS = 8


def _packed_match(q_codes, codes):
    return HASH_BITS - ref.hamming_ref(q_codes, codes)


def _l2_match(q_codes, codes):
    return L2ALSHFamily().match_counts(None, q_codes, codes, codes.shape[1])


def _case(kind, seed, n=300, q=5):
    """(buckets, q_codes, codes, range_id, match_fn) for a small random
    store. Packed codes use 8 bits, so buckets collide and ranks tie."""
    rng = np.random.default_rng(seed)
    if kind == "l2_alsh":
        num_ranges, k = 4, 6
        codes = rng.integers(-2, 3, size=(n, k)).astype(np.int32)
        q_codes = rng.integers(-2, 3, size=(q, k)).astype(np.int32)
        rid = rng.integers(0, num_ranges, size=n).astype(np.int32)
        upper = np.sort(rng.uniform(0.5, 3.0, num_ranges)).astype(np.float32)
        rank = rank_from_scores(L2ALSHFamily().score_table(
            jnp.asarray(upper), k))
        b = build_buckets(jnp.asarray(codes), jnp.asarray(rid),
                          jnp.asarray(upper), k, rank=rank)
        return b, jnp.asarray(q_codes), jnp.asarray(codes), \
            jnp.asarray(rid), _l2_match
    num_ranges, empty = {"r1": (1, None), "r5_empty": (5, 2),
                         "r7": (7, None), "r32": (32, None)}[kind]
    codes = rng.integers(0, 2 ** HASH_BITS, size=(n, 1)).astype(np.uint32)
    q_codes = rng.integers(0, 2 ** HASH_BITS, size=(q, 1)).astype(np.uint32)
    choices = [j for j in range(num_ranges) if j != empty]
    rid = rng.choice(choices, size=n).astype(np.int32)
    upper = np.sort(rng.uniform(0.5, 3.0, num_ranges)).astype(np.float32)
    b = build_buckets(jnp.asarray(codes), jnp.asarray(rid),
                      jnp.asarray(upper), HASH_BITS)
    return b, jnp.asarray(q_codes), jnp.asarray(codes), jnp.asarray(rid), \
        _packed_match


def _gather_rank_csr(b, q_codes, codes, rid, match_fn):
    """(Q, N) rank of each CSR slot, by the per-item gathers."""
    item_rank = b.rank[rid[None, :], match_fn(q_codes, codes)]
    return item_rank[:, b.item_ids]


def _gather_planned(b, q_codes, codes, rid, budgets, match_fn):
    """The planned dense arm in its gather form: per-item rank lookup,
    argsort, range and budget of each slot gathered."""
    budgets, total = engine.check_budgets(
        budgets, item_range_counts(rid, b.num_ranges))
    rank_csr = _gather_rank_csr(b, q_codes, codes, rid, match_fn)
    order = jnp.argsort(rank_csr, axis=-1, stable=True)
    rid_o = rid[b.item_ids][order]
    wpos = range_cum_before(rid_o, jnp.ones_like(rid_o), len(budgets))
    keep = wpos < jnp.asarray(budgets, jnp.int32)[rid_o]
    sel = jnp.argsort(~keep, axis=-1, stable=True)[:, :total]
    return b.item_ids[jnp.take_along_axis(order, sel, axis=-1)]


def _budgets(rid, num_ranges, seed):
    counts = item_range_counts(rid, num_ranges)
    rng = np.random.default_rng(seed)
    return tuple(int(rng.integers(1, c + 3)) if c else 1 for c in counts)


KINDS = ["r1", "r5_empty", "r7", "r32", "l2_alsh"]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", KINDS)
def test_dense_sort_matches_the_gather_form(kind, seed):
    b, q_codes, codes, rid, match_fn = _case(kind, seed)
    r = b.num_ranges
    rank_csr = _gather_rank_csr(b, q_codes, codes, rid, match_fn)
    bounds = _segment_bounds(item_range_counts(rid, r))
    matches_csr = match_fn(q_codes, codes[b.item_ids])
    sorted_key, order = _dense_sort(matches_csr, b.rank, bounds)

    np.testing.assert_array_equal(
        order, jnp.argsort(rank_csr, axis=-1, stable=True))
    np.testing.assert_array_equal(sorted_key % r, rid[b.item_ids][order])
    key = np.empty_like(np.asarray(sorted_key))
    np.put_along_axis(key, np.asarray(order), np.asarray(sorted_key), -1)
    np.testing.assert_array_equal(key // r, rank_csr)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", KINDS)
def test_dense_candidates_match_the_gather_form(kind, seed):
    b, q_codes, codes, rid, match_fn = _case(kind, seed)
    budgets = _budgets(rid, b.num_ranges, seed)
    got = planned_dense_candidates(b, q_codes, codes, rid, budgets,
                                   match_fn=match_fn)
    np.testing.assert_array_equal(
        got, _gather_planned(b, q_codes, codes, rid, budgets, match_fn))

    num_probe = 77
    rank_csr = _gather_rank_csr(b, q_codes, codes, rid, match_fn)
    want = b.item_ids[jnp.argsort(rank_csr, axis=-1,
                                  stable=True)[:, :num_probe]]
    np.testing.assert_array_equal(
        dense_candidates(b, q_codes, codes, rid, num_probe,
                         match_fn=match_fn), want)


def test_l2_alsh_rank_table_interleaves_ranges():
    """The L2-ALSH case above is the one whose probe order is not range
    by range: some range's ranks are not contiguous."""
    b = _case("l2_alsh", 0)[0]
    order = np.argsort(np.asarray(b.rank).reshape(-1), kind="stable")
    rid_in_probe_order = order // b.rank.shape[1]
    changes = np.count_nonzero(np.diff(rid_in_probe_order))
    assert changes > b.num_ranges - 1


def _gathers(jaxpr):
    """Every gather equation in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _gathers(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _gathers(sub)


def _largest_gather_index(fn, q_codes):
    return max(int(np.prod(e.invars[1].aval.shape))
               for e in _gathers(jax.make_jaxpr(fn)(q_codes).jaxpr))


GUARD_Q, GUARD_N = 4, 512


@pytest.mark.parametrize("arm", ["planned", "num_probe"])
def test_dense_arm_has_no_slot_gathers(arm):
    """No gather in the dense arm indexes every one of the Q x N slots
    (the gather form does, which the last assertion checks)."""
    b, q_codes, codes, rid, match_fn = _case("r7", 3, n=GUARD_N, q=GUARD_Q)
    counts = item_range_counts(rid, b.num_ranges)
    budgets = tuple(int(c) // 3 for c in counts)
    if arm == "planned":
        def fn(q):
            return planned_dense_candidates(
                b, q, codes, rid, budgets, match_fn=match_fn,
                range_counts=counts)
    else:
        def fn(q):
            return dense_candidates(b, q, codes, rid, GUARD_N // 4,
                                    match_fn=match_fn, range_counts=counts)
    assert _largest_gather_index(fn, q_codes) < GUARD_Q * GUARD_N
    assert _largest_gather_index(
        lambda q: _gather_planned(b, q, codes, rid, budgets, match_fn),
        q_codes) >= GUARD_Q * GUARD_N
