"""The catalog and the query pool are a function of the seed alone."""

import numpy as np
import pytest

from bench.lib import data

LOGNORMAL = {"num_items": 4096, "dim": 150,
             "norms": {"lognormal": {"sigma": 0.8}}}
MIX = {"batch": 8, "pool_batches": 3}


def items(seed, cfg=LOGNORMAL):
    return np.asarray(data.make_items(cfg, seed))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 33 + 5])
def test_same_seed_same_inputs(seed):
    assert np.array_equal(items(seed), items(seed))
    a, b = data.make_pool(MIX, 150, seed), data.make_pool(MIX, 150, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_seeds_change_values_not_sizes():
    a, b = items(2 ** 33 + 5), items(2 ** 33 + 6)
    assert a.shape == b.shape == (4096, 150) and a.dtype == np.float32
    assert not np.allclose(a, b)
    pool = data.make_pool(MIX, 150, 3)
    assert len(pool) == 3 and pool[0].shape == (8, 150)
    # the query stream is not the item stream
    assert not np.allclose(np.asarray(pool[0]), a[:8])


def test_seed_range():
    with pytest.raises(ValueError):
        data.seed_key(-1)
    with pytest.raises(ValueError):
        data.seed_key(2 ** 64)


def test_lognormal_norms_and_uniform_directions():
    x = items(11).astype(np.float64)
    logn = np.log(np.linalg.norm(x, axis=1))
    assert abs(logn.mean()) < 0.05 and abs(logn.std() - 0.8) < 0.05
    # directions: the mean unit vector is near zero
    u = x / np.linalg.norm(x, axis=1, keepdims=True)
    assert np.linalg.norm(u.mean(axis=0)) < 0.05


def test_normal_mixture_norms():
    cfg = {"num_items": 8192, "dim": 16, "norms": {"normal_mixture": {
        "components": [[0.65, 0.6, 0.08], [0.35, 1.1, 0.08]], "min": 0.1}}}
    n = np.linalg.norm(items(5, cfg).astype(np.float64), axis=1)
    assert n.min() >= 0.1 - 1e-6
    assert abs((n > 0.85).mean() - 0.35) < 0.03


def test_unknown_distributions_are_refused():
    with pytest.raises(ValueError):
        data.make_items({"num_items": 8, "dim": 4,
                         "norms": {"pareto": {}}}, 0)
    with pytest.raises(ValueError):
        data.make_pool({"batch": 2, "pool_batches": 1,
                        "queries": "uniform"}, 4, 0)
