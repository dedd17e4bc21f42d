"""Catalog and query pool made on the device from ``--seed``.

The catalog follows a configuration's ``data`` block: unit directions,
uniform on the sphere, scaled by 2-norms drawn from the stated
distribution, all in float32 (the type the index serves). Each stream
(items, queries, the program's own key) is its own fold of the seed, so
the same seed always gives the same catalog, the same queries and the
same index, and a different seed changes all three but no size.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ITEMS, QUERIES, PROGRAM = 1, 2, 3
MAX_SEED = 2 ** 64


def seed_key(seed: int) -> jax.Array:
    """PRNG key of a seed of up to 64 bits (two 32-bit folds)."""
    seed = int(seed)
    if not 0 <= seed < MAX_SEED:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def stream_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), stream)


def _norm_spec(norms: dict) -> Tuple:
    """Hashable form of a ``norms`` block (a static jit argument).

    ``{"lognormal": {"sigma": s}}`` or
    ``{"normal_mixture": {"components": [[weight, mean, std], ...],
    "min": lo}}``."""
    if set(norms) == {"lognormal"}:
        return ("lognormal", float(norms["lognormal"]["sigma"]))
    if set(norms) == {"normal_mixture"}:
        mix = norms["normal_mixture"]
        comps = tuple((float(w), float(m), float(s))
                      for w, m, s in mix["components"])
        return ("normal_mixture", comps, float(mix["min"]))
    raise ValueError(f"unknown norm distribution {sorted(norms)}")


def _draw_norms(key: jax.Array, n: int, spec: Tuple) -> jax.Array:
    if spec[0] == "lognormal":
        return jnp.exp(spec[1] * jax.random.normal(key, (n,), jnp.float32))
    _, comps, lo = spec
    kc, kz = jax.random.split(key)
    w = jnp.asarray([c[0] for c in comps], jnp.float32)
    pick = jax.random.categorical(kc, jnp.log(w), shape=(n,))
    mean = jnp.asarray([c[1] for c in comps], jnp.float32)[pick]
    std = jnp.asarray([c[2] for c in comps], jnp.float32)[pick]
    z = jax.random.normal(kz, (n,), jnp.float32)
    return jnp.maximum(mean + std * z, lo)


@functools.partial(jax.jit, static_argnames=("n", "d", "norms"))
def _items(key: jax.Array, n: int, d: int, norms: Tuple) -> jax.Array:
    kd, kn = jax.random.split(key)
    x = jax.random.normal(kd, (n, d), jnp.float32)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x * _draw_norms(kn, n, norms)[:, None]


def make_items(data: dict, seed: int) -> jax.Array:
    """(num_items, dim) float32 catalog of a configuration's ``data``."""
    if data.get("directions", "uniform_sphere") != "uniform_sphere":
        raise ValueError(f"unknown directions {data['directions']!r}")
    return _items(stream_key(seed, ITEMS), int(data["num_items"]),
                  int(data["dim"]), _norm_spec(data["norms"]))


@functools.partial(jax.jit, static_argnames=("shape",))
def _normal(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32)


def make_pool(mix: dict, dim: int, seed: int) -> List[jax.Array]:
    """``pool_batches`` device batches of ``batch`` standard-normal
    queries each, drawn in one call; the window cycles through them."""
    if mix.get("queries", "standard_normal") != "standard_normal":
        raise ValueError(f"unknown query model {mix['queries']!r}")
    nb, b = int(mix["pool_batches"]), int(mix["batch"])
    pool = _normal(stream_key(seed, QUERIES), (nb, b, int(dim)))
    return [pool[i] for i in range(nb)]
