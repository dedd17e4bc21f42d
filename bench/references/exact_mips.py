"""Plain reference of exact maximum inner product search, and its control.

The answer to a query is the ``k`` items of largest inner product with
it. :func:`truth` finds them by brute force on the device, every product
and sum in float32 (``Precision.HIGHEST``), in blocks of queries so that
the (block, N) score matrix fits beside the catalog. :func:`inner_products`
recomputes returned (query, item) pairs in float64 on the host. Nothing
here imports the program or takes anything it made; the catalog and the
queries come from the benchmark's own generator.

The control is the same brute force one precision lower, bfloat16 inputs
with float32 sums, put in the program's place (:func:`control_system`):
the scores it returns are not float32 inner products, so the comparison
has to judge it not correct.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256


@functools.partial(jax.jit, static_argnames=("k", "low"))
def _block_topk(queries: jax.Array, items: jax.Array, k: int, low: bool):
    if low:
        scores = jnp.matmul(queries.astype(jnp.bfloat16),
                            items.astype(jnp.bfloat16).T,
                            preferred_element_type=jnp.float32)
    else:
        scores = jnp.matmul(queries, items.T,
                            precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(scores, k)


def truth(queries: Sequence[jax.Array], items: jax.Array, k: int
          ) -> List[np.ndarray]:
    """Exact top-``k`` ids (host, (batch, k)) of each batch of queries."""
    out = []
    for q in queries:
        ids = [np.asarray(_block_topk(q[s:s + BLOCK], items, k, False)[1])
               for s in range(0, q.shape[0], BLOCK)]
        out.append(np.concatenate(ids, axis=0))
    return out


def inner_products(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """float64 ``queries[b] . rows[b, j]`` for (B, d) x (B, k, d)."""
    return np.einsum("bd,bkd->bk", queries.astype(np.float64),
                     rows.astype(np.float64))


class ControlSystem:
    """The reference in bfloat16, served through the system interface."""

    engine_name = None

    def __init__(self, config: Dict, mix: Dict, items: jax.Array):
        self.items = items
        self.k = int(mix["k"])

    def query(self, queries: jax.Array):
        return _block_topk(queries, self.items, self.k, True)

    def with_tracker(self, tracker):
        return self

    def layer_calls(self) -> Dict:
        return {}

    def shapes(self) -> Dict:
        return {}

    def close(self) -> None:
        self.items = None


def control_system(config: Dict, mix: Dict, items: jax.Array, key,
                   phases: Dict) -> ControlSystem:
    return ControlSystem(config, mix, items)
