"""Quickstart: the composable index API (spec-driven builds).

    PYTHONPATH=src python examples/quickstart.py

One declarative ``IndexSpec`` names a base hash family and a partition
scheme; ``build(spec, items, key)`` composes them. The paper's RANGE-LSH
is ``NormRangePartitioned(SimpleLSH)`` — and because partitioning is a
universal catalyst (§5), swapping the family name gives ranged SIGN-ALSH
or L2-ALSH for free, on the same dataset and probe budget.
"""

import jax
import jax.numpy as jnp

from repro.core import topk
from repro.core.bucket_index import build_bucket_index
from repro.core.index import IndexSpec, build
from repro.data.synthetic import make_dataset
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ds = make_dataset("imagenet", jax.random.PRNGKey(0), n=20000,
                      num_queries=100)
    print(f"dataset: {ds.items.shape[0]} items, d={ds.items.shape[1]}")
    norms = jnp.linalg.norm(ds.items, axis=1)
    print(f"norm long tail: max/median = "
          f"{float(jnp.max(norms) / jnp.median(norms)):.1f}")

    # ground truth
    _, truth = topk.exact_mips(ds.queries, ds.items, 10)

    key = jax.random.PRNGKey(1)

    # RANGE-LSH: 32-bit budget, 64 norm ranges (6 bits index + 26 hash)
    idx = build(IndexSpec(family="simple", code_len=32, m=64), ds.items, key)
    print(f"RANGE-LSH: {idx.num_ranges} ranges, {idx.hash_bits} hash bits")
    _, ids = idx.query(ds.queries, k=10, num_probe=400)
    print(f"recall@10 probing 2% of items: "
          f"{float(topk.recall_at(ids, truth)):.3f}")

    # baseline at the same probe budget: drop the partitioning (m=1)
    flat = build(IndexSpec(family="simple", code_len=32), ds.items, key)
    _, ids_s = flat.query(ds.queries, k=10, num_probe=400)
    print(f"SIMPLE-LSH same budget:           "
          f"{float(topk.recall_at(ids_s, truth)):.3f}")

    # the §5 catalyst for free: partition a different base family
    salsh = build(IndexSpec(family="sign_alsh", code_len=32, m=64),
                  ds.items, key)
    _, ids_a = salsh.query(ds.queries, k=10, num_probe=400)
    print(f"ranged SIGN-ALSH same budget:     "
          f"{float(topk.recall_at(ids_a, truth)):.3f}")

    # bucket engine: same probe order through the CSR bucket store —
    # scans the B-bucket directory instead of all N items (DESIGN.md §5)
    buckets = build_bucket_index(idx)
    _, ids_b = idx.query(ds.queries, k=10, num_probe=400,
                         engine="bucket", buckets=buckets)
    print(f"bucket engine ({buckets.num_buckets} buckets for "
          f"{ds.items.shape[0]} items): recall "
          f"{float(topk.recall_at(ids_b, truth)):.3f}")


if __name__ == "__main__":
    main()
