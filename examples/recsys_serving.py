"""The paper's own application end-to-end: recommendation serving.

    PYTHONPATH=src python examples/recsys_serving.py

1. ALS matrix factorization over a synthetic rating matrix (the paper used
   ALS on Netflix / Yahoo!Music — Yun et al. 2013).
2. Item embeddings -> sharded RANGE-LSH index (norm-range == shard
   boundary); user embeddings are the queries.
3. Batched top-10 retrieval through the distributed engine
   (core/distributed.py), validated against exact MIPS.
4. Live catalog updates through the streaming service (repro/streaming/):
   new items inserted (including a hot item whose norm breaches the range
   bound — drift-triggered localized repartition), stale items deleted,
   the delta compacted, and the whole mutable state checkpointed and
   re-mounted — recall tracked against exact MIPS on the mutated catalog
   at every stage.
"""

import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import streaming
from repro.checkpoint.manager import CheckpointManager
from repro.core import distributed, topk
from repro.core.index import IndexSpec
from repro.data.als import als_factorize, synthetic_ratings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh


def main() -> None:
    enable_compile_cache()
    # 1. train embeddings
    ratings, weights = synthetic_ratings(jax.random.PRNGKey(0),
                                         n_users=400, n_items=4000,
                                         density=0.08)
    t0 = time.time()
    state = als_factorize(ratings, weights, rank=32,
                          key=jax.random.PRNGKey(1), iters=8)
    print(f"ALS: observed-MSE {float(state.loss):.4f} "
          f"({time.time() - t0:.1f}s)")
    norms = jnp.linalg.norm(state.items, axis=1)
    print(f"item norms: max/median = "
          f"{float(jnp.max(norms) / jnp.median(norms)):.2f}")

    # 2. index (spec-built, sharded across whatever devices exist locally)
    mesh = make_local_mesh()
    spec = IndexSpec(family="simple", code_len=32, m=32, engine="bucket")
    index = distributed.build_sharded(spec, state.items,
                                      jax.random.PRNGKey(2),
                                      mesh.shape["data"])
    index = distributed.shard_index(index, mesh)

    # 3. serve a batch of user queries through the distributed engine
    # (global budget: 400 per shard, matching the legacy per-shard scan)
    engine = distributed.DistributedEngine(index, mesh)
    users = state.users[:64]
    probe = min(index.num_items, 400 * mesh.shape["data"])
    t0 = time.time()
    vals, ids = engine.query(users, k=10, num_probe=probe)
    jax.block_until_ready(vals)
    dt = (time.time() - t0) * 1e3
    _, truth = topk.exact_mips(users, state.items, 10)
    rec = float(topk.recall_at(ids, truth))
    print(f"served {users.shape[0]} users in {dt:.1f} ms "
          f"(recall@10 = {rec:.3f}, probing 10% of catalog)")

    # 4. live catalog updates: the streaming index service
    def live_recall(mi, tag):
        vecs, gids = mi.live_vectors()
        _, truth = topk.exact_mips(users, vecs, 10)
        _, got = mi.query(users, 10, 400)
        # map exact ids (live-subset positions) to global ids
        rec = float(topk.recall_at(got, jnp.asarray(gids)[truth]))
        print(f"  {tag}: live={mi.live_count} recall@10={rec:.3f} "
              f"[{', '.join(e['kind'] for e in mi.events[-2:])}]")
        return rec

    print("streaming service: live catalog updates")
    mindex = streaming.build(state.items, jax.random.PRNGKey(3),
                             code_len=32, m=16, capacity=256,
                             max_tombstones=128)
    live_recall(mindex, "mounted  ")

    # nightly ALS refresh lands 200 new items; one is tomorrow's hot item
    # with a norm beyond every bound seen at build time (drift!)
    rng = np.random.default_rng(7)
    fresh = rng.normal(size=(200, state.items.shape[1])).astype(np.float32)
    fresh *= np.linalg.norm(np.asarray(state.items), axis=1).mean()
    hot = fresh[:1] / np.linalg.norm(fresh[:1])
    hot *= float(np.linalg.norm(np.asarray(state.items), axis=1).max()) * 1.8
    t0 = time.time()
    mindex.insert(fresh)
    mindex.insert(hot)
    stale = np.arange(0, 300, 3)              # de-list every 3rd old item
    mindex.delete(stale.tolist())
    print(f"  200 inserts + 1 hot item + {stale.size} deletes in "
          f"{(time.time() - t0) * 1e3:.0f} ms "
          f"(repartitions={mindex.num_repartitions})")
    live_recall(mindex, "mutated  ")
    mindex.compact()
    live_recall(mindex, "compacted")

    # serving processes mount the index instead of rebuilding per boot
    with tempfile.TemporaryDirectory() as ckpt_dir:
        streaming.save_index(CheckpointManager(ckpt_dir), 0, mindex)
        mounted = streaming.load_index(ckpt_dir)
        live_recall(mounted, "restored ")


if __name__ == "__main__":
    main()
