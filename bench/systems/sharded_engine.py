"""The system under test for a sharded catalog: the program's
shard-aligned index over the first ``shards`` chips, queried through its
distributed engine.

    mesh   = Mesh(jax.devices()[:shards], ("data",))
    index  = build_sharded(IndexSpec(family, code_len, m, recall_target),
                           items, key, shards, mesh=mesh)
    engine = DistributedEngine(index, mesh)     # engine="auto"
    engine.query(queries, k, recall_target=mix's recall_target)

``build_sharded`` calibrates the recall planner data-parallel over the
mesh and places each shard's rows on its chip; the bucket directory and
the calibration ride replicated. Its phases (index with calibration,
CSR, layout, placement) are timed by its spans and recorded as
``build.<phase>`` beside the harness's own set-up phases.
Only this file calls into the program's sharded index and engine. A
program whose ``build_sharded`` cannot place its shards over a mesh is
refused before anything is built.
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

import jax
import numpy as np

AXIS = "data"
BUILD = "repro.build."


class ShardedEngineSystem:

    def __init__(self, index, engine, mix: Dict):
        self.index = index
        self.engine = engine
        self.mix = mix
        self.k = int(mix["k"])
        self.recall_target = float(mix["recall_target"])

    @property
    def engine_name(self) -> str:
        return self.engine.engine

    def query(self, queries: jax.Array):
        return self.engine.query(queries, self.k,
                                 recall_target=self.recall_target)

    def with_tracker(self, tracker) -> "ShardedEngineSystem":
        """The same placed index behind an engine that reports its spans
        and counters to ``tracker``, sharing the warm jitted collective."""
        return ShardedEngineSystem(self.index,
                                   self.engine.with_tracker(tracker),
                                   self.mix)

    def _plan(self):
        from repro.core.planner import resolve_budgets
        return resolve_budgets(self.index.calib, self.recall_target,
                               k=self.k)

    def layer_calls(self) -> Dict:
        """Calls into single layers that the benchmark times on its own."""
        return {"planner.resolve_budgets": self._plan}

    def shapes(self) -> Dict:
        """Sizes the per-layer readers compute bytes from. Each call of
        the bucket gather in a shard's re-rank loop walks ``runs`` runs
        (every bucket of the global directory and one covering run) for
        ``gather_slots`` slots."""
        from repro.core.distributed import RERANK_BLOCK
        ix = self.index
        width = int(self._plan().num_probe)
        return {"engine": self.engine.engine,
                "batch": int(self.mix["batch"]),
                "shards": int(ix.num_shards),
                "rows_per_shard": int(ix.rows_per_shard),
                "num_items": int(ix.num_items),
                "num_buckets": int(ix.num_buckets),
                "code_words": int(ix.codes.shape[1]),
                "probe_width": width,
                "runs": int(ix.num_buckets) + 1,
                "gather_slots": min(RERANK_BLOCK, width,
                                    int(ix.rows_per_shard))}

    def close(self) -> None:
        self.index = self.engine = None


def make(config: Dict, mix: Dict, items: jax.Array, key: jax.Array,
         phases: Dict) -> ShardedEngineSystem:
    """Build, calibrate and place the sharded index and its engine over
    the first ``index.shards`` chips; records the seconds of each in
    ``phases``."""
    from jax.sharding import Mesh
    from repro.core.distributed import DistributedEngine, build_sharded
    from repro.core.index import IndexSpec
    from repro.obs.tracker import Tracker
    if "mesh" not in inspect.signature(build_sharded).parameters:
        raise RuntimeError("this program's build_sharded cannot place a "
                           "sharded catalog over a mesh: nothing was built")
    ix = config["index"]
    shards = int(ix["shards"])
    devices = jax.devices()[:shards]
    if len(devices) < shards:
        raise RuntimeError(f"{shards} shards need {shards} devices; "
                           f"found {len(devices)}")
    mesh = Mesh(np.array(devices), (AXIS,))
    spec = IndexSpec(family=ix["family"], code_len=int(ix["code_len"]),
                     m=int(ix["m"]), recall_target=float(ix["recall_target"]))
    tracker = Tracker()
    t = time.perf_counter()
    index = build_sharded(spec, items, key, shards, mesh=mesh, axis=AXIS,
                          tracker=tracker)
    jax.block_until_ready(index.items)
    phases["build"] = time.perf_counter() - t
    for name, h in tracker.hists.items():
        if name.startswith(BUILD):
            phases[f"build.{name[len(BUILD):]}"] = h.total
    t = time.perf_counter()
    engine = DistributedEngine(index, mesh, axis=AXIS, engine=ix["engine"])
    phases["engine"] = time.perf_counter() - t
    return ShardedEngineSystem(index, engine, mix)
