"""The system under test: the program's index, built from a
configuration's ``index`` block and queried through its normal path.

    index  = build(IndexSpec(family, code_len, m, recall_target), items, key)
    engine = QueryEngine(index)                 # engine="auto"
    engine.query(queries, k, recall_target=mix's recall_target)

``build`` calibrates the recall planner (the spec carries a recall
target); ``QueryEngine`` builds the bucket store and resolves ``auto`` to
the bucket or the dense engine. Only this file calls into the program's
index and engines.
"""

from __future__ import annotations

import time
from typing import Dict

import jax


class IndexEngineSystem:

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

    def with_tracker(self, tracker) -> "IndexEngineSystem":
        """The same index and bucket store behind an engine that reports
        its stage spans and counters to ``tracker``."""
        from repro.core.engine import QueryEngine
        return IndexEngineSystem(
            self.index, QueryEngine(self.index, engine=self.engine.engine,
                                    buckets=self.engine.buckets,
                                    tracker=tracker), self.mix)

    def _plan(self):
        from repro.core.planner import resolve_budgets
        return resolve_budgets(self.index.calib, self.recall_target,
                               k=self.k)

    def layer_calls(self) -> Dict:
        """Calls into single layers that the benchmark times on its own."""
        return {"planner.resolve_budgets": self._plan}

    def shapes(self) -> Dict:
        """Sizes the per-layer readers compute bytes from."""
        b = self.engine.buckets
        return {"engine": self.engine.engine, "batch": int(self.mix["batch"]),
                "num_items": int(b.num_items),
                "num_buckets": int(b.num_buckets),
                "code_words": int(self.index.codes.shape[1]),
                "probe_width": int(self._plan().num_probe),
                "runs": int(b.num_buckets)}

    def close(self) -> None:
        self.index = self.engine = None


def make(config: Dict, mix: Dict, items: jax.Array, key: jax.Array,
         phases: Dict) -> IndexEngineSystem:
    """Build the index (with calibration) and its engine; records the
    seconds of each in ``phases``."""
    from repro.core.engine import QueryEngine
    from repro.core.index import IndexSpec, build
    ix = config["index"]
    spec = IndexSpec(family=ix["family"], code_len=int(ix["code_len"]),
                     m=int(ix["m"]), recall_target=float(ix["recall_target"]))
    t = time.perf_counter()
    index = build(spec, items, key)
    jax.block_until_ready(index.codes)
    phases["build"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = QueryEngine(index, engine=ix["engine"])
    jax.block_until_ready(engine.buckets.item_ids)
    phases["engine"] = time.perf_counter() - t
    return IndexEngineSystem(index, engine, mix)
