"""The closed-loop client: one caller that issues the next batch once the
previous batch's results are on the host.

Each batch is timed from issue until its ``(vals, ids)`` are host arrays.
The window opens at the first issue and closes when the last batch issued
within ``seconds`` returns, so a window always holds whole batches. The
host phases are marked with profiler annotations (``bench.window``,
``bench.issue``, ``bench.fetch``) so a device trace can attribute idle
gaps to them; outside a trace they cost a few hundred nanoseconds.
"""

from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Callable, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Answer:
    pool_index: int
    vals: np.ndarray      # (batch, k)
    ids: np.ndarray       # (batch, k)


@dataclasses.dataclass
class Window:
    seconds: float                   # first issue to last return
    latencies: List[float]           # per batch, seconds
    answers: List[Answer]
    attempted: int                   # queries issued
    failed: int                      # queries whose batch raised
    error: Optional[str] = None

    @property
    def batches(self) -> int:
        return len(self.latencies)

    @property
    def answered(self) -> int:
        return sum(a.ids.shape[0] for a in self.answers)


def closed_loop(serve: Callable, pool: Sequence, seconds: float, *,
                min_batches: int = 1, start: int = 0,
                clock: Callable[[], float] = time.perf_counter) -> Window:
    """Drive ``serve(queries) -> (vals, ids)`` over ``pool`` (cycled from
    ``start``) for ``seconds``, and at least ``min_batches`` batches. A
    batch that raises ends the window: its queries count as failed."""
    from jax.profiler import TraceAnnotation
    lat: List[float] = []
    answers: List[Answer] = []
    attempted = failed = 0
    error = None
    i = start
    with TraceAnnotation("bench.window"):
        t0 = t_end = clock()
        while t_end - t0 < seconds or len(lat) < min_batches:
            j = i % len(pool)
            q = pool[j]
            attempted += q.shape[0]
            ts = clock()
            try:
                with TraceAnnotation("bench.issue"):
                    vals, ids = serve(q)
                with TraceAnnotation("bench.fetch"):
                    vals, ids = np.asarray(vals), np.asarray(ids)
            except Exception:  # the batch failed: record it, end the window
                failed += q.shape[0]
                error = traceback.format_exc()
                t_end = clock()
                break
            t_end = clock()
            lat.append(t_end - ts)
            answers.append(Answer(j, vals, ids))
            i += 1
    return Window(t_end - t0, lat, answers, attempted, failed, error)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it (the slowest of three batches
    is their 95th percentile)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(v))))
    return float(v[rank - 1])
