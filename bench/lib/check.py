"""The comparison that decides ``correct``.

Every answer of the measured windows is compared with the configuration's
plain reference once the windows have closed and the program's state is
freed. Each number has a limit; the run is correct when every number
keeps to its limit:

  * ``recall_at_k``: mean share of each query's exact top-k (reference,
    float32 brute force) among the k ids returned; at least the recall the
    traffic mix asks for, the contract the index states. It covers the
    query encode, the directory or dense match and probe order, the
    planner's budgets and the candidate gather: a fault in any of them
    loses true neighbours.
  * ``score_rel_err``: the largest gap between a returned score and the
    float64 inner product of its query and item, over ``|q| |x|``. The
    re-rank promises exact float32 inner products.
  * ``malformed_rows``: rows with an id outside the catalog, an id twice,
    a score that is not finite, or scores out of descending order.
  * ``unanswered``: queries whose batch raised.
  * ``kernel_fallbacks``: kernel ops that resolved to another impl than
    the platform's kernels (Pallas on a TPU): such a run does not measure
    this system.
  * ``engine_mismatch``: 1 when ``engine="auto"`` resolved to another
    engine than the configuration says it should.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    rule: str            # ">=" or "<="

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        if self.rule == ">=":
            return self.value >= self.limit
        return self.value <= self.limit

    def as_json(self) -> Dict:
        return {"value": self.value, "limit": self.limit, "rule": self.rule}

    def line(self) -> str:
        return (f"check {self.name} = {self.value!r} (limit {self.rule} "
                f"{self.limit!r}) {'ok' if self.ok else 'FAILED'}")


class DispatchAudit:
    """Counts, through the program's kernel dispatch tracker, which impl
    every kernel op resolved to while the audit is on."""

    PREFIX = "repro.kernels.dispatch."

    def __init__(self):
        from repro.obs.tracker import Tracker
        self.tracker = Tracker()

    @contextlib.contextmanager
    def on(self):
        from repro.kernels import ops
        ops.set_dispatch_tracker(self.tracker)
        try:
            yield
        finally:
            ops.set_dispatch_tracker(None)

    def resolved(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for name, n in self.tracker.counters.items():
            if name.startswith(self.PREFIX):
                op, impl = name[len(self.PREFIX):].rsplit(".", 1)
                out.setdefault(op, {})[impl] = int(n)
        return out

    def fallbacks(self, expected: str) -> int:
        return sum(n for impls in self.resolved().values()
                   for impl, n in impls.items() if impl != expected)


def _rows(items: jax.Array, ids: np.ndarray) -> np.ndarray:
    """Catalog rows of the sorted unique ``ids``, gathered on the device in
    one call padded to a power of two (one compiled shape per size)."""
    n = max(1, 1 << int(np.ceil(np.log2(max(1, ids.size)))))
    idx = np.zeros((n,), np.int32)
    idx[:ids.size] = ids
    return np.asarray(jnp.take(items, jnp.asarray(idx), axis=0))[:ids.size]


def compare(answers: Sequence, pool_host: Sequence[np.ndarray],
            items: jax.Array, reference, *, k: int, recall_limit: float,
            limits: Dict, unanswered: int, kernel_fallbacks: int,
            engine_mismatch: int) -> List[Check]:
    """The checks of one run. ``answers`` are :class:`loop.Answer` of the
    measured windows; ``pool_host`` the query pool on the host."""
    n = int(items.shape[0])
    used = sorted({a.pool_index for a in answers})
    truth = dict(zip(used, reference.truth(
        [jnp.asarray(pool_host[j]) for j in used], items, k)))
    hits = total = malformed = 0
    good = []
    for a in answers:
        ids, vals = a.ids, a.vals
        bad = ((ids < 0) | (ids >= n)).any(axis=1)
        srt = np.sort(ids, axis=1)
        bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
        bad |= ~np.isfinite(vals).all(axis=1)
        bad |= (vals[:, 1:] > vals[:, :-1]).any(axis=1)
        malformed += int(bad.sum())
        hits += int((ids[:, :, None] == truth[a.pool_index][:, None, :]
                     ).any(axis=2).sum())
        total += ids.size
        good.append(~bad)
    err = 0.0
    uniq = np.unique(np.concatenate(
        [a.ids[ok].reshape(-1) for a, ok in zip(answers, good)]
        or [np.zeros((0,), np.int64)]))
    if uniq.size:
        table = _rows(items, uniq)
        for a, ok in zip(answers, good):
            if not ok.any():
                continue
            q = pool_host[a.pool_index][ok]
            rows = table[np.searchsorted(uniq, a.ids[ok])]   # (b, k, d)
            want = reference.inner_products(q, rows)
            scale = (np.linalg.norm(q.astype(np.float64), axis=1)[:, None]
                     * np.linalg.norm(rows.astype(np.float64), axis=2))
            gap = np.abs(a.vals[ok].astype(np.float64) - want) / scale
            err = max(err, float(gap.max()))
    recall = hits / total if total else 0.0
    return [
        Check("recall_at_k", recall, float(recall_limit), ">="),
        Check("score_rel_err", err, float(limits["score_rel_err"]), "<="),
        Check("malformed_rows", float(malformed), 0.0, "<="),
        Check("unanswered", float(unanswered), 0.0, "<="),
        Check("kernel_fallbacks", float(kernel_fallbacks), 0.0, "<="),
        Check("engine_mismatch", float(engine_mismatch), 0.0, "<="),
    ]


def all_ok(checks: Optional[Sequence[Check]]) -> bool:
    return bool(checks) and all(c.ok for c in checks)
