"""jaxpr/trace contract analyzer over the public query entry points
(DESIGN.md §15, engine 2 of repro-lint).

Three contracts, checked by actually driving the entry points —
``QueryEngine.query``, ``DistributedEngine.query``, the planned and
adaptive paths, and the streaming ``delta_scan`` merge — over a tiny
deterministic index (N=256), with the jit-facing pieces additionally
traced under *abstract* inputs (``jax.eval_shape`` /
``jax.make_jaxpr``-style tracing, no device execution):

  C1  **trace-count budget** — the distributed collective must trace
      exactly once per distinct ``(num_probe, k, budgets)`` class and hit
      its executable cache on repeat traffic (the PR 4/5 cache contract);
      an unhashable jit-static argument reaching the cache key is the
      canonical hazard and is reported, not crashed on.
  C2  **dtype discipline** — every entry point returns f32 values and
      i32 ids (adaptive additionally: integer probes_used). Checked on
      concrete outputs for eager/hybrid surfaces and on
      ``jax.eval_shape`` results for the jitted collective and the
      ``delta_scan`` kernel, so the contract holds for the *traced
      program*, not one lucky execution.
  C3  **span purity** — no observability span may open during tracing
      (DESIGN.md §13: "spans never enter jit"). Enforced by guarding
      ``Tracer._push`` while the checks run: a push under an active jax
      trace, or during a forced abstract-tracing section, is a finding.

Findings carry the entry point's real ``file:line`` (via ``inspect``) so
they render next to the AST rules' output and participate in the same
baseline. :func:`run_contracts` returns a :class:`ContractReport` whose
``stats`` expose the measured trace counts — the regression tests pin
them (tests/test_analysis_contracts.py).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding

VALUE_DTYPE = "float32"
ID_DTYPE = "int32"
# declared budget: distinct jitted collectives per (num_probe, k, budgets)
# class (DESIGN.md §11/§12 — planned traffic must stay on the cache).
TRACES_PER_CLASS = 1

HINTS = {
    "C1": "key every jitted collective on hashable statics "
          "((num_probe, k, budgets) tuples) and reuse the cached "
          "executable for repeat classes (core/distributed.py _mapped)",
    "C2": "query surfaces return f32 values and i32 ids; cast at the "
          "boundary, never inside the traced body",
    "C3": "hoist spans/trackers out of traced code — record host-side "
          "after the device sync point (DESIGN.md §13)",
}


def _loc(obj) -> Tuple[str, int]:
    """(repo-relative path, first line) of a callable, for findings."""
    try:
        src = Path(inspect.getsourcefile(obj)).resolve()
        line = inspect.getsourcelines(obj)[1]
        for parent in src.parents:
            if parent.name == "src":
                return src.relative_to(parent.parent).as_posix(), line
        return src.as_posix(), line
    except (TypeError, OSError):
        return "<unknown>", 1


@dataclasses.dataclass
class ContractReport:
    """Findings plus the measured facts the regression tests pin."""

    findings: List[Finding] = dataclasses.field(default_factory=list)
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    def add(self, rule: str, where, message: str) -> None:
        path, line = _loc(where) if not isinstance(where, tuple) else where
        self.findings.append(
            Finding(rule, path, line, message, HINTS[rule]))


# -- span-purity guard (C3) ---------------------------------------------------


def _tracing_now() -> bool:
    """Best-effort "is a jax trace active on this thread" probe across
    jax versions; False when the probe is unavailable (the forced flag
    in :class:`SpanPurityGuard` still covers abstract sections)."""
    import jax
    fn = getattr(jax.core, "trace_state_clean", None)
    if fn is None:
        try:
            from jax._src import core as _core
            fn = getattr(_core, "trace_state_clean", None)
        except Exception:
            fn = None
    if fn is None:
        return False
    try:
        return not fn()
    except Exception:
        return False


class SpanPurityGuard:
    """Context manager patching ``Tracer._push`` to record spans opened
    under tracing. ``forced()`` marks a section (e.g. ``jax.eval_shape``)
    where *any* span push is a violation, independent of the version
    probe."""

    def __init__(self):
        self.violations: List[str] = []
        self._forced = False
        self._orig = None

    def forced(self):
        guard = self

        class _Forced:
            def __enter__(self):
                guard._forced = True

            def __exit__(self, *exc):
                guard._forced = False

        return _Forced()

    def __enter__(self) -> "SpanPurityGuard":
        from repro.obs import trace as trace_mod
        orig = trace_mod.Tracer._push
        guard = self

        def guarded_push(tracer_self, span):
            if guard._forced or _tracing_now():
                guard.violations.append(span.name)
            return orig(tracer_self, span)

        self._orig = (trace_mod, orig)
        trace_mod.Tracer._push = guarded_push
        return self

    def __exit__(self, *exc) -> None:
        mod, orig = self._orig
        mod.Tracer._push = orig


# -- tiny deterministic fixture ----------------------------------------------


def _tiny_setup(n: int = 256, d: int = 16, m: int = 4):
    """Small long-tailed dataset + calibrated spec — big enough to give
    every range members, small enough that the whole analyzer runs in
    seconds on CPU."""
    import jax
    import jax.numpy as jnp
    from repro.core.index import IndexSpec, build

    key = jax.random.PRNGKey(7)
    kv, kn, kq = jax.random.split(key, 3)
    vecs = jax.random.normal(kv, (n, d))
    scale = jnp.exp(0.5 * jax.random.normal(kn, (n, 1)))
    items = vecs * scale
    queries = jax.random.normal(kq, (4, d))
    spec = IndexSpec(family="simple", code_len=16, m=m, engine="bucket",
                     recall_target=0.9)
    cidx = build(spec, items, jax.random.PRNGKey(11))
    return cidx, items, queries


def _check_dtypes(report: ContractReport, where, what: str, vals, ids,
                  extra_int=None) -> None:
    if str(vals.dtype) != VALUE_DTYPE:
        report.add("C2", where,
                   f"{what}: values dtype {vals.dtype}, expected "
                   f"{VALUE_DTYPE}")
    if str(ids.dtype) != ID_DTYPE:
        report.add("C2", where,
                   f"{what}: ids dtype {ids.dtype}, expected {ID_DTYPE}")
    if extra_int is not None and not str(extra_int.dtype).startswith("int"):
        report.add("C2", where,
                   f"{what}: probes_used dtype {extra_int.dtype}, "
                   f"expected an integer type")


# -- entry-point checks -------------------------------------------------------


def check_single_device(report: ContractReport, cidx, queries) -> None:
    """QueryEngine.query (global + planned), ComposedIndex recall
    contract, adaptive early termination: concrete dtype checks (these
    surfaces interleave host work, so abstract eval is not defined for
    them — documented in DESIGN.md §15)."""
    from repro.core.engine import QueryEngine
    from repro.core.planner import adaptive_query

    eng = QueryEngine(cidx, engine="bucket")
    vals, ids = eng.query(queries, 5, 60)
    _check_dtypes(report, QueryEngine.query, "QueryEngine.query", vals,
                  ids)
    budgets = tuple(min(20, int(c)) for c in eng._range_counts)
    vals, ids = eng.query(queries, 5, budgets=budgets)
    _check_dtypes(report, QueryEngine.query, "QueryEngine.query[planned]",
                  vals, ids)
    vals, ids = cidx.query(queries, 5)      # spec recall_target default
    _check_dtypes(report, type(cidx).query, "ComposedIndex.query[contract]",
                  vals, ids)
    vals, ids, probes = adaptive_query(eng, queries, 5,
                                       recall_target=0.9)
    _check_dtypes(report, adaptive_query, "adaptive_query", vals, ids,
                  extra_int=probes)


def check_distributed(report: ContractReport, spec, items, queries, *,
                      classes: Sequence[Tuple[int, int]] = ((60, 5),
                                                           (90, 5)),
                      planned_budget: Optional[int] = 20) -> None:
    """DistributedEngine.query: C1 trace budget over repeat traffic, C2
    dtypes on concrete outputs AND on the jitted collective traced under
    abstract inputs (jax.eval_shape)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed
    from repro.obs import Tracker

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sidx = distributed.build_sharded(spec, items, jax.random.PRNGKey(11),
                                     1)
    placed = distributed.shard_index(sidx, mesh)
    tracker = Tracker()
    eng = distributed.DistributedEngine(placed, mesh, engine="bucket",
                                        tracker=tracker)
    qe = distributed.DistributedEngine.query

    ran = 0
    for num_probe, k in classes:
        try:
            vals, ids = eng.query(queries, k, num_probe)
            eng.query(queries, k, num_probe)    # repeat: must cache-hit
            ran += 1
        except TypeError as e:
            report.add("C1", qe,
                       f"unhashable jit-static argument reached the "
                       f"collective cache for class "
                       f"(num_probe={num_probe}, k={k}): {e}")
            continue
        _check_dtypes(report, qe, f"DistributedEngine.query[{num_probe}"
                      f",{k}]", vals, ids)
    planned_classes = 0
    if planned_budget is not None:
        budgets = tuple(min(planned_budget, int(c))
                        for c in eng._range_counts)
        try:
            vals, ids = eng.query(queries, 5, budgets=budgets)
            eng.query(queries, 5, budgets=budgets)
            planned_classes = 1
            _check_dtypes(report, qe, "DistributedEngine.query[planned]",
                          vals, ids)
        except TypeError as e:
            report.add("C1", qe,
                       f"unhashable jit-static argument reached the "
                       f"collective cache for planned budgets: {e}")

    c = tracker.counters
    misses = int(c.get("repro.engine.distributed.jit_cache.miss", 0))
    hits = int(c.get("repro.engine.distributed.jit_cache.hit", 0))
    gauge = int(tracker.gauges.get(
        "repro.engine.distributed.trace_count", 0))
    expected = (ran + planned_classes) * TRACES_PER_CLASS
    if misses != expected:
        report.add("C1", qe,
                   f"trace-count budget violated: {misses} collective "
                   f"traces for {ran + planned_classes} "
                   f"(num_probe, k, budgets) classes (budget "
                   f"{TRACES_PER_CLASS}/class)")
    if hits != ran + planned_classes:
        report.add("C1", qe,
                   f"repeat traffic missed the collective cache: "
                   f"{hits} hits for {ran + planned_classes} repeated "
                   f"classes")
    if gauge != expected:
        report.add("C1", qe,
                   f"trace_count gauge {gauge} disagrees with the "
                   f"{expected} expected live collectives")
    report.stats.update({
        "distributed_classes": ran,
        "distributed_planned_classes": planned_classes,
        "distributed_traces": misses,
        "distributed_cache_hits": hits,
        "distributed_trace_gauge": gauge,
    })


def check_distributed_abstract(report: ContractReport, spec, items,
                               queries, guard: SpanPurityGuard) -> None:
    """Trace the jitted collective under fully abstract inputs: dtype
    contract on the ShapeDtypeStruct outputs, span purity forced."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sidx = distributed.build_sharded(spec, items, jax.random.PRNGKey(11),
                                     1)
    placed = distributed.shard_index(sidx, mesh)
    eng = distributed.DistributedEngine(placed, mesh, engine="bucket")
    fn = eng._mapped(60, 5, None)
    idx = placed
    q_codes = eng.family.encode_queries(idx.params, queries,
                                        impl=eng.impl)
    concrete = (q_codes, queries, idx.params, idx.dir_code, idx.dir_rid,
                idx.dir_size, idx.dir_shard, idx.dir_local_start,
                idx.rank, idx.items, idx.codes, idx.range_id,
                idx.bucket_of, idx.bucket_off, idx.perm, idx.valid)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), concrete)
    with guard.forced():
        vals_s, ids_s, _ = jax.eval_shape(fn, *abstract)
    _check_dtypes(report, distributed._shard_query,
                  "DistributedEngine collective (abstract)", vals_s,
                  ids_s)


def check_delta_scan_abstract(report: ContractReport,
                              guard: SpanPurityGuard) -> None:
    """delta_scan under abstract inputs: i32 match counts, span-pure
    trace (the streaming merge consumes this inside jit)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    q = jax.ShapeDtypeStruct((4, 1), jnp.uint32)
    d = jax.ShapeDtypeStruct((32, 1), jnp.uint32)
    live = jax.ShapeDtypeStruct((32,), jnp.bool_)
    with guard.forced():
        out = jax.eval_shape(
            functools.partial(ops.delta_scan, hash_bits=16, impl="ref"),
            q, d, live)
    if str(out.dtype) != ID_DTYPE:
        report.add("C2", ops.delta_scan,
                   f"delta_scan (abstract): match counts dtype "
                   f"{out.dtype}, expected {ID_DTYPE}")


def check_streaming(report: ContractReport, cidx, queries) -> None:
    """Streaming merged path end-to-end (insert -> merged query): dtype
    contract on (vals, ids); the jitted merge runs under the C3 guard."""
    import jax
    from repro.streaming.index import MutableIndex

    mi = MutableIndex.from_composed(cidx, capacity=16)
    mi.insert(jax.random.normal(jax.random.PRNGKey(13),
                                (4, cidx.items.shape[1])))
    vals, ids = mi.query(queries, 5, 60)
    _check_dtypes(report, MutableIndex.query, "MutableIndex.query", vals,
                  ids)


# -- driver -------------------------------------------------------------------


def run_contracts(*, classes: Sequence[Tuple[int, int]] = ((60, 5),
                                                          (90, 5))
                  ) -> ContractReport:
    """Run every contract check; returns findings + measured stats.
    Deterministic (fixed PRNG keys), CPU-sized, no files touched."""
    report = ContractReport()
    cidx, items, queries = _tiny_setup()
    with SpanPurityGuard() as guard:
        check_single_device(report, cidx, queries)
        check_distributed(report, cidx.spec, items, queries,
                          classes=classes)
        check_distributed_abstract(report, cidx.spec, items, queries,
                                   guard)
        check_delta_scan_abstract(report, guard)
        check_streaming(report, cidx, queries)
    for name in guard.violations:
        from repro.obs.trace import Tracer
        report.add("C3", Tracer._push,
                   f"span `{name}` opened during jax tracing")
    report.stats["span_violations"] = list(guard.violations)
    return report
