"""Composable index API: norm-range partitioning as a universal catalyst
over pluggable hash families (DESIGN.md §10).

One declarative :class:`IndexSpec` names a base hash family, a code
budget, a partition scheme and a query engine; :func:`build` turns it into
a :class:`ComposedIndex` — the ``NormRangePartitioned(family)`` combinator
instantiated over the dataset:

    build(IndexSpec(family="simple", code_len=32, m=64), items, key)
        == the paper's RANGE-LSH (Algorithm 1)
    build(IndexSpec(family="simple", code_len=32), items, key)
        == SIMPLE-LSH (the m=1 degenerate case)
    build(IndexSpec(family="l2_alsh", code_len=32, m=16), items, key)
        == the §5 norm-ranged L2-ALSH extension
    build(IndexSpec(family="sign_alsh", code_len=32, m=16), items, key)
        == the beyond-paper ranged SIGN-ALSH
    build(IndexSpec(..., num_tables=8), items, key)
        == multi-table single-probe over any family (supplementary)

The combinator owns everything partition-related — ranking items by
2-norm, the percentile/uniform split, the per-range ``U_j`` bounds and the
eq.-12-style global probe order over the family's score table — while the
family owns hashing (core/family.py). Spec-built indexes are bit-identical
in candidate order to the legacy per-module constructors, which are kept
as thin shims over this entry point.

Validation (:meth:`IndexSpec.validate`) catches the silently-wrong
configurations the old kwargs surface allowed: a code budget that the
index bits exhaust, an ``m`` that is not a power of two while index bits
are charged (``ceil(log2 m)`` bits address ``2^b`` ranges — a non-power
silently wastes id space), unknown family/scheme/engine names, and
query-time ``num_probe``/``k`` out of range.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.family import FAMILY_NAMES, HashFamily, get_family
from repro.core.partition import (effective_upper, partition_by_scheme)
from repro.core.probe import DEFAULT_EPS
from repro.core.topk import EXACT, rerank

SCHEMES = ("percentile", "uniform")
ENGINES = ("auto", "dense", "bucket", "fused")
IMPLS = ("auto", "pallas", "ref")


def index_bits(m: int) -> int:
    """Bits of the code budget consumed by the sub-dataset id (§4)."""
    return max(0, math.ceil(math.log2(m))) if m > 1 else 0


@dataclasses.dataclass(frozen=True)
class IndexSpec:
    """Declarative index description (hashable, jit-static).

    Attributes:
      family:    base hash family ("simple" | "l2_alsh" | "sign_alsh").
      code_len:  total code budget L (§4: "same total code length").
      m:         number of norm ranges (1 = un-partitioned / flat).
      scheme:    "percentile" (Algorithm 1) | "uniform" (Fig 3a).
      engine:    default query engine ("dense" | "bucket" | "auto").
      impl:      kernel dispatch ("auto" | "pallas" | "ref").
      num_tables: T > 1 builds multi-table single-probe (supplementary).
      eps:       eq.-12 slack.
      recall_target: default recall contract (e.g. 0.95): ``build``
                 calibrates the index offline (core/planner.py) and
                 queries that pass no explicit budget are planned to meet
                 this target.
      charge_index_bits: override the family's §4 protocol (None = family
                 default; multi-table never charges — the budget is per
                 table).
      alsh_m/alsh_U/alsh_r: ALSH transform order / scaling / quantization
                 width overrides (None = the family's recommended values).
      tracker:   optional :class:`repro.obs.Tracker` the built index's
                 query surfaces report to (DESIGN.md §13). Excluded from
                 equality/hash — attaching observability never changes
                 what the spec *is* (jit-static identity included) or what
                 queries return (parity-tested).

    The "jit-static" tag in this docstring is load-bearing: repro-lint
    rule R4 (DESIGN.md §15) mechanically enforces frozen=True, value
    equality, and ``field(compare=False)`` on runtime-only fields for
    every dataclass carrying it.
    """

    family: str = "simple"
    code_len: int = 32
    m: int = 1
    scheme: str = "percentile"
    engine: str = "dense"
    impl: str = "auto"
    num_tables: int = 1
    eps: float = DEFAULT_EPS
    recall_target: Optional[float] = None
    charge_index_bits: Optional[bool] = None
    alsh_m: Optional[int] = None
    alsh_U: Optional[float] = None
    alsh_r: Optional[float] = None
    tracker: Optional[object] = dataclasses.field(
        default=None, compare=False, repr=False)

    # -- derived -------------------------------------------------------------

    def resolve_family(self) -> HashFamily:
        return get_family(self.family, alsh_m=self.alsh_m,
                          alsh_U=self.alsh_U, alsh_r=self.alsh_r)

    @property
    def charges(self) -> bool:
        if self.charge_index_bits is not None:
            return self.charge_index_bits
        if self.num_tables > 1:
            return False
        return self.resolve_family().charges_index_bits

    @property
    def index_bits(self) -> int:
        return index_bits(self.m) if self.charges else 0

    @property
    def hash_bits(self) -> int:
        """Number of hash functions after the §4 index-bit charge."""
        return self.code_len - self.index_bits

    @property
    def ranged(self) -> bool:
        return self.m > 1

    # -- validation ----------------------------------------------------------

    def validate(self, strict: bool = True) -> "IndexSpec":
        """Raise ``ValueError`` on inconsistent configuration; returns self.

        ``strict=False`` relaxes only the power-of-two rule on ``m`` (the
        legacy shims accept any m, as the old kwargs surface did)."""
        if self.family not in FAMILY_NAMES:
            raise ValueError(f"unknown hash family {self.family!r}; "
                             f"expected one of {FAMILY_NAMES}")
        if self.scheme not in SCHEMES:
            raise ValueError(f"unknown partition scheme {self.scheme!r}; "
                             f"expected one of {SCHEMES}")
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; "
                             f"expected one of {ENGINES}")
        if self.impl not in IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; "
                             f"expected one of {IMPLS}")
        if self.code_len < 1:
            raise ValueError(f"code_len must be >= 1, got {self.code_len}")
        if self.m < 1:
            raise ValueError(f"m (number of norm ranges) must be >= 1, "
                             f"got {self.m}")
        if self.num_tables < 1:
            raise ValueError(f"num_tables must be >= 1, "
                             f"got {self.num_tables}")
        if not 0.0 <= self.eps < 1.0:
            raise ValueError(f"eps must be in [0, 1), got {self.eps}")
        if self.num_tables > 1 and self.engine in ("bucket", "fused"):
            raise ValueError("multi-table single-probe has no bucket "
                             "store; use engine='dense'")
        if self.recall_target is not None:
            if not 0.0 < self.recall_target <= 1.0:
                raise ValueError(f"recall_target must be in (0, 1], got "
                                 f"{self.recall_target}")
            if self.num_tables > 1:
                raise ValueError("multi-table single-probe has no probe "
                                 "budget to plan; recall_target does not "
                                 "apply")
        if self.charges and self.hash_bits <= 0:
            raise ValueError(
                f"code_len={self.code_len} leaves {self.hash_bits} hash "
                f"bits after charging {self.index_bits} index bits for "
                f"m={self.m} ranges (§4 protocol) — raise code_len or "
                f"lower m")
        if strict and self.charges and self.m > 1 \
                and self.m & (self.m - 1) != 0:
            b = index_bits(self.m)
            raise ValueError(
                f"m={self.m} is not a power of two: the {b} charged index "
                f"bits address {2 ** b} ranges, silently wasting id space "
                f"— use m={2 ** (b - 1)} or m={2 ** b}, or set "
                f"charge_index_bits=False")
        if self.alsh_m is not None and self.alsh_m < 1:
            raise ValueError(f"alsh_m must be >= 1, got {self.alsh_m}")
        if self.alsh_U is not None and not 0.0 < self.alsh_U <= 1.0:
            raise ValueError(f"alsh_U must be in (0, 1], got {self.alsh_U}")
        if self.alsh_r is not None and self.alsh_r <= 0.0:
            raise ValueError(f"alsh_r must be > 0, got {self.alsh_r}")
        return self


def _check_probe(num_probe: int, k: Optional[int], n: int) -> int:
    num_probe = int(num_probe)
    if not 0 < num_probe <= n:
        raise ValueError(f"num_probe={num_probe} outside (0, N={n}]")
    if k is not None and not 0 < int(k) <= num_probe:
        raise ValueError(f"k={k} outside (0, num_probe={num_probe}]")
    return num_probe


class ComposedIndex(NamedTuple):
    """``NormRangePartitioned(family)`` instantiated over a dataset.

    Attributes:
      spec:      the IndexSpec that built it.
      items:     (N, d) original item vectors.
      norms:     (N,)   item 2-norms.
      codes:     (N, W) packed codes or (N, K) integer hashes.
      range_id:  (N,)   sub-dataset of each item.
      upper:     (R,)   raw per-range max 2-norm U_j (0 for empty ranges —
                 the paper-facing quantity).
      upper_eff: (R,)   U_j with empty ranges mapped to the global max
                 (what encoding and the score table use; no div-by-zero).
      lower:     (R,)   min 2-norm per range (§5 needs it).
      params:    family hash parameters (array pytree).
      table:     (R, n_hashes+1) score per (range, match count) — the
                 global probe order is the descending argsort of its
                 flattened entries (generalized eq. 12).
      hash_bits: number of hash functions actually drawn.
      calib:     optional :class:`repro.core.planner.CalibrationTable`
                 (attached by ``build`` when the spec carries a
                 ``recall_target``, or by ``planner.calibrate``).
    """

    spec: IndexSpec
    items: jax.Array
    norms: jax.Array
    codes: jax.Array
    range_id: jax.Array
    upper: jax.Array
    upper_eff: jax.Array
    lower: jax.Array
    params: object
    table: jax.Array
    hash_bits: int
    calib: Optional[object] = None

    # -- static views --------------------------------------------------------

    @property
    def family(self) -> HashFamily:
        return self.spec.resolve_family()

    @property
    def num_ranges(self) -> int:
        return self.upper.shape[0]

    @property
    def code_len(self) -> int:
        return self.spec.code_len

    @property
    def eps(self) -> float:
        return self.spec.eps

    # -- query surface -------------------------------------------------------

    def encode_queries(self, queries: jax.Array) -> jax.Array:
        return self.family.encode_queries(self.params, queries,
                                          impl=self.spec.impl)

    def probe_scores(self, queries: jax.Array) -> jax.Array:
        """(Q, N) probe priority (higher = probed earlier): the family's
        score table gathered at each item's (range, match count)."""
        q_codes = self.encode_queries(queries)
        matches = self.family.match_counts(self.params, q_codes, self.codes,
                                           self.hash_bits,
                                           impl=self.spec.impl)
        return self.table[self.range_id[None, :], matches]

    def probe_order(self, queries: jax.Array) -> jax.Array:
        """(Q, N) item ids in global probe order (stable argsort — ties
        break by item id, the legacy dense-arm contract)."""
        return jnp.argsort(-self.probe_scores(queries), axis=-1,
                           stable=True)

    def candidates(self, queries: jax.Array,
                   num_probe: Optional[int] = None, *,
                   engine: Optional[str] = None, buckets=None,
                   budgets=None) -> jax.Array:
        """(Q, P) candidate ids. ``engine="dense"`` (with no prebuilt
        ``buckets``) is the flat scan with item-id ties; any other
        selection dispatches through :class:`QueryEngine` (canonical CSR
        ties, identical candidate *sets*). ``budgets`` selects the
        planner's per-range-prefix contract instead of the global prefix
        (always canonical CSR ties)."""
        engine = self.spec.engine if engine is None else engine
        if budgets is not None:
            if num_probe is not None:
                raise ValueError("pass one of num_probe/budgets")
        else:
            if num_probe is None:
                raise ValueError("pass exactly one of num_probe/budgets")
            num_probe = _check_probe(num_probe, None, self.items.shape[0])
            if engine == "dense" and buckets is None:
                return self.probe_order(queries)[:, :num_probe]
        from repro.core.engine import engine_for
        eng = engine_for(self, engine=engine, buckets=buckets,
                         impl=self.spec.impl, tracker=self.spec.tracker)
        return eng.candidates(queries, num_probe, budgets=budgets)

    def query(self, queries: jax.Array, k: int,
              num_probe: Optional[int] = None, *,
              engine: Optional[str] = None, buckets=None,
              recall_target: Optional[float] = None, budgets=None
              ) -> Tuple[jax.Array, jax.Array]:
        """Algorithm 2 end-to-end: probe, exact re-rank, return (vals,
        ids) each (Q, k).

        The probe set comes from ``num_probe`` (global canonical prefix),
        ``budgets`` (per-range prefixes), or ``recall_target`` (planned
        budgets from the calibration table). With none of the three, the
        spec's ``recall_target`` is the contract — the planner's
        serving-default path."""
        if recall_target is None and num_probe is None and budgets is None:
            recall_target = self.spec.recall_target
        if recall_target is not None:
            if num_probe is not None or budgets is not None:
                raise ValueError(
                    "pass one of num_probe/budgets/recall_target")
            from repro.core.planner import resolve_budgets
            budgets = resolve_budgets(self.calib, recall_target,
                                      k=k).budgets
        if budgets is None:
            if num_probe is None:
                raise ValueError(
                    "pass num_probe, budgets or recall_target (or build "
                    "from an IndexSpec with a recall_target)")
            num_probe = _check_probe(num_probe, k, self.items.shape[0])
        engine = self.spec.engine if engine is None else engine
        if engine == "fused":
            # single-pass kernel: traversal + scoring fuse, so the staged
            # candidates->rerank relay below never materializes (Q, P)
            from repro.core.engine import engine_for
            eng = engine_for(self, engine=engine, buckets=buckets,
                             impl=self.spec.impl, tracker=self.spec.tracker)
            return eng.query(queries, int(k), num_probe, budgets=budgets)
        cand = self.candidates(queries, num_probe, engine=engine,
                               buckets=buckets, budgets=budgets)
        if not 0 < int(k) <= cand.shape[1]:
            raise ValueError(f"k={k} outside (0, probed width "
                             f"{cand.shape[1]}]")
        from repro.obs.tracker import resolve_tracker
        return rerank(queries, self.items, cand, int(k),
                      tracker=resolve_tracker(self.spec.tracker))


class ComposedMultiTable(NamedTuple):
    """Multi-table single-probe composition: T independent parameter draws
    over the (range-)normalized items; a candidate is any item fully
    matching the query's hashes in >= 1 table (supplementary protocol).

    ``upper`` here is the *effective* per-range bound (the multi-table
    score scaling needs a nonzero value, matching the legacy module)."""

    spec: IndexSpec
    items: jax.Array
    norms: jax.Array
    codes: jax.Array       # (T, N, ...) stacked per-table codes
    range_id: jax.Array
    upper: jax.Array
    lower: jax.Array
    params: Tuple[object, ...]
    hash_bits: int

    @property
    def family(self) -> HashFamily:
        return self.spec.resolve_family()

    @property
    def num_tables(self) -> int:
        return self.codes.shape[0]

    def candidate_scores(self, queries: jax.Array) -> jax.Array:
        """(Q, N) score = #tables with an exact full-hash match,
        norm-scaled when partitioned (0 => not a candidate)."""
        fam = self.family
        counts = jnp.zeros((queries.shape[0], self.items.shape[0]),
                           jnp.int32)
        for t in range(self.num_tables):
            qc = fam.encode_queries(self.params[t], queries,
                                    impl=self.spec.impl)
            matches = fam.match_counts(self.params[t], qc, self.codes[t],
                                       self.hash_bits, impl=self.spec.impl)
            counts = counts + (matches == self.hash_bits).astype(jnp.int32)
        scores = counts.astype(jnp.float32)
        if self.spec.ranged:
            scores = scores * self.upper[self.range_id][None, :]
        return scores

    def query(self, queries: jax.Array, k: int, *,
              max_candidates: int = 512
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Single-probe query: exact re-rank restricted to true candidates
        (score > 0). Returns (vals, ids, num_candidates (Q,)); slots
        beyond the candidate count come back as (-inf, -1)."""
        scores = self.candidate_scores(queries)
        n_cand = jnp.sum((scores > 0).astype(jnp.int32), axis=1)
        order = jnp.argsort(-scores, axis=1, stable=True)
        top = order[:, :max_candidates]                   # (Q, C)
        top_scores = jnp.take_along_axis(scores, top, axis=1)
        cand_vec = self.items[top]                        # (Q, C, d)
        ip = jnp.einsum("qd,qcd->qc", queries.astype(jnp.float32),
                        cand_vec.astype(jnp.float32), precision=EXACT)
        ip = jnp.where(top_scores > 0, ip, -jnp.inf)
        vals, pos = jax.lax.top_k(ip, k)
        ids = jnp.take_along_axis(top, pos, axis=1)
        ids = jnp.where(jnp.isfinite(vals), ids, -1)
        return vals, ids, n_cand


def _partition(norms: jax.Array, spec: IndexSpec):
    """(range_id, raw upper, effective upper, lower) per the spec; m=1
    short-circuits to the global bounds (SIMPLE-LSH's normalization)."""
    if spec.m > 1:
        part = partition_by_scheme(norms, spec.m, spec.scheme)
        return (part.range_id, part.upper, effective_upper(part),
                part.lower)
    upper = jnp.max(norms)[None]
    lower = jnp.min(norms)[None]
    rid = jnp.zeros((norms.shape[0],), jnp.int32)
    return rid, upper, upper, lower


def build(spec: IndexSpec, items: jax.Array, key: jax.Array, *,
          num_shards: Optional[int] = None, strict: bool = True,
          calibration_queries: Optional[jax.Array] = None,
          calibration_k: Optional[int] = None):
    """Spec-driven index construction — the single entry point.

    Returns a :class:`ComposedIndex` (or :class:`ComposedMultiTable` when
    ``spec.num_tables > 1``). ``num_shards`` selects the shard-aligned
    path instead: a :class:`repro.core.distributed.ShardedIndex` laid out
    for contiguous placement over a mesh axis (DESIGN.md §11).
    ``strict=False`` relaxes only the power-of-two rule on ``m`` (used by
    the legacy shims).

    A spec with a ``recall_target`` (or explicit ``calibration_queries``/
    ``calibration_k``) triggers offline planner calibration (DESIGN.md
    §12): held-out queries — ``calibration_queries`` or standard-normal
    samples drawn from ``key`` — are measured against brute-force ground
    truth and the fitted :class:`~repro.core.planner.CalibrationTable`
    rides on the index, powering ``query(recall_target=...)``."""
    if num_shards is not None:
        from repro.core.distributed import build_sharded
        return build_sharded(spec, items, key, num_shards, strict=strict,
                             calibration_queries=calibration_queries,
                             calibration_k=calibration_k)
    spec.validate(strict=strict)
    fam = spec.resolve_family()
    items = jnp.asarray(items)
    norms = hashing.l2_norm(items)
    rid, upper, upper_eff, lower = _partition(norms, spec)
    hash_bits = spec.hash_bits
    upper_per_item = upper_eff[rid]
    dim = int(items.shape[-1])
    if spec.num_tables > 1:
        if calibration_queries is not None or calibration_k is not None:
            raise ValueError("multi-table single-probe has no probe "
                             "budget to plan; calibration does not apply")
        keys = jax.random.split(key, spec.num_tables)
        params = tuple(fam.make_params(keys[t], dim, hash_bits)
                       for t in range(spec.num_tables))
        codes = jnp.stack([
            fam.encode_items(p, items, upper_per_item, impl=spec.impl)
            for p in params])
        return ComposedMultiTable(spec, items, norms, codes, rid,
                                  upper_eff, lower, params, hash_bits)
    params = fam.make_params(key, dim, hash_bits)
    codes = fam.encode_items(params, items, upper_per_item, impl=spec.impl)
    table = fam.score_table(upper_eff, hash_bits, eps=spec.eps)
    cidx = ComposedIndex(spec, items, norms, codes, rid, upper, upper_eff,
                         lower, params, table, hash_bits)
    if spec.recall_target is not None or calibration_queries is not None \
            or calibration_k is not None:
        from repro.core import planner
        cidx = cidx._replace(calib=planner.calibrate(
            cidx, calibration_queries,
            k=(planner.DEFAULT_CAL_K if calibration_k is None
               else int(calibration_k)),
            key=jax.random.fold_in(key, 0x5ca1)))
    return cidx
