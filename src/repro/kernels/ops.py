"""Public jit'd entry points for the Pallas kernels.

Each op pads inputs to kernel block multiples, dispatches to the Pallas
kernel on TPU (interpret mode when testing on CPU) or to the pure-jnp oracle
otherwise, and slices padding off the result. ``impl`` selects:

  * "auto"      — Pallas compiled on TPU, jnp reference elsewhere (default;
                  the reference XLA path is the fast path on CPU).
  * "pallas"    — force Pallas (compiled on TPU, interpret on CPU — slow,
                  used by the kernel test-suite).
  * "ref"       — force the jnp oracle.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.obs import cost as _cost
from repro.kernels import bucket_probe as _bucket_probe_mod
from repro.kernels import delta_scan as _delta_scan_mod
from repro.kernels import fused_query as _fused_query_mod
from repro.kernels import hamming as _hamming_mod
from repro.kernels import hash_encode as _hash_encode_mod
from repro.kernels import mips_topk as _mips_topk_mod
from repro.kernels.annotations import KernelAnnotation
from repro.kernels.bucket_probe import (bucket_gather_pallas,
                                        bucket_match_pallas)
from repro.kernels.delta_scan import delta_scan_pallas
from repro.kernels.fused_query import fused_query_pallas
from repro.kernels.hamming import hamming_pallas
from repro.kernels.hash_encode import hash_encode_pallas
from repro.kernels.mips_topk import mips_topk_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# optional dispatch observability (DESIGN.md §13): when a tracker is
# installed, every op call counts ``repro.kernels.dispatch.<op>.<impl>``.
# Ops are called at TRACE time from jitted callers, so counts measure
# traces/eager calls — which backend each op resolved to and how often new
# programs are built — not per-batch executions.
_dispatch_tracker = None


def set_dispatch_tracker(tracker) -> None:
    """Install (or clear, with None) the module-level dispatch tracker."""
    global _dispatch_tracker
    _dispatch_tracker = tracker


def _resolve(impl: str, op: Optional[str] = None) -> str:
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if op is not None and _dispatch_tracker is not None:
        _dispatch_tracker.count(f"repro.kernels.dispatch.{op}.{impl}")
    return impl


def _charge(op: str, cost_fn, *args) -> None:
    """Accumulate the analytic device cost of one op dispatch
    (``repro.kernels.cost.<op>.{flops,hbm_bytes}``, repro/obs/cost.py) —
    the per-op complement of the engine's per-span cost attrs. Lazy like
    the dispatch counters: nothing is computed without a tracker."""
    tr = _dispatch_tracker
    if tr is None:
        return
    c = cost_fn(*args)
    tr.count(f"repro.kernels.cost.{op}.flops", c["flops"])
    tr.count(f"repro.kernels.cost.{op}.hbm_bytes", c["hbm_bytes"])


def _require_nonempty(op: str, **dims: int) -> None:
    """Typed degenerate-shape guard: every listed dimension must be >= 1.

    The wrappers below round shapes up to tile multiples; a zero row or
    column count would silently round up to a phantom tile (or lower a
    zero-size grid) instead of failing loudly. Raise before padding."""
    zero = [f"{k}={v}" for k, v in dims.items() if v <= 0]
    if zero:
        raise ValueError(
            f"{op}: zero-size input dimension(s) {', '.join(zero)} — "
            f"every listed dimension must be >= 1")


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def hash_encode(x: jax.Array, A: jax.Array,
                tail: Optional[jax.Array] = None,
                a_tail: Optional[jax.Array] = None, *,
                impl: str = "auto") -> jax.Array:
    """Fused sign-projection encode to packed uint32 codes.

    x: (N, d); A: (d, L); optional SIMPLE-LSH fold: tail (N,), a_tail (L,).
    Returns (N, ceil(L/32)) uint32.
    """
    impl = _resolve(impl, "hash_encode")
    N, d = x.shape
    L = A.shape[1]
    _require_nonempty("hash_encode", N=N, d=d, L=L)
    _charge("hash_encode", _cost.hash_encode_cost, N, d, L)
    if tail is None:
        tail = jnp.zeros((N,), x.dtype)
        a_tail = jnp.zeros((L,), x.dtype)
    if impl == "ref":
        return _ref.hash_encode_ref(x, A, tail, a_tail)

    bn, bl, bd = 128, 128, min(512, max(128, d))
    xp = _pad_to(_pad_to(x, 0, bn), 1, bd)
    Ap = _pad_to(_pad_to(A, 0, bd), 1, bl)
    tp = _pad_to(tail[:, None], 0, bn)
    ap = _pad_to(a_tail[None, :], 1, bl)
    out = hash_encode_pallas(xp, Ap, tp, ap, bn=bn, bl=bl, bd=bd,
                             interpret=not _on_tpu())
    W = (L + 31) // 32
    out = out[:N, :W]
    # zero the padding bits of the last word (padded columns project to 0,
    # and sign(0) = 1 would otherwise pollute Hamming distances).
    rem = L % 32
    if rem:
        mask = jnp.uint32((1 << rem) - 1)
        out = out.at[:, -1].set(out[:, -1] & mask)
    return out


def hamming_scan(q_codes: jax.Array, db_codes: jax.Array, *,
                 impl: str = "auto") -> jax.Array:
    """All-pairs Hamming distances (Q, W) x (N, W) -> (Q, N) int32."""
    impl = _resolve(impl, "hamming_scan")
    _require_nonempty("hamming_scan", Q=q_codes.shape[0],
                      N=db_codes.shape[0], W=q_codes.shape[1])
    _charge("hamming_scan", _cost.packed_scan_cost, q_codes.shape[0],
            db_codes.shape[0], 32 * q_codes.shape[1])
    if impl == "ref":
        return _ref.hamming_ref(q_codes, db_codes)
    bq, bn = 64, 512
    Q, N = q_codes.shape[0], db_codes.shape[0]
    # zero-padded rows alias code 0 (a REAL code) but only land in rows /
    # columns past (Q, N), which the slice removes — no sentinel needed
    # (pad-site audit, PR 10; K4 probes the unaligned shapes).
    qp = _pad_to(q_codes, 0, bq)
    dp = _pad_to(db_codes, 0, bn)
    out = hamming_pallas(qp, dp, bq=bq, bn=bn, interpret=not _on_tpu())
    return out[:Q, :N]


def mips_topk(queries: jax.Array, items: jax.Array, k: int, *,
              impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Exact top-k inner products: vals (Q, k) f32, ids (Q, k) int32."""
    impl = _resolve(impl, "mips_topk")
    _require_nonempty("mips_topk", Q=queries.shape[0], N=items.shape[0],
                      d=queries.shape[1], k=k)
    if k > items.shape[0]:
        raise ValueError(f"k={k} must not exceed the item count "
                         f"N={items.shape[0]}")
    _charge("mips_topk", _cost.mips_topk_cost,
            queries.shape[0], items.shape[0], queries.shape[1], k)
    if impl == "ref":
        return _ref.mips_topk_ref(queries, items, k)
    bq, bn = 8, 256
    Q, N = queries.shape[0], items.shape[0]
    # Padded item rows must rank strictly last even against negative scores:
    # append a sentinel feature column — 1.0 on queries, 0.0 on real items,
    # -1e30 on padded items — so padded scores are real_dot - 1e30.
    qp = _pad_to(queries, 0, bq)
    qp = jnp.concatenate([qp, jnp.ones((qp.shape[0], 1), qp.dtype)], axis=1)
    sentinel = jnp.zeros((N, 1), items.dtype)
    ip = jnp.concatenate([items, sentinel], axis=1)
    ip = _pad_to(ip, 0, bn, value=0)
    pad_rows = ip.shape[0] - N
    if pad_rows:
        ip = ip.at[N:, -1].set(-1e30)
    vals, ids = mips_topk_pallas(qp, ip, k, bq=bq, bn=bn,
                                 interpret=not _on_tpu())
    return vals[:Q], ids[:Q]


def bucket_match(q_codes: jax.Array, bucket_codes: jax.Array,
                 hash_bits: int, *, impl: str = "auto") -> jax.Array:
    """Bucket-directory match counts: (Q, W) x (B, W) -> (Q, B) int32
    ``l = hash_bits - hamming`` (the eq.-12 input)."""
    impl = _resolve(impl, "bucket_match")
    _require_nonempty("bucket_match", Q=q_codes.shape[0],
                      B=bucket_codes.shape[0], W=q_codes.shape[1])
    _charge("bucket_match", _cost.packed_scan_cost, q_codes.shape[0],
            bucket_codes.shape[0], hash_bits)
    if impl == "ref":
        return _ref.bucket_match_ref(q_codes, bucket_codes, hash_bits)
    bq, bb = 64, 512
    Q, B = q_codes.shape[0], bucket_codes.shape[0]
    # zero-padded directory rows alias bucket code 0; their match counts
    # only occupy columns >= B, removed by the slice (pad-site audit).
    qp = _pad_to(q_codes, 0, bq)
    bp = _pad_to(bucket_codes, 0, bb)
    out = bucket_match_pallas(qp, bp, hash_bits=hash_bits, bq=bq, bb=bb,
                              interpret=not _on_tpu())
    return out[:Q, :B]


def delta_scan(q_codes: jax.Array, delta_codes: jax.Array, live: jax.Array,
               hash_bits: int, *, impl: str = "auto") -> jax.Array:
    """Delta-buffer scan: (Q, W) x (C, W) -> (Q, C) int32 match counts
    ``l = hash_bits - hamming`` with dead slots (``live`` falsy) fused to
    ``-1`` — the streaming merge ranks them last in one pass."""
    impl = _resolve(impl, "delta_scan")
    _require_nonempty("delta_scan", Q=q_codes.shape[0],
                      C=delta_codes.shape[0], W=q_codes.shape[1])
    _charge("delta_scan", _cost.packed_scan_cost, q_codes.shape[0],
            delta_codes.shape[0], hash_bits)
    if impl == "ref":
        return _ref.delta_scan_ref(q_codes, delta_codes, live, hash_bits)
    bq, bc = 64, 128
    Q, C = q_codes.shape[0], delta_codes.shape[0]
    qp = _pad_to(q_codes, 0, bq)
    dp = _pad_to(delta_codes, 0, bc)
    # padded slots carry live=0 and come back as -1 (the declared dead-slot
    # sentinel, NOT an aliased match count); sliced off anyway.
    lp = _pad_to(live.astype(jnp.int32)[None, :], 1, bc)
    out = delta_scan_pallas(qp, dp, lp, hash_bits=hash_bits, bq=bq, bc=bc,
                            interpret=not _on_tpu())
    return out[:Q, :C]


def bucket_gather(cum: jax.Array, starts: jax.Array, num_probe: int, *,
                  impl: str = "auto") -> jax.Array:
    """Segmented candidate gather: CSR positions (Q, num_probe) of the
    first ``num_probe`` probed items, given per-query probe-ordered bucket
    runs as (cum (Q, S+1), starts (Q, S)) int32 arrays."""
    impl = _resolve(impl, "bucket_gather")
    _require_nonempty("bucket_gather", Q=cum.shape[0],
                      S=cum.shape[1] - 1, num_probe=num_probe)
    _charge("bucket_gather", _cost.segmented_gather_cost,
            cum.shape[0], num_probe)
    if impl == "ref":
        return _ref.bucket_gather_ref(cum, starts, num_probe)
    Q, S = starts.shape
    # slots on sublanes, queries on lanes: the kernel reads one run's row
    # across 128 queries with a dynamic sublane index
    lanes = _bucket_probe_mod.LANES
    bp = min(_bucket_probe_mod.GATHER_BP, _round_up(num_probe, 8))
    num_slots = _round_up(num_probe, bp)
    bs = min(_bucket_probe_mod.GATHER_BS, _round_up(S, 8))
    lo = cum[:, :-1]
    off = starts - lo
    # padded runs start past every slot, so they never win a slot; padded
    # query rows get a single covering run [0, num_slots) and are sliced
    # off (rows must stay in-contract: runs cover the probe budget)
    lo = _pad_to(lo, 1, bs, value=num_slots)
    off = _pad_to(off, 1, bs)
    pad = (-Q) % lanes
    if pad:
        cover = jnp.full((pad, lo.shape[1]), num_slots, lo.dtype)
        lo = jnp.concatenate([lo, cover.at[:, 0].set(0)], axis=0)
        off = _pad_to(off, 0, lanes)
    out = bucket_gather_pallas(lo.T, off.T, num_slots, bp=bp,
                               interpret=not _on_tpu())
    return out[:num_probe, :Q].T


def fused_query(queries: jax.Array, cum: jax.Array, starts: jax.Array,
                items: jax.Array, total: int, k: int, *,
                kprime: Optional[int] = None,
                payload: Optional[jax.Array] = None,
                scale: Optional[jax.Array] = None,
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Fused single-pass planned query: vals (Q, k) f32, CSR positions
    (Q, k) i32 (DESIGN.md §17).

    ``cum`` (Q, S+1) / ``starts`` (Q, S): probe-ordered take runs whose
    per-query sizes sum to the static planned width ``total`` (the
    planner contract). ``items`` (N, d): f32 CSR-ordered rows (the
    rescore payload). Optional ``payload`` (N, d) int8 + ``scale`` (N, 1)
    f32 select the quantized phase-1 arm; by default phase 1 scores the
    f32 rows themselves (unit scales), which makes the returned positions
    bit-identical to the staged gather -> re-rank -> top_k relay.
    ``kprime`` is the phase-1 survivor width (>= k; default
    ``min(max(4k, 32), total)``).
    """
    impl = _resolve(impl, "fused_query")
    Q, d = queries.shape
    S = cum.shape[1] - 1
    N = items.shape[0]
    total = int(total)
    k = int(k)
    _require_nonempty("fused_query", Q=Q, d=d, S=S, N=N, k=k, total=total)
    if k > total:
        raise ValueError(f"k={k} must not exceed the planned probe "
                         f"width total={total}")
    if kprime is None:
        kprime = max(k, min(max(4 * k, 32), total))
    kprime = int(kprime)
    if kprime < k:
        raise ValueError(f"kprime={kprime} must be >= k={k}")
    if (payload is None) != (scale is None):
        raise ValueError("fused_query: pass payload and scale together "
                         "(the per-item dequant scales)")
    _charge("fused_query", _cost.fused_query_cost, Q, total, d, k, kprime)
    if impl == "ref":
        return _ref.fused_query_ref(queries, cum, starts, items, total, k,
                                    kprime=kprime, payload=payload,
                                    scale=scale)
    if payload is None:
        payload = items
        scale = jnp.ones((N, 1), jnp.float32)
    bq = 8
    # padded query rows carry all-zero cum rows: a zero take total masks
    # every candidate slot to the NEG sentinel inside the kernel.
    pad = (-Q) % bq
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, d), queries.dtype)], axis=0)
        cum = jnp.concatenate(
            [cum, jnp.zeros((pad, S + 1), cum.dtype)], axis=0)
        starts = jnp.concatenate(
            [starts, jnp.zeros((pad, S), starts.dtype)], axis=0)
    vals, pos = fused_query_pallas(queries, cum, starts, payload, scale,
                                   items, k, kprime=kprime, total=total,
                                   bq=bq, interpret=not _on_tpu())
    return vals[:Q, :k], pos[:Q, :k]


# -- kernel registry (kernelcheck metadata, DESIGN.md §16) --------------------
#
# One entry per op above. The registry is what makes the ops *statically
# analyzable*: repro/analysis/kernelcheck.py walks it to capture each
# ``pallas_call`` under abstract tracing (grid, BlockSpecs, index maps),
# evaluate the same cost model the op's ``_charge`` call bills, derive an
# independent flop/byte count from the ref oracle's jaxpr, and drive the
# K4 sentinel probes. Adding a kernel without registering it here is an R3
# finding; registering it keeps it under the K1–K5 gate forever.


def _arr(abstract: bool, shape: Tuple[int, ...], dtype):
    """One registry input: ShapeDtypeStruct for abstract capture, a cheap
    concrete zero array for jaxpr cost derivation."""
    if abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def _codes(n: int, w: int) -> jax.Array:
    """Deterministic uint32 code patterns for the K4 probes (Knuth
    multiplicative hash of the slot index — no PRNG dependency)."""
    i = jnp.arange(n * w, dtype=jnp.uint32)
    return (i * jnp.uint32(2654435761) + jnp.uint32(12345)).reshape(n, w)


def _parity_problems(op: str, got, want, *, atol: float = 0.0) -> List[str]:
    """Pallas-vs-ref comparison under adversarial padding; any mismatch
    means padded lanes leaked through the wrapper (the PR 4 bug class)."""
    import numpy as np
    g, w = np.asarray(got), np.asarray(want)
    if g.shape != w.shape:
        return [f"{op}: pallas result shape {g.shape} != ref {w.shape} "
                f"under unaligned input shapes"]
    if atol:
        ok = bool(np.allclose(g, w, atol=atol, rtol=1e-5))
    else:
        ok = bool((g == w).all())
    if not ok:
        return [f"{op}: pallas/ref parity broke under padding "
                f"(max abs diff {np.abs(g - w).max()})"]
    return []


def _probe_hash_encode() -> List[str]:
    """Padding-bit discipline: with every projection positive, unmasked
    padding bits of the last word would read sign(0) = 1."""
    n, d, L = 3, 8, 48                       # L % 32 == 16 padding bits
    x = jnp.ones((n, d), jnp.float32)
    A = jnp.ones((d, L), jnp.float32)
    got = hash_encode(x, A, impl="pallas")
    want = _ref.hash_encode_ref(x, A)
    problems = _parity_problems("hash_encode", got, want)
    if bool(jnp.any(jnp.asarray(got)[:, -1] >> jnp.uint32(L % 32))):
        problems.append(
            "hash_encode: padding bits of the final packed word are not "
            "masked to 0 (sign(0) leaked into the code)")
    return problems


def _probe_hamming() -> List[str]:
    q, n, w = 3, 70, 2                       # n far below the 512 tile
    return _parity_problems(
        "hamming_scan",
        hamming_scan(_codes(q, w), _codes(n, w), impl="pallas"),
        _ref.hamming_ref(_codes(q, w), _codes(n, w)))


def _probe_mips_topk() -> List[str]:
    """The PR 4 shard-padding leak, distilled: all real scores strongly
    negative, so an unmasked zero-padded item row (score 0) would win."""
    q, n, d, k = 3, 5, 4, 5                  # k == n: every real id surfaces
    queries = -3.0 * jnp.ones((q, d), jnp.float32)
    items = 1.0 + jnp.arange(n * d, dtype=jnp.float32).reshape(n, d) / (n * d)
    gv, gi = mips_topk(queries, items, k, impl="pallas")
    wv, wi = _ref.mips_topk_ref(queries, items, k)
    problems = []
    if not bool(jnp.all(gi < n)):
        problems.append(
            "mips_topk: padded item ids surfaced in the returned top-k "
            "(sentinel feature column not ranking padded rows last)")
    problems += _parity_problems("mips_topk.ids", gi, wi)
    problems += _parity_problems("mips_topk.vals", gv, wv, atol=1e-4)
    return problems


def _probe_bucket_match() -> List[str]:
    q, b, w = 3, 21, 1                       # b far below the 512 tile
    hash_bits = 32 * w
    return _parity_problems(
        "bucket_match",
        bucket_match(_codes(q, w), _codes(b, w), hash_bits, impl="pallas"),
        _ref.bucket_match_ref(_codes(q, w), _codes(b, w), hash_bits))


def _probe_delta_scan() -> List[str]:
    q, c, w = 3, 5, 1                        # c pads 5 -> 128 dead slots
    hash_bits = 32 * w
    live = jnp.asarray([True, False, True, False, True])
    got = delta_scan(_codes(q, w), _codes(c, w), live, hash_bits,
                     impl="pallas")
    want = _ref.delta_scan_ref(_codes(q, w), _codes(c, w), live, hash_bits)
    problems = _parity_problems("delta_scan", got, want)
    dead = jnp.logical_not(live)
    if not bool(jnp.all(jnp.where(dead[None, :], got == -1, True))):
        problems.append("delta_scan: dead slots did not fuse to the -1 "
                        "sentinel")
    if not bool(jnp.all(jnp.where(live[None, :], got >= 0, True))):
        problems.append("delta_scan: live slots carried the dead-slot "
                        "sentinel")
    return problems


def _probe_bucket_gather() -> List[str]:
    q, s, p = 3, 4, 7                        # q pads 3 -> 128 query lanes
    sizes = jnp.full((q, s), 2, jnp.int32)   # 4 runs x 2 items >= p
    cum = jnp.concatenate(
        [jnp.zeros((q, 1), jnp.int32), jnp.cumsum(sizes, axis=1)], axis=1)
    starts = (17 * jnp.arange(q * s, dtype=jnp.int32)).reshape(q, s)
    problems = _parity_problems(
        "bucket_gather",
        bucket_gather(cum, starts, p, impl="pallas"),
        _ref.bucket_gather_ref(cum, starts, p))
    # row-padding audit: the wrapper's covering run keeps the 125 padded
    # query lanes in-contract; CSR position 0 is a REAL position, so any
    # padded lane leaking into the sliced result would alias item 0 —
    # assert the slice boundary, not just parity.
    got = bucket_gather(cum, starts, p, impl="pallas")
    if got.shape != (q, p):
        problems.append(
            "bucket_gather: padded query rows leaked through the result "
            "slice (covering-run rows alias CSR position 0)")
    return problems


def _probe_fused_query() -> List[str]:
    """Chunk-padding leak, distilled: total=4 probed slots in a bp=128
    chunk leaves 124 padded slots per query, and Q=3 pads to bq=8 with 5
    all-zero cum rows. Item row 0 dominates every real candidate and CSR
    position 0 is *not* probed — an unmasked padded slot (safe-gathered
    row 0) would win every query."""
    q, s, n, d, k = 3, 2, 8, 4, 4
    queries = jnp.ones((q, d), jnp.float32)
    items = jnp.arange(n * d, dtype=jnp.float32).reshape(n, d) / (n * d)
    items = items.at[0].set(100.0)           # the poison row, never probed
    cum = jnp.tile(jnp.asarray([[0, 2, 4]], jnp.int32), (q, 1))
    starts = jnp.asarray([[2, 6], [4, 1], [6, 3]], jnp.int32)
    total = 4
    gv, gp = fused_query(queries, cum, starts, items, total, k,
                         impl="pallas")
    wv, wp = _ref.fused_query_ref(queries, cum, starts, items, total, k)
    problems = []
    if bool(jnp.any(gp == 0)):
        problems.append(
            "fused_query: an unprobed CSR position surfaced in the "
            "returned top-k (padded candidate slots not masked to the "
            "NEG sentinel before the merge)")
    problems += _parity_problems("fused_query.pos", gp, wp)
    problems += _parity_problems("fused_query.vals", gv, wv, atol=1e-4)
    # int8 arm: per-item scales must ride the gather — uniform rows with
    # wildly different scales surface any payload/scale misalignment.
    pay = jnp.ones((n, d), jnp.int8)
    sc = (2.0 ** jnp.arange(n, dtype=jnp.float32))[:, None] / 127.0
    gv8, gp8 = fused_query(queries, cum, starts, items, total, k,
                           payload=pay, scale=sc, impl="pallas")
    wv8, wp8 = _ref.fused_query_ref(queries, cum, starts, items, total, k,
                                    payload=pay, scale=sc)
    problems += _parity_problems("fused_query.int8.pos", gp8, wp8)
    problems += _parity_problems("fused_query.int8.vals", gv8, wv8,
                                 atol=1e-4)
    return problems


@dataclasses.dataclass(frozen=True)
class RegisteredKernel:
    """Registry metadata for one Pallas op (not jit-static — analysis
    only, never enters a trace).

    ``pallas_symbol`` names the jitted ``*_pallas`` builder in this
    module's namespace so kernelcheck can unwrap it past ``jax.jit`` (a
    cached executable would skip ``pallas_call`` and capture nothing).
    ``make_inputs(shapes, abstract)`` builds wrapper inputs for one shape
    class; ``cost_args(shapes)`` positions the same class for ``cost_fn``
    — the *identical* model object the op's ``_charge`` call bills, which
    is what lets K5 assert the attribution can't silently drift.
    ``cost_tol`` is the per-op K5 factor tolerance between the analytic
    model and the jaxpr-derived count (the analytic models charge
    semantic work — one lane-op per popcount word — while the oracle
    jaxpr pays bookkeeping like converts and binary-search steps);
    ``bytes_tol`` overrides it for the hbm_bytes metric when the byte
    models diverge differently than the flop models."""

    op: str
    wrapper: Callable
    pallas_symbol: Optional[str]
    annotation: KernelAnnotation
    cost_fn: Callable
    cost_args: Callable
    ref_fn: Callable
    make_inputs: Callable
    shape_classes: Tuple[Dict[str, int], ...]
    probe: Optional[Callable] = None
    cost_tol: float = 5.0
    bytes_tol: Optional[float] = None


KERNEL_REGISTRY: Dict[str, RegisteredKernel] = {
    "hash_encode": RegisteredKernel(
        op="hash_encode",
        wrapper=hash_encode,
        pallas_symbol="hash_encode_pallas",
        annotation=_hash_encode_mod.ANNOTATION,
        cost_fn=_cost.hash_encode_cost,
        cost_args=lambda s: (s["n"], s["d"], s["L"]),
        ref_fn=_ref.hash_encode_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["n"], s["d"]), jnp.float32),
             _arr(a, (s["d"], s["L"]), jnp.float32),
             _arr(a, (s["n"],), jnp.float32),
             _arr(a, (s["L"],), jnp.float32)), {}),
        # second class drives a multi-slab K loop (d > bd): the revisit
        # declaration on the k_slab grid dim is actually exercised
        shape_classes=({"n": 256, "d": 96, "L": 64},
                       {"n": 128, "d": 1024, "L": 128}),
        probe=_probe_hash_encode,
    ),
    "hamming_scan": RegisteredKernel(
        op="hamming_scan",
        wrapper=hamming_scan,
        pallas_symbol="hamming_pallas",
        annotation=_hamming_mod.ANNOTATION,
        cost_fn=_cost.packed_scan_cost,
        cost_args=lambda s: (s["q"], s["n"], 32 * s["w"]),
        ref_fn=_ref.hamming_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["w"]), jnp.uint32),
             _arr(a, (s["n"], s["w"]), jnp.uint32)), {}),
        shape_classes=({"q": 64, "n": 2048, "w": 2},
                       {"q": 8, "n": 512, "w": 8}),
        probe=_probe_hamming,
    ),
    "mips_topk": RegisteredKernel(
        op="mips_topk",
        wrapper=mips_topk,
        pallas_symbol="mips_topk_pallas",
        annotation=_mips_topk_mod.ANNOTATION,
        cost_fn=_cost.mips_topk_cost,
        cost_args=lambda s: (s["q"], s["n"], s["d"], s["k"]),
        ref_fn=_ref.mips_topk_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["d"]), jnp.float32),
             _arr(a, (s["n"], s["d"]), jnp.float32)), {"k": s["k"]}),
        shape_classes=({"q": 8, "n": 1024, "d": 64, "k": 8},
                       {"q": 16, "n": 512, "d": 128, "k": 16}),
        probe=_probe_mips_topk,
        # byte model charges gathered-candidate-row traffic (q*n*d reads,
        # the hot-path realization); the streaming oracle reads each item
        # row once -> ratio ~ q
        bytes_tol=32.0,
    ),
    "bucket_match": RegisteredKernel(
        op="bucket_match",
        wrapper=bucket_match,
        pallas_symbol="bucket_match_pallas",
        annotation=_bucket_probe_mod.MATCH_ANNOTATION,
        cost_fn=_cost.packed_scan_cost,
        cost_args=lambda s: (s["q"], s["b"], 32 * s["w"]),
        ref_fn=_ref.bucket_match_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["w"]), jnp.uint32),
             _arr(a, (s["b"], s["w"]), jnp.uint32)),
            {"hash_bits": 32 * s["w"]}),
        shape_classes=({"q": 64, "b": 1024, "w": 2},),
        probe=_probe_bucket_match,
    ),
    "delta_scan": RegisteredKernel(
        op="delta_scan",
        wrapper=delta_scan,
        pallas_symbol="delta_scan_pallas",
        annotation=_delta_scan_mod.ANNOTATION,
        cost_fn=_cost.packed_scan_cost,
        cost_args=lambda s: (s["q"], s["c"], 32 * s["w"]),
        ref_fn=_ref.delta_scan_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["w"]), jnp.uint32),
             _arr(a, (s["c"], s["w"]), jnp.uint32),
             _arr(a, (s["c"],), jnp.bool_)),
            {"hash_bits": 32 * s["w"]}),
        shape_classes=({"q": 64, "c": 256, "w": 2},),
        probe=_probe_delta_scan,
        # the oracle additionally pays the liveness select per (q, c) lane
        cost_tol=8.0,
    ),
    "bucket_gather": RegisteredKernel(
        op="bucket_gather",
        wrapper=bucket_gather,
        pallas_symbol="bucket_gather_pallas",
        annotation=_bucket_probe_mod.GATHER_ANNOTATION,
        cost_fn=_cost.segmented_gather_cost,
        cost_args=lambda s: (s["q"], s["p"]),
        ref_fn=_ref.bucket_gather_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["s"] + 1), jnp.int32),
             _arr(a, (s["q"], s["s"]), jnp.int32)),
            {"num_probe": s["p"]}),
        shape_classes=({"q": 32, "s": 16, "p": 64},),
        probe=_probe_bucket_gather,
        # the analytic model charges the semantic walk (one op per probed
        # slot, q*p); the oracle's vmapped searchsorted pays the binary
        # search, bounds selects and index arithmetic per slot (~50x at
        # S=16) — tolerance covers the measured gap with headroom
        cost_tol=96.0,
    ),
    "fused_query": RegisteredKernel(
        op="fused_query",
        wrapper=fused_query,
        pallas_symbol="fused_query_pallas",
        annotation=_fused_query_mod.ANNOTATION,
        cost_fn=_cost.fused_query_cost,
        cost_args=lambda s: (s["q"], s["total"], s["d"], s["k"],
                             s["kprime"]),
        ref_fn=_ref.fused_query_ref,
        make_inputs=lambda s, a: (
            (_arr(a, (s["q"], s["d"]), jnp.float32),
             _arr(a, (s["q"], s["s"] + 1), jnp.int32),
             _arr(a, (s["q"], s["s"]), jnp.int32),
             _arr(a, (s["n"], s["d"]), jnp.float32)),
            {"total": s["total"], "k": s["k"], "kprime": s["kprime"]}),
        # class B is the VMEM-residency envelope: payload/scale/items are
        # whole-array resident, so N*d is bounded by half the VMEM budget
        # (DESIGN.md §17) — shards beyond it go to the distributed engine
        shape_classes=(
            {"q": 16, "s": 8, "total": 256, "n": 4096, "d": 32,
             "k": 8, "kprime": 32},
            {"q": 8, "s": 16, "total": 1024, "n": 16384, "d": 32,
             "k": 16, "kprime": 64}),
        probe=_probe_fused_query,
        # the analytic walk charge is q*total vs the oracle's vmapped
        # searchsorted (the bucket_gather gap, diluted here by the dot
        # flops); the byte model charges int8 candidate-row traffic while
        # the oracle jaxpr pays whole-operand f32 reads
        cost_tol=8.0,
        bytes_tol=16.0,
    ),
}
