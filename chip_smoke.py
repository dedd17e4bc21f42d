"""Chip smoke: drive the index's main path once on a TPU, at a million items.

    python chip_smoke.py [--seed S]             # one chip, the main path
    python chip_smoke.py --chips 4 [--seed S]   # the sharded path only

One chip. N = 2^20 items of the ``imagenet`` profile (d = 128, lognormal
norms) and Q = 128 queries, made on the device from ``--seed``:

  * index A, ``IndexSpec(family="simple", code_len=16, m=32,
    recall_target=0.9)``: B <= 2^16 buckets, so ``engine="auto"`` resolves
    to the bucket engine;
  * index B, the same at ``code_len=32``: B is close to N, so ``auto``
    resolves to the dense engine.

Each index answers ``query(k=10, num_probe=N/100)`` and
``query(k=10, recall_target=0.9)``. The checks: recall@10 against
brute-force ``topk.exact_mips`` within 0.01 of the same engine with the
jnp reference kernels (``impl="ref"``); on index A, the bucket and dense
engines probe the same ids; the packed codes of the Pallas encode agree
with the reference bit for bit, except where the projection is within
rounding of zero; the re-rank scores are f32-exact inner products; the
streaming top-k kernel finds the brute-force top-10; ``engine="fused"``,
whose kernel does not lower for the TPU, refuses at construction; and
every kernel op the main path dispatched resolved to Pallas, none to the
reference.

Four chips. Index A's layout (no calibration) is laid out over four
shards (``build_sharded`` -> ``shard_index`` on a four-device mesh), and
both ``DistributedEngine`` arms must return the single-chip
``QueryEngine`` ids for ``num_probe=N/100`` bit for bit, with the item
rows on four distinct devices.

The lines before the last are smoke timings and checks, not benchmark
metrics: wall seconds per phase, with the seconds spent tracing and
compiling in that phase shown apart. The last line of standard output is
one JSON object naming the device, printed only when every check passed.
Without a TPU the script exits 1 and prints no JSON. It runs in one
process and starts no other.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N = 1 << 20
D = 128
Q = 128
K = 10
M = 32
RECALL_TARGET = 0.9
RECALL_SLACK = 0.01       # pallas arm vs reference arm, same chip

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"check ok: {what}")


@contextlib.contextmanager
def phase(name: str):
    """Wall time of one phase (its results are synced inside) and the
    tracing + compiling seconds JAX reported during it."""
    t0, c0 = time.perf_counter(), _compile_s[0]
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    log(f"phase {name}: wall_s={wall:.3f} compile_s={comp:.3f} "
        f"(smoke timing, not a benchmark metric)")


def ready(*xs):
    import jax
    return jax.block_until_ready(xs if len(xs) > 1 else xs[0])


class DispatchAudit:
    """Installs a tracker on the kernel dispatch layer and checks that
    every op the wrapped phases dispatched resolved to one impl."""

    PREFIX = "repro.kernels.dispatch."

    def __init__(self):
        from repro.kernels import ops
        from repro.obs.tracker import Tracker
        self.ops = ops
        self.tracker = Tracker()

    @contextlib.contextmanager
    def on(self):
        self.ops.set_dispatch_tracker(self.tracker)
        try:
            yield
        finally:
            self.ops.set_dispatch_tracker(None)

    def resolved(self):
        out = {}
        for name, n in self.tracker.counters.items():
            if name.startswith(self.PREFIX):
                op, impl = name[len(self.PREFIX):].rsplit(".", 1)
                out.setdefault(op, {})[impl] = int(n)
        return out

    def require(self, impl: str, label: str) -> None:
        got = self.resolved()
        log(f"dispatch ({label}): {json.dumps(got, sort_keys=True)}")
        check(bool(got) and all(set(v) == {impl} for v in got.values()),
              f"every op dispatched in the {label} phases resolved to "
              f"{impl}")


def make_data(seed: int):
    import jax
    from repro.data.synthetic import make_dataset
    key = jax.random.PRNGKey(seed)
    ds = make_dataset("imagenet", key, n=N, d=D, num_queries=Q)
    return ready(ds.items), ready(ds.queries), jax.random.fold_in(key, 1)


def recall(ids, truth) -> float:
    from repro.core import topk
    return float(topk.recall_at(ids, truth))


def check_encode_bits(idx) -> None:
    """Pallas-built codes vs the reference encode on the same chip. A
    kernel fault flips bits anywhere; precision flips only bits whose
    projection is within rounding of zero."""
    import jax.numpy as jnp
    from repro.core.topk import EXACT
    fam, params = idx.family, idx.params
    upper = idx.upper_eff[idx.range_id]
    ref_codes = fam.encode_items(params, idx.items, upper, impl="ref")
    x = idx.items / upper[:, None]
    tail = jnp.sqrt(jnp.maximum(0.0, 1.0 - jnp.sum(x * x, axis=-1)))
    proj = (jnp.matmul(x, params[:-1], precision=EXACT)
            + tail[:, None] * params[-1][None, :])           # (N, L)
    shifts = jnp.arange(proj.shape[1], dtype=jnp.uint32)
    diff = ((jnp.bitwise_xor(idx.codes[:, 0], ref_codes[:, 0])[:, None]
             >> shifts) & 1).astype(bool)                    # (N, L)
    n_diff = int(jnp.sum(diff))
    scale = float(jnp.sqrt(jnp.mean(proj * proj)))
    worst = float(jnp.max(jnp.where(diff, jnp.abs(proj), 0.0)))
    log(f"hash_encode pallas vs ref: {n_diff} of {diff.size} code bits "
        f"differ; largest |projection| among them {worst:.3e} "
        f"(projection rms {scale:.3e})")
    check(worst <= 1e-4 * scale,
          "every code bit that differs from the reference encode has a "
          "projection within f32 rounding of zero (precision, not a "
          "kernel fault)")


def check_rerank_exact(queries, items, vals, ids) -> None:
    """Returned scores against float64 inner products on the host."""
    import numpy as np
    q = np.asarray(queries, np.float64)
    x = np.asarray(items, np.float64)[np.asarray(ids)]       # (Q, K, d)
    want = np.einsum("qd,qkd->qk", q, x)
    err = float(np.max(np.abs(np.asarray(vals, np.float64) - want)
                       / np.maximum(np.abs(want), 1.0)))
    log(f"re-rank scores vs float64: max relative error {err:.3e}")
    check(err <= 1e-5, "re-rank scores are f32-exact inner products")


def run_index(label, spec, items, queries, truth, key, want_engine,
              audit):
    from repro.core.engine import QueryEngine
    from repro.core.index import build
    from repro.core.planner import resolve_budgets
    with audit.on():
        with phase(f"{label}.build"):
            idx = build(spec, items, key)
            ready(idx.codes)
        with phase(f"{label}.engine"):
            eng = QueryEngine(idx)
            ready(eng.buckets.item_ids)
    plan = resolve_budgets(idx.calib, RECALL_TARGET, k=K)
    log(f"index {label}: N={N} d={D} code_len={spec.code_len} m={spec.m} "
        f"hash_bits={idx.hash_bits} B={eng.buckets.num_buckets} "
        f"auto engine={eng.engine} planned width={plan.num_probe} "
        f"(predicted recall {plan.predicted_recall:.4f})")
    check(eng.engine == want_engine,
          f"index {label}: engine=\"auto\" resolves to {want_engine}")
    check_encode_bits(idx)

    ref_eng = QueryEngine(idx, engine=eng.engine, buckets=eng.buckets,
                          impl="ref")
    for kind, kw in (("num_probe", {"num_probe": N // 100}),
                     ("recall_target", {"recall_target": RECALL_TARGET})):
        with audit.on():
            with phase(f"{label}.query.{kind}.pallas"):
                vals, ids = ready(*eng.query(queries, K, **kw))
        with phase(f"{label}.query.{kind}.ref"):
            rvals, rids = ready(*ref_eng.query(queries, K, **kw))
        r, rr = recall(ids, truth), recall(rids, truth)
        same = float((ids == rids).mean())
        log(f"index {label} {kind}: recall@{K} pallas={r:.4f} ref={rr:.4f} "
            f"ids equal to ref {same:.4f}")
        check(abs(r - rr) <= RECALL_SLACK,
              f"index {label} {kind}: recall within {RECALL_SLACK} of the "
              f"reference arm")
        check_rerank_exact(queries, items, vals, ids)
    return idx, eng, plan


def check_engine_parity(idx, eng, plan, queries, audit) -> None:
    """Bucket and dense engines probe the same ids in the same order."""
    import numpy as np
    from repro.core.engine import QueryEngine
    with audit.on():
        dense = QueryEngine(idx, engine="dense", buckets=eng.buckets)
        with phase("A.parity.dense_vs_bucket"):
            for label, args, kw in (
                    ("num_probe", (N // 100,), {}),
                    ("budgets", (), {"budgets": plan.budgets})):
                b = ready(eng.candidates(queries, *args, **kw))
                d = ready(dense.candidates(queries, *args, **kw))
                check(np.array_equal(np.asarray(b), np.asarray(d)),
                      f"index A {label}: dense and bucket engines probe "
                      f"identical ids ({b.shape[1]} per query)")


def check_fused_refused(idx, eng) -> None:
    """The fused kernel does not lower for the TPU: the engine says so at
    construction instead of failing mid-query."""
    from repro.core.engine import QueryEngine
    refused = False
    try:
        QueryEngine(idx, engine="fused", buckets=eng.buckets)
    except NotImplementedError as e:
        refused = True
        log(f"engine=\"fused\" at construction: {e}")
    check(refused, "engine=\"fused\" refuses at construction on the TPU")


def check_mips_topk(queries, items, truth, audit) -> None:
    from repro.kernels import ops
    with audit.on():
        with phase("mips_topk"):
            _, ids = ready(*ops.mips_topk(queries, items, K))
    r = recall(ids, truth)
    log(f"mips_topk kernel vs exact_mips: recall@{K}={r:.4f}")
    check(r >= 0.99, "the streaming top-k kernel finds the brute-force "
                     "top-10")


def one_chip(seed: int) -> None:
    from repro.core import topk
    from repro.core.index import IndexSpec
    with phase("data"):
        items, queries, key = make_data(seed)
    with phase("truth.exact_mips"):
        _, truth = ready(*topk.exact_mips(queries, items, K))
    audit = DispatchAudit()
    check_mips_topk(queries, items, truth, audit)
    spec_a = IndexSpec(family="simple", code_len=16, m=M,
                       recall_target=RECALL_TARGET)
    idx, eng, plan = run_index("A", spec_a, items, queries, truth, key,
                               "bucket", audit)
    check_engine_parity(idx, eng, plan, queries, audit)
    check_fused_refused(idx, eng)
    del idx, eng
    spec_b = IndexSpec(family="simple", code_len=32, m=M,
                       recall_target=RECALL_TARGET)
    run_index("B", spec_b, items, queries, truth, key, "dense", audit)
    audit.require("pallas", "main-path")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import (DistributedEngine, build_sharded,
                                        shard_index)
    from repro.core.engine import QueryEngine
    from repro.core.index import IndexSpec, build
    devices = jax.devices()
    check(len(devices) >= 4, f"four devices visible (found {len(devices)})")
    with phase("data"):
        items, queries, key = make_data(seed)
    # index A's layout; no calibration: the comparison is at a fixed
    # num_probe, the same on one chip and on four
    spec = IndexSpec(family="simple", code_len=16, m=M)
    audit = DispatchAudit()
    with audit.on():
        with phase("sharded.build"):
            sharded = build_sharded(spec, items, key, 4)
            mesh = Mesh(np.array(devices[:4]), ("data",))
            placed = shard_index(sharded, mesh)
            ready(placed.items)
        with phase("single.build_and_query"):
            single = QueryEngine(build(spec, items, key), engine="bucket")
            _, want = ready(*single.query(queries, K, num_probe=N // 100))
    homes = {s.device for s in placed.items.addressable_shards}
    log(f"sharded index: rows_per_shard={placed.rows_per_shard} "
        f"B={placed.num_buckets} item shards on "
        f"{sorted(str(d) for d in homes)}")
    check(len(homes) == 4, "the item rows sit on four distinct devices")
    check(len({s.device for s in placed.codes.addressable_shards}) == 4,
          "the item codes sit on four distinct devices")
    want = np.asarray(want)
    for arm in ("bucket", "dense"):
        with audit.on():
            dist = DistributedEngine(placed, mesh, engine=arm)
            with phase(f"sharded.{arm}.num_probe"):
                _, got = ready(*dist.query(queries, K, num_probe=N // 100))
        got = np.asarray(got)
        log(f"sharded {arm} num_probe={N // 100}: ids equal to single-chip "
            f"{float((got == want).mean()):.4f}")
        check(np.array_equal(got, want),
              f"sharded {arm} arm returns the single-chip ids bit for bit")
    audit.require("pallas", "sharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the items and queries (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: the sharded "
                         "path and its single-chip comparison only")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import cache_stats, enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    log(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"total wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={_compile_s[0]:.3f}; compile cache {cache_stats()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
