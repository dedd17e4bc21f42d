"""Distributed serving on the composable spec API (DESIGN.md §11).

In-process tests run on a 1-device mesh; the full family x engine x
shard-count parity matrix (plus uneven/tiny shards) runs 8-way in a
subprocess, since the host device count is locked at jax init. The CI
workflow additionally runs this whole file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the in-process
tests also exercise real multi-shard collectives.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed, range_lsh, topk
from repro.core.engine import QueryEngine
from repro.core.index import IndexSpec, build
from repro.launch.mesh import make_local_mesh

KEY = jax.random.PRNGKey(3)


# -- legacy shim surface (seed API preserved) ---------------------------------


def test_sharded_matches_local_quality(longtail_ds):
    """Legacy shim on the local mesh == the single-device RangeLSH engine
    at the same global probe budget."""
    items, queries = longtail_ds.items, longtail_ds.queries[:8]
    mesh = make_local_mesh()
    shards = mesh.shape["data"]
    idx = distributed.build(items, jax.random.PRNGKey(3), 32, 16, shards)
    idx = distributed.shard_index(idx, mesh)
    vals, ids = distributed.query(idx, queries, 10, 400, mesh)
    ri = range_lsh.build(items, jax.random.PRNGKey(3), 32, 16)
    budget = min(items.shape[0], 400 * shards)
    lvals, lids = range_lsh.query(ri, queries, 10, budget)
    _, truth = topk.exact_mips(queries, items, 10)
    rec_d = float(topk.recall_at(ids, truth))
    rec_l = float(topk.recall_at(lids, truth))
    assert abs(rec_d - rec_l) < 1e-6
    np.testing.assert_allclose(np.asarray(vals), np.asarray(lvals),
                               rtol=1e-4)


def test_sharded_full_probe_is_exact(longtail_ds):
    items, queries = longtail_ds.items, longtail_ds.queries[:4]
    n = items.shape[0]
    mesh = make_local_mesh()
    idx = distributed.build(items, jax.random.PRNGKey(0), 32, 8,
                            mesh.shape["data"])
    idx = distributed.shard_index(idx, mesh)
    vals, ids = distributed.query(idx, queries, 5, n, mesh)
    tvals, truth = topk.exact_mips(queries, items, 5)
    assert float(topk.recall_at(ids, truth)) == 1.0
    np.testing.assert_allclose(np.asarray(vals), np.asarray(tvals),
                               rtol=1e-4)


def test_norm_sorted_layout_aligns_ranges_to_shards(longtail_ds):
    """Shard-aligned layout (DESIGN.md §11): rows are in global CSR order
    (range-major), so reading shards in order yields non-decreasing
    range ids — every shard owns a contiguous run of norm ranges."""
    idx = distributed.build(longtail_ds.items, jax.random.PRNGKey(0), 32,
                            16, 4)
    rid = np.asarray(idx.range_id)[np.asarray(idx.valid)]
    assert np.all(np.diff(rid) >= 0)


# -- shard-aligned layout invariants ------------------------------------------


def test_shards_own_whole_buckets(longtail_ds):
    """Every bucket's run fits inside its owner's valid rows, and bucket
    sizes sum to N."""
    spec = IndexSpec(family="simple", code_len=16, m=8)
    sidx = build(spec, longtail_ds.items, KEY, num_shards=4)
    sizes = np.asarray(sidx.dir_size)
    shard = np.asarray(sidx.dir_shard)
    lstart = np.asarray(sidx.dir_local_start)
    counts = np.asarray(sidx.valid).reshape(
        sidx.num_shards, sidx.rows_per_shard).sum(axis=1)
    assert (lstart + sizes <= counts[shard]).all()
    assert int(sizes.sum()) == sidx.num_items


def test_range_alignment_owns_whole_ranges(longtail_ds):
    """align="range": no norm range straddles a shard boundary."""
    spec = IndexSpec(family="simple", code_len=16, m=8)
    sidx = distributed.build_sharded(spec, longtail_ds.items, KEY, 4,
                                     align="range")
    rid = np.asarray(sidx.range_id)
    valid = np.asarray(sidx.valid)
    rows = sidx.rows_per_shard
    owners = {}
    for s in range(sidx.num_shards):
        sl = slice(s * rows, (s + 1) * rows)
        for r in np.unique(rid[sl][valid[sl]]):
            assert owners.setdefault(int(r), s) == s
    with pytest.raises(ValueError, match="align"):
        distributed.build_sharded(spec, longtail_ds.items, KEY, 4,
                                  align="diagonal")


# -- single-device parity matrix (multi-shard arm runs in the subprocess) -----


@pytest.mark.parametrize("engine", ["dense", "bucket"])
@pytest.mark.parametrize("family", ["simple", "l2_alsh", "sign_alsh"])
def test_distributed_parity_matrix(longtail_ds, family, engine):
    """Acceptance: distributed merged (vals, ids) == single-device
    ``QueryEngine.query`` on the same spec — ids bit-identical, vals to
    f32-fusion tolerance (same candidates, different XLA fusion of the
    re-rank einsum)."""
    items, queries = longtail_ds.items, longtail_ds.queries[:6]
    mesh = make_local_mesh()
    shards = mesh.shape["data"]
    spec = IndexSpec(family=family, code_len=16, m=8)
    cidx = build(spec, items, KEY)
    want_v, want_i = QueryEngine(cidx, engine=engine).query(queries, 10,
                                                            200)
    sidx = build(spec, items, KEY, num_shards=shards)
    placed = distributed.shard_index(sidx, mesh)
    eng = distributed.DistributedEngine(placed, mesh, engine=engine)
    got_v, got_i = eng.query(queries, 10, 200)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=2e-6, atol=2e-6)


def test_distributed_pallas_impl(longtail_ds):
    """Regression for the seed-era hard-coded ``impl="ref"``: the Pallas
    kernels (interpret mode on CPU) are reachable through the distributed
    query path and agree with the reference."""
    items, queries = longtail_ds.items[:500], longtail_ds.queries[:3]
    mesh = make_local_mesh()
    spec = IndexSpec(family="simple", code_len=16, m=8, impl="pallas")
    sidx = build(spec, items, KEY, num_shards=mesh.shape["data"])
    placed = distributed.shard_index(sidx, mesh)
    outs = {}
    for impl in ("pallas", "ref"):
        eng = distributed.DistributedEngine(placed, mesh, engine="bucket",
                                            impl=impl)
        assert eng.impl == impl
        outs[impl] = eng.query(queries, 5, 60)
    np.testing.assert_array_equal(np.asarray(outs["pallas"][1]),
                                  np.asarray(outs["ref"][1]))
    np.testing.assert_array_equal(np.asarray(outs["pallas"][0]),
                                  np.asarray(outs["ref"][0]))


def test_distributed_query_validation(longtail_ds):
    mesh = make_local_mesh()
    spec = IndexSpec(family="simple", code_len=16, m=8)
    sidx = build(spec, longtail_ds.items, KEY,
                 num_shards=mesh.shape["data"])
    placed = distributed.shard_index(sidx, mesh)
    eng = distributed.DistributedEngine(placed, mesh)
    n = sidx.num_items
    with pytest.raises(ValueError, match="num_probe"):
        eng.query(longtail_ds.queries[:2], 5)
    with pytest.raises(ValueError, match="num_probe"):
        eng.query(longtail_ds.queries[:2], 5, n + 1)
    with pytest.raises(ValueError, match="k="):
        eng.query(longtail_ds.queries[:2], 50, 10)
    with pytest.raises(ValueError, match="shards"):
        distributed.DistributedEngine(
            build(spec, longtail_ds.items, KEY,
                  num_shards=mesh.shape["data"] + 1), mesh)
    with pytest.raises(ValueError, match="multi-table"):
        build(IndexSpec(family="simple", code_len=16, num_tables=2),
              longtail_ds.items, KEY, num_shards=2)


# -- 8-way subprocess: the real-collective parity matrix ----------------------


SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed
    from repro.core.engine import QueryEngine
    from repro.core.index import IndexSpec, build
    from repro.data.synthetic import make_dataset

    def mesh_of(s):
        return Mesh(np.array(jax.devices()[:s]), ("data",))

    def check(spec, items, queries, k, num_probe, shard_counts):
        cidx = build(spec, items, jax.random.PRNGKey(3))
        wv, wi = QueryEngine(cidx, engine="dense").query(queries, k,
                                                         num_probe)
        for S in shard_counts:
            sidx = distributed.build_sharded(spec, items,
                                             jax.random.PRNGKey(3), S)
            placed = distributed.shard_index(sidx, mesh_of(S))
            for e in ("dense", "bucket"):
                eng = distributed.DistributedEngine(placed, mesh_of(S),
                                                    engine=e)
                gv, gi = eng.query(queries, k, num_probe)
                np.testing.assert_array_equal(np.asarray(gi),
                                              np.asarray(wi))
                np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                           rtol=2e-6, atol=2e-6)
                assert (np.asarray(gi) >= 0).all()

    ds = make_dataset("imagenet", jax.random.PRNGKey(0), n=400, d=16,
                      num_queries=4)
    for family in ("simple", "l2_alsh", "sign_alsh"):
        check(IndexSpec(family=family, code_len=16, m=8), ds.items,
              ds.queries, 10, 60, (2, 8))

    # uneven N: shards get different item counts; padded rows masked
    ds2 = make_dataset("imagenet", jax.random.PRNGKey(5), n=403, d=8,
                       num_queries=3)
    check(IndexSpec(family="simple", code_len=12, m=4), ds2.items,
          ds2.queries, 7, 37, (8,))

    # tiny: shards smaller than k must pad the merge with (-inf, -1),
    # never leak ids
    ds3 = make_dataset("imagenet", jax.random.PRNGKey(6), n=18, d=8,
                       num_queries=3)
    check(IndexSpec(family="simple", code_len=8, m=1), ds3.items,
          ds3.queries, 5, 18, (8,))

    # 2-D decomposition: queries over 'model', items over 'data'
    mesh2d = Mesh(np.array(jax.devices()).reshape(4, 2),
                  ("data", "model"))
    spec = IndexSpec(family="simple", code_len=12, m=4)
    cidx = build(spec, ds2.items, jax.random.PRNGKey(3))
    wv, wi = QueryEngine(cidx, engine="dense").query(ds2.queries[:2], 7,
                                                     37)
    sidx = distributed.build_sharded(spec, ds2.items,
                                     jax.random.PRNGKey(3), 4)
    placed = distributed.shard_index(sidx, mesh2d, axis="data")
    eng = distributed.DistributedEngine(placed, mesh2d, engine="bucket",
                                        query_axis="model")
    gv, gi = eng.query(ds2.queries[:2], 7, 37)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_sharded_parity_on_8_devices():
    """Real 8-way collectives in a subprocess (device count locks at jax
    init, so the main pytest process stays 1-device): the full family x
    engine x shard-count matrix plus uneven-shard, tiny-shard, and 2-D
    decomposition regressions."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]


# -- 4-way subprocess: the planned path at ImageNet's widths ------------------


FOUR_SHARD_PLANNED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed, planner, topk
    from repro.core.engine import QueryEngine
    from repro.core.index import IndexSpec, build
    from repro.obs import Tracker

    N, D, K, BLOCK = 1 << 14, 150, 10, 64
    kd, kn, kq, kc = jax.random.split(jax.random.PRNGKey(15), 4)
    x = jax.random.normal(kd, (N, D))
    items = x / jnp.linalg.norm(x, axis=1, keepdims=True) * jnp.exp(
        0.8 * jax.random.normal(kn, (N,)))[:, None]
    queries = jax.random.normal(kq, (16, D))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    spec = IndexSpec(family="simple", code_len=16, m=32, recall_target=0.9)
    key = jax.random.PRNGKey(3)
    out = {}

    # calibration: one block, small blocks, the mesh, all against the
    # unblocked canonical order fitted on the host
    cidx = build(spec, items, key)
    cal_q = jax.random.normal(kc, (256, D))
    _, truth = topk.exact_mips(cal_q, items, K)
    want = planner.calibrate_from_order(
        planner.canonical_order(cidx, cal_q), np.asarray(cidx.range_id),
        np.asarray(truth), num_ranges=int(cidx.table.shape[0]))
    def same(a, b):
        return all(np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f)))
                   and np.asarray(getattr(a, f)).dtype
                   == np.asarray(getattr(b, f)).dtype for f in a._fields)
    out["calibration"] = {}
    for blk in (None, 48):
        if blk:      # a block that does not divide a device's share
            planner.CAL_BLOCK = blk
        for m in (None, mesh):
            out["calibration"][f"{blk}/{'mesh' if m else 'one'}"] = same(
                want, planner.calibrate(cidx, cal_q, k=K, mesh=m))

    distributed.RERANK_BLOCK = BLOCK

    sidx = distributed.build_sharded(spec, items, key, 4, mesh=mesh)
    truth_q = np.asarray(jnp.argsort(-jnp.matmul(
        queries, items.T, precision=jax.lax.Precision.HIGHEST),
        axis=1)[:, :K])
    width = planner.resolve_budgets(sidx.calib, 0.9, k=K).num_probe
    out["width"], out["block"] = width, BLOCK
    out["arms"] = {}
    for arm in ("bucket", "dense"):
        wv, wi = QueryEngine(cidx, engine=arm).query(queries, K,
                                                     recall_target=0.9)
        t = Tracker()
        eng = distributed.DistributedEngine(sidx, mesh, engine=arm)
        gv, gi = eng.with_tracker(t).query(queries, K, recall_target=0.9)
        gi, gv = np.asarray(gi), np.asarray(gv)
        rows = np.asarray(items, np.float64)[gi]
        exact = np.einsum("qd,qkd->qk", np.asarray(queries, np.float64),
                          rows)
        scale = (np.linalg.norm(np.asarray(queries, np.float64), axis=1)
                 [:, None] * np.linalg.norm(rows, axis=2))
        owned = [t.hists[f"{distributed.OWNED_PROBES}.shard{s}"].total
                 for s in range(4)]
        out["arms"][arm] = {
            "ids_equal": bool(np.array_equal(gi, np.asarray(wi))),
            "recall": float(np.mean([len(set(a) & set(b)) / K
                                     for a, b in zip(gi, truth_q)])),
            "score_rel_err": float(np.max(np.abs(gv - exact) / scale)),
            "owned": owned,
            "untracked_equal": bool(np.array_equal(
                np.asarray(eng.query(queries, K, recall_target=0.9)[1]),
                gi)),
        }

    # no (Q, plan width, d) array in the collective: only (Q, block, d)
    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from shapes(inner)
    budgets = planner.resolve_budgets(sidx.calib, 0.9, k=K).budgets
    Q = queries.shape[0]
    for block in (BLOCK, None):
        distributed.RERANK_BLOCK = block or N
        eng = distributed.DistributedEngine(sidx, mesh, engine="bucket")
        q_codes = eng._encode(sidx.params, queries)
        jx = jax.make_jaxpr(eng._mapped(width, K, budgets))(
            q_codes, queries, sidx.params, sidx.dir_code, sidx.dir_rid,
            sidx.dir_size, sidx.dir_shard, sidx.dir_local_start, sidx.rank,
            sidx.items, sidx.codes, sidx.range_id, sidx.bucket_of,
            sidx.bucket_off, sidx.perm, sidx.valid)
        out["qpd_" + ("block" if block else "plan")] = sorted(
            {s[1] for s in shapes(jx.jaxpr)
             if len(s) == 3 and s[0] == Q and s[2] == D})
    print("FOUR_SHARD_JSON " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_shard_planned():
    """The four-shard planned path at N = 2^14, d = 150, L = 16, m = 32
    with lognormal norms, run once on four forced host devices."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", FOUR_SHARD_PLANNED],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("FOUR_SHARD_JSON ")]
    assert lines, out.stderr[-2000:]
    import json
    return json.loads(lines[-1].split(" ", 1)[1])


@pytest.mark.parametrize("arm", ["bucket", "dense"])
def test_four_shard_planned_ids_match_single_device(four_shard_planned, arm):
    """Ids bit-identical to single-device ``QueryEngine.query`` at
    recall_target 0.9, re-ranked in blocks of 64 slots, with and without
    a tracker."""
    got = four_shard_planned["arms"][arm]
    assert got["ids_equal"]
    assert got["untracked_equal"]


@pytest.mark.parametrize("arm", ["bucket", "dense"])
def test_four_shard_planned_meets_the_contract(four_shard_planned, arm):
    """recall@10 >= 0.9 against brute force at HIGHEST, and every score
    the float64 inner product within 1e-5 of |q| |x|."""
    got = four_shard_planned["arms"][arm]
    assert got["recall"] >= 0.9
    assert got["score_rel_err"] <= 1e-5


def test_four_shard_calibration_is_bit_identical(four_shard_planned):
    """Blocked and mesh-parallel calibration equal the unblocked table
    fitted from the host's canonical order, bit for bit."""
    cal = four_shard_planned["calibration"]
    assert len(cal) == 4 and all(cal.values()), cal


@pytest.mark.parametrize("arm", ["bucket", "dense"])
def test_four_shard_owned_probes_sum_to_the_plan(four_shard_planned, arm):
    """The shards' owned slots per query add up to the planned width."""
    owned = four_shard_planned["arms"][arm]["owned"]
    assert sum(owned) == pytest.approx(four_shard_planned["width"])
    assert min(owned) >= 0


def test_four_shard_rerank_never_holds_the_whole_plan(four_shard_planned):
    """No (Q, width, d) array in the collective: the candidate rows are
    gathered (Q, block, d) at a time. The guard itself sees the whole
    plan's rows when the block is the whole plan."""
    width, block = four_shard_planned["width"], four_shard_planned["block"]
    assert width > 4 * block
    assert four_shard_planned["qpd_block"] == [block]
    assert max(four_shard_planned["qpd_plan"]) >= width // 4


# -- jitted-collective cache --------------------------------------------------


def test_mapped_cache_traces_once_per_budget(longtail_ds, monkeypatch):
    """Regression pin for the PR 4 executable cache: the shard_map body
    must trace exactly once per distinct (num_probe, k[, budgets]) —
    repeat traffic on the same budget hits the cache. Counted at the
    source (the python body runs once per jit trace) AND through the obs
    layer: the tracker's hit/miss counters and ``trace_count`` gauge must
    tell the same story, so cache behavior is observable in production
    where monkeypatching is not an option (DESIGN.md §13)."""
    from repro.obs import Tracker

    mesh = make_local_mesh()
    spec = IndexSpec(family="simple", code_len=16, m=8)
    sidx = build(spec, longtail_ds.items[:400], KEY,
                 num_shards=mesh.shape["data"])
    placed = distributed.shard_index(sidx, mesh)
    tracker = Tracker()
    eng = distributed.DistributedEngine(placed, mesh, engine="bucket",
                                        tracker=tracker)

    traces = []
    real_body = distributed._shard_query

    def counting_body(*args, **kw):
        traces.append(kw.get("num_probe"))
        return real_body(*args, **kw)

    monkeypatch.setattr(distributed, "_shard_query", counting_body)
    q = longtail_ds.queries[:3]
    eng.query(q, 5, 60)
    eng.query(q, 5, 60)          # same pair: cache hit, no retrace
    eng.query(q, 5, 90)          # second pair: exactly one more trace
    assert len(traces) == 2, \
        f"expected 2 traces for 2 (num_probe, k) pairs, saw {len(traces)}"
    c = tracker.counters
    assert c.get("repro.engine.distributed.jit_cache.miss") == 2
    assert c.get("repro.engine.distributed.jit_cache.hit") == 1
    assert tracker.gauges["repro.engine.distributed.trace_count"] == 2
    eng.query(q, 5, budgets=(10, 10, 10, 10, 5, 5, 5, 5))
    eng.query(q, 5, budgets=(10, 10, 10, 10, 5, 5, 5, 5))
    assert len(traces) == 3, "planned budgets must key the cache too"
    assert c.get("repro.engine.distributed.jit_cache.miss") == 3
    assert c.get("repro.engine.distributed.jit_cache.hit") == 2
    assert tracker.gauges["repro.engine.distributed.trace_count"] == 3


# -- vocab-sharded LSH head ---------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_vocab_sharded_lsh_head_matches_unsharded(impl):
    from repro.models import lm_head
    mesh = make_local_mesh(model_parallel=1)
    # model axis of size 1: mesh ('data', 'model') => use 'model'
    d, V = 32, 1024
    key = jax.random.PRNGKey(0)
    unembed = jax.random.normal(key, (d, V)) * \
        jnp.exp(jax.random.normal(jax.random.PRNGKey(1), (1, V)))
    index = lm_head.build_vocab_index(unembed, jax.random.PRNGKey(2),
                                      code_len=64, num_ranges=16)
    hidden = jax.random.normal(jax.random.PRNGKey(3), (4, d))
    v1, i1 = lm_head.lsh_topk_tokens(index, hidden, unembed, k=5,
                                     num_probe=256)
    v2, i2 = lm_head.sharded_lsh_topk_tokens(index, hidden, unembed, mesh,
                                             k=5, num_probe_per_shard=256,
                                             impl=impl)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
