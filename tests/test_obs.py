"""Observability layer (DESIGN.md §13): tracker, histograms, spans,
sinks, recall audits — and the parity contract that attaching any of it
never changes query results.
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner
from repro.core.engine import QueryEngine, engine_for
from repro.core.index import IndexSpec, build
from repro.obs import (JsonlSink, LogHistogram, RecallAuditor,
                       RingBufferSink, StdoutTableSink, Tracker,
                       default_tracker, format_table, read_jsonl,
                       resolve_tracker, set_default_tracker, span_or_null)
from repro.obs.trace import _NullSpan

KEY = jax.random.PRNGKey(5)


# -- histogram ----------------------------------------------------------------


def test_histogram_quantiles_vs_numpy_lognormal():
    """Fixed-bucket log histogram quantiles track numpy within the bucket
    geometry's error bound (~3.4% + estimation slack) on a lognormal
    sample — the distribution span durations actually follow."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-7.0, sigma=1.0, size=20_000)
    h = LogHistogram()
    for s in samples:
        h.record(s)
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        ref = float(np.quantile(samples, q))
        assert est == pytest.approx(ref, rel=0.08), f"q={q}"
    assert h.count == samples.size
    assert h.mean == pytest.approx(float(samples.mean()), rel=1e-6)
    assert h.min == pytest.approx(float(samples.min()))
    assert h.max == pytest.approx(float(samples.max()))


def test_histogram_edge_cases():
    h = LogHistogram()
    assert h.quantile(0.5) == 0.0          # empty
    h.record(0.0)                          # underflow bucket
    h.record(-1.0)
    assert h.counts[0] == 2
    h2 = LogHistogram()
    h2.record(42.0)                        # single sample: clamped exact
    assert h2.quantile(0.5) == pytest.approx(42.0)
    assert h2.quantile(0.99) == pytest.approx(42.0)
    h2.record(1e20)                        # beyond hi: top bucket, max exact
    assert h2.max == 1e20
    with pytest.raises(ValueError):
        h2.quantile(1.5)
    with pytest.raises(ValueError):
        LogHistogram(lo=0.0)


def test_histogram_summary_keys():
    h = LogHistogram()
    h.record(1.0)
    s = h.summary()
    assert set(s) == {"count", "mean", "min", "max", "p50", "p90", "p99"}


def test_histogram_merge_quantile_error_stays_bounded():
    """Shard rollup contract: merging per-shard histograms is bucket-exact,
    so quantiles of the merged view track numpy over the CONCATENATED
    sample within the same geometric bound as a single histogram."""
    rng = np.random.default_rng(3)
    a = rng.lognormal(mean=-7.0, sigma=1.0, size=8_000)
    b = rng.lognormal(mean=-5.5, sigma=0.7, size=4_000)   # shifted shard
    ha, hb = LogHistogram(), LogHistogram()
    for s in a:
        ha.record(s)
    for s in b:
        hb.record(s)
    merged = ha.merge(hb)
    assert merged is ha                      # in-place, returns self
    both = np.concatenate([a, b])
    assert merged.count == both.size
    assert merged.mean == pytest.approx(float(both.mean()), rel=1e-6)
    assert merged.min == pytest.approx(float(both.min()))
    assert merged.max == pytest.approx(float(both.max()))
    for q in (0.5, 0.9, 0.99):
        assert merged.quantile(q) == pytest.approx(
            float(np.quantile(both, q)), rel=0.08), f"q={q}"


def test_histogram_merge_mismatched_geometry_raises():
    h = LogHistogram()
    with pytest.raises(ValueError, match="geometry"):
        h.merge(LogHistogram(growth=1.5))
    with pytest.raises(ValueError, match="geometry"):
        h.merge(LogHistogram(lo=1e-6))
    with pytest.raises(TypeError):
        h.merge([1.0, 2.0])


# -- tracker surface ----------------------------------------------------------


def test_tracker_counter_gauge_observe_event():
    t = Tracker()
    t.count("c")
    t.count("c", 4)
    t.gauge("g", 2.5)
    t.gauge("g", 3.5)                      # last write wins
    t.observe("h", 0.1)
    t.event("e", kind="x", n=1)
    snap = t.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 3.5
    assert snap["hists"]["h"]["count"] == 1
    assert snap["num_events"] == 1
    assert t.events[0] == {"name": "e", "kind": "x", "n": 1}


def test_records_carry_monotonic_t():
    clock_vals = iter([0.0, 1.0, 2.0, 3.0])
    ring = RingBufferSink()
    t = Tracker([ring], clock=lambda: next(clock_vals))
    t.count("a")
    t.count("a")
    ts = [r["t"] for r in ring.records]
    assert ts == [1.0, 2.0]


def test_tracker_merge_folds_aggregates():
    """Per-shard -> fleet rollup: counters sum, gauges last-write (other
    wins), histograms merge bucket-exact (including names only one side
    has), events append; sinks stay local."""
    ring = RingBufferSink()
    fleet = Tracker([ring])
    fleet.count("q", 2)
    fleet.gauge("g", 1.0)
    fleet.observe("lat", 0.010)
    shard = Tracker()
    shard.count("q", 3)
    shard.count("only_shard")
    shard.gauge("g", 9.0)
    shard.observe("lat", 0.020)
    shard.observe("only_shard_lat", 0.5)
    shard.event("repro.streaming.repartition", range_id=2)
    n_sink_records = ring.total
    out = fleet.merge(shard)
    assert out is fleet
    assert fleet.counters["q"] == 5
    assert fleet.counters["only_shard"] == 1
    assert fleet.gauges["g"] == 9.0                    # other wins
    assert fleet.hists["lat"].count == 2
    assert fleet.hists["only_shard_lat"].count == 1
    # the adopted histogram shares the shard's exact geometry
    assert fleet.hists["only_shard_lat"].num_buckets == \
        shard.hists["only_shard_lat"].num_buckets
    assert fleet.events[-1]["name"] == "repro.streaming.repartition"
    assert ring.total == n_sink_records                # merge emits nothing
    with pytest.raises(TypeError):
        fleet.merge({"counters": {}})


# -- spans --------------------------------------------------------------------


def test_span_nesting_paths_and_histograms():
    ring = RingBufferSink()
    t = Tracker([ring])
    with t.span("outer"):
        with t.span("inner") as sp:
            sp.sync(jnp.ones((4,)) * 2)
    recs = ring.query(type="span")
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["path"] == "outer/inner" and inner["depth"] == 1
    assert outer["path"] == "outer" and outer["depth"] == 0
    assert t.hists["inner"].count == 1
    assert t.hists["outer"].count == 1
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0


def test_span_sync_returns_value_unchanged():
    t = Tracker()
    x = jnp.arange(8)
    with t.span("s") as sp:
        y = sp.sync(x)
    assert y is x
    # null-span path (tracker=None) must behave identically
    with span_or_null(None, "s") as sp:
        z = sp.sync(x)
    assert z is x
    # no tracker: a fresh annotation-only span per call, never a record
    sp = span_or_null(None, "anything")
    assert isinstance(sp, _NullSpan)
    assert span_or_null(None, "anything") is not sp


def test_span_exception_drops_record_and_unwinds():
    ring = RingBufferSink()
    t = Tracker([ring])
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert ring.query(type="span") == []
    assert "boom" not in t.hists
    assert t.tracer._stack == []           # stack unwound
    with t.span("after"):                  # tracer still usable
        pass
    assert t.hists["after"].count == 1


def test_span_exception_mid_sync_drops_record(monkeypatch):
    """A sync that fails inside ``block_until_ready`` is a failed span:
    nothing recorded (the duration would measure time-to-error), the
    exception propagates, and the tracer stack unwinds."""
    import jax as jax_mod

    def boom(x):
        raise RuntimeError("device died")

    ring = RingBufferSink()
    t = Tracker([ring])
    monkeypatch.setattr(jax_mod, "block_until_ready", boom)
    with pytest.raises(RuntimeError, match="device died"):
        with t.span("stage") as sp:
            sp.sync(jnp.ones((2,)))
    assert ring.query(type="span") == []
    assert "stage" not in t.hists
    assert t.tracer._stack == []
    monkeypatch.undo()
    with t.span("after") as sp:            # tracer still usable
        sp.sync(jnp.ones((2,)))
    assert t.hists["after"].count == 1


def test_span_attrs_land_in_record():
    ring = RingBufferSink()
    t = Tracker([ring])
    with t.span("stage", attrs={"flops": 10.0}) as sp:
        sp.set_attrs(hbm_bytes=4.0)
    rec, = ring.query(type="span")
    assert rec["attrs"] == {"flops": 10.0, "hbm_bytes": 4.0}
    assert rec["t0"] >= 0.0 and rec["dur_s"] >= 0.0
    # spans without attrs carry no attrs key (record stays lean)
    with t.span("bare"):
        pass
    assert "attrs" not in ring.query(type="span", name="bare")[0]


# -- sinks --------------------------------------------------------------------


def test_ring_buffer_overflow_keeps_newest():
    ring = RingBufferSink(capacity=3)
    for i in range(10):
        ring.emit({"type": "counter", "name": f"n{i}"})
    assert ring.total == 10
    assert ring.dropped == 7
    assert [r["name"] for r in ring.records] == ["n7", "n8", "n9"]
    with pytest.raises(ValueError):
        RingBufferSink(capacity=0)


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    t = Tracker([JsonlSink(path)])
    t.count("c", 2)
    t.gauge("g", 1.5)
    t.observe("h", np.float32(0.25))       # numpy scalars must serialize
    t.event("e", ids=np.arange(3), note="x")
    with t.span("s") as sp:
        sp.sync(jnp.zeros((2,)))
    t.close()
    recs = read_jsonl(path)
    assert [r["type"] for r in recs] == \
        ["counter", "gauge", "observe", "event", "span"]
    assert recs[0]["total"] == 2
    assert recs[2]["value"] == 0.25
    assert recs[3]["fields"]["ids"] == [0, 1, 2]
    assert recs[4]["name"] == "s" and recs[4]["dur_s"] >= 0.0
    json.dumps(recs)                       # fully json-clean


def test_jsonl_rotation_keeps_last_file_and_round_trips(tmp_path):
    """Size-capped JsonlSink: the live file rotates to ``path + '.1'``
    when it would exceed max_bytes (exactly one trailing file kept), no
    record is lost across the last rotation, and both files round-trip
    through read_jsonl."""
    import os

    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path, max_bytes=512)
    t = Tracker([sink])
    for i in range(200):
        t.count("c", 1)
    t.close()
    assert sink.total == 200
    assert sink.rotations >= 1
    live = read_jsonl(path)
    rolled = read_jsonl(path + ".1")
    assert os.path.getsize(path) <= 512
    assert os.path.getsize(path + ".1") <= 512
    # the two files hold the newest records, contiguous and in order
    tail = rolled + live
    assert [r["total"] for r in tail] == \
        list(range(200 - len(tail) + 1, 201))
    with pytest.raises(ValueError):
        JsonlSink(str(tmp_path / "x.jsonl"), max_bytes=0)


def test_jsonl_uncapped_never_rotates(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    t = Tracker([sink])
    for _ in range(100):
        t.count("c")
    t.close()
    assert sink.rotations == 0
    assert len(read_jsonl(path)) == 100


def test_format_table_surfaces_sink_drops_and_counts():
    """Satellite: silent ring-buffer overflow must be visible in the
    rollup — snapshot carries per-sink records/dropped and format_table
    renders them alongside histogram sample counts."""
    ring = RingBufferSink(capacity=4)
    t = Tracker([ring])
    for _ in range(10):
        t.observe("lat", 0.01)
    snap = t.snapshot()
    assert snap["sinks"] == [
        {"sink": "RingBufferSink", "records": 10, "dropped": 6}]
    table = format_table(snap)
    assert "sinks" in table and "dropped" in table
    assert "RingBufferSink" in table
    lines = [ln for ln in table.splitlines() if "RingBufferSink" in ln]
    assert "10" in lines[0] and "6" in lines[0]
    # histogram sample count (n=) still rendered
    hist_lines = [ln for ln in table.splitlines() if ln.strip()
                  .startswith("lat")]
    assert "10" in hist_lines[0]


def test_stdout_table_and_live_events(capsys):
    t = Tracker([StdoutTableSink(live=True)])
    t.event("repro.streaming.compaction", folded=7)
    t.count("repro.engine.queries", 3)
    t.observe("repro.engine.probe_width", 128.0)
    out = capsys.readouterr().out
    assert "repro.streaming.compaction" in out and "folded=7" in out
    table = format_table(t.snapshot())
    assert "repro.engine.queries" in table
    assert "p99" in table
    assert format_table({}) == "(no metrics recorded)"


# -- ambient default tracker --------------------------------------------------


def test_ambient_default_tracker_resolution():
    t = Tracker()
    prev = set_default_tracker(t)
    try:
        assert default_tracker() is t
        assert resolve_tracker(None) is t
        other = Tracker()
        assert resolve_tracker(other) is other   # explicit wins
    finally:
        set_default_tracker(prev)
    assert resolve_tracker(None) is prev


def test_engine_for_sees_ambient_tracker(longtail_ds):
    """The one-slot engine memo must not pin a pre-tracker engine after
    an ambient tracker is installed (the memo keys on the resolved
    tracker identity)."""
    spec = IndexSpec(family="simple", code_len=16, m=8)
    cidx = build(spec, longtail_ds.items[:500], KEY)
    bare = engine_for(cidx, engine="bucket")
    assert bare.tracker is None
    t = Tracker()
    prev = set_default_tracker(t)
    try:
        eng = engine_for(cidx, engine="bucket")
        assert eng.tracker is t
    finally:
        set_default_tracker(prev)


def test_indexspec_hash_ignores_tracker(longtail_ds):
    t = Tracker()
    a = IndexSpec(family="simple", code_len=16, m=8)
    b = IndexSpec(family="simple", code_len=16, m=8, tracker=t)
    assert a == b and hash(a) == hash(b)
    assert "tracker" not in repr(b)


# -- parity: instrumentation must not change results --------------------------


@pytest.fixture(scope="module")
def calibrated_index():
    from repro.data.synthetic import make_dataset
    ds = make_dataset("imagenet", jax.random.PRNGKey(0), n=2000, d=24,
                      num_queries=48)
    spec = IndexSpec(family="simple", code_len=16, m=8,
                     charge_index_bits=False)
    cidx = build(spec, ds.items, KEY, calibration_queries=ds.queries[:32],
                 calibration_k=10)
    return cidx, ds.queries[32:]


@pytest.mark.parametrize("engine", ["bucket", "dense"])
def test_instrumented_query_ids_bit_identical(calibrated_index, engine):
    """The conformance contract: a tracker observes, never participates —
    query ids and values with full instrumentation are bit-identical to
    the bare engine, for both probe modes."""
    cidx, queries = calibrated_index
    bare = QueryEngine(cidx, engine=engine)
    t = Tracker([RingBufferSink()])
    inst = QueryEngine(cidx, engine=engine, tracker=t)
    for kw in ({"num_probe": 300}, {"recall_target": 0.9}):
        v0, i0 = bare.query(queries, 10, **kw)
        v1, i1 = inst.query(queries, 10, **kw)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    # and the instrumentation actually fired: every hot-path stage span
    stages = {"repro.engine.hash_encode", "repro.engine.re_rank",
              "repro.engine.top_k", "repro.engine.query"}
    stages.add("repro.engine.directory_match" if engine == "bucket"
               else "repro.engine.dense_match")
    assert stages <= set(t.hists)
    assert t.counters["repro.engine.queries"] == 2 * queries.shape[0]


def _count_syncs(monkeypatch):
    import jax as jax_mod
    calls = []
    real = jax_mod.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax_mod, "block_until_ready", counting)
    return calls


@pytest.mark.parametrize("engine", ["bucket", "dense"])
def test_untracked_spans_never_sync(calibrated_index, engine, monkeypatch):
    """With no tracker a span is its profiler annotation and nothing else:
    a whole query, every stage span included, makes no
    ``block_until_ready`` call; attaching a tracker makes one per synced
    stage."""
    cidx, queries = calibrated_index
    bare = QueryEngine(cidx, engine=engine)
    inst = QueryEngine(cidx, engine=engine, tracker=Tracker())
    bare.query(queries, 10, recall_target=0.9)
    calls = _count_syncs(monkeypatch)
    with span_or_null(None, "repro.engine.x") as sp:
        sp.sync(jnp.ones((2,)))
    bare.query(queries, 10, recall_target=0.9)
    assert calls == []
    inst.query(queries, 10, recall_target=0.9)
    # hash_encode, match, planned_take, select, re_rank, top_k
    assert len(calls) == 6


@pytest.mark.parametrize("engine", ["bucket", "dense"])
def test_query_ids_bit_identical_under_the_profiler(calibrated_index,
                                                    engine, tmp_path):
    """Tracing on (the profiler running, a tracker attached or not) leaves
    ids and values bit-identical to a bare query."""
    cidx, queries = calibrated_index
    bare = QueryEngine(cidx, engine=engine)
    inst = QueryEngine(cidx, engine=engine, tracker=Tracker())
    v0, i0 = bare.query(queries, 10, recall_target=0.9)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = [e.query(queries, 10, recall_target=0.9)
               for e in (bare, inst)]
    finally:
        jax.profiler.stop_trace()
    for v1, i1 in got:
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_plan_span_inside_query_span(calibrated_index):
    """Planning is timed inside the query: ``repro.engine.plan`` is a
    child of ``repro.engine.query`` and only where a recall target is
    resolved; ``planned_take`` is a child of the select stage."""
    cidx, queries = calibrated_index
    ring = RingBufferSink()
    t = Tracker([ring])
    for engine, select in (("bucket", "segmented_gather"),
                           ("dense", "dense_select")):
        eng = QueryEngine(cidx, engine=engine, tracker=t)
        eng.query(queries, 10, recall_target=0.9)
        paths = {r["path"] for r in ring.query(type="span")}
        assert "repro.engine.query/repro.engine.plan" in paths
        assert (f"repro.engine.query/repro.engine.{select}/"
                "repro.engine.planned_take") in paths
    n = t.hists["repro.engine.plan"].count
    eng.query(queries, 10, 300)
    assert t.hists["repro.engine.plan"].count == n == 2


def test_instrumented_distributed_bit_identical(calibrated_index):
    from repro.core import distributed
    from repro.launch.mesh import make_local_mesh

    cidx, queries = calibrated_index
    spec = IndexSpec(family="simple", code_len=16, m=8,
                     charge_index_bits=False)
    mesh = make_local_mesh()
    sidx = build(spec, cidx.items, KEY, num_shards=mesh.shape["data"])
    placed = distributed.shard_index(sidx, mesh)
    bare = distributed.DistributedEngine(placed, mesh, engine="bucket")
    t = Tracker()
    inst = distributed.DistributedEngine(placed, mesh, engine="bucket",
                                         tracker=t)
    v0, i0 = bare.query(queries, 10, 200)
    v1, i1 = inst.query(queries, 10, 200)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    assert "repro.engine.distributed.collective" in t.hists
    # one probe_width sample per served batch
    assert t.hists["repro.engine.probe_width"].count == 1
    assert t.hists["repro.engine.probe_width"].max == 200


def test_adaptive_query_telemetry(calibrated_index):
    cidx, queries = calibrated_index
    t = Tracker()
    eng = QueryEngine(cidx, engine="bucket", tracker=t)
    pl = planner.plan(cidx.calib, 0.9)
    bare_eng = QueryEngine(cidx, engine="bucket")
    v0, i0, u0 = planner.adaptive_query(bare_eng, queries, 10,
                                        budgets=pl.budgets)
    v1, i1, u1 = planner.adaptive_query(eng, queries, 10,
                                        budgets=pl.budgets)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(u0), np.asarray(u1))
    h = t.hists["repro.planner.probes_used"]
    assert h.count == queries.shape[0]
    assert h.max <= t.gauges["repro.planner.planned_width"]
    assert t.hists["repro.planner.adaptive_savings"].min >= 0.0
    assert t.counters["repro.planner.adaptive_queries"] == queries.shape[0]


def test_per_range_probe_budget_telemetry(calibrated_index):
    cidx, queries = calibrated_index
    t = Tracker()
    eng = QueryEngine(cidx, engine="bucket", tracker=t)
    eng.query(queries, 10, recall_target=0.9)
    per_range = [n for n in t.hists
                 if n.startswith("repro.engine.probes_used.range")]
    assert per_range, "per-range budget histograms missing"
    # every range histogram saw one sample per query batch
    assert all(t.hists[n].count == 1 for n in per_range)


# -- recall auditor -----------------------------------------------------------


def test_auditor_sampling_is_deterministic_fraction():
    aud = RecallAuditor(Tracker(), sample_fraction=0.25)
    decisions = []
    for _ in range(40):
        decisions.append(aud.should_audit())
        aud.batches_seen += 1
    assert sum(decisions) == 10 + 1        # every 4th + forced first batch
    assert decisions[0] is True
    with pytest.raises(ValueError):
        RecallAuditor(Tracker(), sample_fraction=1.5)
    off = RecallAuditor(Tracker(), sample_fraction=0.0)
    assert off.should_audit() is False


def test_auditor_measures_recall_and_shortfall():
    rng = np.random.default_rng(1)
    items = rng.normal(size=(200, 8)).astype(np.float32)
    queries = rng.normal(size=(6, 8)).astype(np.float32)
    scores = queries @ items.T
    truth = np.argsort(-scores, axis=1)[:, :5]
    t = Tracker()
    aud = RecallAuditor(t, recall_target=0.95, sample_fraction=1.0,
                        tolerance=0.02)
    assert aud.audit(queries, truth, items, k=5) == pytest.approx(1.0)
    assert "repro.planner.audit.shortfall" not in t.counters
    junk = np.full_like(truth, 199)        # ~0 recall -> shortfall
    achieved = aud.audit(queries, junk, items, k=5)
    assert achieved < 0.5
    assert t.counters["repro.planner.audit.shortfall"] == 1
    evs = [e for e in t.events if e["name"] == "repro.planner.audit"]
    assert len(evs) == 2
    assert evs[1]["shortfall"] is True
    assert t.gauges["repro.planner.audit.achieved_recall.last"] == \
        pytest.approx(achieved)


def test_auditor_maps_storage_rows_to_global_ids():
    """Streaming surfaces serve global ids while ground truth is
    brute-forced over live rows — item_ids must bridge the id spaces."""
    rng = np.random.default_rng(2)
    items = rng.normal(size=(50, 4)).astype(np.float32)
    queries = rng.normal(size=(3, 4)).astype(np.float32)
    gids = np.arange(50) * 7 + 3           # arbitrary global ids
    truth_rows = np.argsort(-(queries @ items.T), axis=1)[:, :4]
    aud = RecallAuditor(Tracker(), sample_fraction=1.0)
    assert aud.audit(queries, gids[truth_rows], items, item_ids=gids,
                     k=4) == pytest.approx(1.0)


# -- streaming events through the tracker -------------------------------------


def test_streaming_events_mirrored_to_tracker(longtail_ds):
    """Satellite fix: MutableIndex events used to pile up silently in
    ``.events`` with no export path. Every event must now also reach the
    attached tracker (list kept, parity between the two), including the
    typed ``repartition`` event."""
    from repro import streaming

    t = Tracker()
    mi = streaming.build(longtail_ds.items[:600], jax.random.PRNGKey(1),
                         16, 4, capacity=64, max_tombstones=32, tracker=t)
    rng = np.random.default_rng(0)
    norms = np.linalg.norm(np.asarray(longtail_ds.items[:600]), axis=1)
    v = rng.normal(size=(8, longtail_ds.items.shape[1]))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    mi.insert(jnp.asarray(v * (2.0 * norms.max()), jnp.float32))  # breach
    mi.delete(np.flatnonzero(mi._live)[:4].tolist())
    mi.query(longtail_ds.queries[:4], 5, 50)
    mirrored = [e for e in t.events
                if e["name"].startswith("repro.streaming.")]
    assert len(mirrored) == len(mi.events)
    for ev, rec in zip(mi.events, mirrored):
        assert rec["name"] == f"repro.streaming.{ev['kind']}"
        assert {k: v for k, v in rec.items() if k != "name"} == \
            {k: v for k, v in ev.items() if k != "kind"}
    kinds = {e["kind"] for e in mi.events}
    assert "repartition" in kinds
    assert t.counters["repro.streaming.inserts"] == 8
    assert t.counters["repro.streaming.deletes"] == 4
    assert t.counters["repro.streaming.queries"] == 4
    assert "repro.streaming.query" in t.hists
    # stats() is the drift-reporting moment: quantile gauges + snapshot
    mi.stats()
    assert any(n.startswith("repro.streaming.drift.count.")
               for n in t.gauges)
    assert any(e["name"] == "repro.streaming.drift.snapshot"
               for e in t.events)


def test_streaming_query_parity_with_tracker(longtail_ds):
    from repro import streaming

    kw = dict(capacity=64, max_tombstones=32)
    mi0 = streaming.build(longtail_ds.items[:500], jax.random.PRNGKey(1),
                          16, 4, **kw)
    mi1 = streaming.build(longtail_ds.items[:500], jax.random.PRNGKey(1),
                          16, 4, tracker=Tracker(), **kw)
    q = longtail_ds.queries[:6]
    v0, i0 = mi0.query(q, 5, 80)
    v1, i1 = mi1.query(q, 5, 80)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


# -- kernel dispatch counters -------------------------------------------------


def test_kernel_dispatch_counters():
    from repro.kernels import ops

    t = Tracker()
    ops.set_dispatch_tracker(t)
    try:
        x = jnp.ones((4, 8))
        A = jnp.ones((8, 32))
        ops.hash_encode(x, A)
        ops.hash_encode(x, A, impl="ref")
        expect = "pallas" if jax.default_backend() == "tpu" else "ref"
        assert t.counters[
            f"repro.kernels.dispatch.hash_encode.{expect}"] >= 1
        assert t.counters["repro.kernels.dispatch.hash_encode.ref"] >= 1
    finally:
        ops.set_dispatch_tracker(None)
    ops.hash_encode(jnp.ones((2, 8)), jnp.ones((8, 32)))   # no tracker: ok
