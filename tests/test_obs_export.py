"""Performance-observatory layer (DESIGN.md §14): Chrome trace export,
analytic device-cost models, and the SLO monitor."""

import itertools
import json

import jax.numpy as jnp
import pytest

from repro.obs import (RequestClass, RingBufferSink, SloMonitor, Tracker,
                       chrome_trace_events, export_chrome_trace,
                       validate_chrome_trace)
from repro.obs.cost import (BUCKET_STAGES, hash_encode_cost,
                            query_stage_costs, xla_cost)


def _fake_clock_tracker():
    """Tracker on a deterministic integer clock (1s per reading)."""
    clk = itertools.count()
    ring = RingBufferSink(capacity=4096)
    return Tracker([ring], clock=lambda: float(next(clk))), ring


# -- chrome trace export ------------------------------------------------------


def test_nested_spans_export_balanced_and_carry_attrs(tmp_path):
    t, ring = _fake_clock_tracker()
    with t.span("query"):
        with t.span("hash_encode", attrs={"flops": 8.0, "hbm_bytes": 64.0}):
            pass
        with t.span("gather"):
            pass
    path = str(tmp_path / "trace.json")
    trace = export_chrome_trace(t, path)
    stats = validate_chrome_trace(trace)
    assert stats["span_pairs"] == 3
    assert stats["num_pids"] == 1
    begins = {e["name"]: e for e in trace["traceEvents"]
              if e.get("ph") == "B"}
    assert begins["hash_encode"]["args"]["flops"] == 8.0
    assert begins["hash_encode"]["args"]["path"] == "query/hash_encode"
    assert begins["gather"]["args"]["path"] == "query/gather"
    # children begin after the parent and close before it
    evs = [(e["ph"], e["name"]) for e in trace["traceEvents"]
           if e.get("ph") in "BE"]
    assert evs[0] == ("B", "query") and evs[-1] == ("E", "query")
    # file round-trip
    assert validate_chrome_trace(json.load(open(path))) == stats


def test_multi_shard_export_stable_pids():
    """Fleet view: sorted labels -> stable pids, one process_name
    metadata event each, per-shard streams independently balanced."""
    t0, _ = _fake_clock_tracker()
    t1, _ = _fake_clock_tracker()
    with t0.span("s"):
        pass
    with t1.span("s"):
        with t1.span("inner"):
            pass
    trace = export_chrome_trace({"shard1": t1, "shard0": t0})
    stats = validate_chrome_trace(trace)
    assert stats["num_pids"] == 2
    meta = {e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
            if e.get("ph") == "M"}
    assert meta == {0: "shard0", 1: "shard1"}    # sorted-label order
    by_pid = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "B":
            by_pid.setdefault(e["pid"], []).append(e["name"])
    assert by_pid[0] == ["s"] and by_pid[1] == ["s", "inner"]


def test_export_without_ring_sink_raises():
    with pytest.raises(ValueError, match="RingBufferSink"):
        export_chrome_trace(Tracker())


def test_zero_duration_sibling_ties_stay_balanced():
    """Timestamp ties (zero-duration spans, sibling end == next begin)
    must not desync the B/E stack — the exporter replays intervals
    through an explicit stack instead of sorting events blind."""
    records = [
        {"type": "span", "name": "a", "path": "a", "depth": 0,
         "t0": 0.0, "dur_s": 1.0},
        {"type": "span", "name": "z", "path": "a/z", "depth": 1,
         "t0": 0.5, "dur_s": 0.0},                 # zero-duration child
        {"type": "span", "name": "b", "path": "b", "depth": 0,
         "t0": 1.0, "dur_s": 1.0},                 # begins at a's end
    ]
    events = chrome_trace_events(records)
    validate_chrome_trace({"traceEvents": events})


def test_validate_rejects_malformed_traces():
    common = {"pid": 0, "tid": 0, "cat": "x"}
    ok_b = {**common, "ph": "B", "name": "s", "ts": 0.0,
            "args": {"path": "s"}}
    with pytest.raises(ValueError, match="dangling"):
        validate_chrome_trace({"traceEvents": [ok_b]})
    with pytest.raises(ValueError, match="without matching B"):
        validate_chrome_trace({"traceEvents": [
            {**common, "ph": "E", "name": "s", "ts": 0.0}]})
    with pytest.raises(ValueError, match="unbalanced"):
        validate_chrome_trace({"traceEvents": [
            ok_b, {**common, "ph": "E", "name": "other", "ts": 1.0}]})
    with pytest.raises(ValueError, match="monotonic"):
        validate_chrome_trace({"traceEvents": [
            {**ok_b, "ts": 5.0},
            {**common, "ph": "E", "name": "s", "ts": 1.0}]})
    with pytest.raises(ValueError, match="args.path"):
        validate_chrome_trace({"traceEvents": [
            {**common, "ph": "B", "name": "s", "ts": 0.0}]})
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})


# -- device-cost attribution --------------------------------------------------


def test_query_stage_costs_cover_all_stages():
    shape = {"q": 32, "n": 30_000, "d": 32, "code_len": 16,
             "num_buckets": 27_800, "probe_width": 917.0, "k": 10}
    costs = query_stage_costs(shape)
    assert set(costs) == set(BUCKET_STAGES)
    for name, c in costs.items():
        assert c["flops"] > 0 and c["hbm_bytes"] > 0, name
    # re_rank dominates hash_encode at this probe width (sanity ordering)
    assert costs["repro.engine.re_rank"]["flops"] > \
        costs["repro.engine.hash_encode"]["flops"]


def test_kernel_dispatch_charges_cost_counters():
    from repro.kernels import ops

    t = Tracker()
    ops.set_dispatch_tracker(t)
    try:
        q, d, L = 4, 8, 32
        codes = ops.hash_encode(jnp.ones((q, d)), jnp.ones((d, L)))
        ops.hamming_scan(codes, codes)
    finally:
        ops.set_dispatch_tracker(None)
    pred = hash_encode_cost(q, d, L)
    assert t.counters["repro.kernels.cost.hash_encode.flops"] == \
        pred["flops"]
    assert t.counters["repro.kernels.cost.hash_encode.hbm_bytes"] == \
        pred["hbm_bytes"]
    assert t.counters["repro.kernels.cost.hamming_scan.flops"] == \
        q * q * 1                     # W = 1 packed word at L=32


def test_xla_cost_cross_checks_analytic_hash_encode():
    """The analytic encode model must sit within a small factor of XLA's
    own compiled cost estimate (the MAC count dominates both)."""
    q, d, L = 16, 32, 64
    got = xla_cost(lambda x, A: jnp.sign(x @ A),
                   jnp.ones((q, d)), jnp.ones((d, L)))
    if got is None:
        pytest.skip("backend reports no cost_analysis")
    pred = hash_encode_cost(q, d, L)["flops"]
    assert 0.2 * pred <= got["flops"] <= 5.0 * pred


# -- SLO monitor --------------------------------------------------------------


def test_request_class_validation():
    with pytest.raises(ValueError, match="slo_p50_s"):
        RequestClass(name="a", recall_target=0.9, k=10,
                     slo_p50_s=0.1, slo_p99_s=0.05)
    with pytest.raises(ValueError, match="weight"):
        RequestClass(name="a", recall_target=0.9, k=10,
                     slo_p50_s=0.01, slo_p99_s=0.05, weight=0.0)


def test_slo_monitor_burn_rate_and_breach():
    t = Tracker()
    cls = RequestClass(name="standard", recall_target=0.95, k=10,
                       slo_p50_s=0.01, slo_p99_s=0.05)
    mon = SloMonitor(t, [cls], tolerance=0.0, budget_quantile=0.99,
                     min_samples=10)
    for _ in range(98):
        mon.record("standard", 0.005)
    mon.record("standard", 0.2)
    mon.record("standard", 0.2)           # 2/100 over the p99 bound
    # burn: (2/100) / (1 - 0.99) = 2x the error budget
    assert mon.burn_rate("standard") == pytest.approx(2.0)
    v = mon.evaluate()["standard"]
    assert v["n"] == 100 and v["over_budget"] == 2
    assert v["evaluated"] is True
    assert v["p50_s"] == pytest.approx(0.005, rel=0.05)
    assert v["breached"] is True          # p99 ~0.2 >> 0.05 target
    assert t.counters["repro.slo.breach"] == 1
    ev, = [e for e in t.events if e["name"] == "repro.slo.breach"]
    assert ev["request_class"] == "standard"
    assert ev["burn_rate"] == pytest.approx(2.0)
    assert t.gauges["repro.slo.burn_rate.standard"] == pytest.approx(2.0)
    # latency series lives in a mergeable tracker histogram
    assert t.hists["repro.slo.latency.standard"].count == 100


def test_slo_monitor_within_slo_never_breaches():
    t = Tracker()
    cls = RequestClass(name="a", recall_target=0.9, k=10,
                       slo_p50_s=0.01, slo_p99_s=0.05)
    mon = SloMonitor(t, [cls], min_samples=5)
    for _ in range(50):
        mon.record("a", 0.004)
    v = mon.evaluate()["a"]
    assert v["breached"] is False and v["burn_rate"] == 0.0
    assert "repro.slo.breach" not in t.counters


def test_slo_monitor_min_samples_gate():
    """Few samples: reported but never breach-counted (quantiles of a
    handful of requests are noise, the gate must not flap)."""
    t = Tracker()
    cls = RequestClass(name="a", recall_target=0.9, k=10,
                       slo_p50_s=0.001, slo_p99_s=0.002)
    mon = SloMonitor(t, [cls], min_samples=20)
    for _ in range(5):
        mon.record("a", 1.0)              # wildly over SLO
    v = mon.evaluate()["a"]
    assert v["evaluated"] is False and v["breached"] is False
    assert mon.burn_rate("a") > 1.0       # budget accounting still live


def test_slo_monitor_validation():
    t = Tracker()
    c = RequestClass(name="a", recall_target=0.9, k=10,
                     slo_p50_s=0.01, slo_p99_s=0.05)
    with pytest.raises(ValueError, match="duplicate"):
        SloMonitor(t, [c, c])
    with pytest.raises(ValueError, match="budget_quantile"):
        SloMonitor(t, [c], budget_quantile=1.0)
    mon = SloMonitor(t, [c])
    with pytest.raises(KeyError, match="unknown request class"):
        mon.record("nope", 0.01)
