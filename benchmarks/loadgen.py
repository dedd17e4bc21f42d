"""Open-loop SLO load harness (DESIGN.md §14) — writes BENCH_<n>.json.

Replays a seeded heavy-tailed (Pareto inter-arrival) **open-loop** request
stream against a :class:`QueryEngine` serving the recall contract: arrival
times are drawn up front, independent of completions — when the engine
falls behind, requests queue and latency grows, exactly what a
closed-loop (send-next-after-reply) driver cannot see. The driver serves
requests in arrival order on one engine and accounts
``completion_i = max(arrival_i, completion_{i-1}) + service_i`` with
*measured* service times, so reported latency includes queueing delay
without needing wall-clock sleeps (CI-friendly, deterministic arrivals).

Traffic is a weighted mix of request classes — ``(recall_target, k)``
pairs à la DESIGN.md §12's budget-class quantization:

  * ``interactive`` — recall 0.90, k=10, bulk of traffic
  * ``standard``    — recall 0.95, k=10
  * ``thorough``    — recall 0.975, k=20, tail of traffic

Per class an :class:`SloMonitor` tracks p50/p99 against SLOs calibrated
from the warmup service time (portable across CI machines), plus
error-budget burn; a per-class :class:`RecallAuditor` brute-forces
sampled ground-truth audits so the latency numbers are tied to an
*enforced* recall contract. The tracker's span records are exported to a
Chrome trace (validated) and the JSONL sink runs with
``max_bytes`` rotation — the full §14 surface under one sustained load.

``REPRO_BENCH_SMOKE=1`` shrinks to CI-canary size (temp-dir JSON).
"""

import json
import os
import tempfile

import jax
import numpy as np

from benchmarks.common import bench_json_path, bench_smoke, emit, fmt
from repro.core.engine import QueryEngine
from repro.core.index import IndexSpec, build
from repro.data.synthetic import make_dataset
from repro.obs import (JsonlSink, RecallAuditor, RequestClass,
                       RingBufferSink, SloMonitor, Tracker,
                       export_chrome_trace, format_table, read_jsonl,
                       validate_chrome_trace)

ROOT = os.path.join(os.path.dirname(__file__), "..")

if bench_smoke():                    # CI canary: toy sizes
    N, D, Q_CAL, L, M = 3_000, 24, 128, 16, 16
    QB, REQUESTS, WARMUP = 8, 60, 4
    JSONL_MAX_BYTES = 1 << 14        # small cap: rotation must trigger
else:
    N, D, Q_CAL, L, M = 30_000, 32, 256, 16, 32
    QB, REQUESTS, WARMUP = 16, 240, 6
    JSONL_MAX_BYTES = 1 << 20

# (name, recall_target, k, traffic weight)
MIX = (("interactive", 0.90, 10, 6.0),
       ("standard", 0.95, 10, 3.0),
       ("thorough", 0.975, 20, 1.0))
UTILIZATION = 0.7        # offered load vs measured serving capacity
PARETO_ALPHA = 2.5       # heavy-tailed inter-arrivals, finite mean
SEED = 0


def build_serving_stack(tracker):
    ds = make_dataset("imagenet", jax.random.PRNGKey(0), n=N, d=D,
                      num_queries=Q_CAL + 256)
    cal_q, eval_q = ds.queries[:Q_CAL], ds.queries[Q_CAL:]
    spec = IndexSpec(family="simple", code_len=L, m=M,
                     charge_index_bits=False, tracker=tracker)
    cidx = build(spec, ds.items, jax.random.PRNGKey(7),
                 calibration_queries=cal_q,
                 calibration_k=max(k for _, _, k, _ in MIX))
    eng = QueryEngine(cidx, engine="bucket", tracker=tracker)
    return cidx, eng, np.asarray(eval_q)


def measure_service(eng, queries, rng):
    """Warmup + per-class mean service time (one QB-query batch)."""
    import time
    service = {}
    for name, target, k, _ in MIX:
        times = []
        for _ in range(WARMUP):
            qb = queries[rng.choice(queries.shape[0], QB, replace=False)]
            t0 = time.perf_counter()
            # repro-lint: allow[R6] SLO harness times raw service, spanless
            jax.block_until_ready(eng.query(jax.numpy.asarray(qb), k,
                                            recall_target=target))
            times.append(time.perf_counter() - t0)
        # drop the first (trace/compile) sample, mean the rest
        service[name] = float(np.mean(times[1:]))
    return service


def replay(eng, items, queries, monitor, auditors, rng):
    """Open-loop replay: seeded Pareto arrivals, FIFO single-server
    queueing with measured service times. Returns per-class tallies."""
    import time
    names = [c[0] for c in MIX]
    weights = np.array([c[3] for c in MIX])
    classes = {c[0]: c for c in MIX}
    mean_service = float(np.dot(
        [monitor.classes[n].slo_p50_s / 3.0 for n in names],
        weights / weights.sum()))
    # offered rate = UTILIZATION / mean service; Pareto mean = scale/(a-1)
    mean_inter = mean_service / UTILIZATION
    inter = rng.pareto(PARETO_ALPHA, size=REQUESTS) \
        * mean_inter * (PARETO_ALPHA - 1.0)
    arrivals = np.cumsum(inter)
    mix = rng.choice(len(names), size=REQUESTS,
                     p=weights / weights.sum())

    tally = {n: {"requests": 0, "queries": 0, "recalls": []}
             for n in names}
    prev_completion = 0.0
    for i in range(REQUESTS):
        name = names[mix[i]]
        _, target, k, _ = classes[name]
        qb = queries[rng.choice(queries.shape[0], QB, replace=False)]
        t0 = time.perf_counter()
        _, ids = eng.query(jax.numpy.asarray(qb), k, recall_target=target)
        ids = np.asarray(jax.device_get(ids))
        service = time.perf_counter() - t0
        start = max(float(arrivals[i]), prev_completion)
        completion = start + service
        prev_completion = completion
        monitor.record(name, completion - float(arrivals[i]))
        r = auditors[name].audit(qb, ids, items, k=k)
        if r is not None:
            tally[name]["recalls"].append(r)
        tally[name]["requests"] += 1
        tally[name]["queries"] += QB
    span = prev_completion - float(arrivals[0])
    for n in names:
        tally[n]["qps"] = round(tally[n]["queries"] / span, 1)
    tally["_span_s"] = span
    return tally


def check_trace(tracker, trace_path):
    """Export + schema-validate the Chrome trace."""
    return validate_chrome_trace(export_chrome_trace(tracker, trace_path))


def main() -> None:
    tmp = tempfile.mkdtemp(prefix="loadgen_")
    jsonl_path = os.path.join(tmp, "events.jsonl")
    ring = RingBufferSink(capacity=1 << 16)
    jsonl = JsonlSink(jsonl_path, max_bytes=JSONL_MAX_BYTES)
    tracker = Tracker(sinks=[ring, jsonl])
    rng = np.random.default_rng(SEED)

    cidx, eng, queries = build_serving_stack(tracker)
    service = measure_service(eng, queries, rng)

    # SLOs calibrated off the measured unloaded service time: p50 at 3x
    # (queueing headroom at 0.7 utilization), p99 at 12x (heavy tail).
    classes = [RequestClass(name=n, recall_target=t, k=k, weight=w,
                            slo_p50_s=3.0 * service[n],
                            slo_p99_s=12.0 * service[n])
               for n, t, k, w in MIX]
    # evaluation gate scaled to the replay length: the lightest class
    # (weight 1/10) must still clear it in the 60-request smoke run
    monitor = SloMonitor(tracker, classes, tolerance=0.5,
                         min_samples=max(3, REQUESTS // 20))
    auditors = {n: RecallAuditor(tracker, recall_target=t,
                                 sample_fraction=0.25, tolerance=0.05,
                                 prefix=f"repro.slo.audit.{n}")
                for n, t, _, _ in MIX}

    tally = replay(eng, np.asarray(cidx.items), queries, monitor,
                   auditors, rng)
    verdicts = monitor.evaluate()
    trace_path = os.path.join(tmp, "trace.json")
    trace_stats = check_trace(tracker, trace_path)
    tracker.close()
    snap = tracker.snapshot()

    per_class = {}
    for name, target, k, weight in MIX:
        v = verdicts[name]
        recalls = tally[name]["recalls"]
        per_class[name] = {
            "recall_target": target, "k": k, "weight": weight,
            "requests": v["n"], "qps": tally[name]["qps"],
            "p50_s": round(v["p50_s"], 6), "p99_s": round(v["p99_s"], 6),
            "slo_p50_s": round(v["slo_p50_s"], 6),
            "slo_p99_s": round(v["slo_p99_s"], 6),
            "burn_rate": round(v["burn_rate"], 3),
            "breached": v["breached"], "evaluated": v["evaluated"],
            "service_s_unloaded": round(service[name], 6),
            "audits": len(recalls),
            "achieved_recall": round(float(np.mean(recalls)), 4),
        }
        emit(f"loadgen_{name}", v["p50_s"] * 1e6,
             f"p99_s={fmt(v['p99_s'], 4)}|qps={tally[name]['qps']}|"
             f"recall={fmt(per_class[name]['achieved_recall'], 3)}")

    spans = {nm: {kk: (round(vv, 7) if isinstance(vv, float) else vv)
                  for kk, vv in snap["hists"][nm].items()}
             for nm in ("repro.engine.hash_encode",
                        "repro.engine.directory_match",
                        "repro.engine.segmented_gather",
                        "repro.engine.re_rank", "repro.engine.top_k",
                        "repro.engine.query")
             if nm in snap["hists"]}
    recall_ok = all(per_class[n]["achieved_recall"] >= t - 0.05
                    for n, t, _, _ in MIX)
    out = {
        "bench": "loadgen", "n": N, "d": D, "code_len": L,
        "num_ranges": M, "batch_size": QB, "requests": REQUESTS,
        "seed": SEED, "utilization": UTILIZATION,
        "pareto_alpha": PARETO_ALPHA,
        "note": "open-loop: Pareto arrivals drawn up front; latency = "
                "simulated queueing (FIFO, measured service times) so it "
                "includes waiting, not just service",
        "query_shape": {"q": QB, "n": N, "d": D, "code_len": L,
                        "num_buckets": eng.buckets.num_buckets,
                        "probe_width": snap["hists"]
                        ["repro.engine.probe_width"]["p50"],
                        "k": MIX[0][2]},
        "classes": per_class,
        "spans": spans,
        "slo_breaches": int(snap["counters"].get("repro.slo.breach", 0)),
        "trace": trace_stats,
        "export": {"ring_records": ring.total, "ring_dropped": ring.dropped,
                   "jsonl_records": jsonl.total,
                   "jsonl_rotations": jsonl.rotations,
                   "jsonl_live_records": len(read_jsonl(jsonl_path))},
    }
    out["acceptance"] = {
        "recall_contract_met": bool(recall_ok),
        "all_classes_evaluated": all(
            per_class[n]["evaluated"] for n, _, _, _ in MIX),
        "trace_valid": True,           # validate_chrome_trace raised if not
        "jsonl_rotated": bool(jsonl.rotations >= 1) if bench_smoke()
        else True,                     # full runs need not hit the cap
        "meets": bool(recall_ok
                      and all(per_class[n]["evaluated"]
                              for n, _, _, _ in MIX)),
    }

    path = bench_json_path(ROOT)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    emit("loadgen_json", 0.0, os.path.basename(path))
    print(format_table(snap), flush=True)


if __name__ == "__main__":
    main()
