"""CI perf-regression gate over the BENCH_*.json trajectory (DESIGN.md
§14).

Every benchmark in this repo records one BENCH_<n>.json; this module
turns those into a machine-readable **manifest** of scalar metric series
and compares a *current* run against a *trailing baseline* with
per-metric tolerances:

  * **Relative comparisons** (qps, latency, speedups, recall) apply only
    when the two runs have the same **shape** (n, d, code_len, batch
    sizes, ...): a smoke-sized CI run is never compared number-for-number
    against the recorded full-scale trajectory. Tolerances are per-metric
    and deliberately loose (CPU CI wall-clock noise is tens of percent);
    ``--tol-scale`` loosens/tightens all of them at once.
  * **Absolute contract bounds** (recall floors, acceptance ``meets``
    flags, trace validity) always apply, at any scale — a smoke run that
    breaks the recall contract or the trace schema fails the gate even
    though its throughput numbers are incomparable.

Exit status 1 with a delta table on any regression — the CI step after
the benchmark smoke block. Default invocation (no flags) audits the
repo's own recorded trajectory: newest bench of each kind against the
trailing one of the same kind.

Usage::

    python -m benchmarks.regress                       # repo trajectory
    python -m benchmarks.regress --current bench_smoke  # CI smoke gate
    python -m benchmarks.regress --manifest manifest.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

ROOT = os.path.join(os.path.dirname(__file__), "..")

# direction-aware default tolerances (relative); CPU CI timing noise
# dominates, so throughput/latency get wide bands, recall narrow ones.
TOL_QPS = 0.60       # throughput may sag 60% before the gate trips
TOL_LAT = 1.00       # latency may double
TOL_SPEEDUP = 0.50
TOL_RECALL = 0.03


def _m(value, better: str, tol: float) -> dict:
    return {"value": float(value), "better": better, "tol": float(tol)}


def _bound(name: str, ok: bool, detail: str = "") -> dict:
    return {"name": name, "ok": bool(ok), "detail": detail}


def _extract_engine_compare(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n_items", "dim", "num_queries", "num_probe", "k")}
    metrics, bounds = {}, []
    for arm in b.get("arms", []):
        cl = arm["code_len"]
        metrics[f"L{cl}.bucket_qps"] = _m(arm["bucket"]["qps"], "higher",
                                          TOL_QPS)
        metrics[f"L{cl}.dense_qps"] = _m(arm["dense"]["qps"], "higher",
                                         TOL_QPS)
        metrics[f"L{cl}.candgen_speedup"] = _m(arm["candgen_speedup"],
                                               "higher", TOL_SPEEDUP)
        metrics[f"L{cl}.recall"] = _m(arm["bucket"]["recall@10"],
                                      "higher", TOL_RECALL)
        bounds.append(_bound(
            f"L{cl}.engine_parity",
            arm["bucket"]["recall@10"] == arm["dense"]["recall@10"],
            "bucket and dense arms must retrieve identical recall"))
    return shape, metrics, bounds


def _extract_fused(b: dict) -> tuple:
    """Fused single-pass engine bench (benchmarks/fused_bench.py): the
    fused-over-staged speedup is the tentpole metric (direction-aware);
    the int8 arm's recall delta is an absolute contract bound at any
    scale, and the end-to-end win itself is bounded at the full-scale
    protocol (N >= 100k — toy indexes do not amortize the fusion)."""
    shape = {k: b.get(k) for k in
             ("n_items", "dim", "num_queries", "num_probe", "k",
              "code_len", "num_ranges")}
    k = b.get("k", 10)
    metrics, bounds = {}, []
    for name, arm in b.get("arms", {}).items():
        metrics[f"{name}.qps"] = _m(arm["qps"], "higher", TOL_QPS)
        metrics[f"{name}.recall"] = _m(arm[f"recall@{k}"], "higher",
                                       TOL_RECALL)
    metrics["fused_speedup"] = _m(b["fused_speedup"], "higher",
                                  TOL_SPEEDUP)
    metrics["int8_speedup"] = _m(b["int8_speedup"], "higher", TOL_SPEEDUP)
    bounds.append(_bound(
        "fused_parity",
        b["arms"]["fused"][f"recall@{k}"]
        == b["arms"]["staged"][f"recall@{k}"],
        "fused f32 arm must retrieve identical recall to staged "
        "(bit-identical ids)"))
    bounds.append(_bound(
        "int8_recall_delta",
        b.get("int8_recall_delta", 1.0) <= TOL_RECALL,
        f"int8 phase-1 recall delta must stay within {TOL_RECALL}"))
    if b.get("n_items", 0) >= 100_000:
        bounds.append(_bound(
            "fused_beats_staged", b["fused_speedup"] > 1.0,
            "the fused kernel must beat the staged relay end-to-end at "
            "full scale"))
    return shape, metrics, bounds


def _extract_streaming(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n_items", "dim", "num_queries", "num_probe", "k",
              "code_len", "num_ranges", "capacity")}
    s = b["sustained"]
    metrics = {
        "query_qps": _m(s["query_qps"], "higher", TOL_QPS),
        "inserts_per_s": _m(s["inserts_per_s"], "higher", TOL_QPS),
        "compact_ms": _m(b["compaction"]["compact_ms"], "lower", TOL_LAT),
    }
    for r in b.get("repartition", []):
        metrics[f"repartition_speedup_m{r['m']}"] = _m(
            r["speedup"], "higher", TOL_SPEEDUP)
    bounds = [
        _bound("compaction_preserves_recall",
               b["compaction"]["recall@10_after"]
               >= b["compaction"]["recall@10_before"] - 0.02,
               "compaction must not lose recall"),
        _bound("repartition_observed", s.get("repartitions", 0) >= 1,
               "sustained churn must trigger >= 1 repartition"),
    ]
    return shape, metrics, bounds


def _extract_catalyst(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n", "num_queries", "code_len", "num_ranges", "k",
              "target_recall")}
    metrics, bounds = {}, []
    for fam, f in b.get("families", {}).items():
        metrics[f"{fam}.catalyst_speedup"] = _m(
            f["catalyst_speedup"], "higher", TOL_SPEEDUP)
    # the catalyst win is asymptotic in n (the per-range directory
    # overhead is not amortized on toy indexes), so the paper-claim
    # bound only applies at the scale the claim is made at
    if "simple" in b.get("families", {}) and b.get("n", 0) >= 20_000:
        bounds.append(_bound(
            "simple_catalyst_gt_1",
            b["families"]["simple"]["catalyst_speedup"] > 1.0,
            "norm-ranging must beat flat SIMPLE-LSH (the paper's claim)"))
    return shape, metrics, bounds


def _extract_distributed(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n", "num_queries", "code_len", "num_ranges", "k",
              "num_probe")}
    metrics = {f"{name}_qps": _m(arm["qps"], "higher", TOL_QPS)
               for name, arm in b.get("arms", {}).items()}
    metrics["recall"] = _m(b["recall"], "higher", TOL_RECALL)
    return shape, metrics, []


def _extract_planner(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n", "d", "code_len", "num_ranges", "k", "recall_target",
              "calib_queries", "eval_queries")}
    a = b["acceptance"]
    metrics = {
        "planned_recall": _m(a["planned_recall"], "higher", TOL_RECALL),
        "probe_reduction_vs_static": _m(a["probe_reduction_vs_static"],
                                        "higher", 0.2),
    }
    bounds = [_bound("planner_meets", bool(a.get("meets")),
                     "planner acceptance block must hold")]
    return shape, metrics, bounds


def _extract_obs(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n", "d", "code_len", "num_ranges", "k", "recall_target")}
    a = b["acceptance"]
    metrics = {"achieved_recall": _m(a["achieved_recall"], "higher",
                                     TOL_RECALL)}
    q = b.get("spans", {}).get("repro.engine.query")
    if q:
        metrics["query_p50_s"] = _m(q["p50"], "lower", TOL_LAT)
    bounds = [
        _bound("obs_meets", bool(a.get("meets")),
               "obs acceptance block must hold"),
        _bound("stage_spans_present",
               bool(a.get("all_stage_spans_present")),
               "every query-path stage span must be recorded"),
    ]
    return shape, metrics, bounds


def _extract_loadgen(b: dict) -> tuple:
    shape = {k: b.get(k) for k in
             ("n", "d", "code_len", "num_ranges", "batch_size",
              "requests")}
    metrics: Dict[str, dict] = {}
    for name, c in b.get("classes", {}).items():
        metrics[f"{name}.p50_s"] = _m(c["p50_s"], "lower", TOL_LAT)
        metrics[f"{name}.p99_s"] = _m(c["p99_s"], "lower", 1.5)
        metrics[f"{name}.qps"] = _m(c["qps"], "higher", TOL_QPS)
        metrics[f"{name}.achieved_recall"] = _m(
            c["achieved_recall"], "higher", TOL_RECALL)
    a = b["acceptance"]
    bounds = [
        _bound("loadgen_meets", bool(a.get("meets")),
               "loadgen acceptance block must hold"),
        _bound("recall_contract_met", bool(a.get("recall_contract_met")),
               "every request class must meet its recall contract"),
        _bound("trace_valid", bool(a.get("trace_valid")),
               "exported Chrome trace must pass schema validation"),
        _bound("cost_attrs_present", bool(a.get("cost_attrs_present")),
               "hot-path trace slices must carry flops/hbm_bytes attrs"),
    ]
    return shape, metrics, bounds


def _extract_kernelcheck(b: dict) -> tuple:
    """Static analyzer report (repro/analysis/kernelcheck.py): per-kernel
    modelled VMEM fractions and analytic flop/byte bills. All numbers are
    deterministic functions of the code, so tolerances are tight — a
    jump means a kernel's cost model or transient footprint changed. A
    kernel re-tiled onto a different grid is a new design, not a drift:
    the grid is part of its metric names, so its numbers start a new
    series while the other kernels keep comparing."""
    shape = {op: [c["shapes"] for c in v.get("classes", [])]
             for op, v in b.get("kernels", {}).items()}
    metrics: Dict[str, dict] = {}
    worst_frac = 0.0
    for op, v in sorted(b.get("kernels", {}).items()):
        for i, c in enumerate(v.get("classes", [])):
            worst_frac = max(worst_frac, c["vmem_frac"])
            cls = f"{op}.c{i}@{'x'.join(str(g) for g in c['grid'])}"
            metrics[f"{cls}.vmem_frac"] = _m(c["vmem_frac"], "lower", 0.25)
            metrics[f"{cls}.flops"] = _m(c["declared"]["flops"],
                                         "lower", 0.5)
            metrics[f"{cls}.hbm_bytes"] = _m(
                c["declared"]["hbm_bytes"], "lower", 0.5)
    bounds = [
        _bound("kernelcheck_clean", b.get("clean") == 1,
               "K1-K5 must hold on every registered kernel "
               f"({len(b.get('findings', []))} finding(s))"),
        _bound("vmem_within_budget", worst_frac <= 1.0,
               "no kernel's modelled VMEM may exceed the budget"),
    ]
    return shape, metrics, bounds


EXTRACTORS = {
    "engine_compare": _extract_engine_compare,
    "fused": _extract_fused,
    "streaming": _extract_streaming,
    "catalyst": _extract_catalyst,
    "distributed": _extract_distributed,
    "planner": _extract_planner,
    "obs": _extract_obs,
    "loadgen": _extract_loadgen,
    "kernelcheck": _extract_kernelcheck,
}


def extract(bench: dict, file: str = "?") -> Optional[dict]:
    """One manifest entry {file, kind, shape, metrics, bounds} — or None
    for bench kinds the gate has no extractor for."""
    kind = bench.get("bench")
    fn = EXTRACTORS.get(kind)
    if fn is None:
        return None
    shape, metrics, bounds = fn(bench)
    return {"file": os.path.basename(file), "path": os.path.abspath(file),
            "kind": kind, "shape": shape, "metrics": metrics,
            "bounds": bounds}


def load_manifest(root: str) -> List[dict]:
    """Manifest entries for every BENCH_*.json under ``root``, in
    recording order."""
    files = sorted(glob.glob(os.path.join(root, "BENCH_*.json")),
                   key=lambda p: int(re.search(r"(\d+)", os.path.basename(p))
                                     .group(1)))
    out = []
    for f in files:
        with open(f) as fh:
            entry = extract(json.load(fh), f)
        if entry is not None:
            out.append(entry)
    return out


def compare(current: dict, baseline: dict, *,
            tol_scale: float = 1.0) -> List[dict]:
    """Relative metric rows for one (current, baseline) pair of the same
    kind. Shape-gated: differing shapes return a single 'skipped' row —
    numbers at different scales are not comparable."""
    tag = f"{current['kind']}[{current['file']} vs {baseline['file']}]"
    if current["shape"] != baseline["shape"]:
        return [{"metric": tag, "status": "skipped",
                 "detail": "shape mismatch (different scale) — relative "
                           "comparison not applicable"}]
    rows = []
    for name, cur in sorted(current["metrics"].items()):
        base = baseline["metrics"].get(name)
        if base is None or base["value"] == 0:
            continue
        delta = (cur["value"] - base["value"]) / abs(base["value"])
        # signed so that negative always means "worse"
        worse = -delta if cur["better"] == "higher" else delta
        tol = cur["tol"] * tol_scale
        rows.append({
            "metric": f"{current['kind']}.{name}",
            "baseline": base["value"], "current": cur["value"],
            "delta": delta, "tol": tol,
            "status": "regressed" if worse > tol else "ok",
        })
    return rows


def check_bounds(entry: dict) -> List[dict]:
    """Absolute contract-bound rows — applied at any scale."""
    return [{"metric": f"{entry['kind']}.{b['name']}",
             "status": "ok" if b["ok"] else "violated",
             "detail": b["detail"]}
            for b in entry["bounds"]]


def render(rows: List[dict]) -> str:
    header = ["metric", "baseline", "current", "delta", "tol", "status"]
    table = [header]
    for r in rows:
        table.append([
            r["metric"],
            f"{r['baseline']:.4g}" if "baseline" in r else "-",
            f"{r['current']:.4g}" if "current" in r else "-",
            f"{r['delta']:+.1%}" if "delta" in r else "-",
            f"{r['tol']:.0%}" if "tol" in r else "-",
            r["status"] + (f" ({r['detail']})" if r.get("detail") else ""),
        ])
    widths = [max(len(row[i]) for row in table) for i in range(len(header))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     for row in table)


def run_gate(current: List[dict], baseline: List[dict], *,
             tol_scale: float = 1.0) -> tuple:
    """All rows + pass/fail for a current manifest against a baseline
    manifest (newest entry per kind on each side)."""
    newest = {e["kind"]: e for e in current}
    base_by_kind: Dict[str, dict] = {}
    for e in baseline:
        base_by_kind[e["kind"]] = e          # later files win: trailing
    rows: List[dict] = []
    for kind, cur in newest.items():
        base = base_by_kind.get(kind)
        if base is not None and base.get("path") != cur.get("path"):
            rows.extend(compare(cur, base, tol_scale=tol_scale))
        rows.extend(check_bounds(cur))
    failed = [r for r in rows if r["status"] in ("regressed", "violated")]
    return rows, not failed


def trailing_split(manifest: List[dict]) -> tuple:
    """Default trajectory audit: newest entry per kind is 'current', the
    one before it (same kind) is its baseline."""
    current, baseline = {}, {}
    for e in manifest:                        # recording order
        if e["kind"] in current:
            baseline[e["kind"]] = current[e["kind"]]
        current[e["kind"]] = e
    return list(current.values()), list(baseline.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--current", default=None,
                    help="dir of BENCH_*.json for the run under test "
                         "(default: the repo's recorded trajectory)")
    ap.add_argument("--baseline", default=None,
                    help="dir of baseline BENCH_*.json (default: repo "
                         "root trajectory)")
    ap.add_argument("--manifest", default=None,
                    help="also write the extracted manifest JSON here")
    ap.add_argument("--tol-scale", type=float, default=1.0,
                    help="scale all relative tolerances (CI noise knob)")
    args = ap.parse_args(argv)

    if args.current is None and args.baseline is None:
        manifest = load_manifest(ROOT)
        current, baseline = trailing_split(manifest)
    else:
        current = load_manifest(args.current or ROOT)
        baseline = load_manifest(args.baseline or ROOT)
        manifest = baseline + current
    if not current:
        print("regress: no recognized BENCH_*.json found", flush=True)
        return 1
    if args.manifest:
        with open(args.manifest, "w") as f:
            json.dump({"entries": manifest}, f, indent=2)

    rows, ok = run_gate(current, baseline, tol_scale=args.tol_scale)
    print(render(rows), flush=True)
    print(f"\nregress: {'PASS' if ok else 'FAIL'} "
          f"({len(current)} benches, "
          f"{sum(r['status'] == 'ok' for r in rows)} ok, "
          f"{sum(r['status'] == 'skipped' for r in rows)} skipped, "
          f"{sum(r['status'] in ('regressed', 'violated') for r in rows)} "
          f"failing)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
