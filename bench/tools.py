"""Measurements behind the benchmark's limits and records; the
benchmark's own runs never call this.

    python3 bench/tools.py control --workload W --seeds 1,2,3 --seconds S
        the control (the reference in bfloat16, ``exact_mips``) in the
        program's place, one run per seed in one process; prints each
        run's result line (every check beside its limit)
    python3 bench/tools.py phases --workload W --seed S
        the set-up split into its phases: data, encode (the index without
        calibration), the bucket store's CSR build, planner calibration,
        the engine's own bucket store, and the first query (compile or
        cache load); prints seconds per phase as JSON
    python3 bench/tools.py record --workload W --seed S --num-items N \\
            --seconds S --batches B --out FILE
        a traced run at ``N`` items whose per-layer context, cut to its
        first ``B`` profiled batches, is written to FILE: the readers'
        test data
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _setup_jax():
    import os
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def control(args) -> None:
    from bench.lib.cell import load_cell, run_cell
    from bench.references.exact_mips import control_system
    cell = load_cell(args.workload, ROOT)
    for seed in args.seeds.split(","):
        line = run_cell(cell, int(seed), args.seconds, False,
                        make_system=control_system)
        print(json.dumps({"seed": int(seed), "control": True, **line}),
              flush=True)


def phases(args) -> None:
    import numpy as np
    jax = _setup_jax()
    from bench.lib import data
    from bench.lib.cell import load_cell
    from repro.core import planner
    from repro.core.bucket_index import build_bucket_index
    from repro.core.engine import QueryEngine
    from repro.core.index import IndexSpec, build
    cell = load_cell(args.workload, ROOT)
    cfg, mix = cell.config, cell.mix
    out = {}

    def phase(name, fn):
        t = time.perf_counter()
        r = jax.block_until_ready(fn())
        out[name] = time.perf_counter() - t
        print(f"[tools] {name} {out[name]:.3f} s", file=sys.stderr,
              flush=True)
        return r

    items = phase("data", lambda: data.make_items(cfg["data"], args.seed))
    pool = phase("pool", lambda: data.make_pool(
        mix, int(cfg["data"]["dim"]), args.seed))
    ix = cfg["index"]
    spec = IndexSpec(family=ix["family"], code_len=int(ix["code_len"]),
                     m=int(ix["m"]))
    key = data.stream_key(args.seed, data.PROGRAM)
    idx = phase("encode", lambda: build(spec, items, key))
    buckets = phase("csr_build", lambda: build_bucket_index(idx))
    calib = phase("calibration", lambda: planner.calibrate(
        idx, k=planner.DEFAULT_CAL_K, key=jax.random.fold_in(key, 0x5ca1),
        buckets=buckets))
    idx = idx._replace(calib=calib)
    eng = phase("engine", lambda: QueryEngine(idx, engine=ix["engine"]))
    def query():
        return [np.asarray(a) for a in eng.query(
            pool[0], int(mix["k"]),
            recall_target=float(mix["recall_target"]))]
    phase("first_query", query)
    phase("second_query", query)
    print(json.dumps({"workload": args.workload, "engine": eng.engine,
                      "phases_s": out}), flush=True)


def record(args) -> None:
    from bench.lib.cell import load_cell, run_cell
    cell = load_cell(args.workload, ROOT)
    cell.config["data"]["num_items"] = args.num_items
    kept = {}
    line = run_cell(cell, args.seed, args.seconds, True,
                    on_layers=lambda ctx: kept.setdefault("ctx", ctx))
    ctx = kept["ctx"]
    rec = ctx.recording
    fetches = sorted(h[1] + h[2] for h in rec.host if h[0] == "bench.fetch")
    if len(fetches) > args.batches:
        end = fetches[args.batches - 1]
        rec.window = (rec.window[0], end)
        for dev in rec.devices.values():
            dev["ops"] = [o for o in dev["ops"] if o[3] < end]
            dev["modules"] = [m for m in dev["modules"] if m[1] < end]
        rec.host = [h for h in rec.host if h[1] < end]
        ctx.traced_batches = args.batches
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(ctx.to_json()))
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, required=True)
    p = sub.add_parser("phases")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    r = sub.add_parser("record")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--num-items", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--batches", type=int, required=True)
    r.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if args.cmd != "phases":
        _setup_jax()
    {"control": control, "phases": phases, "record": record}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
