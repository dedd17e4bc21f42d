"""One cell's query stages on the device trace's clock; the benchmark's
own runs never call this.

    python3 bench/stage_trace.py --workload W --seed S --seconds S \\
            [--off 1] [--num-items N] [--out FILE --batches B]

Set-up as in ``bench/run.py``, then, in one process: with ``--off 1`` a
closed-loop segment with no tracker and no profiler; the tracked segment
(the program's tracker attached, a third of ``--seconds``); and a
profiled segment of ``--seconds`` with no tracker, reduced by
``bench/lib/stages.py``. Prints one JSON line last: the ``qps`` of each
segment, every per-layer metric of the cell and the stage readers
(``device_ms.*``), the device seconds charged to each stage, how the
executions were linked to their launches, ``stage_ops`` and
``stage_idle_gaps``, the device programs of each batch, and the slowest
and the median batch with their host and device milliseconds per stage.
With ``--out`` the per-layer context, cut to its first ``B`` profiled
batches, is written to FILE: the stage readers' test data. Needs a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
STAGE_READERS = ("device_ms.match", "device_ms.select",
                 "device_ms.planned_take", "device_ms.rerank")


def _qps(win) -> float:
    return win.answered / win.seconds if win.seconds > 0 else 0.0


def _cut(rec, batches: int) -> None:
    """Keep the first ``batches`` batches of a stage recording."""
    fetches = sorted(h[1] + h[2] for h in rec.host if h[0] == "bench.fetch")
    if len(fetches) <= batches:
        return
    end = fetches[batches - 1]
    rec.window = (rec.window[0], end)
    for dev in rec.devices.values():
        keep = [m[1] < end for m in dev["modules"]]
        dev["modules"] = [m for m, k in zip(dev["modules"], keep) if k]
        if "module_run_ids" in dev:
            dev["module_run_ids"] = [r for r, k in
                                     zip(dev["module_run_ids"], keep) if k]
        dev["ops"] = [o for o in dev["ops"] if o[3] < end]
    rec.host = [h for h in rec.host if h[1] < end]
    rec.program = [h for h in rec.program if h[1] < end]
    rec.launches = [l for l in rec.launches if l[1] < end]


def _write_fixture(ctx, batches: int, path: str) -> None:
    """The context, cut to its first ``batches`` profiled batches."""
    from bench.lib.layers import LayerContext
    from bench.lib.stages import StageRecording
    d = json.loads(json.dumps(ctx.to_json()))
    cut = LayerContext.from_json({**d, "recording": None})
    cut.recording = StageRecording.from_json(d["recording"])
    _cut(cut.recording, batches)
    cut.traced_batches = min(cut.traced_batches, batches)
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(cut.to_json()))


def run(args) -> dict:
    import jax
    from bench.lib import data, layers, loop, roofline, stages
    from bench.lib.cell import (TRACKED_SHARE, device_info, device_memory,
                                load_cell, log, time_calls)
    from repro.obs.tracker import Tracker
    cell = load_cell(args.workload, ROOT)
    cfg, mix = cell.config, cell.mix
    if args.num_items:
        cfg["data"]["num_items"] = args.num_items
    dev = device_info()
    t = time.perf_counter()
    items = jax.block_until_ready(data.make_items(cfg["data"], args.seed))
    pool = jax.block_until_ready(
        data.make_pool(mix, int(cfg["data"]["dim"]), args.seed))
    system = layers.load_module("systems", cfg["system"]).make(
        cfg, mix, items, data.stream_key(args.seed, data.PROGRAM), {})
    loop.closed_loop(system.query, pool, 0.0,
                     min_batches=int(mix["warmup_batches"]))
    out = {"workload": args.workload, "seed": args.seed,
           "num_items": int(cfg["data"]["num_items"]), "device": dev,
           "setup_s": time.perf_counter() - t, "qps": {}}
    ctx = layers.LayerContext(
        shapes=system.shapes(), memory=device_memory(),
        peaks=roofline.peaks(dev["kind"]) if dev["platform"] == "tpu"
        else None)
    if args.off:
        off = loop.closed_loop(system.query, pool, args.seconds)
        out["qps"]["off"] = _qps(off)
    for name, fn in system.layer_calls().items():
        ctx.host_calls[name] = time_calls(fn)
    tracker = Tracker()
    tracked = loop.closed_loop(system.with_tracker(tracker).query, pool,
                               args.seconds * TRACKED_SHARE, min_batches=2)
    out["qps"]["tracked"] = _qps(tracked)
    ctx.tracked = layers.from_tracker(tracker)
    ctx.tracked_batches = tracked.batches
    with stages.profile() as prof:
        traced = loop.closed_loop(system.query, pool, args.seconds)
    out["qps"]["profiled"] = _qps(traced)
    rec = ctx.recording = prof.recording
    ctx.traced_batches = traced.batches
    system.close()
    if args.out:
        _write_fixture(ctx, args.batches, args.out)
    log(f"stage trace: tracked {tracked.batches} batches, profiled "
        f"{traced.batches} in {traced.seconds:.3f} s")

    out["metrics"] = {}
    for name in [m["name"] for m in cell.per_layer] + list(STAGE_READERS):
        v = layers.load_reader(name)(ctx)
        out["metrics"][name] = None if v is None else float(v)
    busy = rec.busy_s()
    per_stage = rec.stage_busy_s()
    out["busy_s"], out["window_s"] = busy, rec.window_s
    out["stage_busy_ms_per_batch"] = {
        k: 1e3 * v / traced.batches for k, v in
        sorted(per_stage.items(), key=lambda kv: -kv[1])}
    out["stage_busy_sum_over_busy"] = (sum(per_stage.values()) / busy
                                       if busy else None)
    out["unattributed_share"] = (per_stage.get(stages.UNATTRIBUTED, 0.0)
                                 / busy if busy else None)
    out["links"] = rec.link_stats()
    out["breakdown"] = {"device_ops": rec.top_ops(10),
                        "idle_gaps": rec.idle_gaps(10),
                        "stage_ops": rec.stage_ops(10),
                        "stage_idle_gaps": rec.stage_idle_gaps(10)}
    gaps = rec.stage_idle_gaps(10 ** 6)
    issue = sum(s for n, s in gaps if n.startswith(stages.BATCH_START))
    staged = sum(s for n, s in gaps
                 if n.startswith(stages.BATCH_START + "/"))
    out["issue_idle_s"] = issue
    out["issue_idle_staged_share"] = staged / issue if issue else None
    batches = rec.batches()
    out["programs_per_batch_each"] = [b["programs"] for b in batches]
    if batches:
        order = sorted(range(len(batches)), key=lambda i: batches[i]["ms"])
        bounds = rec.batch_bounds()
        for key, i in (("slowest_batch", order[-1]),
                       ("median_batch", order[len(order) // 2])):
            one = dataclasses.replace(rec, window=bounds[i])
            out[key] = {"batch": i, **batches[i],
                        "idle_ms": 1e3 * (one.window_s - one.busy_s()),
                        "idle_gaps": one.stage_idle_gaps(5)}
        out["batch_ms"] = {
            "median": statistics.median(b["ms"] for b in batches),
            "max": batches[order[-1]]["ms"]}
        log(f"slowest batch: {out['slowest_batch']}")
    return out


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--off", type=int, choices=(0, 1), default=0)
    ap.add_argument("--num-items", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--batches", type=int, default=3)
    args = ap.parse_args(argv)
    import os
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("[stage_trace] needs a TPU; nothing was run", file=sys.stderr)
        return 1
    print(json.dumps(run(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
