"""One cell of ``BENCHMARK.json``, end to end.

A cell names a configuration (``bench/configs/<config>.json``: the
catalog, the index, the system and the reference it is compared with)
and a traffic mix (``bench/traffic/<mix>.json``). The system is the
module ``bench/systems/<config["system"]>.py`` and the reference
``bench/references/<config["reference"]>.py``; per-layer readers are
found by metric name (``layers.py``). Adding a cell, a configuration, a
mix or a metric adds files and entries and edits none.

A run: set-up (catalog and query pool on the device from the seed, the
system built, every shape of the window warmed up), then the measured
window, then the comparison with the reference once the system's state
is freed. With ``trace`` the window is replaced by three segments read by
the per-layer metrics: host-timed calls into single layers, a tracked
segment with the program's tracker attached, and a profiled segment with
none.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import check, data, layers, loop, roofline
from bench.lib.devtrace import profile

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
GIB = float(1 << 30)
HOST_CALL_S = 0.25        # least host-clock span of a timed layer call
TRACKED_SHARE = 1 / 3     # tracked segment, as a share of --seconds


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    workload: Dict
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    entry = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(w, config, mix,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


class JaxEvents:
    """Backend compiles and persistent-cache hits and misses, counted
    from JAX's monitoring events while this object is installed."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def memory(stat: str) -> int:
    """The largest ``memory_stats()[stat]`` over the local devices (0 where
    the backend reports none)."""
    import jax
    vals = [(d.memory_stats() or {}).get(stat, 0) for d in jax.local_devices()]
    return int(max(vals)) if vals else 0


def device_memory() -> Dict[str, int]:
    """The device memory the system holds once set-up is over, on the
    fullest chip, after Python's garbage is collected (arrays that set-up
    dropped but a reference cycle keeps alive are not the system's):

      * ``live_bytes``: the bytes of every live device array (catalog,
        index and engine state, query pool): ``index_hbm_gib``;
      * ``bytes_in_use``: the allocator's reading, which adds the
        runtime's own allocations that no array owns: ``hbm_in_use_gib``.
    """
    import jax
    gc.collect()
    live: Dict = {}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            live[s.device] = live.get(s.device, 0) + s.data.nbytes
    out = {"live_bytes": int(max(live.values(), default=0)),
           "bytes_in_use": memory("bytes_in_use")}
    log(f"device memory: {out['live_bytes']} bytes in live arrays, "
        f"{out['bytes_in_use']} in use by the allocator")
    return out


def time_calls(fn: Callable, min_s: float = HOST_CALL_S) -> Dict:
    n, t0 = 0, time.perf_counter()
    while True:
        fn()
        n += 1
        t = time.perf_counter() - t0
        if t >= min_s and n >= 3:
            return {"count": n, "total_s": t}


def device_info() -> Dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             make_system: Optional[Callable] = None,
             on_layers: Optional[Callable] = None) -> Dict:
    """One run of ``cell``; returns the result line as a dict (its
    ``checks`` key last). ``make_system`` replaces the configuration's
    system (the control); ``on_layers`` is handed the traced run's
    :class:`layers.LayerContext`."""
    import jax
    events = JaxEvents()
    dev = device_info()
    kernels = "pallas" if dev["platform"] == "tpu" else "ref"
    cfg, mix = cell.config, cell.mix
    reference = layers.load_module("references", cfg["reference"])
    make = make_system or layers.load_module("systems", cfg["system"]).make

    t_setup = time.perf_counter()
    phases: Dict[str, float] = {}
    items = jax.block_until_ready(data.make_items(cfg["data"], seed))
    pool = jax.block_until_ready(
        data.make_pool(mix, int(cfg["data"]["dim"]), seed))
    phases["data"] = time.perf_counter() - t_setup
    audit = check.DispatchAudit()
    with audit.on():
        system = make(cfg, mix, items, data.stream_key(seed, data.PROGRAM),
                      phases)
        t = time.perf_counter()
        warm = loop.closed_loop(system.query, pool, 0.0,
                                min_batches=int(mix["warmup_batches"]))
        phases["warmup"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_setup
    hbm = device_memory()
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items())
        + f"; compiles {events.compiles} ({events.compile_s:.3f} s), "
        f"cache hits {events.hits} misses {events.misses}")
    log(f"engine {system.engine_name}; shapes {system.shapes()}; "
        f"dispatch {audit.resolved()}")

    windows = [warm] if warm.error else []
    result: Dict = {}
    if not trace:
        before = events.compiles
        win = loop.closed_loop(system.query, pool, seconds)
        windows.append(win)
        log(f"window {win.seconds:.3f} s, {win.batches} batches, "
            f"{events.compiles - before} compiles inside it")
        if win.batches:
            slow = sorted(range(win.batches), key=lambda i: -win.latencies[i])
            log(f"batch ms: median "
                f"{1e3 * float(np.median(win.latencies)):.1f}; slowest "
                + ", ".join(f"{1e3 * win.latencies[i]:.1f} (batch {i}, "
                            f"pool {win.answers[i].pool_index})"
                            for i in slow[:5]))
        e2e = {}
        if win.batches:
            e2e = {"qps": win.answered / win.seconds,
                   "latency_p95_ms": 1e3 * loop.percentile(win.latencies, 95),
                   "index_hbm_gib": hbm["live_bytes"] / GIB,
                   "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    else:
        metrics, layer, extra = _traced(cell, system, pool, seconds, dev,
                                       hbm)
        windows.extend(extra)
        if on_layers is not None:
            on_layers(layer)
        result["breakdown"] = {
            "device_ops": layer.recording.top_ops(10),
            "idle_gaps": layer.recording.idle_gaps(10)}
    dev["memory_peak_bytes"] = memory("peak_bytes_in_use")
    if trace:
        dev["busy_s"] = layer.recording.busy_s()
        dev["window_s"] = layer.recording.window_s
    engine = system.engine_name
    system.close()
    del system

    answers = [a for w in windows for a in w.answers]
    failed = sum(w.failed for w in windows)
    attempted = sum(w.attempted for w in windows)
    for w in windows:
        if w.error:
            log(f"a batch failed:\n{w.error}")
    pool_host = [np.asarray(q) for q in pool]
    t = time.perf_counter()
    checks = check.compare(
        answers, pool_host, items, reference, k=int(mix["k"]),
        recall_limit=float(mix["recall_target"]), limits=cfg["limits"],
        unanswered=failed, kernel_fallbacks=audit.fallbacks(kernels),
        engine_mismatch=int(cfg.get("expect_engine") not in (None, engine)))
    log(f"comparison with the reference {time.perf_counter() - t:.3f} s "
        f"over {len(answers)} batches")
    ok = check.all_ok(checks)
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev}
    line.update(result)
    line["checks"] = {c.name: c.as_json() for c in checks}
    for c in checks:
        log(c.line())
    return line


def _traced(cell: Cell, system, pool, seconds: float, dev: Dict,
            hbm: Dict):
    from repro.obs.tracker import Tracker
    shapes = system.shapes()
    ctx = layers.LayerContext(
        shapes=shapes, memory=hbm,
        peaks=roofline.peaks(dev["kind"]) if dev["platform"] == "tpu"
        else None)
    for name, fn in system.layer_calls().items():
        ctx.host_calls[name] = time_calls(fn)
    tracker = Tracker()
    tracked = loop.closed_loop(system.with_tracker(tracker).query, pool,
                               seconds * TRACKED_SHARE, min_batches=2)
    ctx.tracked = layers.from_tracker(tracker)
    ctx.tracked_batches = tracked.batches
    with profile() as prof:
        traced = loop.closed_loop(system.query, pool, seconds)
    ctx.recording = prof.recording
    ctx.traced_batches = traced.batches
    log(f"traced: tracked {tracked.batches} batches, "
        f"profiled {traced.batches} batches in {traced.seconds:.3f} s")
    metrics = {}
    for m in cell.per_layer:
        v = layers.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            log(f"per-layer metric {m['name']}: nothing to read")
    return metrics, ctx, [tracked, traced]
