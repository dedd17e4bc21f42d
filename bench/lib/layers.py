"""What the per-layer readers read, and how the harness finds them.

Each per-layer metric of ``BENCHMARK.json`` has a reader of its own,
``bench/layer_metrics/<metric name>.py``, with one function
``read(ctx: LayerContext) -> float | None``. A reader that finds nothing
to read returns None and the harness leaves the metric out of the line.

The context holds, from one traced run:

  * ``shapes``: sizes the system reports (engine, batch, items, buckets,
    code words, planned probe width, bucket runs);
  * ``peaks``: the device's row of ``bench/peaks.json``;
  * ``memory``: the device memory after set-up (``live_bytes``,
    ``bytes_in_use``; ``cell.device_memory``);
  * ``host_calls``: ``{name: {"count", "total_s"}}``, calls into single
    layers timed by the benchmark on the host clock;
  * ``tracked``: ``{name: {"count", "total"}}`` of every histogram of
    the program's tracker (stage spans in seconds, observations such as
    ``repro.engine.probe_width`` in their own unit) over the tracked
    segment of ``tracked_batches`` batches;
  * ``recording``: the device trace of the profiled segment of
    ``traced_batches`` batches, run with no tracker attached.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Callable, Dict, Optional

from bench.lib.devtrace import Recording

BENCH = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class LayerContext:
    shapes: Dict = dataclasses.field(default_factory=dict)
    peaks: Optional[Dict] = None
    memory: Dict = dataclasses.field(default_factory=dict)
    host_calls: Dict = dataclasses.field(default_factory=dict)
    tracked: Dict = dataclasses.field(default_factory=dict)
    tracked_batches: int = 0
    traced_batches: int = 0
    recording: Optional[Recording] = None

    def span_ms_per_batch(self, names) -> Optional[float]:
        """Milliseconds per tracked batch of the listed spans, summed over
        those present; None when none is."""
        got = [self.tracked[n] for n in names if n in self.tracked]
        if not got or self.tracked_batches <= 0:
            return None
        return 1e3 * sum(s["total"] for s in got) / self.tracked_batches

    def mean(self, name: str) -> Optional[float]:
        """Mean of one tracker observation; None when never observed."""
        h = self.tracked.get(name)
        if not h or h["count"] <= 0:
            return None
        return h["total"] / h["count"]

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d["recording"] = (self.recording.to_json()
                          if self.recording is not None else None)
        return d

    @classmethod
    def from_json(cls, d: Dict) -> "LayerContext":
        d = dict(d)
        if d.get("recording") is not None:
            d["recording"] = Recording.from_json(d["recording"])
        return cls(**d)


def from_tracker(tracker) -> Dict[str, Dict]:
    """Every histogram of a program tracker as ``{"count", "total"}``."""
    return {name: {"count": h.count, "total": h.total}
            for name, h in tracker.hists.items()}


def load_module(kind: str, name: str):
    """The benchmark's file ``bench/<kind>/<name>.py``, loaded by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    return load_module("layer_metrics", name).read
