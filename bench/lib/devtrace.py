"""The device trace of a profiled window, reduced to what the per-layer
metrics read.

:func:`profile` runs a block under the JAX profiler (Python tracer off)
and reduces the ``.xplane.pb`` it writes to a :class:`Recording`:

  * per device plane (``/device:TPU:<n>``), every op of the ``XLA Ops``
    line as ``[module, op, category, start_ns, dur_ns]`` and every program
    execution of the ``XLA Modules`` line as ``[module, start_ns,
    dur_ns]``; ``op`` is the HLO instruction name, ``module`` the program
    name without its fingerprint (the execution that contains the op
    where the event does not name it), ``category`` the HLO category
    where the trace gives one;
  * the benchmark's own host annotations (``bench.*``, see ``loop.py``);
  * the window: the ``bench.window`` annotation.

A :class:`Recording` is plain JSON, so a small one recorded on the chip is
the test data of the readers. Busy time is the union of op intervals
inside the window, averaged over the devices that ran ops; an idle gap is
named by the innermost ``bench.*`` annotation the host was in at its
middle and by the program the device ran next.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from bisect import bisect_left, bisect_right
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


def op_name(event_name: str) -> str:
    """HLO instruction name of a TPU op event, whose name is the whole
    instruction text (``%sort.0 = (s32[...]) sort(...)`` -> ``sort.0``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """Program name without the fingerprint (``jit_argsort(1234)``)."""
    return event_name.split("(", 1)[0]


def op_kind(op: str) -> str:
    """Instruction name without its numeric suffixes (``sort.0.clone``
    -> ``sort``)."""
    return re.sub(r"(\.(\d+|clone))+$", "", op) or op


@dataclasses.dataclass
class Recording:
    window: Tuple[float, float]
    devices: Dict[str, Dict[str, list]]
    host: List[list]

    # -- reading ------------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _active(self) -> List[Dict[str, list]]:
        return [d for d in self.devices.values() if d["ops"]]

    def _clip(self, start: float, dur: float) -> Tuple[float, float]:
        return max(start, self.window[0]), min(start + dur, self.window[1])

    def busy_intervals(self, dev: Dict[str, list]
                       ) -> List[Tuple[float, float]]:
        spans = sorted(self._clip(o[3], o[4]) for o in dev["ops"])
        merged: List[List[float]] = []
        for a, b in spans:
            if b <= a:
                continue
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_s(self) -> float:
        devs = self._active()
        if not devs:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in devs) / len(devs) / 1e9

    def op_seconds(self, pick: Callable[[list], bool]) -> float:
        """Device seconds of the ops ``pick`` selects, per device."""
        devs = self._active()
        if not devs:
            return 0.0
        return sum(sum(o[4] for o in d["ops"] if pick(o))
                   for d in devs) / len(devs) / 1e9

    def module_calls(self, pick: Callable[[str], bool]) -> Tuple[float, float]:
        """(device seconds of the ops, executions) of the programs whose
        name ``pick`` selects, per device."""
        devs = self._active()
        if not devs:
            return 0.0, 0.0
        secs = sum(sum(o[4] for o in d["ops"] if pick(o[0])) for d in devs)
        calls = sum(sum(1 for m in d["modules"] if pick(m[0])) for d in devs)
        return secs / len(devs) / 1e9, calls / len(devs)

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` (module/op kind) groups that took most device time."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self._active():
            for o in d["ops"]:
                tot[f"{o[0]}/{op_kind(o[1])}"] += o[4] / 1e9
        k = max(1, len(self._active()))
        return [[name, s / k] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def _host_levels(self) -> List[Tuple[List[float], List[list]]]:
        """Host annotations by nesting depth, each level sorted by start
        (annotations of one level never overlap)."""
        levels: List[List[list]] = []
        stack: List[float] = []
        for h in sorted(self.host, key=lambda h: (h[1], -h[2])):
            while stack and stack[-1] <= h[1]:
                stack.pop()
            if len(levels) <= len(stack):
                levels.append([])
            levels[len(stack)].append(h)
            stack.append(h[1] + h[2])
        return [([h[1] for h in lv], lv) for lv in levels]

    @staticmethod
    def _host_at(levels, t: float) -> str:
        for starts, lv in reversed(levels):
            j = bisect_right(starts, t) - 1
            if j >= 0 and t < lv[j][1] + lv[j][2]:
                return lv[j][0]
        return "no annotation"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds grouped by what the host was doing and which
        program the device ran next; the ``n`` largest groups."""
        tot: Dict[str, float] = defaultdict(float)
        devs = self._active()
        levels = self._host_levels()
        end = self.window[1]
        for d in devs:
            starts = sorted((o[3], o[0]) for o in d["ops"])
            keys = [s for s, _ in starts]
            edge = self.window[0]
            for a, b in self.busy_intervals(d) + [(end, end)]:
                if a > edge:
                    j = bisect_left(keys, a)
                    nxt = (starts[j][1] if a < end and j < len(starts)
                           else "end of window")
                    host = self._host_at(levels, (edge + a) / 2)
                    tot[f"{host} > {nxt}"] += (a - edge) / 1e9
                edge = max(edge, b)
        k = max(1, len(devs))
        return [[name, s / k] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    # -- storage -------------------------------------------------------------

    def to_json(self) -> Dict:
        return {"window": list(self.window), "devices": self.devices,
                "host": self.host}

    @classmethod
    def from_json(cls, d: Dict) -> "Recording":
        return cls(tuple(d["window"]), d["devices"], d["host"])


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def reduce_xplane(path: str) -> Recording:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        st = _stats(ev)
                        ops.append([module_name(st.get("hlo_module", "")),
                                    op_name(ev.name),
                                    str(st.get("hlo_category", "")),
                                    float(ev.start_ns), float(ev.duration_ns)])
                elif line.name == MODULES_LINE:
                    mods.extend([module_name(ev.name), float(ev.start_ns),
                                 float(ev.duration_ns)] for ev in line.events)
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for o in ops:
                if not o[0]:
                    j = bisect_right(starts, o[3]) - 1
                    if j >= 0 and o[3] < mods[j][1] + mods[j][2]:
                        o[0] = mods[j][0]
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, float(ev.start_ns),
                             float(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} annotation in the trace {path}")
    w = max(win, key=lambda h: h[2])
    return Recording((w[1], w[1] + w[2]), devices, host)


class Profiled:
    recording: Optional[Recording] = None


@contextlib.contextmanager
def profile():
    """Profile the block; afterwards ``.recording`` holds its reduction.
    The trace is written under the temporary directory and removed."""
    import jax
    out = Profiled()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        out.recording = reduce_xplane(max(paths, key=os.path.getmtime))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
