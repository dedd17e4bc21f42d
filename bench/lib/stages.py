"""The engine's stages on the device trace's clock.

The program's spans (``repro.*``, ``repro/obs/trace.py``) are profiler
annotations on the host timeline of the trace. The query path runs
eagerly, so every stage issues its own device programs, and one program
name (``jit_gather``) serves several stages; only the launch says which
stage a device execution belongs to. :func:`reduce_stages` keeps, beside
what :func:`devtrace.reduce_xplane` keeps:

  * ``program``: every ``repro.*`` host annotation, ``[name, start_ns,
    dur_ns]``;
  * ``launches``: every host launch of a compiled program,
    ``[function, start_ns, dur_ns, run_id]``, from the ``PjitFunction(f)``
    event (the outermost of a nest) and the ``run_id`` of the execute
    event inside it (None where there is none);
  * per device, ``module_run_ids``: the ``run_id`` of each execution of
    the ``XLA Modules`` line, in the order of ``modules`` (None where the
    plane gives none).

:meth:`StageRecording.links` ties each device execution to its launch: by
``run_id`` where both the launch and the device plane give one (the CPU
runtime does; on a v5e the device plane numbers its executions but the
host's launch carries no ``run_id``); otherwise by issue order (one
caller, one stream, and the closed loop waits for each batch's results),
pairing the executions and launches of one batch in order, where both
counts agree, and only when ``PjitFunction(f)`` names the execution's
``jit_f``. An execution is then charged to the innermost ``repro.*``
annotation open at its launch. What cannot be linked is charged to
``unattributed``, never guessed: on a v5e that is a few tiny programs
whose compiled executable another, identical one supplied
(``PjitFunction(squeeze)`` running as ``jit_broadcast_in_dim``).
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
from typing import Dict, List, Optional, Tuple

from bench.lib.devtrace import (DEVICE_PREFIX, MODULES_LINE, Profiled,
                                Recording, _stats, module_name, op_kind,
                                reduce_xplane)

PROGRAM_PREFIX = "repro."
STAGE_PREFIX = "repro.engine."
LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
UNATTRIBUTED = "unattributed"
NO_STAGE = "no stage"
BATCH_START, BATCH_END = "bench.issue", "bench.fetch"


def stage_of(annotation: str) -> str:
    """Short stage name of a program annotation
    (``repro.engine.directory_match`` -> ``directory_match``)."""
    if annotation.startswith(STAGE_PREFIX):
        return annotation[len(STAGE_PREFIX):]
    return annotation


def link_by_run_id(launches: List[list], run_ids: List) -> List[Optional[int]]:
    """For each execution, the index of the launch with its ``run_id``."""
    by_id = {l[3]: i for i, l in enumerate(launches) if l[3] is not None}
    return [by_id.get(r) if r is not None else None for r in run_ids]


def link_by_order(launches: List[list], modules: List[list],
                  bounds: List[Tuple[float, float]]
                  ) -> List[Optional[int]]:
    """For each execution, the index of its launch by issue order: within
    each ``(t0, t1)`` bound (one batch of the closed loop), the k-th
    launch issued runs as the k-th execution. A bound whose counts
    differ links nothing; a pair whose names differ stays unlinked."""
    links: List[Optional[int]] = [None] * len(modules)
    lstarts = [l[1] for l in launches]
    mstarts = [m[1] for m in modules]
    for t0, t1 in bounds:
        li = range(bisect_left(lstarts, t0), bisect_left(lstarts, t1))
        mi = range(bisect_left(mstarts, t0), bisect_left(mstarts, t1))
        if len(li) != len(mi):
            continue
        for i, j in zip(li, mi):
            if modules[j][0] == "jit_" + launches[i][0]:
                links[j] = i
    return links


@dataclasses.dataclass
class StageRecording(Recording):
    program: List[list] = dataclasses.field(default_factory=list)
    launches: List[list] = dataclasses.field(default_factory=list)

    # -- linking ------------------------------------------------------------

    def batch_bounds(self) -> List[Tuple[float, float]]:
        """``(issue start, fetch end)`` of each batch; the whole time line
        where the trace has no batches."""
        issues = sorted(h[1] for h in self.host if h[0] == BATCH_START)
        fetches = sorted(h[1] + h[2] for h in self.host
                         if h[0] == BATCH_END)
        if not issues or len(issues) != len(fetches):
            return [(float("-inf"), float("inf"))]
        return list(zip(issues, fetches))

    def _by_run_id(self, dev: Dict[str, list]) -> bool:
        return (any(r is not None for r in dev.get("module_run_ids") or [])
                and any(l[3] is not None for l in self.launches))

    def links(self, dev: Dict[str, list]) -> List[Optional[int]]:
        """Launch index of each execution of ``dev`` (None: unlinked)."""
        if self._by_run_id(dev):
            return link_by_run_id(self.launches, dev["module_run_ids"])
        return link_by_order(self.launches, dev["modules"],
                             self.batch_bounds())

    def launch_stages(self) -> List[str]:
        """The innermost program annotation open at each launch."""
        levels = Recording(self.window, {}, self.program)._host_levels()
        out = []
        for l in self.launches:
            name = Recording._host_at(levels, l[1])
            out.append(NO_STAGE if name == "no annotation"
                       else stage_of(name))
        return out

    def _charged_ops(self):
        """Per active device: each op with the stage its execution is
        charged to."""
        stages = self.launch_stages()
        for dev in self._active():
            mods = dev["modules"]
            links = self.links(dev)
            starts = [m[1] for m in mods]
            out = []
            for o in dev["ops"]:
                j = bisect_right(starts, o[3]) - 1
                i = (links[j] if j >= 0 and o[3] < mods[j][1] + mods[j][2]
                     else None)
                out.append((o, UNATTRIBUTED if i is None else stages[i]))
            yield out

    # -- reading ------------------------------------------------------------

    def stage_busy_s(self) -> Dict[str, float]:
        """Device seconds charged to each stage: the union of its ops'
        intervals inside the window, per device."""
        tot: Dict[str, float] = defaultdict(float)
        n = 0
        for charged in self._charged_ops():
            n += 1
            per: Dict[str, list] = defaultdict(list)
            for o, stage in charged:
                per[stage].append(o)
            for stage, ops in per.items():
                sub = Recording(self.window, {"d": {"ops": ops}}, [])
                tot[stage] += sum(b - a for a, b in
                                  sub.busy_intervals(sub.devices["d"]))
        return {k: v / max(1, n) / 1e9 for k, v in tot.items()}

    def link_stats(self) -> Dict[str, object]:
        """Launches, executions, how many executions were linked, and
        how (``run_id`` or ``order``), per device."""
        out = {"launches": len(self.launches), "executions": 0,
               "linked": 0, "by": "order"}
        for dev in self._active():
            if self._by_run_id(dev):
                out["by"] = "run_id"
            out["executions"] += len(dev["modules"])
            out["linked"] += sum(i is not None for i in self.links(dev))
        return out

    def stage_ops(self, n: int = 10) -> List[list]:
        """The ``n`` ``<stage> | <module>/<op kind>`` groups that took most
        device time (op durations, per device)."""
        tot: Dict[str, float] = defaultdict(float)
        k = 0
        for charged in self._charged_ops():
            k += 1
            for o, stage in charged:
                tot[f"{stage} | {o[0]}/{op_kind(o[1])}"] += o[4] / 1e9
        return [[name, s / max(1, k)] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def stage_idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds grouped as ``<bench annotation>/<innermost program
        annotation> > <next program>``; the ``n`` largest groups."""
        tot: Dict[str, float] = defaultdict(float)
        devs = self._active()
        bench = self._host_levels()
        prog = Recording(self.window, {}, self.program)._host_levels()
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
                    mid = (edge + a) / 2
                    host = self._host_at(bench, mid)
                    stage = self._host_at(prog, mid)
                    if stage != "no annotation":
                        host = f"{host}/{stage_of(stage)}"
                    tot[f"{host} > {nxt}"] += (a - edge) / 1e9
                edge = max(edge, b)
        k = max(1, len(devs))
        return [[name, s / k] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def batches(self) -> List[Dict]:
        """Per batch of the closed loop: its host milliseconds, the host
        milliseconds inside each program annotation (children included in
        their parents), the device milliseconds charged to each stage, and
        the device programs it ran."""
        bounds = self.batch_bounds()
        if bounds[0][0] == float("-inf"):
            return []
        out = [{"ms": (t1 - t0) / 1e6, "host_ms": defaultdict(float),
                "device_ms": defaultdict(float), "programs": 0}
               for t0, t1 in bounds]
        starts = [t0 for t0, _ in bounds]

        def batch_of(t):
            j = bisect_right(starts, t) - 1
            return j if j >= 0 and t < bounds[j][1] else None

        for name, t, dur in self.program:
            j = batch_of(t)
            if j is not None:
                out[j]["host_ms"][stage_of(name)] += dur / 1e6
        devs = self._active()
        for charged in self._charged_ops():
            for o, stage in charged:
                j = batch_of(o[3])
                if j is not None:
                    out[j]["device_ms"][stage] += o[4] / 1e6 / len(devs)
        for dev in devs:
            for m in dev["modules"]:
                j = batch_of(m[1])
                if j is not None:
                    out[j]["programs"] += 1
        for b in out:
            b["host_ms"] = dict(b["host_ms"])
            b["device_ms"] = dict(b["device_ms"])
            b["programs"] = b["programs"] // max(1, len(devs))
        return out

    # -- storage -------------------------------------------------------------

    def to_json(self) -> Dict:
        d = super().to_json()
        d["program"] = self.program
        d["launches"] = self.launches
        return d

    @classmethod
    def from_json(cls, d: Dict) -> "StageRecording":
        return cls(tuple(d["window"]), d["devices"], d["host"],
                   d.get("program", []), d.get("launches", []))


def _launches(line_events) -> List[list]:
    """The outermost ``PjitFunction(f)`` events of one host line, each
    with the ``run_id`` of the first execute event inside it."""
    evs = sorted(line_events, key=lambda e: (e[1], -e[2]))
    out: List[list] = []
    end = float("-inf")
    for name, start, dur, st in evs:
        m = LAUNCH.match(name)
        if m and start >= end:
            out.append([m.group(1), start, dur, None])
            end = start + dur
        elif (out and start < end and out[-1][3] is None
              and "run_id" in st and "hlo_op" not in st):
            out[-1][3] = str(st["run_id"])
    return out


def reduce_stages(path: str) -> StageRecording:
    from jax.profiler import ProfileData
    base = reduce_xplane(path)
    pd = ProfileData.from_file(path)
    program: List[list] = []
    launches: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = sorted(
                        ((module_name(ev.name), float(ev.start_ns),
                          _stats(ev).get("run_id")) for ev in line.events),
                        key=lambda m: m[1])
                    base.devices[plane.name]["module_run_ids"] = [
                        None if r is None else str(r) for _, _, r in mods]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append([ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)])
                    elif LAUNCH.match(ev.name):
                        evs.append((ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), {}))
                    else:
                        st = _stats(ev)
                        if "run_id" in st:
                            evs.append((ev.name, float(ev.start_ns),
                                        float(ev.duration_ns), st))
                launches.extend(_launches(evs))
    launches.sort(key=lambda l: l[1])
    program.sort(key=lambda h: h[1])
    return StageRecording(base.window, base.devices, base.host, program,
                          launches)


@contextlib.contextmanager
def profile():
    """:func:`devtrace.profile` with :func:`reduce_stages` as the
    reduction; afterwards ``.recording`` is a :class:`StageRecording`."""
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
        out.recording = reduce_stages(max(paths, key=os.path.getmtime))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def device_ms_per_batch(ctx, names) -> Optional[float]:
    """Device milliseconds per profiled batch charged to the listed
    stages, summed over those present; None when the context holds no
    stage recording or none of them ran."""
    rec = ctx.recording
    if (not isinstance(rec, StageRecording) or not rec.launches
            or ctx.traced_batches <= 0):
        return None
    per = rec.stage_busy_s()
    got = [per[s] for s in names if s in per]
    if not got:
        return None
    return 1e3 * sum(got) / ctx.traced_batches
