"""Device work charged to the engine's stages (``bench/lib/stages.py``):
the program's spans as profiler annotations on the CPU, the link from each
device execution to its launch, and each stage reader on small traces
recorded on one TPU v5e against the same quantity worked out here by hand.

The data (``data/stages_<cell>.json``) are the first three profiled
batches of ``python3 bench/stage_trace.py`` runs of each cell at 262,144
items."""

import json
from collections import Counter
from pathlib import Path

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench.lib import stages
from bench.lib.layers import LayerContext, load_reader
from bench.lib.stages import (StageRecording, UNATTRIBUTED, _launches,
                              link_by_order, link_by_run_id)

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BUCKET, DENSE = "imagenet-L16.r90.b128", "imagenet-L32.r90.b128"
NEW = ("device_ms.match", "device_ms.select", "device_ms.planned_take",
       "device_ms.rerank", "host_ms.plan", "programs_per_batch")
IN_BENCHMARK = ("host_ms.plan", "programs_per_batch")
STAGE_NAMES = {
    "device_ms.match": ("directory_match", "dense_match"),
    "device_ms.select": ("segmented_gather", "dense_select", "planned_take"),
    "device_ms.planned_take": ("planned_take",),
    "device_ms.rerank": ("re_rank", "top_k"),
}


# -- linking, on hand-made events ---------------------------------------------


def two_batches(run_ids=True, names=("gather", "gather", "cumsum")):
    """Two closed-loop batches; in each, ``directory_match`` launches one
    program and ``segmented_gather`` one, then its child ``planned_take``
    one."""
    host = [["bench.window", 0, 1000], ["bench.issue", 0, 400],
            ["bench.fetch", 400, 50], ["bench.issue", 500, 400],
            ["bench.fetch", 900, 50]]
    program, launches, mods = [], [], []
    for b, t in enumerate((0, 500)):
        program += [["repro.engine.query", t + 10, 380],
                    ["repro.engine.directory_match", t + 20, 100],
                    ["repro.engine.segmented_gather", t + 130, 200],
                    ["repro.engine.planned_take", t + 150, 50]]
        for k, (lt, name) in enumerate(zip((30, 140, 160), names)):
            rid = str(3 * b + k) if run_ids else None
            launches.append([name, t + lt, 5, rid])
        mods += [["jit_gather", t + 40, 20], ["jit_gather", t + 145, 10],
                 ["jit_cumsum", t + 170, 30]]
    dev = {"ops": [[m[0], "fusion.3", "", m[1], m[2]] for m in mods],
           "modules": mods}
    if run_ids:
        dev["module_run_ids"] = [str(i) for i in range(len(mods))]
    return StageRecording((0, 1000), {"/device:TPU:0": dev}, host, program,
                          launches)


def dev_of(rec):
    (dev,) = rec.devices.values()
    return dev


def test_link_by_run_id():
    rec = two_batches()
    assert rec.links(dev_of(rec)) == list(range(6))
    assert rec.link_stats()["by"] == "run_id"
    # the run id decides, not the order: swapped ids swap the links
    assert link_by_run_id(rec.launches, ["1", "0", None, "9"]) == \
        [1, 0, None, None]


def test_link_by_order_within_each_batch():
    rec = two_batches(run_ids=False)
    assert rec.links(dev_of(rec)) == list(range(6))
    assert rec.launch_stages() == ["directory_match", "segmented_gather",
                                   "planned_take"] * 2
    busy = rec.stage_busy_s()
    assert busy == pytest.approx({"directory_match": 40e-9,
                                  "segmented_gather": 20e-9,
                                  "planned_take": 60e-9}, rel=1e-12)
    assert sum(busy.values()) == pytest.approx(rec.busy_s(), rel=1e-12)


def test_name_mismatch_leaves_the_execution_unattributed():
    # each batch's second launch names another program than the
    # execution it would pair with: that execution alone is unlinked
    rec = two_batches(run_ids=False, names=("gather", "sort", "cumsum"))
    links = rec.links(dev_of(rec))
    assert links == [0, None, 2, 3, None, 5]
    busy = rec.stage_busy_s()
    assert busy[UNATTRIBUTED] == pytest.approx(20e-9, rel=1e-12)
    assert "segmented_gather" not in busy
    assert rec.link_stats() == {"launches": 6, "executions": 6,
                                "linked": 4, "by": "order"}


def test_batch_whose_counts_differ_links_nothing():
    rec = two_batches(run_ids=False)
    rec.launches.pop(4)                       # a launch the trace lost
    assert link_by_order(rec.launches, dev_of(rec)["modules"],
                         rec.batch_bounds()) == [0, 1, 2, None, None, None]


def test_launches_outermost_pjit_with_the_run_id_inside():
    evs = [("PjitFunction(gather)", 0.0, 10.0, {}),
           ("PjitFunction(gather)", 1.0, 8.0, {}),
           ("ExecuteHelper", 2.0, 3.0, {"run_id": 77}),
           ("fusion", 2.5, 1.0, {"run_id": 77, "hlo_op": "fusion"}),
           ("PjitFunction(cumsum)", 20.0, 5.0, {}),
           ("Execute", 30.0, 1.0, {"run_id": 5})]       # outside any launch
    assert _launches(evs) == [["gather", 0.0, 10.0, "77"],
                              ["cumsum", 20.0, 5.0, None]]


def test_stage_breakdowns_name_stage_and_program():
    rec = two_batches()
    ops = dict(rec.stage_ops(10))
    assert ops == pytest.approx({
        "planned_take | jit_cumsum/fusion": 60e-9,
        "directory_match | jit_gather/fusion": 40e-9,
        "segmented_gather | jit_gather/fusion": 20e-9}, rel=1e-12)
    # each gap is named by what the host was in at its middle: [0, 40)
    # and both [60, 145) gaps in directory_match, [155, 170) in
    # planned_take, [200, 540) between the stages of the first query,
    # [700, 1000) after the last launch
    gaps = dict(rec.stage_idle_gaps(10 ** 6))
    assert gaps == pytest.approx({
        "bench.issue/directory_match > jit_gather": (40 + 85 + 85) * 1e-9,
        "bench.issue/planned_take > jit_cumsum": (15 + 15) * 1e-9,
        "bench.issue/query > jit_gather": 340e-9,
        "bench.issue/query > end of window": 300e-9}, rel=1e-12)
    assert sum(gaps.values()) == pytest.approx(
        rec.window_s - rec.busy_s(), rel=1e-12)
    (b0, b1) = rec.batches()
    assert b0 == b1
    assert b0["programs"] == 3
    assert b0["host_ms"]["segmented_gather"] == pytest.approx(200e-6)
    assert b0["device_ms"] == pytest.approx(
        {"directory_match": 20e-6, "segmented_gather": 10e-6,
         "planned_take": 30e-6})


# -- the program's spans on a CPU profile -------------------------------------


@pytest.fixture(scope="module")
def small_index():
    from repro.core.index import IndexSpec, build
    from repro.data.synthetic import make_dataset
    ds = make_dataset("imagenet", jax.random.PRNGKey(0), n=2000, d=24,
                      num_queries=40)
    spec = IndexSpec(family="simple", code_len=16, m=4,
                     charge_index_bits=False)
    idx = build(spec, ds.items, jax.random.PRNGKey(5),
                calibration_queries=ds.queries[:32], calibration_k=10)
    return idx, ds.queries[32:]


@pytest.mark.parametrize("tracked", [False, True])
@pytest.mark.parametrize("engine", ["bucket", "dense"])
def test_profiled_query_nests_launches_in_stage_annotations(
        small_index, engine, tracked):
    from repro.core.engine import QueryEngine
    from repro.obs import Tracker
    idx, queries = small_index
    eng = QueryEngine(idx, engine=engine,
                      tracker=Tracker() if tracked else None)
    eng.query(queries, 5, recall_target=0.9)
    with stages.profile() as prof:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.issue"):
                _, ids = eng.query(queries, 5, recall_target=0.9)
            with TraceAnnotation("bench.fetch"):
                jax.device_get(ids)
    rec = prof.recording
    match, select = (("directory_match", "segmented_gather")
                     if engine == "bucket" else ("dense_match",
                                                 "dense_select"))
    device_stages = {"hash_encode", match, select, "planned_take",
                     "re_rank", "top_k"}
    assert ({stages.stage_of(h[0]) for h in rec.program}
            == device_stages | {"query", "plan"})
    launched = Counter(rec.launch_stages())
    assert set(launched) == device_stages
    # every launch lies inside the annotation it is charged to, and the
    # CPU runtime's execute event gives each one its run id
    for l, stage in zip(rec.launches, rec.launch_stages()):
        assert any(stages.stage_of(h[0]) == stage
                   and h[1] <= l[1] < h[1] + h[2] for h in rec.program)
        assert l[3] is not None
    # the plan runs on the host: it launches nothing
    (plan,) = [h for h in rec.program if h[0] == "repro.engine.plan"]
    assert not [l for l in rec.launches
                if plan[1] <= l[1] < plan[1] + plan[2]]


# -- the readers on traces recorded on the chip -------------------------------


def ctx_of(cell) -> LayerContext:
    d = json.loads((DATA / f"stages_{cell}.json").read_text())
    ctx = LayerContext.from_json({**d, "recording": None})
    ctx.recording = StageRecording.from_json(d["recording"])
    return ctx


def innermost(program, t):
    """The shortest program annotation open at ``t``, by brute force."""
    open_ = [h for h in program if h[1] <= t < h[1] + h[2]]
    if not open_:
        return stages.NO_STAGE
    return stages.stage_of(min(open_, key=lambda h: h[2])[0])


def union_ns(spans):
    total, end = 0.0, None
    for a, b in sorted(spans):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def by_hand(ctx):
    """Device ns per stage: each op's execution found by its interval;
    the k-th execution of a batch is the batch's k-th launch where their
    names agree (the v5e's launches carry no run id)."""
    rec = ctx.recording
    (dev,) = rec.devices.values()
    w0, w1 = rec.window
    stage_of_exec = {}
    for t0, t1 in rec.batch_bounds():
        ls = [l for l in rec.launches if t0 <= l[1] < t1]
        ms = [j for j, m in enumerate(dev["modules"]) if t0 <= m[1] < t1]
        assert len(ls) == len(ms)
        for l, j in zip(ls, ms):
            if dev["modules"][j][0] == "jit_" + l[0]:
                stage_of_exec[j] = innermost(rec.program, l[1])
    spans = {}
    for o in dev["ops"]:
        stage = UNATTRIBUTED
        for j, m in enumerate(dev["modules"]):
            if m[1] <= o[3] < m[1] + m[2]:
                stage = stage_of_exec.get(j, UNATTRIBUTED)
                break
        a, b = max(o[3], w0), min(o[3] + o[4], w1)
        if b > a:
            spans.setdefault(stage, []).append((a, b))
    return {k: union_ns(v) for k, v in spans.items()}


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_stage_recording_is_from_the_chip(cell):
    ctx = ctx_of(cell)
    (dev,) = ctx.recording.devices.values()
    assert ctx.shapes["engine"] == ("bucket" if cell == BUCKET else "dense")
    assert ctx.traced_batches == 3
    assert ctx.recording.launches and ctx.recording.program
    assert len(dev["module_run_ids"]) == len(dev["modules"])
    # the device numbers its executions in issue order; the launches on
    # the host carry no run id, so the stages are linked by order
    assert [int(r) for r in dev["module_run_ids"]] == list(range(
        int(dev["module_run_ids"][0]),
        int(dev["module_run_ids"][0]) + len(dev["modules"])))
    assert all(l[3] is None for l in ctx.recording.launches)


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_device_ms_readers(cell):
    ctx = ctx_of(cell)
    ns = by_hand(ctx)
    for name, names in STAGE_NAMES.items():
        want = sum(ns.get(s, 0.0) for s in names) / 1e6 / 3
        got = load_reader(name)(ctx)
        assert got == pytest.approx(want, rel=1e-9), name
        assert got > 0, name


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_stages_add_up_to_the_busy_time(cell):
    rec = ctx_of(cell).recording
    busy = rec.stage_busy_s()
    assert sum(busy.values()) == pytest.approx(rec.busy_s(), rel=0.02)
    assert busy.get(UNATTRIBUTED, 0.0) <= 0.01 * rec.busy_s()


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_programs_per_batch(cell):
    ctx = ctx_of(cell)
    (dev,) = ctx.recording.devices.values()
    w0, w1 = ctx.recording.window
    n = sum(1 for m in dev["modules"] if w0 <= m[1] < w1)
    got = load_reader("programs_per_batch")(ctx)
    assert got == pytest.approx(n / 3, rel=1e-12)
    each = [b["programs"] for b in ctx.recording.batches()]
    assert each == [got] * 3


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_host_ms_plan(cell):
    ctx = ctx_of(cell)
    h = ctx.tracked["repro.engine.plan"]
    assert h["count"] == ctx.tracked_batches
    got = load_reader("host_ms.plan")(ctx)
    assert got == pytest.approx(1e3 * h["total"] / ctx.tracked_batches,
                                rel=1e-9)
    assert 0 < got < 1e3 * ctx.tracked["repro.engine.query"]["total"]


@pytest.mark.parametrize("name", NEW)
def test_new_reader_with_nothing_to_read_returns_none(name):
    read = load_reader(name)
    assert read(LayerContext()) is None
    # a recording the harness reduced without the stage data
    from bench.lib.devtrace import Recording
    empty = Recording((0.0, 1e9), {"/device:TPU:0": {"ops": [],
                                                      "modules": []}}, [])
    assert read(LayerContext(recording=empty, traced_batches=3,
                             shapes={"engine": "dense"})) is None


@pytest.mark.parametrize("name", IN_BENCHMARK)
def test_new_metric_has_a_reader_and_both_cells(name):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert callable(load_reader(name))
    assert m["workloads"] == [BUCKET, DENSE]
    assert m["moves"] == "qps"
    assert BENCH["per_layer"][-len(IN_BENCHMARK):] == [
        n for n in BENCH["per_layer"] if n["name"] in IN_BENCHMARK]
