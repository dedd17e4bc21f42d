"""Each per-layer reader on small traces recorded on one TPU v5e, against
the same quantity worked out here by hand from the recorded events.

The data (``data/rec_<cell>.json``) are the first three profiled batches
of a ``--trace 1`` run of each cell at 262,144 items (``python3
bench/tools.py record ...``)."""

import json
import re
from pathlib import Path

import pytest

from bench.lib import roofline
from bench.lib.devtrace import Recording
from bench.lib.layers import LayerContext, load_reader

DATA = Path(__file__).resolve().parent / "data"
BUCKET, DENSE = "imagenet-L16.r90.b128", "imagenet-L32.r90.b128"


def ctx_of(cell) -> LayerContext:
    return LayerContext.from_json(
        json.loads((DATA / f"rec_{cell}.json").read_text()))


def read(name, ctx):
    return load_reader(name)(ctx)


def only_device(ctx):
    (dev,) = ctx.recording.devices.values()
    return dev


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


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_recording_is_from_the_chip(cell):
    ctx = ctx_of(cell)
    assert ctx.peaks == roofline.peaks("TPU v5 lite")
    assert ctx.shapes["engine"] == ("bucket" if cell == BUCKET else "dense")
    assert ctx.traced_batches == 3
    assert len(only_device(ctx)["ops"]) > 0


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_device_idle_pct(cell):
    ctx = ctx_of(cell)
    w0, w1 = ctx.recording.window
    busy = union_ns([(max(o[3], w0), min(o[3] + o[4], w1))
                     for o in only_device(ctx)["ops"]
                     if min(o[3] + o[4], w1) > max(o[3], w0)])
    want = 100.0 * (1.0 - busy / (w1 - w0))
    got = read("device_idle_pct", ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_idle_gaps_add_up_to_the_idle_time(cell):
    rec = ctx_of(cell).recording
    gaps = sum(s for _, s in rec.idle_gaps(10 ** 6))
    assert gaps == pytest.approx(rec.window_s - rec.busy_s(), rel=1e-9)
    assert all(" > " in name for name, _ in rec.idle_gaps())


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_device_ms_sort(cell):
    ctx = ctx_of(cell)
    ns = sum(o[4] for o in only_device(ctx)["ops"]
             if re.sub(r"[.\d]+$", "", o[1]) == "sort" or o[2] == "sort")
    got = read("device_ms.sort", ctx)
    assert got == pytest.approx(ns / 1e6 / 3, rel=1e-9)
    assert got > 0


def _kernel(ctx, program):
    dev = only_device(ctx)
    secs = sum(o[4] for o in dev["ops"] if program in o[0]) / 1e9
    calls = sum(1 for m in dev["modules"] if program in m[0])
    return secs, calls


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_hamming_scan_roofline(cell):
    ctx = ctx_of(cell)
    s = ctx.shapes
    secs, calls = _kernel(ctx, "hamming_pallas")
    assert calls == 3          # one scan per batch
    rows = s["num_buckets"] if cell == BUCKET else s["num_items"]
    nbytes = calls * 4 * (s["batch"] * s["code_words"]
                          + rows * s["code_words"] + s["batch"] * rows)
    want = 100.0 * nbytes / 819e9 / secs
    got = read("hamming_scan_roofline", ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0.0 < got <= 100.0


def test_bucket_gather_roofline():
    ctx = ctx_of(BUCKET)
    s = ctx.shapes
    secs, calls = _kernel(ctx, "bucket_gather_pallas")
    assert calls == 3
    q, runs, p = s["batch"], s["runs"], s["probe_width"]
    nbytes = calls * 4 * (q * (runs + 1) + q * runs + q * p)
    want = 100.0 * nbytes / 819e9 / secs
    got = read("bucket_gather_roofline", ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0.0 < got <= 100.0
    assert read("bucket_gather_roofline", ctx_of(DENSE)) is None


@pytest.mark.parametrize("cell", [BUCKET, DENSE])
def test_program_span_and_counter_readers(cell):
    ctx = ctx_of(cell)
    t, n = ctx.tracked, ctx.tracked_batches
    traverse = (("repro.engine.directory_match",
                 "repro.engine.segmented_gather") if cell == BUCKET else
                ("repro.engine.dense_match", "repro.engine.dense_select"))
    assert read("stage_ms.traverse", ctx) == pytest.approx(
        1e3 * sum(t[s]["total"] for s in traverse) / n, rel=1e-9)
    assert read("stage_ms.rerank", ctx) == pytest.approx(
        1e3 * (t["repro.engine.re_rank"]["total"]
               + t["repro.engine.top_k"]["total"]) / n, rel=1e-9)
    pw = t["repro.engine.probe_width"]
    assert read("probe_width", ctx) == pytest.approx(
        pw["total"] / pw["count"], rel=1e-9)
    assert read("probe_width", ctx) == ctx.shapes["probe_width"]
    c = ctx.host_calls["planner.resolve_budgets"]
    assert c["total_s"] >= 0.25
    assert read("plan_ms", ctx) == pytest.approx(
        1e3 * c["total_s"] / c["count"], rel=1e-9)


def test_hbm_in_use_gib():
    ctx = LayerContext(memory={"live_bytes": 3 << 29,
                               "bytes_in_use": (3 << 29) + (1 << 20)})
    assert read("hbm_in_use_gib", ctx) == pytest.approx(1.5 + 2 ** -10,
                                                        rel=1e-12)


@pytest.mark.parametrize("name", [
    "probe_width", "plan_ms", "stage_ms.traverse", "stage_ms.rerank",
    "bucket_gather_roofline", "hamming_scan_roofline", "device_ms.sort",
    "device_idle_pct", "hbm_in_use_gib"])
def test_reader_with_nothing_to_read_returns_none(name):
    assert read(name, LayerContext()) is None
    empty = Recording((0.0, 1e9), {"/device:TPU:0": {"ops": [],
                                                      "modules": []}}, [])
    assert read(name, LayerContext(recording=empty, traced_batches=3,
                                   shapes={"engine": "dense"})) is None
