"""The four-chip sharded cell: its system driven end to end on four forced
CPU devices at a tiny size, its refusal of a program that cannot place a
sharded catalog, and its four readers on a trace recorded on a
four-chip TPU v5e host.

The data (``data/rec_imagenet-L16-x4.r90.b128.json``) are the first two
profiled batches of a ``--trace 1`` run of the cell at its full 4,194,304
items (``python3 bench/tools.py record ... --batches 2``)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench.lib import cell as cellmod
from bench.lib.devtrace import Recording, op_kind
from bench.lib.layers import LayerContext, load_module, load_reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "imagenet-L16-x4.r90.b128"
READERS = ("stage_ms.collective", "device_ms.all_gather", "probe_skew",
           "bucket_gather_roofline.sharded")
OWNED = "repro.engine.distributed.owned_probes.shard"

TINY_RUN = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {src!r}]
    from bench.lib import cell as cellmod
    c = cellmod.load_cell("imagenet-L16-x4.r90.b128")
    c.config["data"]["num_items"] = 16384
    c.config["index"].update(code_len=8, m=2)
    c.mix["pool_batches"] = 2
    kept = {{}}
    line = cellmod.run_cell(c, 2 ** 33 + 12345, 0.5, True,
                            on_layers=lambda ctx: kept.setdefault("c", ctx))
    ctx = kept["c"]
    line["shapes"] = ctx.shapes
    line["tracked"] = sorted(ctx.tracked)
    print("TINY_JSON " + json.dumps(line))
""")


@pytest.fixture(scope="module")
def tiny_line():
    """One traced run of the cell at 16,384 items (8-bit codes, 2 ranges,
    so that ``auto`` still takes the bucket engine) on four CPU devices."""
    code = TINY_RUN.format(root=str(ROOT), src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("TINY_JSON ")]
    assert lines, out.stderr[-2000:]
    return json.loads(lines[-1].split(" ", 1)[1])


def test_tiny_sharded_run_is_correct(tiny_line):
    assert tiny_line["correct"], tiny_line["checks"]
    assert tiny_line["device"]["count"] == 4
    s = tiny_line["shapes"]
    assert s["engine"] == "bucket" and s["shards"] == 4
    assert s["rows_per_shard"] * 4 >= s["num_items"] == 16384
    assert 0 < s["probe_width"] <= 16384
    assert s["runs"] == s["num_buckets"] + 1
    assert 0 < s["gather_slots"] <= s["probe_width"]


def test_tiny_sharded_run_reports_its_program_metrics(tiny_line):
    """The tracked segment carries the collective span and one owned-probe
    observation per shard; the metrics that read them are in the line
    wherever the cell lists them."""
    assert "repro.engine.distributed.collective" in tiny_line["tracked"]
    assert [t for t in tiny_line["tracked"] if t.startswith(OWNED)] == [
        f"{OWNED}{s}" for s in range(4)]
    assert {"probe_width", "plan_ms"} <= set(tiny_line["metrics"])


def test_program_without_a_mesh_build_is_refused(monkeypatch):
    """The cell's files laid over a program whose ``build_sharded`` cannot
    place its shards over a mesh fail before anything is built."""
    from repro.core import distributed

    def old_build_sharded(spec, items, key, num_shards, *, align="bucket"):
        raise AssertionError("must not be called")

    monkeypatch.setattr(distributed, "build_sharded", old_build_sharded)
    c = cellmod.load_cell(CELL, ROOT)
    make = load_module("systems", c.config["system"]).make
    with pytest.raises(RuntimeError, match="mesh"):
        make(c.config, c.mix, None, None, {})


def ctx_of() -> LayerContext:
    return LayerContext.from_json(
        json.loads((DATA / f"rec_{CELL}.json").read_text()))


def test_recording_is_from_the_four_chip_host():
    ctx = ctx_of()
    assert sorted(ctx.recording.devices) == [
        f"/device:TPU:{i}" for i in range(4)]
    assert all(d["ops"] for d in ctx.recording.devices.values())
    assert ctx.shapes["shards"] == 4 and ctx.shapes["engine"] == "bucket"
    assert ctx.shapes["num_items"] == 4194304
    assert ctx.traced_batches == 2


def test_stage_ms_collective():
    ctx = ctx_of()
    h = ctx.tracked["repro.engine.distributed.collective"]
    assert h["count"] == ctx.tracked_batches
    got = load_reader("stage_ms.collective")(ctx)
    assert got == pytest.approx(1e3 * h["total"] / ctx.tracked_batches,
                                rel=1e-9)
    assert got > 0


def test_device_ms_all_gather():
    """The merge's all-gather ops, summed per chip and averaged over the
    four chips, per profiled batch; every chip runs them."""
    ctx = ctx_of()
    per_chip = [sum(o[4] for o in dev["ops"]
                    if op_kind(o[1]).startswith("all-gather"))
                for dev in ctx.recording.devices.values()]
    assert all(ns > 0 for ns in per_chip)
    want = sum(per_chip) / 4 / 1e6 / ctx.traced_batches
    got = load_reader("device_ms.all_gather")(ctx)
    assert got == pytest.approx(want, rel=1e-9)


def test_probe_skew():
    """The busiest shard's mean owned probes over the mean across shards;
    the shards' owned probes add up to the planned width."""
    ctx = ctx_of()
    means = [ctx.tracked[f"{OWNED}{s}"]["total"]
             / ctx.tracked[f"{OWNED}{s}"]["count"] for s in range(4)]
    assert sum(means) == pytest.approx(ctx.shapes["probe_width"], rel=1e-9)
    got = load_reader("probe_skew")(ctx)
    assert got == pytest.approx(max(means) / (sum(means) / 4), rel=1e-12)
    assert 1.0 <= got <= 4.0


def test_bucket_gather_roofline_sharded():
    """The gather calls inside the collective, on the one chip that owns
    the planned slots: bytes per call (every directory run read for a
    block of slots) times calls, over the calls' device time."""
    ctx = ctx_of()
    s = ctx.shapes
    calls = [o[4] for dev in ctx.recording.devices.values()
             for o in dev["ops"] if op_kind(o[1]) == "bucket_gather_pallas"]
    # one call per 4,096-slot block of the 131,072 planned, per batch
    assert len(calls) == ctx.traced_batches * s["probe_width"] \
        // s["gather_slots"]
    per_call = 4 * s["batch"] * (2 * s["runs"] + 1 + s["gather_slots"])
    want = 100 * len(calls) * per_call / 819e9 / (sum(calls) / 1e9)
    got = load_reader("bucket_gather_roofline.sharded")(ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 < got < 100


@pytest.mark.parametrize("name", READERS)
def test_sharded_reader_with_nothing_to_read_returns_none(name):
    read = load_reader(name)
    assert read(LayerContext()) is None
    empty = Recording((0.0, 1e9), {"/device:TPU:0": {"ops": [],
                                                      "modules": []}}, [])
    assert read(LayerContext(recording=empty, traced_batches=2,
                             shapes={"engine": "bucket"})) is None
