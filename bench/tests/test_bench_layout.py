"""BENCHMARK.json against the benchmark contract, and every cell's files
found by name."""

import json
import re
from pathlib import Path

import pytest

from bench.lib import cell as cellmod
from bench.lib import layers, roofline

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_bounds():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    c = cellmod.load_cell(name, ROOT)
    assert c.config["name"] == c.workload["config"]
    assert c.mix["name"] == c.workload["traffic"]
    for kind, key in (("systems", "system"), ("references", "reference")):
        assert hasattr(layers.load_module(kind, c.config[key]),
                       "make" if kind == "systems" else "truth")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(layers.load_reader(m["name"]))
    assert c.workload["chips"] in (1, 4)
    assert len(c.workload["why"]) <= 200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file(entry):
    path = ROOT / entry["file"]
    assert path.is_file()
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    cfg = json.loads(path.read_text())
    assert cfg["name"] == entry["name"]
    # every key cut from the published deployment is listed, and only it
    published = cfg["published"]
    assert sorted(entry["reduced"]) == sorted(
        k for k, v in published.items() if cfg["data"][k] != v)
    assert cfg["data"]["dim"] == 150
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    assert 0 < cfg["limits"]["score_rel_err"] < 1e-3


def test_peaks_table_lookup():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
