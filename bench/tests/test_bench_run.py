"""The harness driven end to end on the CPU at a tiny size, with the
look for a chip skipped: a sound run is correct, and the control and each
fault planted under the timed path come out not correct. Without a TPU
the command exits non-zero and prints no result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench.lib import cell as cellmod
from bench.references.exact_mips import control_system

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 33 + 12345
BUCKET, DENSE = "imagenet-L16.r90.b128", "imagenet-L32.r90.b128"


def tiny(name):
    """The cell at 8,192 items and two pooled batches. The bucket cell
    keeps fewer ranges and bits, so that at this size ``auto`` still
    resolves to the bucket engine."""
    c = cellmod.load_cell(name, ROOT)
    c.config["data"]["num_items"] = 8192
    c.mix["pool_batches"] = 2
    if name == BUCKET:
        c.config["index"].update(code_len=8, m=2)
    return c


def run(name, trace=False, **kw):
    return cellmod.run_cell(tiny(name), SEED, 0.5, trace, **kw)


def failed_checks(line):
    return {k for k, v in line["checks"].items()
            if not (v["value"] >= v["limit"] if v["rule"] == ">="
                    else v["value"] <= v["limit"])}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", [BUCKET, DENSE])
def test_sound_run_is_correct(name, trace):
    line = run(name, trace)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) <= {m["name"] for m in
                                    cellmod.load_cell(name, ROOT).end_to_end
                                    + cellmod.load_cell(name, ROOT).per_layer}
    if trace:
        assert {"probe_width", "plan_ms", "stage_ms.traverse",
                "stage_ms.rerank"} <= set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert {"qps", "latency_p95_ms", "index_hbm_gib",
                "setup_s"} == set(line["metrics"])


def test_control_is_not_correct():
    line = run(BUCKET, make_system=control_system)
    assert not line["correct"]
    assert "score_rel_err" in failed_checks(line)


def _wrap_rerank(monkeypatch, fault):
    from repro.core import engine
    orig = engine.rerank

    def broken(queries, items, cand, k, tracker=None):
        return fault(orig, queries, items, cand, k, tracker)
    monkeypatch.setattr(engine, "rerank", broken)


def _altered_answer(orig, queries, items, cand, k, tracker):
    vals, ids = orig(queries, items, cand, k, tracker=tracker)
    return vals, ids.at[0, 0].set((ids[0, 0] + 1) % items.shape[0])


def _half_batch(orig, queries, items, cand, k, tracker):
    h = queries.shape[0] // 2
    vals, ids = orig(queries[:h], items, cand[:h], k, tracker=tracker)
    return jnp.concatenate([vals, vals]), jnp.concatenate([ids, ids])


@pytest.mark.parametrize("name", [BUCKET, DENSE])
@pytest.mark.parametrize("fault", [_altered_answer, _half_batch],
                         ids=["altered_answer", "half_batch"])
def test_fault_in_the_answer_is_not_correct(monkeypatch, name, fault):
    _wrap_rerank(monkeypatch, fault)
    line = run(name)
    assert not line["correct"]
    assert "score_rel_err" in failed_checks(line)


@pytest.mark.parametrize("name,stage", [
    (BUCKET, "planned_bucket_candidates"),
    (DENSE, "planned_dense_candidates")])
def test_fault_in_the_traversal_is_not_correct(monkeypatch, name, stage):
    """Candidates shifted to the neighbouring item ids: the re-rank stays
    exact, so only the recall check can see it."""
    from repro.core import engine
    orig = getattr(engine, stage)

    def shifted(buckets, *a, **kw):
        return (orig(buckets, *a, **kw) + 1) % buckets.num_items
    monkeypatch.setattr(engine, stage, shifted)
    line = run(name)
    assert not line["correct"]
    assert failed_checks(line) == {"recall_at_k"}


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", BUCKET, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "nothing was run" in p.stderr
