"""kernelcheck (K1–K5) unit tests: fixture kernels that violate each rule,
pragma handling, baseline round-trip, the regress report round-trip, and
the acceptance assertion that the repo's own registry is clean."""

import importlib.util
import json
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from benchmarks import regress
from repro.analysis import findings as fnd
from repro.analysis import kernel_model as km
from repro.analysis import kernelcheck as kc
from repro.kernels import ops
from repro.kernels.annotations import KernelAnnotation, SentinelSpec


# -- fixture registry machinery ----------------------------------------------


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _fixture_wrapper(*, n=16, bn=4, grid=None, in_map=None, out_map=None,
                     dtype=jnp.float32):
    """A minimal one-operand wrapper: (n,) -> (n,) identity copy with
    configurable grid/index maps (the K1–K3 violation knobs)."""
    grid = grid if grid is not None else (n // bn,)
    in_map = in_map or (lambda i: (i,))
    out_map = out_map or (lambda i: (i,))

    def wrapper(x, *, impl="pallas"):
        return pl.pallas_call(
            _copy_kernel, grid=grid,
            in_specs=[pl.BlockSpec((bn,), in_map)],
            out_specs=pl.BlockSpec((bn,), out_map),
            out_shape=jax.ShapeDtypeStruct((n,), dtype),
        )(x)
    return wrapper


def _reg(wrapper, *, annotation=None, n=16, cost_fn=None, ref_fn=None,
         probe=None, cost_tol=5.0):
    ann = annotation or KernelAnnotation(
        name="fx", grid_names=("i",), pad_contained=True)
    return ops.RegisteredKernel(
        op="fx", wrapper=wrapper, pallas_symbol=None, annotation=ann,
        cost_fn=cost_fn or (lambda m: {"flops": float(m),
                                       "hbm_bytes": 8.0 * m}),
        cost_args=lambda s: (s["n"],),
        ref_fn=ref_fn or (lambda x: x + 1.0),
        make_inputs=lambda s, a: (
            ((jax.ShapeDtypeStruct((s["n"],), jnp.float32) if a
              else jnp.zeros((s["n"],), jnp.float32)),), {}),
        shape_classes=({"n": n},),
        probe=probe, cost_tol=cost_tol)


def _run(reg):
    return kc.run_kernelcheck({"fx": reg}, probes=True,
                              apply_pragmas=False)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# -- passing fixture ----------------------------------------------------------


def test_clean_fixture_has_no_findings():
    findings, report = _run(_reg(_fixture_wrapper()))
    assert findings == []
    assert report["clean"] == 1
    row = report["kernels"]["fx"]["classes"][0]
    assert row["grid"] == [4]
    assert row["vmem_bytes"] > 0
    assert row["ratio"]["flops"] == pytest.approx(1.0)


# -- K1: VMEM budget ----------------------------------------------------------


def test_k1_flags_over_budget_tile():
    # (2048, 2048) f32 block = 16 MiB; double-buffered in+out = 64 MiB
    def wrapper(x, *, impl="pallas"):
        return pl.pallas_call(
            _copy_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((2048, 2048), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((2048, 2048), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
        )(x)

    reg = ops.RegisteredKernel(
        op="fx", wrapper=wrapper, pallas_symbol=None,
        annotation=KernelAnnotation(name="fx", grid_names=("i",),
                                    pad_contained=True),
        cost_fn=lambda m: {"flops": float(m * m), "hbm_bytes": 8.0 * m * m},
        cost_args=lambda s: (s["n"],),
        ref_fn=lambda x: x + 1.0,
        make_inputs=lambda s, a: (
            ((jax.ShapeDtypeStruct((s["n"], s["n"]), jnp.float32) if a
              else jnp.zeros((s["n"], s["n"]), jnp.float32)),), {}),
        shape_classes=({"n": 2048},), cost_tol=5.0)
    findings, report = _run(reg)
    assert "K1" in _rules_of(findings)
    [f] = [f for f in findings if f.rule == "K1"]
    assert "MiB VMEM" in f.message
    assert report["kernels"]["fx"]["classes"][0]["vmem_frac"] > 1.0


def test_k1_charges_declared_transient_peak():
    ann = KernelAnnotation(
        name="fx", grid_names=("i",), pad_contained=True,
        extra_vmem=lambda ins, outs: 100 * 2**20)   # declared 100 MiB peak
    findings, _ = _run(_reg(_fixture_wrapper(), annotation=ann))
    assert "K1" in _rules_of(findings)


# -- K2: index-map bounds -----------------------------------------------------


def test_k2_flags_out_of_bounds_index_map():
    wrapper = _fixture_wrapper(in_map=lambda i: (i + 1,))   # shifts past end
    findings, _ = _run(_reg(wrapper))
    assert "K2" in _rules_of(findings)
    [f] = [f for f in findings if f.rule == "K2"]
    assert "exceeds operand axis" in f.message


def test_k2_flags_negative_index_map():
    wrapper = _fixture_wrapper(in_map=lambda i: (i - 1,))
    findings, _ = _run(_reg(wrapper))
    assert "K2" in _rules_of(findings)


# -- K3: write races ----------------------------------------------------------


def test_k3_flags_undeclared_output_aliasing():
    # every grid point writes out block 0 with no revisit declaration
    wrapper = _fixture_wrapper(n=16, bn=4, grid=(4,),
                               in_map=lambda i: (i,),
                               out_map=lambda i: (0,))
    findings, _ = _run(_reg(wrapper))
    rules = _rules_of(findings)
    assert "K3" in rules
    [f] = [f for f in findings if f.rule == "K3"]
    assert "revisit_dims" in f.message


def test_k3_passes_declared_revisit():
    wrapper = _fixture_wrapper(n=16, bn=4, grid=(4,),
                               in_map=lambda i: (i,),
                               out_map=lambda i: (0,))
    ann = KernelAnnotation(name="fx", grid_names=("i",), revisit_dims=(0,),
                          pad_contained=True)
    findings, _ = _run(_reg(wrapper, annotation=ann))
    # the deliberate accumulate is declared; only K2 stays quiet too
    assert "K3" not in _rules_of(findings)


def test_k3_real_registry_shape_mips_accumulate_is_declared():
    """The mips_topk item axis revisits (i, 0) out blocks — K3 must accept
    it solely because the annotation declares dim 1."""
    reg = ops.KERNEL_REGISTRY["mips_topk"]
    model = km.capture_kernel(reg, reg.shape_classes[0])
    assert kc.check_k3(model, reg.annotation) == []
    bare = KernelAnnotation(name="mips_topk", grid_names=("q", "n"))
    assert kc.check_k3(model, bare) != []


# -- K4: sentinel discipline --------------------------------------------------


def test_k4_flags_missing_padding_discipline():
    ann = KernelAnnotation(name="fx", grid_names=("i",))   # nothing declared
    findings, _ = _run(_reg(_fixture_wrapper(), annotation=ann))
    assert "K4" in _rules_of(findings)
    [f] = [f for f in findings if f.rule == "K4"]
    assert "padding discipline" in f.message


def test_k4_flags_stale_sentinel_declaration():
    ann = KernelAnnotation(
        name="fx", grid_names=("i",),
        sentinel=SentinelSpec(kind="vals", value=-987654321,
                              note="nowhere in the source"))
    findings, _ = _run(_reg(_fixture_wrapper(), annotation=ann))
    assert any(f.rule == "K4" and "stale" in f.message for f in findings)


def test_k4_probe_failure_becomes_finding():
    findings, _ = _run(_reg(
        _fixture_wrapper(),
        probe=lambda: ["fx: padded lanes leaked into the top-k"]))
    assert any(f.rule == "K4" and "padded lanes leaked" in f.message
               for f in findings)


def test_k4_probes_skippable():
    reg = _reg(_fixture_wrapper(),
               probe=lambda: ["fx: padded lanes leaked"])
    findings, _ = kc.run_kernelcheck({"fx": reg}, probes=False,
                                     apply_pragmas=False)
    assert findings == []


# -- K5: cost-model cross-check -----------------------------------------------


def test_k5_flags_mischarged_cost_model():
    # analytic model bills 100x what the oracle jaxpr derives
    findings, report = _run(_reg(
        _fixture_wrapper(),
        cost_fn=lambda m: {"flops": 100.0 * m, "hbm_bytes": 8.0 * m}))
    assert any(f.rule == "K5" and "flops" in f.message for f in findings)
    row = report["kernels"]["fx"]["classes"][0]
    assert row["ratio"]["flops"] == pytest.approx(100.0)


def test_k5_tolerance_is_per_op():
    reg = _reg(_fixture_wrapper(),
               cost_fn=lambda m: {"flops": 100.0 * m, "hbm_bytes": 8.0 * m},
               cost_tol=150.0)
    findings, _ = _run(reg)
    assert "K5" not in _rules_of(findings)


def test_k5_flags_drifted_charge_call():
    """A wrapper billing a different cost fn than the registry declares."""
    def other_cost(m):
        return {"flops": float(m), "hbm_bytes": 8.0 * m}
    other_cost.__name__ = "registered_cost"

    def wrapper(x, *, impl="pallas"):
        _charge("fx", _cost.some_other_cost, x.shape[0])  # noqa: F821
        return pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((4,), lambda i: (i,))],
            out_specs=pl.BlockSpec((4,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((16,), jnp.float32),
        )(x)

    assert kc._billed_cost_fn_name(wrapper, "fx") == "some_other_cost"
    # the AST arm reads source only — no need to execute the broken call
    model = km.capture_kernel(_reg(_fixture_wrapper()), {"n": 16})
    reg = _reg(_fixture_wrapper(), cost_fn=other_cost)
    object.__setattr__(reg, "wrapper", wrapper)
    findings, _ = kc.check_k5(reg, model, {"n": 16})
    assert any("attribution drift" in f.message for f in findings)


# -- pragma handling ----------------------------------------------------------


_PRAGMA_MODULE = """
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...]


# repro-lint: allow[K4] fixture kernel, padding handled by caller
def wrapper(x, *, impl="pallas"):
    return pl.pallas_call(
        _k, grid=(4,),
        in_specs=[pl.BlockSpec((4,), lambda i: (i,))],
        out_specs=pl.BlockSpec((4,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((16,), jnp.float32),
    )(x)
"""


def _import_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_pragma_suppresses_kernel_finding(tmp_path):
    mod_path = tmp_path / "fixture_kernel.py"
    mod_path.write_text(textwrap.dedent(_PRAGMA_MODULE))
    mod = _import_module(mod_path)
    ann = KernelAnnotation(name="fx", grid_names=("i",))   # K4: undeclared
    reg = _reg(mod.wrapper, annotation=ann)
    raw, _ = kc.run_kernelcheck({"fx": reg}, probes=False,
                                apply_pragmas=False)
    assert "K4" in _rules_of(raw)
    filtered, _ = kc.run_kernelcheck({"fx": reg}, probes=False,
                                     apply_pragmas=True)
    assert "K4" not in _rules_of(filtered)


def test_pragma_rule_mismatch_does_not_suppress(tmp_path):
    mod_path = tmp_path / "fixture_kernel2.py"
    mod_path.write_text(textwrap.dedent(
        _PRAGMA_MODULE.replace("allow[K4]", "allow[K1]")))
    mod = _import_module(mod_path)
    ann = KernelAnnotation(name="fx", grid_names=("i",))
    reg = _reg(mod.wrapper, annotation=ann)
    filtered, _ = kc.run_kernelcheck({"fx": reg}, probes=False,
                                     apply_pragmas=True)
    assert "K4" in _rules_of(filtered)


# -- baseline round-trip ------------------------------------------------------


def test_kernel_findings_round_trip_through_baseline(tmp_path):
    ann = KernelAnnotation(name="fx", grid_names=("i",))
    findings, _ = kc.run_kernelcheck({"fx": _reg(_fixture_wrapper(),
                                                 annotation=ann)},
                                     probes=False, apply_pragmas=False)
    assert findings
    bl_path = tmp_path / "baseline.json"
    fnd.save_baseline(bl_path, findings)
    new, suppressed = fnd.split_by_baseline(
        findings, fnd.load_baseline(bl_path))
    assert new == []
    assert {f.key for f in suppressed} == {f.key for f in findings}


# -- report / regress round-trip ----------------------------------------------


def test_report_round_trips_through_regress(tmp_path):
    _, report = kc.run_kernelcheck(probes=False)
    kc.write_report(report, tmp_path / "BENCH_0042.json")
    manifest = regress.load_manifest(str(tmp_path))
    assert len(manifest) == 1
    entry = manifest[0]
    assert entry["kind"] == "kernelcheck"
    assert any(m.endswith("vmem_frac") for m in entry["metrics"])
    rows = regress.check_bounds(entry)
    assert all(r["status"] == "ok" for r in rows)
    # identical reports compare clean relative to each other
    rows, ok = regress.run_gate([entry], [dict(entry, path="other")])
    assert ok


def test_regress_bound_trips_on_dirty_report(tmp_path):
    _, report = kc.run_kernelcheck(probes=False)
    report["clean"] = 0
    report["findings"] = [{"rule": "K1", "path": "x.py", "line": 1,
                           "message": "boom"}]
    kc.write_report(report, tmp_path / "BENCH_0042.json")
    [entry] = regress.load_manifest(str(tmp_path))
    rows = regress.check_bounds(entry)
    assert any(r["status"] == "violated" for r in rows)


# what the code decides per kernel shape class. The jaxpr-derived counts
# ("jaxpr", "ratio") follow the installed jax's tracing and are not pinned.
PINNED_FIELDS = ("shapes", "grid", "kernel", "vmem_bytes", "vmem_frac",
                 "declared")


def _pinned(kernels):
    return {op: [{f: c[f] for f in PINNED_FIELDS} for c in v["classes"]]
            for op, v in kernels.items()}


def test_committed_trajectory_report_matches_current():
    """The newest committed kernelcheck report (BENCH_*.json) must stay in
    sync with what the code decides: grids, VMEM bytes and declared
    bills."""
    reports = [p for p in sorted(km.REPO_ROOT.glob("BENCH_*.json"))
               if json.loads(p.read_text()).get("bench") == "kernelcheck"]
    committed = json.loads(reports[-1].read_text())
    _, current = kc.run_kernelcheck(probes=False)
    assert _pinned(committed["kernels"]) == _pinned(
        json.loads(json.dumps(current["kernels"])))
    assert committed["clean"] == 1


# -- the repo's own registry --------------------------------------------------


def test_repo_registry_is_kernelcheck_clean():
    """Acceptance: K1–K5 hold on every registered kernel, probes
    included, with no pragmas or baseline entries needed."""
    findings, report = kc.run_kernelcheck()
    assert findings == []
    assert report["clean"] == 1
    assert set(report["kernels"]) == set(ops.KERNEL_REGISTRY)


def test_lint_cli_kernels_flag(tmp_path, capsys):
    from repro.analysis import lint as lint_cli
    report_path = tmp_path / "kc.json"
    rc = lint_cli.run(["--kernels", "--kernel-report", str(report_path)])
    assert rc == 0
    assert json.loads(report_path.read_text())["bench"] == "kernelcheck"


def test_kernelcheck_cli(tmp_path, capsys):
    rc = kc.run(["--no-probes", "--report", str(tmp_path / "r.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "kernelcheck:" in out
    assert json.loads((tmp_path / "r.json").read_text())["clean"] == 1
