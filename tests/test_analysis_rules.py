"""repro-lint AST engine: one passing + one violating fixture per rule
R1-R6, pragma suppression, baseline round-trip and the CLI exit-code
contract (DESIGN.md §15)."""

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import findings as fnd
from repro.analysis import lint as lint_cli
from repro.analysis import rules


def _lint_src(tmp_path: Path, source: str, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return rules.lint_file(p, tmp_path)


def _rules_of(found):
    return sorted({f.rule for f in found})


# -- R1: bare assert ----------------------------------------------------------


def test_r1_flags_bare_assert(tmp_path):
    found = _lint_src(tmp_path, """
        def append(self, k):
            assert k <= self.free, "overflow"
    """)
    assert _rules_of(found) == ["R1"]
    assert found[0].line == 3
    assert "k <= self.free" in found[0].message


def test_r1_passes_typed_raise(tmp_path):
    found = _lint_src(tmp_path, """
        def append(self, k):
            if k > self.free:
                raise ValueError("overflow")
    """)
    assert found == []


# -- R2: tracker/span inside jit-entered functions ----------------------------


def test_r2_flags_span_under_jit_decorator(tmp_path):
    found = _lint_src(tmp_path, """
        import jax

        @jax.jit
        def step(tr, x):
            with tr.span("bad"):
                return x + 1
    """)
    assert _rules_of(found) == ["R2"]
    assert "`.span`" in found[0].message
    assert "`step`" in found[0].message


def test_r2_flags_partial_shard_map_alias(tmp_path):
    # the PR 4 collective idiom: partial alias -> shard_map -> jax.jit
    found = _lint_src(tmp_path, """
        import functools, jax

        def _shard_query(x, *, k):
            resolve_tracker(None)
            return x

        def build(mesh):
            body = functools.partial(_shard_query, k=5)
            return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                                         out_specs=()))
    """)
    assert _rules_of(found) == ["R2"]
    assert "_shard_query" in found[0].message


def test_r2_passes_host_side_spans(tmp_path):
    # spans AROUND the jitted call (the sanctioned pattern) are fine, and
    # trace-time `.count` dispatch accounting is deliberately allowed.
    found = _lint_src(tmp_path, """
        import jax

        @jax.jit
        def step(x, tracker_count):
            _dispatch.count("op")
            return x + 1

        def query(tr, x):
            with tr.span("host"):
                return step(x, 0)
    """)
    assert found == []


# -- R3: kernel registry ------------------------------------------------------


_OPS_OK = """
def hash_encode(x, *, impl="auto"):
    impl = _resolve(impl, "hash_encode")
    _charge("hash_encode", _cost.fn, 1)
    if impl == "ref":
        return _ref.hash_encode_ref(x)
    return x
"""

_REF_OK = """
def hash_encode_ref(x):
    return x
"""


def _registry(tmp_path, ops_src, ref_src):
    ops = tmp_path / "ops.py"
    ref = tmp_path / "ref.py"
    ops.write_text(textwrap.dedent(ops_src))
    ref.write_text(textwrap.dedent(ref_src))
    return rules.check_kernel_registry(ops, ref, "kernels/ops.py")


def test_r3_passes_full_registration(tmp_path):
    assert _registry(tmp_path, _OPS_OK, _REF_OK) == []


def test_r3_flags_missing_charge(tmp_path):
    src = _OPS_OK.replace('    _charge("hash_encode", _cost.fn, 1)\n', "")
    found = _registry(tmp_path, src, _REF_OK)
    assert _rules_of(found) == ["R3"]
    assert "_charge" in found[0].message


def test_r3_flags_missing_oracle(tmp_path):
    found = _registry(tmp_path, _OPS_OK, "def other_ref(x):\n    return x\n")
    assert _rules_of(found) == ["R3"]
    assert "_ref.hash_encode_ref" in found[0].message


def test_r3_flags_no_oracle_reference(tmp_path):
    src = _OPS_OK.replace("_ref.hash_encode_ref(x)", "x")
    found = _registry(tmp_path, src, _REF_OK)
    assert any("references no ref oracle" in f.message for f in found)


def _registry_with_tests(tmp_path, test_body):
    ops = tmp_path / "ops.py"
    ref = tmp_path / "ref.py"
    ops.write_text(textwrap.dedent(_OPS_OK))
    ref.write_text(textwrap.dedent(_REF_OK))
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_kernels.py").write_text(textwrap.dedent(test_body))
    return rules.check_kernel_registry(ops, ref, "kernels/ops.py",
                                       tests_root=tests)


def test_r3_flags_missing_interpret_parity_test(tmp_path):
    found = _registry_with_tests(tmp_path, """
        def test_something_else():
            assert ops.hash_encode(x, impl="ref").shape
    """)
    assert _rules_of(found) == ["R3"]
    assert "interpret-mode parity test" in found[0].message


def test_r3_passes_with_interpret_parity_test(tmp_path):
    found = _registry_with_tests(tmp_path, """
        def test_hash_encode_matches_ref():
            got = ops.hash_encode(x, impl="pallas")
            assert got is not None
    """)
    assert found == []


def test_r3_parity_sweep_skipped_without_tests_root(tmp_path):
    # check_kernel_registry without tests_root (or with a missing dir)
    # only runs the registration arms — fixture repos without a test
    # tree stay analyzable
    assert _registry(tmp_path, _OPS_OK, _REF_OK) == []
    found = rules.check_kernel_registry(
        tmp_path / "ops.py", tmp_path / "ref.py", "kernels/ops.py",
        tests_root=tmp_path / "no_such_dir")
    assert found == []


# -- R4: jit-static dataclasses -----------------------------------------------


def test_r4_flags_unfrozen_and_compared_tracker(tmp_path):
    found = _lint_src(tmp_path, """
        import dataclasses

        @dataclasses.dataclass
        class Spec:
            '''A spec (hashable, jit-static).'''
            code_len: int = 32
            tracker: object = None
    """)
    msgs = [f.message for f in found]
    assert _rules_of(found) == ["R4"]
    assert any("not frozen=True" in m for m in msgs)
    assert any("Spec.tracker" in m for m in msgs)


def test_r4_passes_frozen_with_excluded_tracker(tmp_path):
    found = _lint_src(tmp_path, """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class Spec:
            '''A spec (hashable, jit-static).'''
            code_len: int = 32
            tracker: object = dataclasses.field(
                default=None, compare=False, repr=False)
    """)
    assert found == []


def test_r4_ignores_untagged_dataclasses(tmp_path):
    # mutable runtime dataclasses without the jit-static docstring tag
    # are out of scope
    found = _lint_src(tmp_path, """
        import dataclasses

        @dataclasses.dataclass
        class Stats:
            '''Mutable accumulator.'''
            n: int = 0
    """)
    assert found == []


# -- R5: float64 / x64 toggles ------------------------------------------------


def test_r5_flags_float64_literal_and_x64_toggle(tmp_path):
    found = _lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        def widen(x):
            jax.config.update("jax_enable_x64", True)
            return jnp.asarray(x, jnp.float64)
    """)
    assert _rules_of(found) == ["R5"]
    assert len(found) == 2


def test_r5_allows_compat_module(tmp_path):
    found = _lint_src(tmp_path, """
        import jax.numpy as jnp

        def widest_float():
            return jnp.float64
    """, name="compat.py")
    assert found == []


# -- R6: block_until_ready ----------------------------------------------------


def test_r6_flags_stray_sync(tmp_path):
    found = _lint_src(tmp_path, """
        import jax

        def run(fn):
            return jax.block_until_ready(fn())
    """)
    assert _rules_of(found) == ["R6"]


def test_r6_allows_obs_trace(tmp_path):
    (tmp_path / "obs").mkdir()
    found = _lint_src(tmp_path, """
        import jax

        def sync(x):
            return jax.block_until_ready(x)
    """, name="obs/trace.py")
    assert found == []


# -- pragmas ------------------------------------------------------------------


def test_pragma_suppresses_same_and_previous_line(tmp_path):
    found = _lint_src(tmp_path, """
        import jax

        def timed(fn):
            # repro-lint: allow[R6] timing harness syncs on purpose
            jax.block_until_ready(fn())
            jax.block_until_ready(fn())  # repro-lint: allow[R6] ditto
    """)
    assert found == []


def test_pragma_without_justification_is_r0(tmp_path):
    found = _lint_src(tmp_path, """
        import jax

        def timed(fn):
            jax.block_until_ready(fn())  # repro-lint: allow[R6]
    """)
    assert _rules_of(found) == ["R0", "R6"]


def test_pragma_rule_mismatch_does_not_suppress(tmp_path):
    found = _lint_src(tmp_path, """
        import jax

        def timed(fn):
            # repro-lint: allow[R1] wrong rule id
            jax.block_until_ready(fn())
    """)
    assert _rules_of(found) == ["R6"]


# -- baseline -----------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    f1 = fnd.Finding("R1", "a.py", 3, "bare assert in library code: `x`")
    f2 = fnd.Finding("R6", "b.py", 9, "device sync `jax.block_until_ready`")
    path = tmp_path / "baseline.json"
    fnd.save_baseline(path, [f1, f2])
    baseline = fnd.load_baseline(path)
    assert len(baseline) == 2

    # same finding on a shifted line still matches its entry
    moved = fnd.Finding("R1", "a.py", 30, f1.message)
    fresh = fnd.Finding("R1", "a.py", 4, "bare assert: `new`")
    new, suppressed = fnd.split_by_baseline([moved, fresh], baseline)
    assert new == [fresh]
    assert suppressed == [moved]


def test_baseline_missing_file_is_empty(tmp_path):
    assert fnd.load_baseline(tmp_path / "nope.json") == {}


def test_baseline_version_mismatch_raises(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError, match="version"):
        fnd.load_baseline(p)


# -- CLI ----------------------------------------------------------------------


def _tree(tmp_path: Path, source: str) -> Path:
    root = tmp_path / "proj"
    (root / "lib").mkdir(parents=True)
    (root / "lib" / "mod.py").write_text(textwrap.dedent(source))
    return root


def test_cli_exit_1_on_violation_with_location(tmp_path, capsys):
    root = _tree(tmp_path, """
        def f(x):
            assert x > 0
    """)
    rc = lint_cli.run([str(root / "lib"), "--repo-root", str(root),
                       "--baseline", str(tmp_path / "b.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "lib/mod.py:3: R1" in out


def test_cli_exit_0_on_clean_tree(tmp_path):
    root = _tree(tmp_path, """
        def f(x):
            return x + 1
    """)
    rc = lint_cli.run([str(root / "lib"), "--repo-root", str(root),
                       "--baseline", str(tmp_path / "b.json")])
    assert rc == 0


def test_cli_fix_baseline_then_clean(tmp_path, capsys):
    root = _tree(tmp_path, """
        def f(x):
            assert x > 0
    """)
    base = tmp_path / "b.json"
    argv = [str(root / "lib"), "--repo-root", str(root),
            "--baseline", str(base)]
    assert lint_cli.run(argv + ["--fix-baseline"]) == 0
    data = json.loads(base.read_text())
    assert len(data["findings"]) == 1
    capsys.readouterr()
    # baselined finding no longer fails the run...
    assert lint_cli.run(argv) == 0
    assert "1 baselined" in capsys.readouterr().out
    # ...but a NEW violation still does
    (root / "lib" / "mod.py").write_text(
        "def f(x):\n    assert x > 0\n\ndef g(y):\n    assert y\n")
    assert lint_cli.run(argv) == 1


def test_cli_unknown_root_is_usage_error(tmp_path, capsys):
    rc = lint_cli.run([str(tmp_path / "missing")])
    assert rc == 2
    assert "does not exist" in capsys.readouterr().out


def test_cli_skips_tests_directories(tmp_path):
    root = tmp_path / "proj"
    (root / "lib" / "tests").mkdir(parents=True)
    (root / "lib" / "tests" / "test_x.py").write_text(
        "def test_a():\n    assert 1 == 1\n")
    rc = lint_cli.run([str(root / "lib"), "--repo-root", str(root),
                       "--baseline", str(tmp_path / "b.json")])
    assert rc == 0


# -- the repo itself ----------------------------------------------------------


def test_repo_is_lint_clean():
    """The shipped tree must hold its own invariants with an empty
    baseline (the CI lint job runs exactly this)."""
    rc = lint_cli.run([])
    assert rc == 0
    assert json.loads(
        lint_cli.DEFAULT_BASELINE.read_text())["findings"] == []
