"""The persistent compilation cache helper every entry point calls first.

Each case runs in a fresh interpreter: enabling the cache is process-wide
and must not leak into the rest of the suite.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

_PROBE = """
import json, sys
from pathlib import Path
import repro.launch.compile_cache as cc
if len(sys.argv) > 1:
    cc.REPO_CACHE_DIR = Path(sys.argv[1])
path = cc.enable_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"path": path, **cc.cache_stats()}))
"""


def _probe(env_dir=None, repo_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{SRC}{os.pathsep}{os.environ.get('PYTHONPATH', '')}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _PROBE]
    if repo_dir is not None:
        argv.append(str(repo_dir))
    out = subprocess.run(argv, env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_dir_wins_and_second_run_hits(tmp_path):
    env_dir, repo_dir = tmp_path / "env_cache", tmp_path / "repo_cache"
    first = _probe(env_dir=env_dir, repo_dir=repo_dir)
    assert first["path"] == str(env_dir)
    assert first["misses"] >= 1 and first["hits"] == 0
    assert any(env_dir.iterdir())
    assert not repo_dir.exists()          # no second directory is set
    second = _probe(env_dir=env_dir, repo_dir=repo_dir)
    assert second["hits"] >= 1 and second["misses"] == 0


def test_fixed_repo_dir_without_env(tmp_path):
    repo_dir = tmp_path / "repo_cache"
    got = _probe(repo_dir=repo_dir)
    assert got["path"] == str(repo_dir)
    assert any(repo_dir.iterdir())


def test_default_dir_is_fixed_under_the_repo():
    from repro.launch import compile_cache as cc
    assert cc.REPO_CACHE_DIR == SRC.parent / ".jax_cache"
