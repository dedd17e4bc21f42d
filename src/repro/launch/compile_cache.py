"""Persistent compilation cache placement, shared by every entry point.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` before their first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the cache
lives there; this module then sets no directory. Otherwise the cache
lives at the fixed ``<repo>/.jax_cache``: a later run finds its entries
only if the path does not change, so it carries no temp name, pid or
time. Either way every compiled program is cached, however quick its
compile was.

:func:`cache_stats` counts persistent-cache hits and misses since the
cache was enabled (from JAX's monitoring events), so a run can show that
a second process found what the first one wrote.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_stats: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_) -> None:
    if event == _HIT:
        _stats["hits"] += 1
    elif event == _MISS:
        _stats["misses"] += 1


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses seen since the cache was enabled."""
    return dict(_stats)
