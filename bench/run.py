"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``). The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``, each compared number
beside its limit; the same checks are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. JAX's persistent compilation cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names
another directory.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib.cell import load_cell, log, run_cell
    cell = load_cell(args.workload, ROOT)

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s): nothing was run")
        return 1
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
