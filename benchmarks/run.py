"""Benchmark driver: one module per paper table/figure (DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV to stdout. Run as
``PYTHONPATH=src python -m benchmarks.run`` (optionally ``--only fig2``).
"""

import argparse
import sys
import traceback

MODULES = [
    "benchmarks.fig1_rho",
    "benchmarks.fig1_dists",
    "benchmarks.bucket_balance",
    "benchmarks.fig2_recall",
    "benchmarks.fig3_partitioning",
    "benchmarks.fig3_m_sweep",
    "benchmarks.fig_l2alsh_ext",
    "benchmarks.fig_sign_alsh",
    "benchmarks.fig_multitable",
    "benchmarks.theory_rho",
    "benchmarks.kernel_bench",
    "benchmarks.engine_bench",
    "benchmarks.streaming_bench",
    "benchmarks.catalyst_bench",
    "benchmarks.distributed_bench",
    "benchmarks.planner_bench",
    "benchmarks.obs_report",
    "benchmarks.lsh_decode",
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception:
            failed.append(mod_name)
            traceback.print_exc(file=sys.stderr)
            print(f"{mod_name},nan,FAILED")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
