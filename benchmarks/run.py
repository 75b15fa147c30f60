"""Benchmark harness — one module per paper table/figure (+ the beyond-paper
and roofline reports). Prints CSV blocks per benchmark.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table1 fig5

Wall numbers in a single run mix first-compile cost into the timings
(``ms_per_round`` in the streaming/temporal benchmarks most of all).
``--repeat N`` runs each benchmark N times in-process: run 1 is the
warmup that pays the jit compiles, the reported rows come from the LAST
run (steady state, caches hot), and a ``# wall`` footer separates the
warmup wall time from the mean steady-state wall time so compile cost is
visible instead of smeared into the means.

    PYTHONPATH=src python -m benchmarks.run --repeat 3 temporal
"""

from __future__ import annotations

import argparse
import time

BENCHES = {
    "table1": "benchmarks.table1_graphs",
    "fig4": "benchmarks.fig4_core_distribution",
    "fig5": "benchmarks.fig5_total_messages",
    "fig67": "benchmarks.fig67_messages_over_time",
    "fig89": "benchmarks.fig89_active_nodes",
    "fig10": "benchmarks.fig10_runtime",
    "beyond_gs": "benchmarks.beyond_block_gs",
    "roofline": "benchmarks.roofline",
    "streaming": "benchmarks.streaming_maintenance",
    "temporal": "benchmarks.temporal_replay",
    "serving": "benchmarks.serving_mixed",
    "static": "benchmarks.static_decomposition",
    "scale": "benchmarks.scale_decomposition",
}


def main() -> None:
    import importlib

    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", metavar="BENCH",
                    help=f"benchmarks to run (default: all): "
                         f"{' '.join(BENCHES)}")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="run each benchmark N times; report the last "
                         "(steady-state) run, print warmup wall separately")
    args = ap.parse_args()
    unknown = [n for n in args.names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; pick from {list(BENCHES)}")
    names = args.names or list(BENCHES)
    repeat = max(args.repeat, 1)
    from repro import platform

    platform.enable_compile_cache()

    for name in names:
        mod = importlib.import_module(BENCHES[name])
        walls = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            rows = mod.run()
            walls.append(time.perf_counter() - t0)
        print(f"\n===== {name} ({BENCHES[name]}) [{walls[-1]:.1f}s] =====")
        for r in rows:
            print(r)
        if repeat > 1:
            steady = sum(walls[1:]) / len(walls[1:])
            print(f"# wall: warmup={walls[0]:.1f}s "
                  f"steady_mean={steady:.1f}s over {repeat - 1} repeats")


if __name__ == "__main__":
    main()
