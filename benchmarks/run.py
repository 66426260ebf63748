"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``          — full suite.
``PYTHONPATH=src python -m benchmarks.run --smoke``  — every benchmark at
toy sizes (the CI fast-lane smoke job: benchmark scripts can't silently
rot). Prints CSV rows ``name,us_per_call,derived`` either way.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run every benchmark at toy sizes (CI smoke lane)")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark titles")
    args = ap.parse_args(argv)
    if args.smoke:
        # Must land in the environment BEFORE benchmark modules import
        # benchmarks.common (module-level sizes read the flag once).
        os.environ["BENCH_SMOKE"] = "1"

    from repro.utils import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_ingest, bench_kernels, bench_scaleout,
                            bench_train, fig5_microbench, fig6_rates_windows,
                            fig7_scale_skew, fig8_means_over_time,
                            fig9_network_traffic, fig10_taxi, fig_emission,
                            fig_quantiles, fig_recovery, fig_runtime_modes)
    modules = [
        ("fig5(a-c) microbenchmarks", fig5_microbench),
        ("fig6 arrival rates + windows", fig6_rates_windows),
        ("fig7 scalability + skew", fig7_scale_skew),
        ("fig8 means over time", fig8_means_over_time),
        ("fig9 network traffic case study", fig9_network_traffic),
        ("fig10 taxi case study", fig10_taxi),
        ("quantile engine accuracy/latency", fig_quantiles),
        ("runtime modes: batched vs pipelined", fig_runtime_modes),
        ("recovery: checkpoint overhead + replay latency", fig_recovery),
        ("emission: staleness, cadence vs watermark", fig_emission),
        ("ingest hot path: fused vs masked-vmap vs one-kernel", bench_ingest),
        ("scale-out: mesh throughput + elastic rescale", bench_scaleout),
        ("kernel bench", bench_kernels),
        ("training-plane bench", bench_train),
    ]
    if args.only:
        modules = [(t, m) for t, m in modules if args.only in t]
    print("name,us_per_call,derived")
    failures = 0
    for title, mod in modules:
        print(f"# --- {title} ---")
        try:
            mod.run()
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
