"""Benchmark harness entry point: one section per paper table/figure.

  python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the wall-clock SpMM measurements")
    args = ap.parse_args()

    from benchmarks import (bench_spmm, breakdown, fig8_grouping,
                            table4_reorder, table23_inference)

    t0 = time.time()
    print("#" * 72)
    fig8_grouping.run()
    print("#" * 72)
    table23_inference.run(measure_wallclock=not args.quick)
    print("#" * 72)
    breakdown.run()
    print("#" * 72)
    table4_reorder.run()
    if not args.quick:
        print("#" * 72)
        bench_spmm.run()
    print("#" * 72)
    print(f"all benchmarks done in {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
