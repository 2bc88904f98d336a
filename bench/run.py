"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell comes from ``BENCHMARK.json`` at the root of the checkout. The
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit, which also close standard error). Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    from bench.lib import harness
    cell = harness.load_cell(benchmark, args.workload, bool(args.trace))
    log(f"set-up: imports done at {time.monotonic() - T_START:.3f} s")

    err = harness.chip_error(cell.chips)
    log(f"set-up: JAX backend up at {time.monotonic() - T_START:.3f} s")
    if err is not None:
        log(f"bench: {args.workload} {err}")
        return 1
    harness.enable_compile_cache()

    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START, log=log)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
