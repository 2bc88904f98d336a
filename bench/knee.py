"""Find the highest Poisson rate an open-loop cell sustains.

    python3 bench/knee.py --config gcn-paper.pubmed --traffic queue.poisson \
        --seed 7 --seconds 20 --rates 3,4,5,6

Sets the configuration up once under the open-loop traffic mix, then
offers each rate in turn for ``--seconds`` (the mix with
``rate_per_s`` replaced) and prints one JSON
line per rate: answered per second, p50 and p90 latency from the due
time, how late the generator ran, and ``growth``, the median latency of
the window's last third over its first third. A rate is sustained when
every request is answered and the backlog does not grow (``growth``
under 1.5). ``knee`` is the highest sustained rate whose p90 also
stays under the queue's deadline; ``knee_backlog`` the highest
sustained rate. The cell offers about 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    from bench.lib import harness
    from repro.serving.frontend import DEFAULT_DEADLINE_MS
    name = f"{args.config}/{args.traffic}"
    config = harness.load_json(harness.BENCH_DIR, "configs", args.config)
    cell = harness.Cell(
        name=name, chips=1, metrics=[], config=config,
        traffic=harness.load_json(harness.BENCH_DIR, "traffic",
                                  args.traffic),
        model=harness.load_model(harness.BENCH_DIR, config))
    err = harness.chip_error(cell.chips)
    if err is not None:
        raise SystemExit(f"{name}: {err}")
    harness.enable_compile_cache()
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{name}: not an open-loop traffic mix")
    deadline_ms = cell.traffic.get("queue", {}).get(
        "default_deadline_ms", DEFAULT_DEADLINE_MS)
    graphs, entry = harness.set_up(
        cell, args.seed, trace=False,
        log=lambda m: print(m, file=sys.stderr, flush=True))
    knee = knee_backlog = None
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            trf = dict(cell.traffic, rate_per_s=rate)
            t0 = harness.clock() + 0.5
            t_end = t0 + args.seconds
            recs = harness.drive(entry, graphs, trf, args.seed, args.seconds,
                                 t0, stream=10 + i)
            ok = [r for r in recs if r.y is not None]
            lat = np.array([r.t_done - r.t_due for r in ok]) * 1e3
            third = max(len(ok) // 3, 1)
            growth = float(np.median(lat[-third:]) / np.median(lat[:third]))
            late = max(r.t_done for r in ok) - t_end
            lag = max(r.late_s for r in recs)
            row = {"rate_per_s": rate, "requests": len(recs),
                   "answered_per_s": sum(r.t_done <= t_end for r in ok)
                   / args.seconds,
                   "failed": len(recs) - len(ok),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p90_ms": float(np.percentile(lat, 90)),
                   "growth": growth, "last_answer_after_close_s": late,
                   "generator_late_max_s": lag}
            row["sustained"] = row["failed"] == 0 and growth < 1.5
            row["p90_under_deadline"] = row["p90_ms"] < deadline_ms
            if row["sustained"]:
                knee_backlog = rate
                if row["p90_under_deadline"]:
                    knee = rate
            print(json.dumps(row), flush=True)
    finally:
        entry.close()
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "deadline_ms": deadline_ms,
                      "knee_req_per_s": knee,
                      "knee_backlog_req_per_s": knee_backlog}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
