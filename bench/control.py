"""Readings that set a configuration's limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed: the cell's graphs, parameters and feature pool as a run
makes them, registered with a fresh `Engine` as a run registers them
(all through the configuration's model file). Each compared number
(`bench.lib.harness.COMPARED`) is read, widest over the pool, for the
program's ``Engine.infer`` and for the two controls put in its place:
the model's plain forward with its matmul operands in float8 (``fp8``)
and computed wholly in bfloat16 (``bf16``). One JSON line per seed,
then one with the program's largest and each control's smallest
reading of each number: a limit lies above the first and below the
second.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench.lib import harness
    from bench.lib import reference as ref_lib
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), args.workload, False)
    err = harness.chip_error(cell.chips)
    if err is not None:
        raise SystemExit(f"{args.workload}: {err}")
    harness.enable_compile_cache()
    import jax
    import numpy as np
    from repro.engine import Engine
    model = cell.model
    graphs = harness.build_graphs(cell.config, model)
    sides = {"fp8": ref_lib.reference_fp8, "bf16": ref_lib.reference_bf16}
    extreme = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        harness.make_params(seed, cell.config, model, graphs)
        harness.make_pools(seed, cell.config, cell.traffic, graphs)
        engine = Engine()
        for g in graphs:
            model.register(engine, g.name, harness._program_csr(g.csr),
                           reorder=cell.config["reorder"], params=g.params)
        worst = {side: dict.fromkeys(harness.COMPARED, 0.0)
                 for side in ("program", *sides)}
        for g in graphs:
            for x in g.pool:
                ys = {"program": np.asarray(engine.infer(g.name, x))}
                ys.update((side, fn(model.forward, g.csr, x, g.params))
                          for side, fn in sides.items())
                refs = harness.references(model, g, x)
                for side, y in ys.items():
                    for k, v in harness.numbers(y, *refs).items():
                        worst[side][k] = max(worst[side][k], v)
        del engine
        for side, nums in worst.items():
            pick = max if side == "program" else min
            for k, v in nums.items():
                key = (side, k)
                extreme[key] = pick(extreme.get(key, v), v)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": worst,
                          "limits": cell.config["limits"],
                          "device": jax.devices()[0].device_kind}),
              flush=True)
    summary = {side: {k: extreme[(side, k)] for k in harness.COMPARED}
               for side in ("program", *sides)}
    print(json.dumps({"workload": args.workload, "program_largest":
                      summary.pop("program"), "control_smallest": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
