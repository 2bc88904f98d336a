"""Beyond-paper: measured CPU wall-clock of the tri-hybrid SpMM executor
vs dense matmul vs pure-COO (segment_sum) on the synthesized datasets —
shows the partitioned executor is a real executable artifact, not only a
cost model. The hybrid path runs through the shape-class serving engine
(cached compiled executor), i.e. exactly what `repro.engine.Engine`
serves in production.

The ``--dispatch`` axis A/B-tests the ELL dispatch modes (``ragged`` is
the production default, ``fused``/``loop`` are the legacy per-K-launch
paths) and reports, per dataset and mode, the traced ELL kernel
launches per SpMM, the padded-MAC waste of the ELL slice, and the
ragged launch's roofline picture: the contract's analytic DMA and
compute bounds (`repro.kernels.ell_spmm.contract_cost` over the
roofline constants) and ``achieved_roofline_frac`` — the ELL slice's
roofline bound over the measured hybrid time (a lower bound, since the
measurement includes the dense + COO engines).

``--autotune`` runs the contract-checked sweep
(`repro.kernels.autotune`) through ``Engine.autotune`` before timing
the ragged path; the report then carries both ``ms`` (tuned) and
``untuned_ms`` measured on the same data.

Run:  PYTHONPATH=src python benchmarks/bench_spmm.py
      [--dispatch ragged|fused|loop|all] [--backend xla|pallas]
      [--smoke] [--autotune]

``--smoke`` is the tier-1 CI mode: a small graph through the Pallas
interpret-mode kernels, one rep — fails loudly on kernel regressions,
and asserts the ragged path beats the pre-banding (PR-6) baseline on
both time and padded-MAC waste.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.hlo import count_pallas_calls
from repro.core import csr_to_scipy, pad_b_to_tiles, reorder
from repro.core.hybrid_spmm import hybrid_spmm
from repro.core.formats import (CooResidual, TriPartition, DenseTiles,
                                empty_ragged_ell)
from repro.core.partition import PartitionConfig, analyze_and_partition
from repro.data.graphs import make_paper_dataset
from repro.engine import Engine, ShapePolicy

DATASETS = {"cora": 1.0, "pubmed": 1.0, "flickr": 0.1}
SMOKE_DATASETS = {"cora": 0.25}
F = 128
DISPATCHES = ("ragged", "fused", "loop")

# PR-6 (pre-banding, pre-autotune) smoke baseline on this container —
# the v2 kernel must beat both, asserted in --smoke (the CI mode).
SMOKE_BASELINE_RAGGED_MS = 5.5589
SMOKE_BASELINE_WASTE_X = 14.92
SMOKE_MIN_SPEEDUP = 1.3


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
        r = r[0] if isinstance(r, tuple) else r
        r.block_until_ready()
    return (time.perf_counter() - t0) / reps


def _ell_launches(part, meta, dispatch: str) -> int:
    """ELL kernel launches one SpMM traces on the raw (unpadded) graph."""
    from repro.kernels import ops as kops
    if part.ell.cols.shape[0] == 0:
        return 0
    b = jnp.ones((meta.n_cols, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda bb: kops.ell_matmul(part, bb, meta, dispatch=dispatch))(b)
    return count_pallas_calls(jaxpr.jaxpr)


def _ell_roofline(sc, f: int, tune: dict) -> dict:
    """Analytic DMA/compute bounds of the class's ragged launch."""
    from repro.analysis.roofline import V5E, peaks_for
    from repro.kernels.ell_spmm import contract_cost, ragged_ell_contract
    knobs = {k: v for k, v in tune.items()
             if k in ("bf", "max_bands", "buffer_depth", "gu")}
    c = ragged_ell_contract(sc.ell_units, sc.r_block, sc.ell_kmax,
                            sc.n_col_tiles, sc.tile, f,
                            segments=sc.bands, **knobs)
    cost = contract_cost(c)
    peaks = peaks_for(V5E)
    return {"dma_s": cost["hbm_bytes"] / peaks.hbm_bw,
            "compute_s": cost["flops"] / peaks.flops}


def run(verbose: bool = True, dispatches=("ragged",), backend: str = "xla",
        f: int = F, reps: int = 5, smoke: bool = False,
        autotune: bool = False) -> dict:
    datasets = SMOKE_DATASETS if smoke else DATASETS
    if smoke:
        backend, f, reps = "pallas", 32, 1
    results = {}
    for name, scale in datasets.items():
        csr, x, _, st = make_paper_dataset(name, scale=scale)
        csr2, _, _ = reorder(csr, "labels",
                             labels=make_paper_dataset.last_labels)
        rng = np.random.default_rng(0)
        n = csr2.shape[0]
        b = rng.standard_normal((n, f)).astype(np.float32)
        bj = jnp.asarray(b)
        # the unpadded partition, for launch counting per dispatch
        # (same PartitionConfig(tile=64) the Engine defaults to)
        raw_part, raw_meta, _ = analyze_and_partition(
            csr2, PartitionConfig(tile=64))

        res = {"dispatch": {}}
        for dispatch in dispatches:
            # tight classes (no registry headroom): this benchmark
            # isolates kernel execution, so don't charge the hybrid
            # column for the serving policy's growth padding the
            # baselines never pay
            engine = Engine(policy=ShapePolicy(growth=1.0, coo_growth=1.0),
                            backend=backend, ell_dispatch=dispatch)
            handle = engine.register(name, csr2)
            meta = handle.meta

            # Time the cached class executor on device-resident,
            # pre-padded features — the same footing the dense/COO
            # baselines get below (engine.spmm would also charge
            # per-call host padding + H2D).
            b_pad = pad_b_to_tiles(bj, handle.padded_meta)
            tuned_cfg: dict = {}
            untuned_ms = None
            if autotune and dispatch == "ragged":
                # measure the default launch on the same data first, so
                # the report carries the tuned-vs-untuned delta
                fn0 = engine.executors.spmm(handle.sclass, f)
                untuned_ms = _time(lambda bb: fn0(handle.part, bb), b_pad,
                                   reps=reps) * 1e3
                tuned_cfg = engine.autotune(name, f)
            hybrid_fn = engine.executors.spmm(handle.sclass, f)
            t = _time(lambda bb: hybrid_fn(handle.part, bb), b_pad,
                      reps=reps)

            # padded-MAC waste on the ELL slice: class capacity (the
            # banded MAC slots the kernel actually issues) over real nnz
            cap = handle.sclass.ell_mac_capacity
            waste = cap / max(meta.nnz_ell, 1) if cap else 0.0
            entry = {
                "ms": t * 1e3,
                "launches_per_spmm": _ell_launches(raw_part, raw_meta,
                                                   dispatch),
                "ell_mac_capacity": cap,
                "ell_pad_waste_x": waste,
            }
            if dispatch == "ragged" and cap:
                rl = _ell_roofline(handle.sclass, f, tuned_cfg)
                bound_s = max(rl["dma_s"], rl["compute_s"])
                entry["dma_bound_us"] = rl["dma_s"] * 1e6
                entry["compute_bound_us"] = rl["compute_s"] * 1e6
                entry["achieved_roofline_frac"] = bound_s / t
            if untuned_ms is not None:
                entry["untuned_ms"] = untuned_ms
            res["dispatch"][dispatch] = entry
        meta = raw_meta   # true (unpadded) meta for the baselines below

        a_dense = jnp.asarray(csr_to_scipy(csr2).toarray())
        dense = jax.jit(lambda bb: a_dense @ bb)
        t_dense = _time(dense, bj, reps=reps)

        # pure scatter path (everything COO — the "PL-only" ablation)
        m = csr_to_scipy(csr2).tocoo()
        coo_all = TriPartition(
            dense=DenseTiles(jnp.zeros((0, meta.tile, meta.tile)),
                             jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32)),
            ell=empty_ragged_ell(),
            coo=CooResidual(jnp.asarray(m.row.astype(np.int32)),
                            jnp.asarray(m.col.astype(np.int32)),
                            jnp.asarray(m.data.astype(np.float32))))
        coo_fn = jax.jit(lambda bb: hybrid_spmm(coo_all, bb, meta=meta))
        t_coo = _time(coo_fn, bj, reps=reps)

        d0 = res["dispatch"][dispatches[0]]
        res.update({"dense_ms": t_dense * 1e3, "coo_ms": t_coo * 1e3,
                    "speedup_vs_dense": t_dense * 1e3 / d0["ms"],
                    "speedup_vs_coo": t_coo * 1e3 / d0["ms"]})
        results[name] = res
        if smoke and "ragged" in res["dispatch"]:
            # CI regression gate vs the PR-6 (pre-banding) baseline
            d = res["dispatch"]["ragged"]
            assert d["launches_per_spmm"] == 1, \
                f"ragged dispatch traced {d['launches_per_spmm']} launches"
            assert d["ell_pad_waste_x"] < SMOKE_BASELINE_WASTE_X, \
                (f"ELL pad waste {d['ell_pad_waste_x']:.2f}x did not "
                 f"improve on the {SMOKE_BASELINE_WASTE_X}x baseline")
            assert d["ms"] * SMOKE_MIN_SPEEDUP < SMOKE_BASELINE_RAGGED_MS, \
                (f"ragged {d['ms']:.2f}ms is not >= {SMOKE_MIN_SPEEDUP}x "
                 f"faster than the {SMOKE_BASELINE_RAGGED_MS}ms baseline")
    if verbose:
        print(f"== measured CPU SpMM wall-clock (engine-cached executors, "
              f"backend={backend}) ==")
        print(f"{'dataset':>8} {'dispatch':>8} {'hybrid':>9} {'dense':>9} "
              f"{'coo-only':>9} {'launches':>9} {'pad-MACs':>9} "
              f"{'roofline':>9}")
        for name, r in results.items():
            for dispatch, d in r["dispatch"].items():
                rf = d.get("achieved_roofline_frac")
                rf = f"{rf:>8.1e}" if rf is not None else f"{'-':>8}"
                tuned = (f"  (untuned {d['untuned_ms']:.2f}ms)"
                         if "untuned_ms" in d else "")
                print(f"{name:>8} {dispatch:>8} {d['ms']:>7.2f}ms "
                      f"{r['dense_ms']:>7.2f}ms {r['coo_ms']:>7.2f}ms "
                      f"{d['launches_per_spmm']:>9d} "
                      f"{d['ell_pad_waste_x']:>8.2f}x {rf}{tuned}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dispatch", default="ragged",
                    choices=list(DISPATCHES) + ["all"],
                    help="ELL dispatch mode(s) to benchmark")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--features", type=int, default=F)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny pallas-interpret run for CI kernel smoke")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep + apply the ragged kernel autotuner "
                         "before timing (reports tuned + untuned ms)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json perf-trajectory file "
                         "(schema checked by lint_repro --bench-check)")
    args = ap.parse_args()
    dispatches = DISPATCHES if args.dispatch == "all" else (args.dispatch,)
    results = run(dispatches=dispatches, backend=args.backend,
                  f=args.features, reps=args.reps, smoke=args.smoke,
                  autotune=args.autotune)
    if args.json:
        from repro.analysis.static.bench_check import write_bench_json
        write_bench_json(
            args.json, "bench_spmm",
            "bench_spmm " + " ".join(a for a in sys.argv[1:]
                                     if not a.startswith("--json")
                                     and a != args.json),
            time.strftime("%Y-%m-%d"), results)
