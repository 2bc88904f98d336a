"""Cold-trace vs cached shape-class executors, across ELL dispatch modes.

Workload: a family of structurally-similar synthetic SBM graphs, each
serving ``--reps`` repeated SpMM inferences. Servers:

  seed path — what the pre-engine code did: one fresh ``jax.jit`` of
      ``hybrid_spmm`` per graph (bucket-loop ELL dispatch), so every new
      graph pays a full trace + XLA compile before its first answer.
  engine[d] — graphs padded into canonical (Kmax, units) shape classes;
      all class members share ONE compiled executor per ELL dispatch
      mode d (``ragged`` = single-launch production default, ``fused`` =
      legacy per-K baseline), so only the first member of a class ever
      compiles.

Reports per-dispatch cold/warm wall-clock, shape-class count, and the
ELL kernel launches per SpMM — the ragged path must hold throughput
against the fused baseline while tracing exactly one ELL kernel.

``--drift`` runs the shape-class lifecycle scenario instead: an SBM
family whose size distribution shifts mid-run (big graphs register and
serve, then smaller cousins arrive and pad into the oversized class).
Two identical traffic replays — retirement disabled vs enabled
(`LifecycleManager`) — must show LOWER total padded-MAC waste with
retirement, recompiles bounded by the per-window budget, and bitwise
IDENTICAL outputs (class padding is value-neutral, so the lifecycle can
never change an answer).

Run:  PYTHONPATH=src python benchmarks/bench_engine.py [--graphs 6]
      PYTHONPATH=src python benchmarks/bench_engine.py --drift
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import csr_from_scipy
from repro.core.hybrid_spmm import hybrid_spmm
from repro.core.partition import PartitionConfig, analyze_and_partition
from repro.data.graphs import normalized_adjacency, sbm_graph
from repro.engine import Engine, LifecycleConfig, LifecycleManager

ENGINE_DISPATCHES = ("ragged", "fused")


def make_family(n_graphs: int, n: int = 2000, seed0: int = 0):
    """Structurally-similar graphs: same SBM config, different seeds,
    jittered vertex counts (what one customer's daily graphs look like)."""
    out = []
    for i in range(n_graphs):
        rng = np.random.default_rng(seed0 + i)
        ni = n + int(rng.integers(-n // 50, n // 50))
        a = sbm_graph(ni, 8 * ni, seed=seed0 + i)
        out.append((f"sbm{i}", csr_from_scipy(normalized_adjacency(a)), ni))
    return out


def bench_seed_path(graphs, b_of, reps):
    """Per-graph jit of the bucket-loop hybrid_spmm (the pre-engine path)."""
    cold, warm, outs = 0.0, 0.0, {}
    for name, csr, n in graphs:
        part, meta, _ = analyze_and_partition(csr, PartitionConfig(tile=64))
        fwd = jax.jit(lambda bb, p=part, m=meta: hybrid_spmm(
            p, bb, meta=m, ell_dispatch="loop"))
        b = jnp.asarray(b_of(n))
        t0 = time.perf_counter()
        y = fwd(b).block_until_ready()          # trace + compile + run
        cold += time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fwd(b).block_until_ready()
        warm += time.perf_counter() - t0
        outs[name] = np.asarray(y)
    return cold, warm, outs


def bench_engine_path(graphs, b_of, reps, dispatch="ragged"):
    """Shape-class engine: cached executors, selectable ELL dispatch."""
    engine = Engine(ell_dispatch=dispatch)
    for name, csr, n in graphs:
        engine.register(name, csr)
    cold, warm, outs = 0.0, 0.0, {}
    for name, csr, n in graphs:
        b = b_of(n)
        t0 = time.perf_counter()
        y = engine.spmm(name, b).block_until_ready()   # compile iff new class
        cold += time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            y = engine.spmm(name, b).block_until_ready()
        warm += time.perf_counter() - t0
        outs[name] = np.asarray(y)
    return cold, warm, outs, engine


def run(n_graphs: int = 6, reps: int = 20, f: int = 64,
        verbose: bool = True) -> dict:
    graphs = make_family(n_graphs)
    rng = np.random.default_rng(0)
    feats = {n: rng.standard_normal((n, f)).astype(np.float32)
             for _, _, n in graphs}
    b_of = feats.__getitem__

    s_cold, s_warm, s_out = bench_seed_path(graphs, b_of, reps)
    engines = {}
    for dispatch in ENGINE_DISPATCHES:
        engines[dispatch] = bench_engine_path(graphs, b_of, reps, dispatch)

    for name in s_out:   # every server must answer identically
        for dispatch, (_, _, e_out, _) in engines.items():
            err = np.abs(s_out[name] - e_out[name]).max()
            assert err < 2e-4, (dispatch, name, err)

    e_cold, e_warm, _, engine = engines["ragged"]
    f_cold, f_warm, _, _ = engines["fused"]
    stats = engine.stats()
    res = {
        "n_graphs": n_graphs, "reps": reps,
        "seed_cold_s": s_cold, "seed_warm_s": s_warm,
        "seed_total_s": s_cold + s_warm,
        "engine_cold_s": e_cold, "engine_warm_s": e_warm,
        "engine_total_s": e_cold + e_warm,
        "fused_cold_s": f_cold, "fused_warm_s": f_warm,
        "fused_total_s": f_cold + f_warm,
        "shape_classes": stats["shape_classes"],
        "executors_compiled": stats["cache_misses"],
        "total_speedup": (s_cold + s_warm) / (e_cold + e_warm),
        "cold_speedup": s_cold / e_cold,
        "ragged_vs_fused_warm": f_warm / max(e_warm, 1e-9),
    }
    if verbose:
        print(f"== engine vs per-graph jit | {n_graphs} graphs x "
              f"(1 cold + {reps} warm) SpMM, F={f} ==")
        print(f"{'':16s} {'cold(s)':>9} {'warm(s)':>9} {'total(s)':>9} "
              f"{'traces':>7} {'launches':>9}")
        print(f"{'seed-jit (loop)':16s} {s_cold:>9.2f} {s_warm:>9.2f} "
              f"{s_cold + s_warm:>9.2f} {n_graphs:>7d} {'per-K':>9}")
        for dispatch in ENGINE_DISPATCHES:
            c, w, _, eng = engines[dispatch]
            st = eng.stats()
            launches = "1" if dispatch == "ragged" else "per-K"
            print(f"{'engine ' + dispatch:16s} {c:>9.2f} {w:>9.2f} "
                  f"{c + w:>9.2f} {st['cache_misses']:>7d} {launches:>9}")
        print(f"speedup vs seed: total {res['total_speedup']:.2f}x, "
              f"cold {res['cold_speedup']:.2f}x | ragged warm vs fused "
              f"{res['ragged_vs_fused_warm']:.2f}x | "
              f"{n_graphs} graphs -> {stats['shape_classes']} shape classes")
        print(engine.summary())
    return res


# ---------------------------------------------------------------------------
# Drift scenario: waste-budget retirement vs the no-retirement baseline
# ---------------------------------------------------------------------------

def _total_waste(engine):
    """(absolute padded-MAC slots wasted, waste fraction) over all classes."""
    cw = engine.class_waste()
    cap = sum(e["ell_capacity"] + e["dense_capacity"] + e["coo_capacity"]
              for e in cw.values())
    true = sum(e["ell_nnz"] + e["dense_nnz"] + e["coo_nnz"]
               for e in cw.values())
    return cap - true, (1.0 - true / cap) if cap else 0.0


def run_drift(n_big: int = 3, n_small: int = 4, reps: int = 2, f: int = 32,
              windows: int = 3, waste_budget: float = None,
              verbose: bool = True) -> dict:
    """Identical drifting traffic, retirement disabled vs enabled.

    Phase 1 registers + serves the big family (founds the class); the
    mix then shifts to a family half the size that pads into the same
    class. ``windows`` serve-then-``step()`` rounds follow. The budget
    defaults to the midpoint between the steady-state and post-drift
    waste fractions measured on the baseline run — i.e. the retirement
    trigger is the *drift*, not the founding headroom.
    """
    big = make_family(n_big, n=1024, seed0=0)
    small = [(f"small{i}", csr, n) for i, (_, csr, n)
             in enumerate(make_family(n_small, n=512, seed0=100))]
    rng = np.random.default_rng(1)
    feats = {name: rng.standard_normal((n, f)).astype(np.float32)
             for name, _, n in big + small}

    def drive(budget):
        engine = Engine()
        for name, csr, n in big:
            engine.register(name, csr)
        for name, _, n in big:
            engine.spmm(name, feats[name]).block_until_ready()
        waste_steady = _total_waste(engine)[1]
        for name, csr, n in small:
            engine.register(name, csr)
        waste_drifted = _total_waste(engine)[1]
        mgr = None
        if budget is not None:
            cfg = LifecycleConfig(waste_budget=budget, breach_windows=2,
                                  min_traffic=1, max_retires_per_window=1,
                                  max_recompiles_per_window=4)
            mgr = LifecycleManager(engine, config=cfg)
        outs = {}
        reports = []
        for w in range(windows):
            for name, _, n in big + small:
                for _ in range(reps):
                    y = engine.spmm(name, feats[name]).block_until_ready()
                outs[name] = np.asarray(y)
            if mgr is not None:
                reports.append(mgr.step())
        return engine, mgr, outs, waste_steady, waste_drifted, reports

    base_eng, _, base_outs, w_steady, w_drift, _ = drive(None)
    if waste_budget is None:
        waste_budget = 0.5 * (w_steady + w_drift)
    life_eng, mgr, life_outs, _, _, reports = drive(waste_budget)

    # padding is value-neutral: retirement must never change an answer
    for name in base_outs:
        assert np.array_equal(base_outs[name], life_outs[name]), \
            f"retirement changed outputs for {name!r}"
    per_window_ok = all(r["recompiles"] <= mgr.config.max_recompiles_per_window
                       for r in reports)
    assert per_window_ok, reports
    base_abs, base_frac = _total_waste(base_eng)
    life_abs, life_frac = _total_waste(life_eng)
    assert mgr.retires >= 1, "drift must trigger at least one retirement"
    assert life_abs < base_abs, \
        f"retirement must cut padded-MAC waste ({life_abs} vs {base_abs})"

    res = {
        "waste_budget": waste_budget,
        "waste_steady_frac": w_steady, "waste_drifted_frac": w_drift,
        "baseline_waste_slots": base_abs, "baseline_waste_frac": base_frac,
        "lifecycle_waste_slots": life_abs, "lifecycle_waste_frac": life_frac,
        "retires": mgr.retires, "reclassed": mgr.reclassed_members,
        "recompiles": mgr.recompiles,
        "recompile_budget_per_window": mgr.config.max_recompiles_per_window,
        "baseline_compiles": base_eng.stats()["cache_misses"],
        "lifecycle_compiles": life_eng.stats()["cache_misses"],
        "outputs_bitwise_equal": True,
    }
    if verbose:
        print(f"== drift scenario | {n_big} big + {n_small} small graphs, "
              f"{windows} windows x {reps} reps, F={f} ==")
        print(f"waste frac: steady {w_steady:.3f} -> drifted {w_drift:.3f} "
              f"(budget {waste_budget:.3f})")
        print(f"{'':14s} {'waste slots':>12} {'waste frac':>11} "
              f"{'compiles':>9}")
        print(f"{'no retirement':14s} {base_abs:>12d} {base_frac:>11.3f} "
              f"{res['baseline_compiles']:>9d}")
        print(f"{'lifecycle':14s} {life_abs:>12d} {life_frac:>11.3f} "
              f"{res['lifecycle_compiles']:>9d}")
        print(f"retires={mgr.retires} reclassed={mgr.reclassed_members} "
              f"recompiles={mgr.recompiles} (<= "
              f"{mgr.config.max_recompiles_per_window}/window over "
              f"{windows} windows) | outputs bitwise-equal: yes")
        print(life_eng.summary())
    return res


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=6)
    ap.add_argument("--reps", type=int, default=None,
                    help="reps per graph (default: 20, or 2 with --drift)")
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--drift", action="store_true",
                    help="run the shape-class lifecycle drift scenario")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json perf-trajectory file "
                         "(schema checked by lint_repro --bench-check)")
    args = ap.parse_args()
    if args.drift:
        results = run_drift(reps=2 if args.reps is None else args.reps,
                            f=args.features)
    else:
        results = run(args.graphs, 20 if args.reps is None else args.reps,
                      args.features)
    if args.json:
        import sys
        from repro.analysis.static.bench_check import write_bench_json
        write_bench_json(
            args.json, "bench_engine",
            "bench_engine " + " ".join(a for a in sys.argv[1:]
                                       if not a.startswith("--json")
                                       and a != args.json),
            time.strftime("%Y-%m-%d"), results)
