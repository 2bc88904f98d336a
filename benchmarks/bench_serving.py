"""Standing request queue vs call-at-a-time serving, under real traffic.

The shape-class engine made *executors* cheap to share; this benchmark
measures whether the serving frontend makes *launches* cheap to share:
the same Poisson / bursty arrival trace over an SBM graph family is
replayed twice —

  call-at-a-time — ``engine.serve_batch([(name, x)])`` per arrival, the
      pre-frontend request path: occupancy is locked at 1 request per
      vmapped launch no matter how bunched the arrivals are.
  queue         — arrivals land in the standing `RequestQueue`; the
      scheduler closes batches on pow2 target size / deadline slack /
      drain and dispatches each through ONE ``serve_group`` launch.
  pipelined     — (``--pipeline``) the same queue dispatching through
      the `DispatchPipeline`: host staging overlaps device compute
      behind a bounded in-flight window. Compared against serial queue
      dispatch on **queue delay** (mean sojourn: intended arrival →
      future resolution — under overload the serial pump delays the
      submissions themselves, so submit→resolve latency alone
      under-counts) with bitwise-equal outputs required.

Reports occupancy (mean batch size), pad occupancy, latency
percentiles, and deadline misses per mode, then checks the acceptance
invariants: queue occupancy strictly above call-at-a-time, zero misses
at the default deadline, and every queue output bitwise-equal to the
per-request ``engine.infer`` answer. ``--pipeline`` additionally checks
pipelined-vs-serial bitwise equality and no added deadline misses (the
deterministic >=2x queue-delay bound is asserted by the zero-compile
``--smoke --pipeline`` simulation, where the overlap model is exact).

Run:    PYTHONPATH=src python benchmarks/bench_serving.py [--graphs 6]
        PYTHONPATH=src python benchmarks/bench_serving.py --pipeline
Smoke:  PYTHONPATH=src python benchmarks/bench_serving.py --smoke
        PYTHONPATH=src python benchmarks/bench_serving.py --smoke --pipeline
        PYTHONPATH=src python benchmarks/bench_serving.py --smoke --replicas 4
        PYTHONPATH=src python benchmarks/bench_serving.py --smoke --chaos
        (deterministic scheduler simulation, virtual clock, no compiles)

``--replicas N`` adds the multi-replica axis (ISSUE 9): the 1-vs-N
`ReplicaSet` comparison on simulated devices (bitwise-equal outputs,
per-key order preserved, >=3x aggregate throughput at N=4) plus the
fault-injection rescue smoke; with ``--json`` the per-replica
utilization and aggregate throughput land in BENCH_serving.json.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.obs.metrics import percentile
from repro.serving import (Arrival, RequestQueue, attach_resolve_probe,
                           bursty_trace, poisson_trace, replay_trace,
                           run_chaos_smoke, run_lifecycle_smoke,
                           run_pipeline_smoke, run_replica_fault_smoke,
                           run_replica_smoke, run_smoke, run_trace_smoke)


def make_family(n_graphs: int, f_in: int, hidden: int, n_classes: int,
                n: int = 2000, seed0: int = 0):
    """SBM family with shared weight shapes: same config, jittered sizes,
    so every graph pads into one shape class and one serve group."""
    from repro.core import csr_from_scipy
    from repro.data.graphs import normalized_adjacency, sbm_graph
    rng = np.random.default_rng(seed0)
    graphs = []
    for i in range(n_graphs):
        g = np.random.default_rng(seed0 + i)
        ni = n + int(g.integers(-n // 50, n // 50))
        a = sbm_graph(ni, 8 * ni, seed=seed0 + i)
        ws = [(rng.standard_normal((f_in, hidden)) * 0.05).astype(np.float32),
              (rng.standard_normal((hidden, n_classes)) * 0.05
               ).astype(np.float32)]
        graphs.append((f"sbm{i}", csr_from_scipy(normalized_adjacency(a)),
                       ni, ws))
    return graphs


def build_engine(graphs):
    from repro.engine import Engine
    engine = Engine()
    for name, csr, _n, ws in graphs:
        engine.register(name, csr, weights=ws)
    return engine


def warm_executors(engine, graphs, target_batch: int):
    """Compile every executor the replay can hit (single + pow2 batched)
    before traffic starts — cold XLA compiles are an offline cost in
    this serving model, never part of a request's deadline budget."""
    name0, _, n0, _ = graphs[0]
    x0 = np.zeros((n0, engine.handle(name0).weights[0].shape[0]), np.float32)
    engine.infer(name0, x0)
    bs = 1
    while bs < target_batch:
        bs <<= 1
        engine.serve_group([(name0, x0)] * bs)


def _sleep_until(until_s: float) -> None:
    dt = until_s - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def run_baseline(engine, trace, xs) -> dict:
    """Call-at-a-time: serve each arrival alone, as it lands."""
    lat = []
    t_start = time.monotonic()
    t0 = time.monotonic()
    for i, arr in enumerate(trace):
        _sleep_until(t_start + arr.t_s)
        y = engine.serve_batch([(arr.name, xs[i])])[0]
        y.block_until_ready()
        lat.append(time.monotonic() - (t_start + arr.t_s))
    wall = time.monotonic() - t0
    lat_ms = np.asarray(lat) * 1e3
    return {"mode": "call-at-a-time", "batches": len(trace),
            "mean_batch": 1.0, "pad_occupancy": 1.0,
            "p50_ms": percentile(lat_ms, 50),
            "p99_ms": percentile(lat_ms, 99),
            "deadline_misses": 0, "wall_s": wall,
            "req_per_s": len(trace) / wall}


def run_queue(engine, trace, xs, *, target_batch: int,
              deadline_ms=None, pipelined: bool = False,
              max_inflight: int = 4) -> tuple:
    """Replay the trace through the standing queue in real time.

    Queue delay is measured as sojourn — resolution wall time minus the
    trace's *intended* arrival — via done-callbacks, so a backed-up
    serial pump (which also delays the submissions behind it) can't
    hide its backlog from the metric.
    """
    queue = RequestQueue(engine, target_batch=target_batch,
                         pipelined=pipelined, max_inflight=max_inflight)
    resolve_at = attach_resolve_probe(queue, clock=time.monotonic)
    t_start = time.monotonic()
    shifted = [Arrival(t_start + a.t_s, a.name) for a in trace]
    it = iter(range(len(trace)))
    x_of = lambda _name: xs[next(it)]        # noqa: E731 — trace-ordered
    t0 = time.monotonic()
    futures, rejected = replay_trace(queue, shifted, x_of,
                                     wait=_sleep_until,
                                     deadline_ms=deadline_ms)
    assert not any(rejected), "default admission policy must admit all"
    outs = [f.result(timeout=30.0) for f in futures]
    for y in outs:
        y.block_until_ready()
    wall = time.monotonic() - t0
    sojourn_ms = np.array([resolve_at[id(f)] - a.t_s
                           for a, f in zip(shifted, futures)]) * 1e3
    snap = queue.stats.snapshot()
    mode = (f"pipelined(w={max_inflight})" if pipelined
            else f"queue(target={target_batch})")
    res = {"mode": mode,
           "batches": snap["batches"], "mean_batch": snap["mean_batch"],
           "pad_occupancy": snap["pad_occupancy"],
           "p50_ms": snap["p50_ms"], "p99_ms": snap["p99_ms"],
           "deadline_misses": snap["deadline_misses"], "wall_s": wall,
           "req_per_s": len(trace) / wall,
           "queue_delay_ms": float(sojourn_ms.mean()),
           "sojourn_p99_ms": percentile(sojourn_ms, 99),
           "overlap_ratio": snap["overlap_ratio"],
           "inflight_peak": snap["inflight_peak"]}
    return res, outs, queue


def _report(rows):
    cols = ("mode", "batches", "mean_batch", "pad_occupancy", "p50_ms",
            "p99_ms", "deadline_misses", "req_per_s")
    print(f"{'mode':22s} {'batches':>7} {'meanB':>6} {'padOcc':>6} "
          f"{'p50ms':>8} {'p99ms':>8} {'misses':>6} {'req/s':>7}")
    for r in rows:
        print(f"{r['mode']:22s} {r['batches']:>7d} {r['mean_batch']:>6.2f} "
              f"{r['pad_occupancy']:>6.2f} {r['p50_ms']:>8.1f} "
              f"{r['p99_ms']:>8.1f} {r['deadline_misses']:>6d} "
              f"{r['req_per_s']:>7.1f}")
    return {r["mode"]: {c: r[c] for c in cols} for r in rows}


def run(n_graphs: int = 6, n_requests: int = 96, rate_hz: float = 150.0,
        f_in: int = 32, hidden: int = 32, n_classes: int = 8,
        target_batch: int = 8, pipeline: bool = False,
        max_inflight: int = 4, verbose: bool = True) -> dict:
    graphs = make_family(n_graphs, f_in, hidden, n_classes)
    engine = build_engine(graphs)
    warm_executors(engine, graphs, target_batch)
    sizes = {name: n for name, _, n, _ in graphs}
    names = [name for name, _, _, _ in graphs]
    rng = np.random.default_rng(1)

    results: dict = {}
    traces = {
        "poisson": poisson_trace(n_requests, rate_hz, names, seed=7),
        "bursty": bursty_trace(n_requests // 12, 12,
                               12 / rate_hz * 2.0, names, seed=8,
                               jitter_s=0.002),
    }
    for tname, trace in traces.items():
        xs = [rng.standard_normal((sizes[a.name], f_in)).astype(np.float32)
              for a in trace]
        base = run_baseline(engine, trace, xs)
        qres, qouts, queue = run_queue(engine, trace, xs,
                                       target_batch=target_batch)
        rows = [base, qres]
        pouts = None
        if pipeline:
            pres, pouts, pqueue = run_queue(
                engine, trace, xs, target_batch=target_batch,
                pipelined=True, max_inflight=max_inflight)
            rows.append(pres)
        if verbose:
            print(f"\n== {tname} trace | {len(trace)} requests over "
                  f"{len(names)} SBM graphs (rate~{rate_hz:.0f}/s) ==")
        results[tname] = _report(rows)

        # acceptance invariants (ISSUE 3) — checked on every run
        assert qres["mean_batch"] > base["mean_batch"], \
            f"{tname}: queue occupancy {qres['mean_batch']} must beat " \
            f"call-at-a-time {base['mean_batch']}"
        assert qres["deadline_misses"] == 0, \
            f"{tname}: default deadline must never be missed: {qres}"
        mism = 0
        for arr, x, y in zip(trace, xs, qouts):
            ref = engine.infer(arr.name, x)
            if not np.array_equal(np.asarray(y), np.asarray(ref)):
                mism += 1
        assert mism == 0, f"{tname}: {mism} batch outputs differ bitwise " \
                          f"from per-request infer"
        if verbose:
            print(f"[{tname}] occupancy {qres['mean_batch']:.2f}x vs 1.00x "
                  f"baseline; 0 deadline misses; {len(trace)}/{len(trace)} "
                  f"outputs bitwise-equal to per-request infer")
        if pipeline:
            # pipelined acceptance (ISSUE 5): bitwise-equal to serial
            # queue dispatch, no added misses; the hard >=2x queue-delay
            # bound is asserted by the deterministic --smoke --pipeline
            # simulation (wall-clock runs report the measured ratio).
            for i, (a, b) in enumerate(zip(qouts, pouts)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    f"{tname}: request {i} differs bitwise between " \
                    f"serial and pipelined dispatch"
            assert pres["deadline_misses"] <= qres["deadline_misses"], \
                f"{tname}: pipelining must not add deadline misses"
            ratio = qres["queue_delay_ms"] / max(pres["queue_delay_ms"],
                                                 1e-9)
            if verbose:
                print(f"[{tname}] pipelined queue delay "
                      f"{qres['queue_delay_ms']:.1f} -> "
                      f"{pres['queue_delay_ms']:.1f}ms ({ratio:.2f}x), "
                      f"p99 sojourn {qres['sojourn_p99_ms']:.1f} -> "
                      f"{pres['sojourn_p99_ms']:.1f}ms, overlap "
                      f"{pres['overlap_ratio']:.2f}, inflight peak "
                      f"{pres['inflight_peak']}; outputs bitwise-equal "
                      f"to serial")
    if verbose:
        st = engine.stats()
        print(f"\nengine: {st['executors']} executors, "
              f"{st['shape_classes']} classes, stacks "
              f"hits={st['stack_hits']} misses={st['stack_misses']} "
              f"evictions={st['stack_evictions']}")
        waste = next(iter(st["class_waste"].values()), {})
        if waste:
            print(f"class_waste[0]: members={waste['members']} "
                  f"ell_waste={waste['ell_waste_frac']:.2f} "
                  f"total_pad_waste={waste['padded_mac_waste_frac']:.2f}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic scheduler simulation only "
                         "(virtual clock, stub engine, no compiles)")
    ap.add_argument("--pipeline", action="store_true",
                    help="add the pipelined-dispatch axis: serial vs "
                         "pipelined queue under the same traces (with "
                         "--smoke: the deterministic serial-vs-pipelined "
                         "comparison with the >=2x queue-delay bound)")
    ap.add_argument("--graphs", type=int, default=6)
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--rate", type=float, default=150.0)
    ap.add_argument("--target-batch", type=int, default=8)
    ap.add_argument("--max-inflight", type=int, default=4)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json perf-trajectory file "
                         "(schema checked by lint_repro --bench-check)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with --smoke: write the traced run's Perfetto "
                         "JSON here (loadable in ui.perfetto.dev; "
                         "analyzed offline by scripts/trace_report.py)")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="add the multi-replica axis: with --smoke, the "
                         "deterministic 1-vs-N ReplicaSet comparison "
                         "(>=3x throughput at N=4, outputs bitwise-"
                         "equal, per-key order preserved) plus the "
                         "fault-injection rescue smoke")
    ap.add_argument("--chaos", action="store_true",
                    help="with --smoke: replay the end-to-end failure-"
                         "containment smoke — every chaos site fires "
                         "(dispatch/compile/hang/poison/replica) plus "
                         "the brownout flood; see docs/ROBUSTNESS.md")
    args = ap.parse_args()
    if args.smoke and args.pipeline:
        results = {"pipeline_smoke": run_pipeline_smoke(
            trace_path=args.trace)}
    elif args.smoke:
        results = {"smoke": run_smoke(),
                   "lifecycle": run_lifecycle_smoke(),
                   "tracing": run_trace_smoke(trace_path=args.trace)}
    else:
        results = run(args.graphs, args.requests, args.rate,
                      target_batch=args.target_batch,
                      pipeline=args.pipeline,
                      max_inflight=args.max_inflight)
    if args.smoke and args.replicas:
        results["replica_smoke"] = run_replica_smoke(
            replicas=args.replicas)
        results["replica_fault"] = run_replica_fault_smoke()
    if args.smoke and args.chaos:
        results["chaos"] = run_chaos_smoke()
    if args.json:
        import sys
        from repro.analysis.static.bench_check import write_bench_json
        write_bench_json(
            args.json, "bench_serving",
            "bench_serving " + " ".join(a for a in sys.argv[1:]
                                        if not a.startswith("--json")
                                        and a != args.json),
            time.strftime("%Y-%m-%d"), results)
