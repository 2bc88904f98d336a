"""Run one cell of the benchmark and build its result line.

A cell names a configuration (``configs/<name>.json``: the model and
the graphs it serves) and a traffic mix (``traffic/<name>.json``, read
by `bench.lib.traffic`). The configuration's ``model.kind`` names a
model file ``models/<kind>.py``: its parameters, operation count, plain
forward and registration with the program. Each metric a cell reports
is a reader ``metrics/<name>.py`` whose ``read(run)`` returns a number
or None. Nothing here names a configuration, a model, a traffic mix or
a metric.

One run:

  set-up   synthesize the graphs (structure fixed by the configuration),
           make the model's parameters on the device and the feature
           pool from the seed, register every graph with the `Engine`
           (its default backend) as the model file does, build the
           served entry, compile or load from the persistent cache
           every shape the traffic uses, and send ``warm_requests``
           through it;
  window   drive the traffic for ``seconds``; with ``trace`` a
           ``jax.profiler`` trace covers exactly the window;
  check    wait for the window's answers, read the peak device memory,
           free the program, then compare every answer with the model's
           plain forward in float32 and at the configuration's stated
           precision (`bench.lib.reference`).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import queue as queue_mod
import shutil
import threading
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Optional

import numpy as np

from . import graphs as graph_lib
from . import reference as ref_lib
from . import trace as trace_lib
from . import traffic as traffic_lib

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"
# how long past the window's close the harness waits for its answers
STRAGGLER_S = 60.0

clock = time.monotonic


def chip_error(chips: int) -> Optional[str]:
    """Why this process cannot run a cell on ``chips`` TPU chips, or
    None when it can."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"needs a TPU, JAX found {devices[0].platform}"
    if len(devices) < chips:
        return f"needs {chips} chips, JAX sees {len(devices)}"
    return None


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------- catalog ----
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    metrics: List[dict]        # BENCHMARK.json entries this run reports
    model: ModuleType          # models/<config.model.kind>.py


def load_json(bench_dir: Path, kind: str, name: str) -> dict:
    with open(Path(bench_dir) / kind / f"{name}.json") as f:
        return json.load(f)


def _load_module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: Path, name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    return _load_module(Path(bench_dir) / "metrics" / f"{name}.py",
                        "bench_metric_").read


def load_model(bench_dir: Path, config: dict) -> ModuleType:
    """``models/<kind>.py`` of the configuration's ``model.kind``:
    ``make_params(key, model, spec)``, ``flops(csr, model, spec)``,
    ``forward(rows, cols, vals, x, params, *, n, dtype, precision,
    operands)`` and ``register(engine, name, csr, *, reorder,
    params)``."""
    kind = config["model"]["kind"]
    path = Path(bench_dir) / "models" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names model kind "
            f"{kind!r}, and there is no {path}")
    return _load_module(path, "bench_model_")


def load_cell(benchmark: dict, workload: str, trace: bool,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` of a parsed ``BENCHMARK.json``, with the
    metrics a run of that kind reports: the end-to-end ones without
    ``trace``, the per-layer ones with it."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in benchmark["configs"] if c["name"] == w["config"])

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in benchmark["end_to_end"] if applies(m)]
    if trace:
        moved = {m["name"] for m in e2e}
        metrics = [m for m in benchmark["per_layer"]
                   if applies(m) and m["moves"] in moved]
    else:
        metrics = e2e
    config = load_json(bench_dir, "configs", Path(cfg["file"]).stem)
    return Cell(name=workload, config=config,
                traffic=load_json(bench_dir, "traffic", w["traffic"]),
                chips=int(w["chips"]), metrics=metrics,
                model=load_model(bench_dir, config))


# --------------------------------------------------------------- set-up ----
@dataclasses.dataclass
class Graph:
    name: str
    spec: dict                  # the configuration's entry of this graph
    csr: object                 # scipy CSR of A_tilde
    flops: float                # per request
    params: object = None       # the model's parameters, on the device
    pool: list = None           # host feature matrices


def build_graphs(config: dict, model: ModuleType) -> List[Graph]:
    out = []
    for spec in config["graphs"]:
        csr = graph_lib.make_graph(spec, config["graph_seed"])
        out.append(Graph(spec["name"], spec, csr,
                         model.flops(csr, config["model"], spec)))
    return out


def make_params(seed: int, config: dict, model: ModuleType,
                graphs: List[Graph]) -> None:
    """The parameters of every graph, made on the device in one jitted
    call from the seed: graph ``i``'s by the model file from
    ``fold_in(root, i)``."""
    import jax
    specs = [g.spec for g in graphs]

    def gen(key):
        return [model.make_params(jax.random.fold_in(key, gi),
                                  config["model"], spec)
                for gi, spec in enumerate(specs)]

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    for g, params in zip(graphs, jax.jit(gen)(key)):
        g.params = params


def make_pools(seed: int, config: dict, traffic: dict,
               graphs: List[Graph]) -> None:
    """``pool`` feature matrices per graph: one binary matrix from the
    seed, each pool entry keeping a random ``keep`` share of its rows."""
    density = config["feature_density"]
    for gi, g in enumerate(graphs):
        rng = traffic_lib.rng_for(seed, 100 + gi)
        n = g.csr.shape[0]
        x = graph_lib.make_features(n, g.spec["n_features"], density, rng)
        g.pool = [x * (rng.random(n) < traffic["keep"]).astype(
            np.float32)[:, None] for _ in range(traffic["pool"])]


# ------------------------------------------------------------ entries ----
class InferEntry:
    """``Engine.infer``, one blocking call per request."""

    def __init__(self, engine, traffic, trace):
        self.engine = engine

    def warm_shapes(self, graphs) -> None:
        pass                    # warm requests cover the one shape

    def start(self) -> None:
        pass

    def call(self, name: str, x) -> np.ndarray:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("client.infer"):
            return np.asarray(self.engine.infer(name, x))

    def counters(self) -> Optional[dict]:
        return None

    def queue_waits(self, lo: float, hi: float) -> Optional[list]:
        return None

    def close(self) -> None:
        pass


class QueueEntry:
    """A started ``RequestQueue``: ``submit`` returns a future."""

    def __init__(self, engine, traffic, trace):
        from repro.obs.trace import Tracer
        from repro.serving import RequestQueue
        self.engine = engine
        self.traffic = traffic
        self.tracer = Tracer(capacity=1 << 20) if trace else None
        self.queue = RequestQueue(engine, tracer=self.tracer,
                                  **traffic.get("queue", {}))

    def warm_shapes(self, graphs) -> None:
        """Every pow2 group size the traffic can close, on every lane."""
        import jax
        replicas = self.traffic.get("queue", {}).get("replicas")
        views = ([self.engine.replica_view(i) for i in range(replicas)]
                 if replicas else [self.engine])
        top = traffic_lib.max_batch(self.traffic, len(graphs))
        for view in views:
            for g in graphs:
                bs = 1
                while bs <= top:
                    jax.block_until_ready(
                        view.serve_group([(g.name, g.pool[0])] * bs))
                    bs *= 2

    def start(self) -> None:
        self.queue.start()

    def submit(self, name: str, x):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("client.submit"):
            return self.queue.submit(name, x)

    def call(self, name: str, x) -> np.ndarray:
        from jax.profiler import TraceAnnotation
        fut = self.submit(name, x)
        with TraceAnnotation("client.wait"):
            return np.asarray(fut.result(timeout=STRAGGLER_S))

    def counters(self) -> dict:
        s = self.queue.stats
        hist = s.metrics.get("serving.staging_s")
        return {"completed": s.completed, "batches": s.batches,
                "staging_n": hist.count, "staging_s": hist.total}

    def queue_waits(self, lo: float, hi: float) -> Optional[list]:
        """Seconds each request of [lo, hi) spent in the ``queue`` span
        (submit to batch close)."""
        if self.tracer is None:
            return None
        begins, out = {}, []
        for e in self.tracer.events():
            if e["ph"] == "B" and e["name"] == "queue":
                begins[e["sid"]] = e["ts"]
            elif e["ph"] == "E" and e["sid"] in begins:
                t = begins.pop(e["sid"])
                if lo <= t < hi:
                    out.append(e["ts"] - t)
        return out

    def close(self) -> None:
        self.queue.stop()


ENTRIES = {"infer": InferEntry, "queue": QueueEntry}


# ------------------------------------------------------------- window ----
@dataclasses.dataclass
class Rec:
    graph: int
    pool_idx: int
    t_due: float
    t_done: Optional[float] = None
    y: Optional[np.ndarray] = None
    error: Optional[str] = None
    refused: bool = False
    late_s: float = 0.0           # how late the generator submitted it
    err: Optional[dict] = None    # each compared number of this answer


def run_closed(entry, graphs, streams, t_end: float,
               limit: Optional[int] = None) -> List[Rec]:
    """Each client calls, waits, calls again until ``t_end`` (or until
    it has made ``limit`` calls)."""
    per_client: List[List[Rec]] = [[] for _ in streams]

    def client(i):
        for j, req in enumerate(streams[i]):
            t = clock()
            if t >= t_end or (limit is not None and j >= limit):
                return
            rec = Rec(req.graph, req.pool_idx, t)
            g = graphs[req.graph]
            try:
                rec.y = entry.call(g.name, g.pool[req.pool_idx])
            except Exception as e:   # noqa: BLE001 — a failed request
                rec.error = repr(e)
            rec.t_done = clock()
            per_client[i].append(rec)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(streams))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(t_end - clock(), 0) + STRAGGLER_S)
    return [r for recs in per_client for r in recs]


def run_open(entry, graphs, schedule, t0: float) -> tuple:
    """Submit each request when due; a collector thread takes each
    answer to the host as its future resolves. Returns the records and
    the collector, for `_wait_open`."""
    from repro.serving.frontend import AdmissionError
    done: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
    recs: List[Rec] = []
    submitted = 0

    def collect(n_expected, stop):
        got = 0
        while got < n_expected[0] and not stop.is_set():
            try:
                rec, fut = done.get(timeout=0.05)
            except queue_mod.Empty:
                continue
            try:
                rec.y = np.asarray(fut.result())
            except Exception as e:   # noqa: BLE001 — a failed request
                rec.error = repr(e)
            rec.t_done = clock()
            got += 1

    n_expected = [len(schedule)]
    stop = threading.Event()
    collector = threading.Thread(target=collect, args=(n_expected, stop),
                                 daemon=True)
    collector.start()
    for req in schedule:
        due = t0 + req.due_s
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        g = graphs[req.graph]
        rec = Rec(req.graph, req.pool_idx, due, late_s=clock() - due)
        recs.append(rec)
        try:
            fut = entry.submit(g.name, g.pool[req.pool_idx])
        except AdmissionError as e:
            rec.error, rec.refused = repr(e), True
            continue
        submitted += 1
        fut.add_done_callback(lambda f, r=rec: done.put((r, f)))
    n_expected[0] = submitted
    return recs, collector, stop


def _wait_open(collector, stop, t_end: float) -> None:
    collector.join(timeout=max(t_end - clock(), 0) + STRAGGLER_S)
    stop.set()
    collector.join()


def drive(entry, graphs, traffic, seed: int, seconds: float, t0: float,
          stream: int, window_annotation: bool = False) -> List[Rec]:
    """The cell's traffic from ``t0`` for ``seconds``; returns every
    request due in that window, each answered or failed."""
    from jax.profiler import TraceAnnotation
    t_end = t0 + seconds
    if traffic["loop"] == "closed":
        streams = traffic_lib.closed_streams(traffic, seed, len(graphs),
                                             stream)
        box = {}
        worker = threading.Thread(
            target=lambda: box.update(
                recs=run_closed(entry, graphs, streams, t_end)),
            daemon=True)
        with TraceAnnotation(trace_lib.WINDOW if window_annotation
                             else "bench.drive"):
            worker.start()
            _sleep_until(t_end)
        worker.join(timeout=STRAGGLER_S + 1)
        return box.get("recs", [])
    schedule = traffic_lib.open_schedule(traffic, seed, seconds, len(graphs),
                                         stream)
    with TraceAnnotation(trace_lib.WINDOW if window_annotation
                         else "bench.drive"):
        recs, collector, stop = run_open(entry, graphs, schedule, t0)
        _sleep_until(t_end)
    _wait_open(collector, stop, t_end)
    return recs


def _sleep_until(t: float) -> None:
    while True:
        left = t - clock()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def warm(entry, graphs, traffic, seed: int) -> None:
    """Send ``warm_requests`` through the served path (stream 1 of the
    seed, apart from the window's)."""
    n = int(traffic["warm_requests"])
    if traffic["loop"] == "closed":
        streams = traffic_lib.closed_streams(traffic, seed, len(graphs), 1)
        per = -(-n // len(streams))
        recs = run_closed(entry, graphs, streams, clock() + 3600, limit=per)
    else:
        seconds = n / float(traffic["rate_per_s"])
        schedule = traffic_lib.open_schedule(traffic, seed, seconds,
                                             len(graphs), 1)
        recs, collector, stop = run_open(entry, graphs, schedule, clock())
        _wait_open(collector, stop, clock() + seconds)
    bad = [r.error for r in recs if r.error is not None or r.y is None]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")


# -------------------------------------------------------------- check ----
@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    seconds: float
    setup_s: float
    records: List[Rec]
    answered: List[Rec]           # correct answers inside the window
    latencies_s: List[float]      # every answered request of the window
    flops_answered: float
    chips: int
    peak_flops: Optional[float]
    counters: Optional[dict]      # queue counters over the window
    queue_waits_s: Optional[list]
    trace: Optional[trace_lib.TraceSummary]


COMPARED = ("max_err_ratio", "bf16_exact_share")


def compare(records: List[Rec], graphs: List[Graph],
            model: ModuleType) -> dict:
    """Every answer against the references of its pool entry (the
    model's forward, `bench.lib.reference`): sets each answer's
    ``err``, each number of `COMPARED`, and returns the largest of each
    over the answers."""
    worst = dict.fromkeys(COMPARED, 0.0)
    refs: dict = {}
    for rec in records:
        if rec.y is None:
            continue
        key = (rec.graph, rec.pool_idx)
        if key not in refs:
            g = graphs[rec.graph]
            refs[key] = references(model, g, g.pool[rec.pool_idx])
        rec.err = numbers(rec.y, *refs[key])
        for k, v in rec.err.items():
            worst[k] = max(worst[k], v)
    return worst


def references(model: ModuleType, g: Graph, x) -> tuple:
    """The float32 and the stated-precision references of features
    ``x`` of graph ``g``."""
    return (ref_lib.reference(model.forward, g.csr, x, g.params),
            ref_lib.reference_stated(model.forward, g.csr, x, g.params))


def numbers(y, ref, stated) -> dict:
    """Each number of `COMPARED` for answer ``y``, given the float32
    and the stated-precision references of its features."""
    return {"max_err_ratio": ref_lib.max_err_ratio(y, ref, stated),
            "bf16_exact_share": ref_lib.bf16_exact_share(y)}


def set_up(cell: Cell, seed: int, trace: bool, log=print) -> tuple:
    """Everything before the window: returns (graphs, entry). The
    entry's engine has every graph registered, every shape the traffic
    uses compiled or loaded, and has answered ``warm_requests``."""
    from repro.engine import Engine
    cfg, trf, model = cell.config, cell.traffic, cell.model
    t = clock()

    def phase(name):
        nonlocal t
        now = clock()
        log(f"set-up: {name} {now - t:.3f} s")
        t = now

    graphs = build_graphs(cfg, model)
    phase("graphs")
    make_params(seed, cfg, model, graphs)
    make_pools(seed, cfg, trf, graphs)
    phase("parameters and features")
    engine = Engine()
    for g in graphs:
        h = model.register(engine, g.name, _program_csr(g.csr),
                           reorder=cfg["reorder"], params=g.params)
        phase(f"register {g.name} ({h.sclass.summary()})")
    entry = ENTRIES[trf["entry"]](engine, trf, trace)
    entry.warm_shapes(graphs)
    entry.start()
    phase("shapes")
    warm(entry, graphs, trf, seed)
    phase("warm requests")
    return graphs, entry


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, bench_dir: Path = BENCH_DIR,
             log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    from .peaks import peaks_for

    devices = jax.devices()[: cell.chips]
    cfg = cell.config
    log(f"set-up: process start to set-up {clock() - t_start:.3f} s")
    graphs, entry = set_up(cell, seed, trace, log)
    before = entry.counters()
    compiles = _CompileCounter()
    pauses = _GcPauses()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t0 = clock()
    setup_s = t0 - t_start
    with compiles, pauses:
        records = drive(entry, graphs, cell.traffic, seed, seconds, t0,
                        stream=0,
                        window_annotation=trace)
    t_end = t0 + seconds
    if trace:
        jax.profiler.stop_trace()
    after = entry.counters()
    waits = entry.queue_waits(t0, t_end)
    entry.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del entry
    gc.collect()

    summary = trace_lib.reduce(trace_lib.load(str(TRACE_DIR))) \
        if trace else None
    worst = compare(records, graphs, cell.model)
    limits = cfg["limits"]
    unanswered = [r for r in records if r.y is None and not r.refused]
    ok = [r.y is not None and all(r.err[k] <= limits[k] for k in COMPARED)
          for r in records]
    wrong = [r for r, good in zip(records, ok)
             if r.y is not None and not good]
    answered = [r for r, good in zip(records, ok)
                if good and r.t_done <= t_end]
    lat = [r.t_done - r.t_due for r in records if r.y is not None]
    kind = devices[0].device_kind
    run = Run(seconds=seconds, setup_s=setup_s, records=records,
              answered=answered, latencies_s=lat,
              flops_answered=sum(graphs[r.graph].flops for r in answered),
              chips=len(devices),
              peak_flops=peaks_for(kind).bf16_flops,
              counters=(None if before is None else
                        {k: after[k] - before[k] for k in after}),
              queue_waits_s=waits, trace=summary)
    metrics = {}
    for m in cell.metrics:
        value = load_reader(bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    out = {"correct": bool(records) and not wrong and not unanswered
           and bool(answered),
           "attempted": len(records),
           "failed": sum(r.y is None for r in records),
           "metrics": metrics, "device": device}
    bd = trace_lib.breakdown(summary)
    if bd is not None:
        out["breakdown"] = bd
    log(f"window: opened {setup_s:.3f} s after process start, "
        f"{len(records)} requests, {len(answered)} answered "
        f"correctly inside it, {len(wrong)} wrong, {len(unanswered)} "
        f"unanswered, {sum(r.refused for r in records)} refused, "
        f"{compiles.n} compiles inside the window, generator late by "
        f"{max((r.late_s for r in records), default=0.0):.4f} s at most, "
        f"{pauses.n} gc passes for {pauses.total_s:.4f} s (longest "
        f"{pauses.max_s:.4f} s)")
    slow = sorted((r for r in records if r.y is not None),
                  key=lambda r: r.t_due - r.t_done)[:5]
    log("slowest: " + ", ".join(f"{r.t_done - r.t_due:.3f} s at "
                                 f"{r.t_due - t0:.2f} s" for r in slow))
    out["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                     for k in COMPARED}
    out["checks"]["unanswered"] = {"value": len(unanswered), "limit": 0}
    return out


def _program_csr(a):
    from repro.core.formats import csr_from_scipy
    return csr_from_scipy(a)


class _GcPauses:
    """Python's garbage-collector passes while active: count, total and
    longest pause."""

    def __init__(self):
        self.n, self.total_s, self.max_s = 0, 0.0, 0.0
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = clock()
        elif self._t is not None:
            d = clock() - self._t
            self.n += 1
            self.total_s += d
            self.max_s = max(self.max_s, d)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counting: list = []        # active _CompileCounter instances
_listening: list = []       # non-empty once the JAX listener is in


def _on_jax_event(name, *_args, **_kw):
    if name == _COMPILE_EVENT:
        for counter in _counting:
            counter.n += 1


class _CompileCounter:
    """Counts XLA compilations while active (there should be none)."""

    def __init__(self):
        self.n = 0
        if not _listening:      # JAX cannot take a listener back
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_event)
            _listening.append(True)

    def __enter__(self):
        _counting.append(self)
        return self

    def __exit__(self, *exc):
        _counting.remove(self)
