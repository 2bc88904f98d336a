"""CPU tests of the benchmark harness: trace reduction, operation
counts, traffic, lookup by name (of models too), whole runs at a tiny
size, and runs with the served path broken underneath, which must come
out incorrect.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import collections
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.lib import harness, peaks, reference, trace, traffic
from bench.lib import graphs as graph_lib

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = CHECKOUT / "bench"
with open(CHECKOUT / "BENCHMARK.json") as f:
    BENCHMARK = json.load(f)

TINY_CONFIG = {
    "name": "tiny", "model": {"kind": "gcn", "n_layers": 2, "d_hidden": 16},
    "graphs": [{"name": "g0", "n_vertices": 300, "n_edges": 1800,
                "n_features": 24, "n_classes": 3},
               {"name": "g1", "n_vertices": 200, "n_edges": 1200,
                "n_features": 24, "n_classes": 3}],
    "graph_seed": 0, "reorder": "community", "feature_density": 0.2,
    # the CPU computes every matmul in float32, far closer to the
    # float32 reference than the stated precision is
    "limits": {"max_err_ratio": 0.05, "bf16_exact_share": 0.3},
}
GCN = harness.load_model(BENCH, TINY_CONFIG)
TINY_TRAFFIC = {
    "closed": {"loop": "closed", "entry": "infer", "clients": 2, "pool": 3,
               "keep": 0.9, "warm_requests": 2},
    "open": {"loop": "open", "entry": "queue",
             "queue": {"pipelined": True, "target_batch": 4},
             "rate_per_s": 40.0, "pool": 3, "keep": 0.9,
             "warm_requests": 8},
}


def tiny_bench(tmp_path, loop: str) -> tuple:
    """A bench directory holding only a test configuration, a test
    traffic file, the real model files and the real metric readers;
    returns it and its cell."""
    for kind in ("configs", "traffic", "models"):
        (tmp_path / kind).mkdir()
    for p in (BENCH / "models").glob("*.py"):
        os.symlink(p, tmp_path / "models" / p.name)
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (tmp_path / "traffic" / f"tiny.{loop}.json").write_text(
        json.dumps(TINY_TRAFFIC[loop]))
    os.symlink(BENCH / "metrics", tmp_path / "metrics")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    bench = {"configs": [{"name": "tiny", "file": "x/configs/tiny.json"}],
             "workloads": [{"name": f"tiny.{loop}", "config": "tiny",
                            "traffic": f"tiny.{loop}", "chips": 1}],
             "end_to_end": BENCHMARK["end_to_end"],
             # every per-layer reader there is, whether or not a cell
             # of BENCHMARK.json reports it yet
             "per_layer": [{"name": p.stem, "unit": "1",
                            "moves": "req_per_s",
                            "workloads": [f"tiny.{loop}"]}
                           for p in sorted((BENCH / "metrics").glob("*.py"))
                           if p.stem not in e2e]}
    return tmp_path, bench


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """Peaks for the CPU, so that a run here reads ``step_mfu``: a
    number of no meaning, only exercised."""
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peaks(bf16_flops=1e12, hbm_bytes=1e11))


def run_tiny(tmp_path, loop, *, trace_on=False, seed=2**31 + 17):
    bench_dir, bench = tiny_bench(tmp_path, loop)
    harness.TRACE_DIR = tmp_path / "trace"
    cell = harness.load_cell(bench, f"tiny.{loop}", trace_on, bench_dir)
    return harness.run_cell(cell, seed=seed, seconds=1.0, trace=trace_on,
                            t_start=harness.clock(), bench_dir=bench_dir,
                            log=lambda m: None)


# ----------------------------------------------------------- trace ----
def synthetic_planes():
    ms = 1_000_000
    return {
        "/host:CPU": {"python": [
            ("bench.window", 0, 100 * ms),
            ("client.infer", 0, 60 * ms),
            ("client.wait", 40 * ms, 20 * ms),
        ]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_fwd(7)", 10 * ms, 20 * ms),
                            ("jit_gather(3)", 50 * ms, 5 * ms),
                            ("jit_fwd(7)", 95 * ms, 10 * ms)],
            "XLA Ops": [("fusion.1", 10 * ms, 15 * ms),
                        ("fusion.2", 20 * ms, 10 * ms),
                        ("gather", 50 * ms, 5 * ms),
                        ("fusion.1", 95 * ms, 10 * ms)],
        },
    }


def test_trace_reduction_busy_union_idle_and_modules():
    s = trace.reduce(synthetic_planes())
    assert s.window_s == pytest.approx(0.1)
    # ops 10-30 (overlapping), 50-55 and 95-100 (clipped at the window)
    assert s.busy_s == pytest.approx(0.030)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.7)
    assert s.module_s("jit_fwd") == pytest.approx(0.025)
    assert s.module_s("jit_gather") == pytest.approx(0.005)
    assert s.top_ops()[0] == ("fusion.1", pytest.approx(0.020))
    # gaps 0-10, 30-50, 55-95: the longest is attributed to the
    # innermost host event at its midpoint (75 ms: none covers it)
    names = dict((round(sec, 3), n) for n, sec in s.gap_names)
    assert names == {0.04: "host.none", 0.02: "client.wait",
                     0.01: "client.infer"}
    bd = trace.breakdown(s)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_trace_reduction_averages_busy_over_devices():
    planes = synthetic_planes()
    planes["/device:TPU:1"] = {"XLA Ops": [("fusion.9", 0, 100_000_000)]}
    s = trace.reduce(planes)
    assert s.busy_s == pytest.approx((0.030 + 0.1) / 2)


@pytest.mark.parametrize("modules, want_ms", [
    ([("jit__stage_x(4)", 60, 2), ("jit__unstage_y(5)", 70, 1),
      ("jit__stage_x(4)", 98, 4)], 5 / 4),
    ([], None),
], ids=["staged", "no_staging_modules"])
def test_staging_device_time_per_request(modules, want_ms):
    """``stage_device_ms_per_req``: both staging modules' device time in
    the window (the last one clipped at its close) over the answered
    requests; nothing where the program ran no staging module."""
    ms = 1_000_000
    planes = synthetic_planes()
    planes["/device:TPU:0"]["XLA Modules"] += [
        (name, s * ms, d * ms) for name, s, d in modules]
    run = harness.Run(seconds=0.1, setup_s=1.0, records=[],
                      answered=[1] * 4, latencies_s=[0.1], flops_answered=0,
                      chips=1, peak_flops=None, counters=None,
                      queue_waits_s=None, trace=trace.reduce(planes))
    got = harness.load_reader(BENCH, "stage_device_ms_per_req")(run)
    assert got == (None if want_ms is None else pytest.approx(want_ms))


def test_trace_reduction_needs_the_window_annotation():
    planes = synthetic_planes()
    planes["/host:CPU"]["python"] = planes["/host:CPU"]["python"][1:]
    with pytest.raises(ValueError):
        trace.reduce(planes)


# ----------------------------------------------------------- flops ----
@pytest.mark.parametrize("cfg", [c["name"] for c in BENCHMARK["configs"]])
def test_flops_per_configuration(cfg):
    config = harness.load_json(BENCH, "configs", cfg)
    model = harness.load_model(BENCH, config)
    for g in harness.build_graphs(config, model):
        n, nnz = g.csr.shape[0], g.csr.nnz
        spec = next(s for s in config["graphs"] if s["name"] == g.name)
        f, h, c = (spec["n_features"], config["model"]["d_hidden"],
                   spec["n_classes"])
        want = (2 * n * f * h + 2 * nnz * h) + (2 * n * h * c + 2 * nnz * c)
        assert g.flops == want
        assert n == spec["n_vertices"]
        # A_tilde holds the published graph's edges, each direction
        # once, and one self loop per vertex
        assert nnz == spec["n_edges"] + n
        assert (g.csr != g.csr.T).nnz == 0


def test_pubmed_flops_match_the_hand_count():
    # 2 x 19717 x 500 x 128 dominates: ~2.56 GFLOP a request
    assert GCN.gcn_flops(19717, 88648 + 19717,
                         [500, 128, 3]) == pytest.approx(2.56e9, rel=0.01)


# The GCN's parameters, operation counts and references for one seed,
# read before they moved into ``models/gcn.py``: the model file has to
# make, count and compute them as the harness did before it.
PIN_SEED = 2**31 + 17
PIN_PARAMS = {
    "g0": "5d069ca76c424213a4cb185a81577b7d5bb9f76a29f5f224aeb3d2f83d7feb71",
    "g1": "70eefabee4e4e3d17f4b9a606c3bb86d7be4858704f7526acced7c88349926f0"}
PIN_FLOPS = {"g0": 339000.0, "g1": 226000.0}
# sha256 of g0's logits for its first pool entry, each side, to the bit
PIN_LOGITS = {"reference": "95166d314af1528b",
              "reference_stated": "d4607334ecc83c26",
              "reference_bf16": "e88b5423b0f3dae9",
              "reference_fp8": "fecea2209eb0edc4"}


def test_the_gcn_model_file_makes_counts_and_computes_as_before():
    gs = harness.build_graphs(TINY_CONFIG, GCN)
    harness.make_params(PIN_SEED, TINY_CONFIG, GCN, gs)
    harness.make_pools(PIN_SEED, TINY_CONFIG, TINY_TRAFFIC["closed"], gs)
    for g in gs:
        digest = hashlib.sha256()
        for w in g.params:
            digest.update(np.asarray(w).tobytes())
        assert digest.hexdigest() == PIN_PARAMS[g.name]
        assert g.flops == PIN_FLOPS[g.name]
    g = gs[0]
    for side, want in PIN_LOGITS.items():
        y = getattr(reference, side)(GCN.forward, g.csr, g.pool[0], g.params)
        assert hashlib.sha256(y.tobytes()).hexdigest()[:16] == want, side


# --------------------------------------------------------- traffic ----
@pytest.mark.parametrize("name", sorted(
    p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_traffic_same_seed_same_schedule(name):
    t = harness.load_json(BENCH, "traffic", name)
    seed = 2**31 + 5
    if t["loop"] == "open":
        a = traffic.open_schedule(t, seed, 10.0, 4)
        b = traffic.open_schedule(t, seed, 10.0, 4)
        c = traffic.open_schedule(t, seed + 1, 10.0, 4)
        assert a == b and a != c
        # same work for every seed: the same gaps in another order (each
        # schedule leaves out the one gap that would follow its last)
        gaps = lambda s: collections.Counter(
            np.diff([r.due_s for r in s]).round(9))
        assert len(a) == len(c) == round(t["rate_per_s"] * 10)
        assert sum(((gaps(a) - gaps(c)) + (gaps(c) - gaps(a))).values()) <= 2
    else:
        take = lambda s: [[next(it) for _ in range(20)] for it in s]
        a = take(traffic.closed_streams(t, seed, 4))
        b = take(traffic.closed_streams(t, seed, 4))
        assert a == b
        assert [r.graph for r in a[-1]] == [(len(a) - 1) % 4] * 20


def test_open_schedule_is_poisson_at_the_rate():
    t = {"rate_per_s": 50.0, "pool": 8}
    s = traffic.open_schedule(t, 3, 100.0, 1)
    gaps = np.diff([r.due_s for r in s])
    assert len(s) == 5000
    assert gaps.mean() == pytest.approx(1 / 50.0, rel=0.01)
    assert gaps.std() == pytest.approx(1 / 50.0, rel=0.05)   # exponential


# ---------------------------------------------------- found by name ----
def test_every_cell_resolves_by_name():
    names = {m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    for name in names:
        assert callable(harness.load_reader(BENCH, name))
    for w in BENCHMARK["workloads"]:
        for tr in (False, True):
            cell = harness.load_cell(BENCHMARK, w["name"], tr)
            assert cell.metrics, (w["name"], tr)
        e2e = harness.load_cell(BENCHMARK, w["name"], False)
        assert {m["name"] for m in e2e.metrics} >= {"setup_s"}


def test_a_new_config_and_traffic_need_no_edit_of_the_core(tmp_path):
    bench_dir, bench = tiny_bench(tmp_path, "closed")
    cell = harness.load_cell(bench, "tiny.closed", False, bench_dir)
    assert cell.config == TINY_CONFIG
    assert cell.traffic == TINY_TRAFFIC["closed"]
    assert [m["name"] for m in cell.metrics] == [
        m["name"] for m in BENCHMARK["end_to_end"]]


# A model the harness has never seen: one linear layer ``A_tilde X W``
# with its weight in a dict, which the program serves as a GCN of one
# layer.
TOY_MODEL = '''
import functools

import jax
import jax.numpy as jnp


def make_params(key, model, spec):
    return {"w": jax.random.uniform(
        key, (spec["n_features"], spec["n_classes"]), jnp.float32,
        -model["scale"], model["scale"])}


def flops(csr, model, spec):
    c = spec["n_classes"]
    return 2.0 * csr.shape[0] * spec["n_features"] * c + 2.0 * csr.nnz * c


@functools.partial(jax.jit, static_argnames=("n", "dtype", "precision",
                                             "operands"))
def forward(rows, cols, vals, x, params, *, n, dtype, precision,
            operands=None):
    def rounded(a):
        return a if operands is None else a.astype(operands).astype(dtype)
    hw = jnp.dot(rounded(x.astype(dtype)),
                 rounded(params["w"].astype(dtype)), precision=precision)
    h = jax.ops.segment_sum(vals.astype(dtype)[:, None] * hw[cols], rows,
                            num_segments=n)
    return h.astype(jnp.float32)


def register(engine, name, csr, *, reorder, params):
    return engine.register(name, csr, reorder=reorder,
                           weights=[params["w"]])
'''


def test_a_new_model_needs_only_new_files(tmp_path):
    bench_dir, bench = tiny_bench(tmp_path, "closed")
    (bench_dir / "models" / "toy.py").write_text(TOY_MODEL)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(
        dict(TINY_CONFIG, model={"kind": "toy", "scale": 0.5})))
    harness.TRACE_DIR = tmp_path / "trace"
    cell = harness.load_cell(bench, "tiny.closed", False, bench_dir)
    assert cell.model.__file__ == str(bench_dir / "models" / "toy.py")
    out = harness.run_cell(cell, seed=2**31 + 23, seconds=1.0, trace=False,
                           t_start=harness.clock(), bench_dir=bench_dir,
                           log=lambda m: None)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0


def test_a_model_kind_without_a_file_fails_before_any_run(tmp_path):
    bench_dir, bench = tiny_bench(tmp_path, "closed")
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(
        dict(TINY_CONFIG, model={"kind": "nosuch"})))
    with pytest.raises(FileNotFoundError, match="model kind 'nosuch'"):
        harness.load_cell(bench, "tiny.closed", False, bench_dir)


# ------------------------------------------------------- whole runs ----
def test_closed_infer_run_is_correct(tmp_path):
    out = run_tiny(tmp_path, "closed")
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"req_per_s", "latency_p50_ms",
                                   "latency_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_err_ratio"]["value"] < 0.01


def test_open_queue_run_is_correct_and_traced(tmp_path):
    out = run_tiny(tmp_path, "open", trace_on=True)
    assert out["correct"], out
    m = out["metrics"]
    assert m["batch_occupancy"]["value"] >= 1.0
    assert m["queue_wait_ms"]["value"] >= 0.0
    assert m["stage_ms"]["value"] > 0.0
    assert out["device"]["window_s"] == pytest.approx(1.0, rel=0.2)


def _alter_one_answer(monkeypatch):
    """A logit changed where the executor produces it."""
    from repro.engine import executor as ex_mod
    real = ex_mod.ExecutorCache._gcn_build

    def broken(self, sc):
        fwd = real(self, sc)

        def altered(part, x, weights):
            y = fwd(part, x, weights)
            return y.at[0, 0].add(0.05 * (1.0 + abs(y).max()))
        return altered
    monkeypatch.setattr(ex_mod.ExecutorCache, "_gcn_build", broken)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_an_altered_answer_is_incorrect(tmp_path, monkeypatch, loop):
    _alter_one_answer(monkeypatch)
    out = run_tiny(tmp_path, loop)
    assert out["correct"] is False
    assert out["checks"]["max_err_ratio"]["value"] > \
        out["checks"]["max_err_ratio"]["limit"]


def test_half_of_a_batch_answered_from_the_other_half_is_incorrect(
        tmp_path, monkeypatch):
    """The batched executor answers its second half with the first
    half's logits."""
    from repro.engine import executor as ex_mod
    real = ex_mod.ExecutorCache.gcn_batched

    def broken(self, sc, f_in, w_shapes, batch):
        fn = real(self, sc, f_in, w_shapes, batch)
        if batch < 2:
            return fn

        def halved(parts, xs, ws):
            ys = fn(parts, xs, ws)
            h = batch // 2
            return ys.at[h:].set(ys[:h])
        return halved
    monkeypatch.setattr(ex_mod.ExecutorCache, "gcn_batched", broken)
    out = run_tiny(tmp_path, "open")
    assert out["correct"] is False


def test_a_request_that_never_answers_is_incorrect(tmp_path, monkeypatch):
    from repro.engine import serving as serving_mod

    def fails(self, name, x):
        raise RuntimeError("dispatch lost")
    monkeypatch.setattr(serving_mod.Engine, "infer", fails)
    monkeypatch.setattr(harness, "warm", lambda *a, **k: None)
    out = run_tiny(tmp_path, "closed")
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0


# --------------------------------------------------------- control ----
def control_readings(cfg: str, seed: int) -> dict:
    """Each compared number of the program's two controls, put in its
    place, on one feature matrix at the configuration's size (on the
    CPU here; ``bench/control.py`` reads them on the chip)."""
    config = harness.load_json(BENCH, "configs", cfg)
    spec = config["graphs"][0]
    a = graph_lib.make_graph(spec, config["graph_seed"])
    rng = traffic.rng_for(seed, 0)
    x = graph_lib.make_features(spec["n_vertices"], spec["n_features"],
                                config["feature_density"], rng)
    dims = [spec["n_features"], config["model"]["d_hidden"],
            spec["n_classes"]]
    ws = [rng.standard_normal((p, q)).astype(np.float32)
          * np.float32(np.sqrt(2.0 / (p + q)))
          for p, q in zip(dims[:-1], dims[1:])]
    fwd = harness.load_model(BENCH, config).forward
    refs = (reference.reference(fwd, a, x, ws),
            reference.reference_stated(fwd, a, x, ws))
    return {side: harness.numbers(fn(fwd, a, x, ws), *refs)
            for side, fn in (("fp8", reference.reference_fp8),
                             ("bf16", reference.reference_bf16))}


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCHMARK["configs"]])
def test_each_control_fails_a_limit(cfg):
    """float8 matmul operands fail ``max_err_ratio``; bfloat16 storage
    fails ``bf16_exact_share``."""
    limits = harness.load_json(BENCH, "configs", cfg)["limits"]
    got = control_readings(cfg, 1)
    assert got["fp8"]["max_err_ratio"] > limits["max_err_ratio"]
    assert got["bf16"]["bf16_exact_share"] > limits["bf16_exact_share"]


def test_the_bf16_control_in_the_programs_place_is_incorrect(monkeypatch):
    """A whole run of a cell of BENCHMARK.json at its size, with every
    answer computed by the all-bfloat16 control in place of
    ``Engine.infer``: ``bf16_exact_share`` fails its limit."""
    from repro.engine import serving as serving_mod
    built = {}
    real_build = harness.build_graphs

    def build(config, model):
        gs = real_build(config, model)
        built.update((g.name, (g, model)) for g in gs)
        return gs

    def control(self, name, x):
        g, model = built[name]
        return reference.reference_bf16(model.forward, g.csr, x, g.params)
    monkeypatch.setattr(harness, "build_graphs", build)
    monkeypatch.setattr(serving_mod.Engine, "infer", control)
    name = next(w["name"] for w in BENCHMARK["workloads"]
                if w["config"] == "gcn-paper.pubmed")
    cell = harness.load_cell(BENCHMARK, name, False)
    out = harness.run_cell(cell, seed=2**31 + 29, seconds=1.0, trace=False,
                           t_start=harness.clock(), log=lambda m: None)
    assert out["correct"] is False
    c = out["checks"]["bf16_exact_share"]
    assert c["value"] > c["limit"]


def test_compared_numbers_of_plain_arrays():
    ref = np.full((4, 3), 1.5, np.float32)
    stated = ref + np.float32(0.25)
    assert reference.max_err_ratio(ref + 0.5, ref, stated) == 2.0
    assert reference.max_err_ratio(ref[:3], ref, stated) == float("inf")
    bad = ref.copy()
    bad[0, 0] = np.nan
    assert reference.max_err_ratio(bad, ref, stated) == float("inf")
    y = ref.copy()
    y[0, :2] = np.nextafter(np.float32(1.5), np.float32(2))
    assert reference.bf16_exact_share(y) == 10 / 12


# ---------------------------------------------------- entry point ----
def test_no_device_is_touched_at_import():
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import bench.lib.harness, bench.lib.trace, bench.lib.reference\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            % (str(CHECKOUT), str(CHECKOUT / "src")))
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, cwd=str(CHECKOUT),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout == ""
