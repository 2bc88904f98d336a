"""CPU tests of the GAT model file (``bench/models/gat.py``) and its
cell: operation and byte counts by hand, the plain forward against a
dense masked softmax, lookup by name, the controls against the
configuration's limits, and whole tiny runs, one with the served
answer altered.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

from bench.lib import graphs as graph_lib
from bench.lib import harness, peaks, reference, traffic

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = CHECKOUT / "bench"
with open(CHECKOUT / "BENCHMARK.json") as f:
    BENCHMARK = json.load(f)
CELL = "pubmed-gat.infer.closed"
READERS = ("attn_score_ms_per_req", "attn_dense_ms_per_req",
           "attn_ell_ms_per_req", "attn_coo_ms_per_req", "attn_roofline")
SMALL = {"kind": "gat", "n_layers": 2, "heads": [2, 3],
         "d_head": [4, "classes"], "concat": [True, False],
         "activation": "elu", "negative_slope": 0.2}
SMALL_SPEC = {"n_features": 5, "n_classes": 2}
GAT = harness.load_model(BENCH, {"model": SMALL})


def a_tilde(n: int, edges) -> sp.csr_matrix:
    """A_tilde's pattern: the undirected ``edges`` and one self loop a
    vertex (values 1; the GAT reads the pattern only)."""
    rows = [i for i, j in edges] + [j for i, j in edges] + list(range(n))
    cols = [j for i, j in edges] + [i for i, j in edges] + list(range(n))
    return sp.csr_matrix((np.ones(len(rows), np.float32), (rows, cols)),
                         shape=(n, n))


def params_for(seed: int, model=SMALL, spec=SMALL_SPEC):
    import jax
    return GAT.make_params(jax.random.key(seed), model, spec)


# ------------------------------------------------------------ counts ----
def test_flops_and_attn_cost_by_hand_on_four_vertices():
    """The path 0-1-2-3: 6 directed edges and 4 self loops, nnz 10.
    Layer 1 (5 -> 2 heads of 4, concatenated), layer 2 (8 -> 3 heads
    of 2 classes, averaged)."""
    a = a_tilde(4, [(0, 1), (1, 2), (2, 3)])
    assert a.nnz == 10
    # X W: 2*4*5*8 = 320 and 2*4*8*6 = 384; projections 4*4*8 = 128 and
    # 4*4*6 = 96; aggregation 2*10*2*5 = 200 and 2*10*3*3 = 180
    assert GAT.flops(a, SMALL, SMALL_SPEC) == 320 + 128 + 200 + 384 + 96 + 180
    fl, by = GAT.attn_cost(4, 10, SMALL, SMALL_SPEC)
    assert fl == 200 + 180
    # a layer, 4 bytes each: [z | 1] N H (F'+1), s_l s_r max 3 N H,
    # an index a nonzero, an offset a vertex, the output once
    layer1 = 4 * 2 * 5 + 3 * 4 * 2 + 10 + 4 + 4 * 8
    layer2 = 4 * 3 * 3 + 3 * 4 * 3 + 10 + 4 + 4 * 2
    assert by == 4 * (layer1 + layer2)


def test_the_configuration_is_at_the_published_widths():
    config = harness.load_json(BENCH, "configs", "gat.pubmed")
    spec = config["graphs"][0]
    layers = GAT._layers(config["model"], spec)
    assert layers == [(500, 8, 8, True), (64, 8, 3, False)]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "gat.pubmed")
    assert entry["reduced"] == ["graph", "reorder"]
    assert {"bias", "dropout", "weights"} <= set(config["assumed"])


def test_make_params_refuses_a_shape_the_program_does_not_serve():
    with pytest.raises(ValueError, match="concatenated"):
        params_for(0, dict(SMALL, concat=[True, True]))
    with pytest.raises(ValueError, match="concatenated"):
        params_for(0, dict(SMALL, negative_slope=0.1))


# ----------------------------------------------------------- forward ----
def dense_gat(a, x, params) -> np.ndarray:
    """The GAT as masked [H, N, N] softmaxes over A's dense pattern, in
    float64."""
    mask = a.toarray() != 0
    h = x.astype(np.float64)
    for i, p in enumerate(params):
        w, a_l, a_r = (np.asarray(p[k], np.float64)
                       for k in ("w", "a_l", "a_r"))
        heads, d = a_l.shape
        z = (h @ w).reshape(-1, heads, d).transpose(1, 0, 2)   # [H, N, F']
        e = (np.einsum("hnf,hf->hn", z, a_l)[:, :, None]
             + np.einsum("hnf,hf->hn", z, a_r)[:, None, :])
        e = np.where(e > 0, e, 0.2 * e)
        e = np.where(mask[None], e, -np.inf)
        alpha = np.exp(e - e.max(axis=2, keepdims=True))
        alpha /= alpha.sum(axis=2, keepdims=True)
        out = alpha @ z                                        # [H, N, F']
        if i < len(params) - 1:
            out = out.transpose(1, 0, 2).reshape(len(h), heads * d)
            h = np.where(out > 0, out, np.expm1(out))
        else:
            h = out.mean(axis=0)
    return h


def test_forward_equals_a_dense_masked_softmax():
    rng = np.random.default_rng(3)
    n = 24
    edges = {(int(i), int(j)) for i, j in rng.integers(0, n, (40, 2))
             if i != j}
    a = a_tilde(n, sorted(edges))
    a.data = rng.uniform(0.1, 1.0, a.nnz).astype(np.float32)  # not read
    x = rng.standard_normal((n, 5)).astype(np.float32)
    params = params_for(7)
    y = reference.reference(GAT.forward, a, x, params)
    want = dense_gat(a, x, params)
    assert y.shape == (n, 2)
    # float32 against float64: a few ulps of the logits' scale
    np.testing.assert_allclose(y, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


# ----------------------------------------------------------- by name ----
def test_the_gat_cell_resolves_its_configuration_model_and_readers():
    cell = harness.load_cell(BENCHMARK, CELL, True)
    assert cell.config["name"] == "gat.pubmed"
    assert Path(cell.model.__file__) == BENCH / "models" / "gat.py"
    assert cell.chips == 1
    names = {m["name"] for m in cell.metrics}
    assert set(READERS) <= names
    for name in READERS:
        assert callable(harness.load_reader(BENCH, name))
    e2e = {m["name"] for m in harness.load_cell(BENCHMARK, CELL,
                                                False).metrics}
    assert {"setup_s", "latency_p50_ms", "req_per_s"} <= e2e


# ---------------------------------------------------------- controls ----
def test_each_control_fails_the_gat_limits():
    """On one feature matrix at the configuration's size (the CPU here;
    ``bench/control.py`` reads them on the chip): float8 matmul
    operands fail ``max_err_ratio``, bfloat16 storage fails
    ``bf16_exact_share``."""
    import jax
    config = harness.load_json(BENCH, "configs", "gat.pubmed")
    limits, spec = config["limits"], config["graphs"][0]
    a = graph_lib.make_graph(spec, config["graph_seed"])
    x = graph_lib.make_features(spec["n_vertices"], spec["n_features"],
                                config["feature_density"],
                                traffic.rng_for(1, 0))
    params = GAT.make_params(jax.random.key(1), config["model"], spec)
    refs = (reference.reference(GAT.forward, a, x, params),
            reference.reference_stated(GAT.forward, a, x, params))
    fp8 = harness.numbers(
        reference.reference_fp8(GAT.forward, a, x, params), *refs)
    bf16 = harness.numbers(
        reference.reference_bf16(GAT.forward, a, x, params), *refs)
    assert fp8["max_err_ratio"] > limits["max_err_ratio"]
    assert bf16["bf16_exact_share"] > limits["bf16_exact_share"]


# -------------------------------------------------------- whole runs ----
TINY = {
    "name": "tiny-gat", "model": dict(SMALL, heads=[4, 2]),
    "graphs": [{"name": "g0", "n_vertices": 300, "n_edges": 1800,
                "n_features": 24, "n_classes": 3}],
    "graph_seed": 0, "reorder": "community", "feature_density": 0.2,
    # the CPU computes every matmul in float32, far closer to the
    # float32 reference than the stated precision is
    "limits": {"max_err_ratio": 0.05, "bf16_exact_share": 0.3},
}
TINY_TRAFFIC = {"loop": "closed", "entry": "infer", "clients": 1, "pool": 3,
                "keep": 0.9, "warm_requests": 2}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """Peaks for the CPU, so that a run here reads the shares: numbers
    of no meaning, only exercised."""
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peaks(bf16_flops=1e12, hbm_bytes=1e11))


def run_tiny_gat(tmp_path, trace_on: bool) -> dict:
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    os.symlink(BENCH / "models", tmp_path / "models")
    os.symlink(BENCH / "metrics", tmp_path / "metrics")
    (tmp_path / "configs" / "tiny-gat.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic" / "tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    bench = {"configs": [{"name": "tiny-gat",
                          "file": "x/configs/tiny-gat.json"}],
             "workloads": [{"name": "tiny-gat.closed", "config": "tiny-gat",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": BENCHMARK["end_to_end"],
             "per_layer": [m for m in BENCHMARK["per_layer"]
                           if CELL in m.get("workloads", ())
                           and m["name"] not in e2e]}
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny-gat.closed"]
    harness.TRACE_DIR = tmp_path / "trace"
    cell = harness.load_cell(bench, "tiny-gat.closed", trace_on, tmp_path)
    return harness.run_cell(cell, seed=2**31 + 41, seconds=1.0,
                            trace=trace_on, t_start=harness.clock(),
                            bench_dir=tmp_path, log=lambda m: None)


def test_a_tiny_gat_run_is_correct_and_traced(tmp_path):
    out = run_tiny_gat(tmp_path, trace_on=True)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    # the CPU's profile has no TPU plane: the scope readers find
    # nothing to read and say so with no number
    assert out["metrics"]["step_mfu"]["value"] > 0
    assert not {"attn_score_ms_per_req", "attn_roofline"} & set(out["metrics"])


def test_an_altered_gat_answer_is_incorrect(tmp_path, monkeypatch):
    """A logit changed where the GAT executor produces it."""
    from repro.engine import executor as ex_mod
    real = ex_mod.ExecutorCache._gat_build

    def broken(self, sc):
        fwd = real(self, sc)

        def altered(part, x, params):
            y = fwd(part, x, params)
            return y.at[0, 0].add(0.05 * (1.0 + abs(y).max()))
        return altered
    monkeypatch.setattr(ex_mod.ExecutorCache, "_gat_build", broken)
    out = run_tiny_gat(tmp_path, trace_on=False)
    assert out["correct"] is False
    c = out["checks"]["max_err_ratio"]
    assert c["value"] > c["limit"]
