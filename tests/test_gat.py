"""GAT served by the engine (`repro.core.gat` through `Engine.register` /
`infer`, `serve_group` and `RequestQueue`) against the benchmark's own
plain reference, ``bench/models/gat.py``, loaded as the harness loads
it, on seeded random parameters at a small size."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import csr_from_dense, gcn_forward
from repro.engine import Engine, ShapePolicy
from repro.engine.executor import ExecutorCache
from repro.engine.serving import MODELS
from repro.serving import RequestQueue

from conftest import make_heterogeneous_matrix

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))
from bench.lib import harness, reference  # noqa: E402

GAT = harness.load_model(CHECKOUT / "bench", {"model": {"kind": "gat"}})
F_IN, CLASSES = 16, 3
MODEL = {"kind": "gat", "n_layers": 2, "heads": [4, 2],
         "d_head": [8, "classes"], "concat": [True, False],
         "activation": "elu", "negative_slope": 0.2}
SPEC = {"n_features": F_IN, "n_classes": CLASSES}
SCOPES = ("combine", "attn.score", "attn.dense", "attn.ell", "attn.coo")

# Largest gap to the float32 reference (HIGHEST), over the largest
# logit. Both sides compute in float32 (eps 1.2e-7) and differ only in
# the order of their sums: X W over F_IN = 16 terms, each softmax and
# weighted sum over a row's <= ~120 slots, split across three engines
# and added in another order. That bounds the gap near 100 eps; 1e-5
# leaves room for it, and bfloat16 intermediates (eps 7.8e-3) land
# two orders of magnitude above it
# (`test_bfloat16_intermediates_fail_the_tolerance`).
TOL = 1e-5


def _graph(n: int = 300, seed: int = 0) -> np.ndarray:
    """The three regimes of `make_heterogeneous_matrix`, with positive
    values and a self loop on every vertex, as A_tilde has."""
    a = np.abs(make_heterogeneous_matrix(n, seed=seed))
    np.fill_diagonal(a, 1.0)
    return a


def _params(seed: int, model=MODEL):
    return GAT.make_params(jax.random.key(seed), model, SPEC)


def _x(n: int = 300, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(
        (n, F_IN)).astype(np.float32)


def _ref(a, x, params, at=reference.reference) -> np.ndarray:
    return at(GAT.forward, sp.csr_matrix(a), x, params)


def _gap(y, ref) -> float:
    return float(np.abs(np.asarray(y) - ref).max() / np.abs(ref).max())


def _sparse(n: int = 300, seed: int = 0) -> np.ndarray:
    """Scattered edges and self loops: no tile is dense enough for the
    dense engine."""
    a = (np.random.default_rng(seed).random((n, n)) < 0.004).astype(
        np.float32)
    a = np.maximum(a, a.T)
    np.fill_diagonal(a, 1.0)
    return a


GRAPHS = {"three_engines": _graph, "no_dense_tiles": _sparse,
          "self_loops_only": lambda: np.eye(300, dtype=np.float32)}


@pytest.mark.parametrize("graph, reorder", [
    ("three_engines", None), ("three_engines", "community"),
    ("no_dense_tiles", None), ("self_loops_only", None)])
def test_engine_matches_the_reference(graph, reorder):
    a, x, params = GRAPHS[graph](), _x(), _params(0)
    eng = Engine()
    h = eng.register("g", csr_from_dense(a), reorder=reorder, model="gat",
                     params=params)
    m, sc = h.meta, h.sclass
    if graph == "three_engines":
        # every engine holds a share of the pattern, ELL units of more
        # than one K, and the class pads each of them
        assert m.nnz_dense and m.nnz_coo and len(m.ell_ks) >= 2
        assert sc.coo_nnz > m.nnz_coo
        assert sc.ell_units > int((np.asarray(h.part.ell.unit_k) > 0).sum())
    elif graph == "no_dense_tiles":
        assert sc.n_dense_tiles == 0 and m.nnz_ell and m.nnz_coo
    else:
        assert sc.ell_units == 0 and sc.coo_nnz == 0
    y = eng.infer("g", x)
    assert y.shape == (300, CLASSES)
    assert _gap(y, _ref(a, x, params)) <= TOL
    # the class's padding rows attend to nothing and come out 0
    padded = np.asarray(eng.executors.gat(sc, F_IN, h.param_shapes)(
        h.part, eng.prepare_x("g", x), h.params))
    assert padded.shape[0] > 300 and np.isfinite(padded).all()
    assert not padded[300:].any()


def test_bfloat16_intermediates_fail_the_tolerance():
    a, x, params = _graph(), _x(), _params(0)
    ref = _ref(a, x, params)
    assert _gap(_ref(a, x, params, reference.reference_bf16), ref) > 100 * TOL


def test_padded_slots_change_nothing():
    """One graph in a tight class and in a loose one, whose padding adds
    dense tiles, ELL units and COO triples: the logits are equal."""
    a, x, params = _graph(), _x(), _params(1)
    tight = Engine(policy=ShapePolicy(growth=1.0, coo_growth=1.0))
    loose = Engine(policy=ShapePolicy(growth=4.0, coo_growth=4.0))
    ht, hl = (e.register("g", csr_from_dense(a), model="gat", params=params)
              for e in (tight, loose))
    st, sl = ht.sclass, hl.sclass
    assert sl.n_dense_tiles > st.n_dense_tiles
    assert sl.ell_units > st.ell_units and sl.coo_nnz > st.coo_nnz
    np.testing.assert_array_equal(np.asarray(tight.infer("g", x)),
                                  np.asarray(loose.infer("g", x)))


def test_a_row_whose_only_neighbour_is_itself_returns_its_own_z():
    """One averaged layer: vertex 7 attends to itself alone, so its
    logits are its own ``z`` averaged over the heads."""
    a = _graph()
    a[7, :] = 0.0
    a[:, 7] = 0.0
    a[7, 7] = 1.0
    model = dict(MODEL, n_layers=1, heads=[4], d_head=["classes"],
                 concat=[False])
    params, x = _params(2, model), _x()
    eng = Engine()
    eng.register("g", csr_from_dense(a), model="gat", params=params)
    y = np.asarray(eng.infer("g", x))
    z = (x[7].astype(np.float64) @ np.asarray(params[0]["w"], np.float64))
    np.testing.assert_allclose(y[7], z.reshape(4, CLASSES).mean(axis=0),
                               rtol=1e-6, atol=1e-6)
    assert _gap(y, _ref(a, x, params)) <= TOL


def _three_graphs():
    eng = Engine()
    params = _params(3)
    xs = {}
    for i, n in enumerate([300, 304, 308]):
        a = _graph(n, seed=i)
        eng.register(f"g{i}", csr_from_dense(a), model="gat", params=params)
        xs[f"g{i}"] = _x(n, seed=i)
    return eng, xs


def test_serve_group_and_the_queue_match_infer_bit_for_bit():
    eng, xs = _three_graphs()
    assert len({eng.group_key(n, x) for n, x in xs.items()}) == 1
    want = {n: np.asarray(eng.infer(n, x)) for n, x in xs.items()}
    for n, y in zip(xs, eng.serve_group(list(xs.items()))):
        np.testing.assert_array_equal(np.asarray(y), want[n])
    queue = RequestQueue(eng, target_batch=2, pipelined=True,
                         max_inflight=2, stage_workers=2,
                         default_deadline_ms=60_000.0)
    queue.start()
    try:
        futs = [(n, queue.submit(n, x)) for n, x in list(xs.items()) * 2]
        outs = [(n, f.result(timeout=60.0)) for n, f in futs]
    finally:
        queue.stop()
    for n, y in outs:
        np.testing.assert_array_equal(np.asarray(y), want[n],
                                      err_msg=f"queued {n} differs")
    snap = queue.stats.snapshot()
    assert snap["completed"] == 6 and snap["dispatch_errors"] == 0
    assert snap["batches"] < 6, "no queued batch held two requests"


def test_a_gcn_and_a_gat_handle_never_share_a_group_key():
    a, x = _graph(), _x()
    rng = np.random.default_rng(0)
    eng = Engine()
    eng.register("gat", csr_from_dense(a), model="gat", params=_params(4))
    eng.register("gcn", csr_from_dense(a), weights=[
        rng.standard_normal(s).astype(np.float32)
        for s in ((F_IN, 32), (32, CLASSES))])
    k_gat, k_gcn = eng.group_key("gat", x), eng.group_key("gcn", x)
    assert k_gat[0] == k_gcn[0], "one graph, one shape class"
    assert (k_gat[3], k_gcn[3]) == ("gat", "gcn") and k_gat != k_gcn
    with pytest.raises(ValueError, match="share one"):
        eng.serve_group([("gat", x), ("gcn", x)])


def _op_names(compiled_text):
    import re
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("batch", [0, 2], ids=["single", "batched"])
def test_the_gat_executor_is_jit_fwd_and_carries_the_attention_scopes(batch):
    eng = Engine()
    h = eng.register("g", csr_from_dense(_graph()), model="gat",
                     params=_params(5))
    xp = eng.prepare_x("g", _x())
    args = (h.part, xp, h.params)
    if batch:
        fn = eng.executors.gat_batched(h.sclass, F_IN, h.param_shapes, batch)
        args = [jax.tree_util.tree_map(lambda *v: jnp.stack(v), *[a] * batch)
                for a in args]
    else:
        fn = eng.executors.gat(h.sclass, F_IN, h.param_shapes)
    text = fn.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_fwd")
    names = _op_names(text)
    missing = [s for s in SCOPES
               if not any(f"/{s}/" in n or f"({s})/" in n for n in names)]
    assert not missing, f"scopes missing from op names: {missing}"


def test_the_gcn_executor_key_and_hlo_do_not_move():
    """The GCN handle keeps its executor key, and its executor lowers
    to exactly the module of `gcn_forward` jitted alone."""
    rng = np.random.default_rng(0)
    ws = [rng.standard_normal(s).astype(np.float32)
          for s in ((F_IN, 32), (32, CLASSES))]
    eng = Engine()
    h = eng.register("g", csr_from_dense(_graph()), weights=ws)
    w_shapes = ((F_IN, 32), (32, CLASSES))
    assert (h.model, h.param_shapes, h.weights) == ("gcn", w_shapes, h.params)
    eng.infer("g", _x())
    assert list(eng.executors._fns) == [
        ("gcn", h.sclass, F_IN, w_shapes, "xla", 0, "ragged", ())]
    meta = h.sclass.to_meta()

    def fwd(part, x, weights):
        return gcn_forward(part, x, weights, meta=meta, backend="xla",
                           block_cols=0, ell_dispatch="ragged",
                           ell_tune=None)
    xp = eng.prepare_x("g", _x())
    want = jax.jit(fwd).lower(h.part, xp, ws).as_text()
    got = eng.executors.gcn(h.sclass, F_IN, w_shapes).lower(
        h.part, xp, h.weights).as_text()
    assert got == want


def test_attention_counters_read_the_padding_waste():
    eng = Engine()
    h = eng.register("g", csr_from_dense(_graph()), model="gat",
                     params=_params(6))
    for seed in range(2):
        eng.infer("g", _x(seed=seed))
    sc, heads = h.sclass, 4 + 2
    slots = (sc.n_dense_tiles * sc.tile ** 2
             + sc.ell_units * sc.r_block * sc.ell_kmax + sc.coo_nnz)
    s = eng.stats()
    assert s["attn_edge_slots"] == 2 * heads * slots
    assert s["attn_edges"] == 2 * heads * h.meta.nnz
    assert s["attn_edge_slots"] > s["attn_edges"]


def test_register_refuses_what_the_gat_cannot_serve():
    a = _graph()
    with pytest.raises(NotImplementedError, match="xla"):
        Engine(backend="pallas").register("g", csr_from_dense(a),
                                          model="gat", params=_params(0))
    bad = _params(0)
    bad[1] = dict(bad[1], a_r=bad[1]["a_r"][:1])
    with pytest.raises(ValueError, match="layer 1"):
        Engine().register("g", csr_from_dense(a), model="gat", params=bad)
    with pytest.raises(ValueError, match="square"):
        Engine().register("g", csr_from_dense(a[:, :200]), model="gat",
                          params=_params(0))
    with pytest.raises(ValueError, match="unknown model"):
        Engine().register("g", csr_from_dense(a), model="gin")


def test_infer_without_parameters_names_the_model():
    eng = Engine()
    eng.register("g", csr_from_dense(_graph()), model="gat")
    with pytest.raises(ValueError, match=r"\(gat\).*params="):
        eng.infer("g", _x())


@pytest.mark.parametrize("model", sorted(MODELS))
def test_each_served_model_names_executors_the_cache_has(model):
    kind = MODELS[model]
    for name in (kind.executor, kind.executor + "_batched"):
        assert callable(getattr(ExecutorCache, name, None)), name


def test_the_latency_prior_prices_the_attention_widths(monkeypatch):
    """A GAT key is priced at its aggregation widths H (F' + 1) a layer
    ([z | 1] per head) and its W matmuls, not as a GCN's."""
    from repro.analysis import roofline
    peaks = roofline.PEAKS[roofline.V5E]
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind, peaks)
    monkeypatch.setattr(Engine, "LAUNCH_FLOOR_S", 0.0)
    eng = Engine()
    eng.register("g", csr_from_dense(_graph()), model="gat",
                 params=_params(0))
    key = eng.group_key("g", _x())
    sc = key[0]
    macs = (sc.ell_mac_capacity + sc.n_dense_tiles * sc.tile ** 2
            + sc.coo_nnz)
    n_rows = sc.n_row_tiles * sc.tile
    widths = 4 * (8 + 1) + 2 * (CLASSES + 1)
    flops = (2.0 * macs * widths
             + 2.0 * n_rows * (F_IN * 32 + 32 * 2 * CLASSES))
    byts = 4.0 * (macs + n_rows * widths)
    assert eng.latency_prior(key, 2) == pytest.approx(
        2 * max(flops / peaks.flops, byts / peaks.hbm_bw))
