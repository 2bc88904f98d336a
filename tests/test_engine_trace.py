"""The engine's marks on the profiler's clock: `Tracer.span` and its two
sinks, the host spans of `Engine.infer`, the named scopes of the SpMM
engines in the compiled executor, and the ``engine.h2d_bytes`` counter.
"""
import glob
import re

import jax
import jax.tree_util as jtu
import numpy as np
import pytest
from jax._src.profiler import ProfileData

from repro.core import csr_from_dense
from repro.engine import Engine
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Tracer

from conftest import make_heterogeneous_matrix

ENGINE_SPANS = ("engine.h2d", "engine.pad", "engine.launch",
                "engine.unpad")
SCOPES = ("combine", "agg.dense", "agg.ell", "agg.coo")
F_IN, HIDDEN, CLASSES = 16, 8, 4


@pytest.fixture(scope="module")
def engine():
    """One graph that loads all three SpMM engines, with weights."""
    rng = np.random.default_rng(0)
    ws = [rng.standard_normal((F_IN, HIDDEN)).astype(np.float32),
          rng.standard_normal((HIDDEN, CLASSES)).astype(np.float32)]
    eng = Engine()
    h = eng.register("g", csr_from_dense(make_heterogeneous_matrix(300)),
                     weights=ws, reorder="community")
    assert min(h.meta.nnz_dense, h.meta.nnz_ell, h.meta.nnz_coo) > 0
    return eng


def _x(seed=1):
    return np.random.default_rng(seed).standard_normal(
        (300, F_IN)).astype(np.float32)


# ------------------------------------------------------------ span ----
class TestSpan:
    def test_enabled_tracer_writes_ring_events(self):
        tr = Tracer(capacity=16)
        with tr.span("pad", "engine", {"n": 1}):
            pass
        evs = tr.events()
        assert [e["ph"] for e in evs] == ["B", "E"]
        assert evs[0]["name"] == "pad" and evs[0]["cat"] == "engine"
        assert evs[0]["args"] == {"n": 1}
        assert evs[0]["sid"] == evs[1]["sid"]

    @pytest.mark.parametrize("tracer", [NULL_TRACER,
                                        Tracer(capacity=4, enabled=False)],
                             ids=["null", "disabled"])
    def test_off_returns_the_shared_no_op_and_records_nothing(self, tracer):
        span = tracer.span("pad", "engine")
        assert span is NULL_SPAN
        with span:
            pass
        assert tracer.events() == []
        assert all(s is None for s in tracer._slots)

    def test_span_closes_on_error(self):
        tr = Tracer(capacity=16)
        with pytest.raises(KeyError):
            with tr.span("launch", "engine"):
                raise KeyError("x")
        assert [e["ph"] for e in tr.events()] == ["B", "E"]


# -------------------------------------------------- profiler spans ----
def _profile(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            lines[(plane.name, line.name)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
    return lines


@pytest.mark.parametrize("with_tracer", [False, True],
                         ids=["null_tracer", "ring_tracer"])
def test_infer_spans_are_in_the_profile_on_the_calling_thread(
        tmp_path, engine, with_tracer):
    x = _x()
    np.asarray(engine.infer("g", x))              # compile outside
    tracer = Tracer(capacity=64) if with_tracer else NULL_TRACER
    engine.attach_tracer(tracer)
    try:
        def call():
            with jax.profiler.TraceAnnotation("test.call"):
                np.asarray(engine.infer("g", x))
        lines = _profile(tmp_path, call)
    finally:
        engine.attach_tracer(NULL_TRACER)
    (events,) = [evs for evs in lines.values()
                 if any(n == "test.call" for n, _, _ in evs)]
    (_, lo, hi), = [e for e in events if e[0] == "test.call"]
    mine = [e for e in events if e[0].startswith("engine.")]
    assert [n for n, _, _ in mine] == list(ENGINE_SPANS)
    assert all(lo <= s <= e <= hi for _, s, e in mine)
    if with_tracer:
        names = [e["name"] for e in tracer.events() if e["ph"] == "B"]
        assert names == ["h2d", "pad", "launch", "unpad"]


def test_h2d_span_carries_the_bytes_the_counter_adds(tmp_path, engine):
    x = _x()
    np.asarray(engine.infer("g", x))              # compile outside
    before = engine.stats()["h2d_bytes"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        np.asarray(engine.infer("g", x))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    sent = [dict(e.stats).get("bytes")
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == "engine.h2d"]
    assert sent == [engine.stats()["h2d_bytes"] - before]


# ----------------------------------------------------- named scopes ----
def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _has_scope(op_names, scope):
    return any(f"/{scope}/" in n or f"({scope})/" in n for n in op_names)


@pytest.mark.parametrize("batch", [0, 2], ids=["single", "batched"])
def test_executor_hlo_carries_the_engine_scopes(engine, batch):
    h = engine.handle("g")
    w_shapes = tuple(tuple(w.shape) for w in h.weights)
    xp = engine.prepare_x("g", _x())
    if batch:
        fn = engine.executors.gcn_batched(h.sclass, F_IN, w_shapes, batch)

        def stack(*leaves):
            return jax.numpy.stack(leaves)
        args = [jtu.tree_map(stack, *[a] * batch)
                for a in (h.part, xp, h.weights)]
    else:
        fn = engine.executors.gcn(h.sclass, F_IN, w_shapes)
        args = [h.part, xp, h.weights]
    names = _op_names(fn.lower(*args).compile().as_text())
    missing = [s for s in SCOPES if not _has_scope(names, s)]
    assert not missing, f"scopes missing from op names: {missing}"


# ---------------------------------------------------------- counter ----
def test_h2d_bytes_counts_the_padded_features_of_each_infer(engine):
    """The features cross to the device unpadded, in input order: the
    class's zero rows are added on the device (`_stage_x`), so the
    counter adds exactly the request's own rows."""
    h = engine.handle("g")
    assert h.sclass.n_col_tiles * h.sclass.tile > h.meta.n_cols
    before = engine.stats()["h2d_bytes"]
    for seed in range(3):
        engine.infer("g", _x(seed))
    grown = engine.stats()["h2d_bytes"] - before
    assert grown == 3 * h.meta.n_cols * F_IN * 4
    assert engine.metrics.get("engine.h2d_bytes").value == \
        engine.stats()["h2d_bytes"]
