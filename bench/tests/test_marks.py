"""CPU tests of `bench.lib.marks`: the program's spans, its ``engine.h2d``
bytes and the device time by named scope, read from a profile.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import glob
import json
import types

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness, marks, trace
from test_harness import (BENCH, GCN, TINY_CONFIG,  # noqa: F401
                          cpu_peaks, synthetic_planes, tiny_bench)

MS = 1_000_000
SCOPE_METRICS = ("combine_ms_per_req", "agg_dense_ms_per_req",
                 "agg_ell_ms_per_req", "agg_coo_ms_per_req")


def profile(planes, stats=None):
    """A stand-in for ``ProfileData`` of plain ``{plane: {line:
    [(name, start_ns, duration_ns)]}}``; ``stats`` maps an event's
    (name, start_ns) to its stats."""
    stats = stats or {}

    def event(name, s, d):
        return types.SimpleNamespace(name=name, start_ns=s, duration_ns=d,
                                     stats=list(stats.get((name, s), {})
                                                .items()))
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pname, lines=[
            types.SimpleNamespace(name=lname,
                                  events=[event(*e) for e in evs])
            for lname, evs in lines.items()])
        for pname, lines in planes.items()])


def marked_planes():
    """`synthetic_planes` with the program's own marks: ``engine.*``
    host spans (one crossing the window's close, one nested in another
    of its name, one on a second thread) and HLO-named device ops."""
    planes = synthetic_planes()
    planes["/host:CPU"]["python"] += [
        ("engine.pad", 0, 8 * MS), ("engine.pad", 2 * MS, 3 * MS),
        ("engine.h2d", 8 * MS, 2 * MS), ("engine.pad", 96 * MS, 10 * MS),
        ("engine.h2d", 106 * MS, 1 * MS)]
    planes["/host:CPU"]["worker"] = [("engine.pad", 60 * MS, 4 * MS)]
    planes["/device:TPU:0"]["XLA Ops"] = [
        ("%fusion.1 = f32[8,128]{1,0} fusion(...)", 10 * MS, 12 * MS),
        ("%fusion.2 = f32[8,128]{1,0} fusion(...)", 22 * MS, 8 * MS),
        ("%fusion.1 = f32[4]{0} fusion(...)", 50 * MS, 5 * MS),
        ("%copy.3 = f32[8]{0} copy(...)", 95 * MS, 4 * MS)]
    return planes


H2D_STATS = {("engine.h2d", 8 * MS): {"bytes": 4096},
             ("engine.h2d", 106 * MS): {"bytes": 4096}}
SCOPE_MAPS = {"jit_fwd(7)": {"fusion.1": "agg.ell", "fusion.2": "combine"}}
WINDOW = (0, 100 * MS)


def test_engine_spans_are_clipped_to_the_window_and_counted_once():
    m = marks.reduce(profile(marked_planes(), H2D_STATS), WINDOW, {})
    # pad: 0-8 (2-5 nested in it), 96-100 of 96-106, 60-64 elsewhere
    assert m.host_s("engine.pad") == pytest.approx(0.016)
    assert m.host_s("engine.h2d") == pytest.approx(0.002)
    assert m.host_s("engine.launch") is None
    assert "client.infer" not in m.host_ns
    # bytes of every h2d span, the one after the window's close too
    assert m.h2d_bytes == 8192
    assert m.scope_ns == {}


def test_scopes_sum_by_module_and_the_rest_closes_to_module_time():
    m = marks.reduce(profile(marked_planes()), WINDOW, SCOPE_MAPS)
    # fusion.1 inside jit_gather is another module's op: not counted
    assert m.scope_s("agg.ell") == pytest.approx(0.012)
    assert m.scope_s("combine") == pytest.approx(0.008)
    assert m.scope_s("agg.coo") is None
    # the second jit_fwd (95-100 in the window) ran only copy.3, which
    # has no scope
    assert m.scope_s(marks.UNSCOPED) == pytest.approx(0.005)
    summary = trace.reduce(marked_planes())
    assert sum(m.scope_ns.values()) / 1e9 == pytest.approx(
        summary.module_s("jit_fwd"))
    assert m.h2d_bytes is None


@pytest.mark.parametrize("maps, found", [
    ({"jit_fwd(7)": {"fusion.1": "agg.ell"}}, True),
    ({"jit_fwd(9)": {"fusion.1": "agg.ell"}}, True),
    ({"jit_fwd(8)": {"fusion.1": "agg.ell"},
      "jit_fwd(9)": {"fusion.1": "agg.coo"}}, False),
    ({"jit_other(7)": {"fusion.1": "agg.ell"}}, False),
], ids=["program_id", "one_of_its_name", "two_of_its_name", "other_name"])
def test_a_module_is_matched_by_program_id_else_by_a_unique_name(maps,
                                                                 found):
    m = marks.reduce(profile(marked_planes()), WINDOW, maps)
    assert (m.scope_s("agg.ell") is not None) == found


def test_idle_gaps_are_named_by_the_engine_spans_with_no_change():
    # the harness's own reduction names a gap by the innermost host
    # event at its midpoint: 0-10 ms and 99-100 ms lie inside engine.pad
    names = {round(sec, 3): n
             for n, sec in trace.reduce(marked_planes()).gap_names}
    assert names == {0.04: "host.none", 0.02: "client.wait",
                     0.01: "engine.pad", 0.001: "engine.pad"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(fwd)/combine/dot_general", "combine"),
    ("jit(fwd)/vmap(agg.coo)/jit(_take)/gather", "agg.coo"),
    ("jit(fwd)/agg.dense/tij,tjf->tif/dot_general", "agg.dense"),
    ("jit(fwd)/vmap(agg.ell)/slice;jit(fwd)/vmap(agg.ell)/slice",
     "agg.ell"),
    ("jit(fwd)/jit(relu)/max", None),
    ("jit(fwd)/vmap()/add", None),
    ("scatter-add", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert marks.scope_of(op_name) == scope


def test_scope_map_of_a_module_proto():
    from jax._src.lib import xla_client
    text = "\n".join([
        "HloModule jit_fwd",
        "ENTRY %main.4 (x.1: f32[8,4], w.1: f32[4,2]) -> f32[8,2] {",
        "  %x.1 = f32[8,4]{1,0} parameter(0)",
        "  %w.1 = f32[4,2]{1,0} parameter(1)",
        "  %dot.9 = f32[8,2]{1,0} dot(%x.1, %w.1), lhs_contracting_dims={1}, "
        'rhs_contracting_dims={0}, metadata={op_name="jit(fwd)/combine/'
        'dot_general"}',
        "  ROOT %negate.1 = f32[8,2]{1,0} negate(%dot.9), "
        'metadata={op_type="x" op_name="jit(fwd)/vmap(agg.coo)/neg"}',
        "}"])
    proto = xla_client._xla.hlo_module_from_text(
        text).as_serialized_hlo_module_proto()
    assert marks.scope_map(proto) == {"dot.9": "combine",
                                      "negate.1": "agg.coo"}


def test_the_profile_carries_the_scopes_of_a_module_compiled_before_it(
        tmp_path):
    @jax.jit
    def probe(x, w):
        with jax.named_scope("combine"):
            y = x @ w
        with jax.named_scope("agg.coo"):
            return jax.ops.segment_sum(y, jnp.arange(8) % 3, 3)
    x, w = jnp.ones((8, 4)), jnp.ones((4, 2))
    probe(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    probe(x, w).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    with open(path, "rb") as f:
        maps = marks.scope_maps(marks.hlo_protos(f.read()))
    # every live module is in the profile; this one once
    (name,) = [n for n in maps if n.startswith("jit_probe(")]
    assert set(maps[name].values()) == {"combine", "agg.coo"}


# ------------------------------------------------------- whole runs ----
ONE_GRAPH = dict(TINY_CONFIG, graphs=TINY_CONFIG["graphs"][:1])


def run_one_graph(tmp_path, trace_on):
    bench_dir, bench = tiny_bench(tmp_path, "closed")
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(ONE_GRAPH))
    harness.TRACE_DIR = tmp_path / "trace"
    cell = harness.load_cell(bench, "tiny.closed", trace_on, bench_dir)
    return harness.run_cell(cell, seed=2**33 + 5, seconds=1.0,
                            trace=trace_on, t_start=harness.clock(),
                            bench_dir=bench_dir, log=lambda m: None)


def test_traced_closed_infer_run_reads_the_staging_metrics(tmp_path):
    out = run_one_graph(tmp_path, trace_on=True)
    assert out["correct"], out
    m = out["metrics"]
    assert m["pad_ms_per_req"]["value"] > 0
    assert m["h2d_ms_per_req"]["value"] > 0
    # every request puts its features [n_cols, f], unpadded and
    # unpermuted, on the device; the class's padding is made there
    from repro.engine import Engine
    (g,) = harness.build_graphs(ONE_GRAPH, GCN)
    h = Engine().register(g.name, harness._program_csr(g.csr),
                          reorder=ONE_GRAPH["reorder"])
    assert h.sclass.n_col_tiles * h.sclass.tile > g.csr.shape[1]
    assert m["h2d_mb_per_req"]["value"] == pytest.approx(
        g.csr.shape[1] * g.spec["n_features"] * 4 / 1e6)
    # a CPU trace has no device plane to read scopes from, but carries
    # the executor's HLO and its scopes
    for name in SCOPE_METRICS:
        assert name not in m
    path = glob.glob(str(tmp_path / "trace/plugins/profile/*/*.xplane.pb"))
    with open(path[-1], "rb") as f:
        maps = marks.scope_maps(marks.hlo_protos(f.read()))
    scopes = {s for name, m in maps.items() if name.startswith("jit_fwd(")
              for s in m.values()}
    meta = h.meta
    loaded = {"combine"} | {f"agg.{k}" for k in ("dense", "ell", "coo")
                            if getattr(meta, f"nnz_{k}")}
    assert len(loaded) >= 3
    assert loaded <= scopes <= loaded | {"agg.dense"}


def test_an_untraced_run_reads_no_marks(tmp_path):
    run = harness.Run(seconds=1.0, setup_s=1.0, records=[], answered=[1],
                      latencies_s=[0.1], flops_answered=1e9, chips=1,
                      peak_flops=1e12, counters=None, queue_waits_s=None,
                      trace=None)
    assert marks.of_run(run) is None
    for p in sorted((BENCH / "metrics").glob("*_per_req.py")):
        if p.stem != "device_ms_per_req":
            assert harness.load_reader(BENCH, p.stem)(run) is None
