"""The served path's kernels compile for a TPU v5e (no chip needed).

Ahead-of-time compiles for a described ``v5e:2x2`` topology, at the
class shapes of two Table I graphs at their published size: Mosaic
refuses constructs that interpret mode runs happily (an in-kernel row
gather, a vector load from SMEM), and only these compiles catch that
off the chip. Nothing here runs; a passing compile is not a chip run.

The topology is described inside a module fixture, never at import, so
that under pytest-xdist only the worker given this file loads the TPU
compiler. Keep every such compile in this one file.
"""
from __future__ import annotations

import importlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.data.graphs import make_paper_dataset
from repro.engine import Engine
from repro.kernels import ops as kops
from repro.kernels.bsr_spmm import bsr_spmm

ell = importlib.import_module("repro.kernels.ell_spmm")

GRAPHS = ("pubmed", "flickr")
HIDDEN = 128          # gcn-paper: 2 layers, hidden 128
N_CLASSES = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — any failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to a persistent
        # cache but cannot be read back without one: keep it off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def served():
    """graph name -> (engine, handle, f_in), partitioned and classed on
    the host exactly as ``Engine.register`` does for the chip."""
    out = {}
    for name in GRAPHS:
        csr, x, _, _ = make_paper_dataset(name, scale=1.0, seed=0)
        eng = Engine(backend="pallas")
        w = [np.zeros((x.shape[1], HIDDEN), np.float32),
             np.zeros((HIDDEN, N_CLASSES), np.float32)]
        out[name] = (eng, eng.register(name, csr, weights=w), x.shape[1])
    return out


def _on(sharding, a, lead=()):
    return jax.ShapeDtypeStruct(tuple(lead) + tuple(a.shape), a.dtype,
                                sharding=sharding)


def _custom_calls(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("f", [HIDDEN, N_CLASSES])
@pytest.mark.parametrize("graph", GRAPHS)
def test_bsr_spmm_compiles(graph, f, served, one_chip):
    _, h, _ = served[graph]
    dense = h.part.dense
    sc = h.sclass
    bt = jax.ShapeDtypeStruct((sc.n_col_tiles, sc.tile, f), jnp.float32,
                              sharding=one_chip)
    assert _custom_calls(bsr_spmm, _on(one_chip, dense.tiles),
                         _on(one_chip, dense.tile_col), bt) == 1


@pytest.mark.parametrize("gu", [1, "auto"])
@pytest.mark.parametrize("f", [HIDDEN, N_CLASSES])
@pytest.mark.parametrize("graph", GRAPHS)
def test_ragged_ell_spmm_compiles(graph, f, gu, served, one_chip):
    _, h, _ = served[graph]
    e = h.part.ell
    sc = h.sclass
    u, r, kmax = e.cols.shape
    if gu == "auto":
        gu = ell.auto_gu(u, r, kmax, sc.n_col_tiles, sc.tile, f)
    bt = jax.ShapeDtypeStruct((sc.n_col_tiles, sc.tile, f), jnp.float32,
                              sharding=one_chip)

    def launch(cols, vals, tile_col, unit_k, b):
        return ell.ragged_ell_spmm(cols, vals, tile_col, unit_k, b,
                                   segments=tuple(sc.bands), gu=gu)

    assert _custom_calls(launch, *[_on(one_chip, a) for a in
                                   (e.cols, e.vals, e.tile_col, e.unit_k)],
                         bt) == 1


@pytest.mark.parametrize("gu", [1, 4])
def test_ragged_ell_band_switch_compiles(gu, one_chip):
    # three K bands: the kernel body selects each unit's chain with
    # lax.switch, which the Table I classes above (one band) never hit
    u, r, kmax, nct, t, f = 48, 8, 8, 16, 64, HIDDEN
    segments = ((8, 16), (4, 16), (2, 16))
    shapes = [((u, r, kmax), jnp.int32), ((u, r, kmax), jnp.float32),
              ((u,), jnp.int32), ((u,), jnp.int32),
              ((nct, t, f), jnp.float32)]

    def launch(*args):
        return ell.ragged_ell_spmm(*args, segments=segments, gu=gu)

    assert _custom_calls(launch, *[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes]) == 1


@pytest.mark.parametrize("graph", GRAPHS)
def test_batched_pallas_gcn_executor_compiles(graph, served, one_chip,
                                              monkeypatch):
    # the executor asks the platform whether to interpret its kernels;
    # here the platform is the CPU, so steer it to the Mosaic path
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    eng, h, f_in = served[graph]
    sc = h.sclass
    batch = 2
    w_shapes = tuple(tuple(w.shape) for w in h.weights)
    fn = eng.executors.gcn_batched(sc, f_in, w_shapes, batch)
    part = jax.tree.map(lambda a: _on(one_chip, a, (batch,)), h.part)
    x = jax.ShapeDtypeStruct((batch, sc.n_col_tiles * sc.tile, f_in),
                             jnp.float32, sharding=one_chip)
    weights = [_on(one_chip, w, (batch,)) for w in h.weights]
    # per layer: one dense-tile launch and ONE ragged ELL launch
    assert _custom_calls(fn, part, x, weights) == 2 * len(h.weights)


@pytest.mark.parametrize("permuted", [True, False], ids=["perm", "no_perm"])
@pytest.mark.parametrize("graph", GRAPHS)
def test_request_staging_compiles(graph, permuted, served, one_chip):
    """The device-side permute + zero-pad of a request's features to the
    class's rows, and the slice + un-permute of its logits."""
    from repro.engine.executor import _stage_fn, _unstage_fn
    _, h, f_in = served[graph]
    n, rows = h.meta.n_cols, h.sclass.n_col_tiles * h.sclass.tile
    perm = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
            if permuted else None)
    x = jax.ShapeDtypeStruct((n, f_in), jnp.float32, sharding=one_chip)
    staged = _stage_fn(rows).lower(x, perm).compile().as_text()
    assert f"f32[{rows},{f_in}]" in staged.split("ENTRY", 1)[1]
    y = jax.ShapeDtypeStruct((h.padded_meta.n_padded_rows, N_CLASSES),
                             jnp.float32, sharding=one_chip)
    unstaged = _unstage_fn(h.n_rows).lower(y, perm).compile().as_text()
    assert f"f32[{h.n_rows},{N_CLASSES}]" in unstaged.split("ENTRY", 1)[1]
