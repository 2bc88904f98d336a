"""Request features are permuted and zero-padded on the device: the staged
array, the logits and the counters equal what the host permute and
``np.pad`` they replace would give, for every shape of staging a handle
can ask for. The staging functions live in the executor cache: bounded,
counted, dropped with their class, and reported cold when built."""
import dataclasses

import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from repro.core import csr_from_dense
from repro.engine import Engine
from repro.engine.lifecycle import RetirementPlan

from conftest import make_heterogeneous_matrix

F_IN, HIDDEN, CLASSES = 16, 8, 4
# name -> (vertices, reorder): 300 vertices pad to the class's 512 input
# rows, 256 fill their class's 4 tiles of 64 exactly
GRAPHS = {"perm": (300, "community"), "no_perm": (300, None),
          "perm_no_pad": (256, "community"), "no_pad": (256, None)}


def _weights(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((F_IN, HIDDEN)).astype(np.float32),
            rng.standard_normal((HIDDEN, CLASSES)).astype(np.float32)]


def _x(n, seed=1):
    return np.random.default_rng(seed).standard_normal(
        (n, F_IN)).astype(np.float32)


def _engine(kind, name="g"):
    n, reorder = GRAPHS[kind]
    eng = Engine()
    eng.register(name, csr_from_dense(make_heterogeneous_matrix(n)),
                 weights=_weights(), reorder=reorder)
    return eng


def _host_staged(h, x):
    """The host reference: permute, then zero rows up to the class's."""
    rows = h.sclass.n_col_tiles * h.sclass.tile
    if h.perm is not None:
        x = x[np.asarray(h.perm)]
    return np.pad(x, ((0, rows - x.shape[0]), (0, 0)))


def _host_unstaged(h, y):
    y = np.asarray(y)[: h.n_rows]
    return y if h.inv_perm is None else y[np.asarray(h.inv_perm)]


def _staging_misses(eng):
    return eng.stats()["staging"]["misses"]


def _w_shapes(h):
    return tuple(tuple(w.shape) for w in h.weights)


@pytest.mark.parametrize("kind", list(GRAPHS))
def test_staged_features_equal_the_host_permute_and_pad(kind):
    eng = _engine(kind)
    h = eng.handle("g")
    n, reorder = GRAPHS[kind]
    assert (h.perm is not None) == (reorder is not None)
    padded = h.sclass.n_col_tiles * h.sclass.tile != n
    assert padded == (kind in ("perm", "no_perm"))
    x = _x(n)
    staged = eng.prepare_x("g", x)
    assert staged.shape == (h.sclass.n_col_tiles * h.sclass.tile, F_IN)
    assert staged.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(staged), _host_staged(h, x))
    # the permutation is a device array beside the partition
    if reorder is not None:
        assert h.perm.devices() == h.inv_perm.devices() == \
            jtu.tree_leaves(h.part)[0].devices()
        np.testing.assert_array_equal(
            np.asarray(h.perm)[np.asarray(h.inv_perm)], np.arange(n))


@pytest.mark.parametrize("kind", ["perm", "perm_no_pad"])
def test_infer_logits_equal_a_host_staged_reference(kind):
    eng = _engine(kind)
    h = eng.handle("g")
    x = _x(GRAPHS[kind][0])
    fn = eng.executors.gcn(h.sclass, F_IN, _w_shapes(h))
    want = _host_unstaged(
        h, fn(h.part, jnp.asarray(_host_staged(h, x)), h.weights))
    np.testing.assert_array_equal(np.asarray(eng.infer("g", x)), want)


def test_batched_serve_group_equals_a_host_staged_batch():
    """Three reordered members (one batch of four, the last repeated):
    each member is permuted on the device once, and the logits equal the
    batched executor's on host-staged features."""
    eng = Engine()
    n = 300
    names = ["a", "b", "c"]
    for i, name in enumerate(names):
        eng.register(name, csr_from_dense(make_heterogeneous_matrix(
            n, seed=i)), weights=_weights(), reorder="community")
    hs = [eng.handle(name) for name in names]
    assert len({h.sclass for h in hs}) == 1
    assert all(h.perm is not None for h in hs)
    xs = [_x(n, seed=10 + i) for i in range(3)]
    before = eng.stats()["device_permutes"]
    ys = eng.serve_group(list(zip(names, xs)))
    assert eng.stats()["device_permutes"] - before == 3
    batch = hs + [hs[-1]]
    fn = eng.executors.gcn_batched(hs[0].sclass, F_IN, _w_shapes(hs[0]), 4)
    stack = lambda *leaves: jnp.stack(leaves)       # noqa: E731
    ref = fn(jtu.tree_map(stack, *[h.part for h in batch]),
             jnp.stack([_host_staged(h, x)
                        for h, x in zip(batch, xs + [xs[-1]])]),
             jtu.tree_map(stack, *[h.weights for h in batch]))
    for j, (h, y) in enumerate(zip(hs, ys)):
        np.testing.assert_array_equal(np.asarray(y),
                                      _host_unstaged(h, ref[j]))


def test_staging_pads_to_the_successor_class_after_reclassification():
    eng = _engine("perm")
    h = eng.handle("g")
    x = _x(GRAPHS["perm"][0])
    before = np.asarray(eng.infer("g", x))
    sc = h.sclass
    wider = dataclasses.replace(sc, n_row_tiles=2 * sc.n_row_tiles,
                                n_col_tiles=2 * sc.n_col_tiles)
    eng.execute_retirement(RetirementPlan(
        sclass=sc, names=("g",), targets=(wider,), new_classes=(wider,)))
    assert eng.handle("g").sclass == wider
    # the retired class's staging went with its executors
    assert eng.stats()["staging"]["invalidations"] == 2
    assert eng.stats()["staging"]["entries"] == 0
    staged = eng.prepare_x("g", x)
    assert staged.shape == (wider.n_col_tiles * wider.tile, F_IN)
    np.testing.assert_array_equal(np.asarray(staged), _host_staged(h, x))
    np.testing.assert_array_equal(np.asarray(eng.infer("g", x)), before)


@pytest.mark.parametrize("kind,permutes", [("perm", 1), ("no_perm", 0),
                                           ("no_pad", 0)])
def test_device_permutes_counts_reordered_requests(kind, permutes):
    eng = _engine(kind)
    x = _x(GRAPHS[kind][0])
    for _ in range(3):
        eng.infer("g", x)
    eng.prepare_x("g", x)
    assert eng.stats()["device_permutes"] == 4 * permutes
    assert eng.metrics.get("engine.device_permutes").value == 4 * permutes


def test_staging_rejects_features_of_the_wrong_row_count():
    eng = _engine("perm")
    with pytest.raises(ValueError, match="expects 300"):
        eng.prepare_x("g", _x(299))
    assert eng.stats()["device_permutes"] == 0
    assert eng.stats()["h2d_bytes"] == 0


def test_staging_and_unstaging_run_outside_the_executor_module():
    """The forward's module stays ``jit_fwd`` alone: staging and
    unstaging compile as modules of their own names."""
    eng = _engine("perm")
    n = GRAPHS["perm"][0]
    h = eng.handle("g")
    sc = h.sclass
    rows = sc.n_col_tiles * sc.tile
    staged = eng.executors.stage(sc, n, F_IN, True).lower(
        jnp.asarray(_x(n)), h.perm)
    assert "jit__stage_x" in staged.as_text()
    unstaged = eng.executors.unstage(sc, h.n_rows, CLASSES, True).lower(
        jnp.zeros((rows, CLASSES)), h.inv_perm)
    assert "jit__unstage_y" in unstaged.as_text()


@pytest.mark.parametrize("kind", list(GRAPHS))
def test_register_builds_the_staging_its_infer_needs(kind):
    """A graph registered with weights compiles its staging and
    unstaging at registration (one each, none where neither a permutation
    nor padding is needed), so its first request builds nothing more
    than the executor."""
    eng = _engine(kind)
    h = eng.handle("g")
    sc = h.sclass
    n = GRAPHS[kind][0]
    want = (int(h.perm is not None or sc.n_col_tiles * sc.tile != n)
            + int(h.perm is not None or sc.n_row_tiles * sc.tile != n))
    staging = eng.stats()["staging"]
    assert staging["misses"] == staging["entries"] == want
    _, meta = eng.serve_group_async([("g", _x(n))])
    assert meta["cold"]                  # the executor compiled
    assert _staging_misses(eng) == want
    assert eng.stats()["staging"]["hits"] == want
    _, meta = eng.serve_group_async([("g", _x(n, seed=2))])
    assert not meta["cold"]


def test_a_new_graph_size_in_a_warm_class_is_prewarmed_or_cold():
    """Two reordered graphs of different vertex counts share one class
    and one executor. The second one's staging is built at its
    registration, so its first request is warm; once the bound evicts a
    graph's staging, its next request rebuilds it and says so."""
    eng = Engine(executor_max_entries=2)
    sizes = {"a": 300, "b": 280}
    for name, n in sizes.items():
        eng.register(name, csr_from_dense(make_heterogeneous_matrix(n)),
                     weights=_weights(), reorder="community")
        if name == "a":
            eng.infer("a", _x(n))            # the class's executor
    assert eng.handle("a").sclass == eng.handle("b").sclass
    staging = eng.stats()["staging"]
    # four functions built, two live: a's pair fell out of the bound
    assert (staging["misses"], staging["entries"],
            staging["evictions"]) == (4, 2, 2)
    ys, meta = eng.serve_group_async([("b", _x(sizes["b"]))])
    assert not meta["cold"]
    assert ys[0].shape == (sizes["b"], CLASSES)
    ys, meta = eng.serve_group_async([("a", _x(sizes["a"]))])
    assert meta["cold"]                  # a's staging was rebuilt
    assert _staging_misses(eng) == 6
    assert eng.stats()["staging"]["entries"] == 2
    assert eng.stats()["executors"] == 1
    np.testing.assert_array_equal(np.asarray(ys[0]),
                                  np.asarray(eng.infer("a", _x(sizes["a"]))))
