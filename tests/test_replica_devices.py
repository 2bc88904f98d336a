"""Replica lanes run on their own devices (4 fake CPU devices, in a
subprocess so the main test process keeps its single-device view)."""
import textwrap

from test_distributed import run_in_subprocess

HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
"""


def test_replica_view_places_lane_on_its_device():
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        from repro.data.graphs import make_paper_dataset
        from repro.engine import Engine
        csr, x, _, _ = make_paper_dataset("cora", scale=0.05, seed=0)
        rng = np.random.default_rng(0)
        w = [rng.standard_normal((x.shape[1], 8)).astype(np.float32),
             rng.standard_normal((8, 4)).astype(np.float32)]
        eng = Engine()
        eng.register("g", csr, weights=w)
        eng.register("h", csr, weights=w)
        want = np.asarray(eng.infer("g", x))
        devs = jax.devices()
        assert len(devs) == 4
        for i, dev in enumerate(devs):
            view = eng.replica_view(i)
            assert view.device == dev
            # single-member and batched (vmapped) dispatch paths
            for group in ([("g", x)], [("g", x), ("h", x)]):
                prepared = [view.prepare_x(n, xx) for n, xx in group]
                assert all(p.devices() == {dev} for p in prepared)
                outs, meta = view.serve_group_async(group, prepared)
                meta["complete"]()
                for y in outs:
                    assert y.devices() == {dev}, (i, y.devices())
                    np.testing.assert_array_equal(np.asarray(y), want)
            placed = eng._placed(eng.handle("g"), dev)
            # registered in input order
            assert (placed.perm, placed.inv_perm) == (None, None)
            leaves = jax.tree.leaves(placed)
            assert all(a.devices() == {dev} for a in leaves), i
        # a copy is made once per device, and a re-register drops it
        h = eng.handle("g")
        assert set(h.copies) == set(devs)
        assert eng._placed(h, devs[2])[0] is h.copies[devs[2]][0]
        eng.register("g", csr, weights=w)
        assert eng.handle("g").copies == {}
        try:
            eng.replica_view(4)
        except ValueError as e:
            assert "own device" in str(e)
        else:
            raise AssertionError("a fifth lane has no device of its own")
        print("PLACED_OK")
        """))
    assert "PLACED_OK" in out


def test_replica_view_permutes_and_pads_on_its_device():
    """A reordered graph's features are staged on the lane's own device
    (its copy of the permutation) exactly as a host permute and zero-pad
    would stage them, and its logits come back in input order."""
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        from repro.data.graphs import make_paper_dataset
        from repro.engine import Engine
        csr, x, _, _ = make_paper_dataset("cora", scale=0.05, seed=0)
        rng = np.random.default_rng(0)
        w = [rng.standard_normal((x.shape[1], 8)).astype(np.float32),
             rng.standard_normal((8, 4)).astype(np.float32)]
        eng = Engine()
        h = eng.register("g", csr, weights=w, reorder="community")
        perm = np.asarray(h.perm)
        rows = h.sclass.n_col_tiles * h.sclass.tile
        want_x = np.pad(x[perm], ((0, rows - x.shape[0]), (0, 0)))
        want = np.asarray(eng.infer("g", x))
        for i, dev in enumerate(jax.devices()):
            view = eng.replica_view(i)
            xp = view.prepare_x("g", x)
            assert xp.devices() == {dev}, i
            np.testing.assert_array_equal(np.asarray(xp), want_x)
            placed = eng._placed(h, dev)
            perms = (placed.perm, placed.inv_perm)
            assert all(p.devices() == {dev} for p in perms), i
            np.testing.assert_array_equal(np.asarray(placed.perm), perm)
            outs, meta = view.serve_group_async([("g", x)])
            meta["complete"]()
            assert outs[0].devices() == {dev}, i
            np.testing.assert_array_equal(np.asarray(outs[0]), want)
            # the lane's own cache built the lane's staging
            assert view.executors.staging_snapshot()["entries"] == 2, i
        assert eng.stats()["device_permutes"] == 1 + 2 * len(jax.devices())
        print("STAGED_OK")
        """))
    assert "STAGED_OK" in out
