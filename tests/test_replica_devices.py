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
            part, weights = eng._placed(eng.handle("g"), dev)
            leaves = jax.tree.leaves((part, weights))
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
