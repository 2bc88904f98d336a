"""Substrate tests: optimizer, checkpointing, fault tolerance, sampler,
data streams, FM identities, gradient compression."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data.graphs import sbm_graph
from repro.data.recsys import ClickStream
from repro.data.sampler import NeighborSampler, max_sizes
from repro.data.tokens import TokenStream
from repro.distributed.collectives import (compress_with_error_feedback,
                                           ef_init, quantize_int8)
from repro.distributed.fault_tolerance import (RunnerConfig, SimulatedFailure,
                                               TrainingRunner)
from repro.models import fm as fm_m
from repro.train.optimizer import (AdamW, SGD, clip_by_global_norm,
                                   global_norm, warmup_cosine)

KEY = jax.random.PRNGKey(0)


class TestOptimizer:
    def test_adamw_quadratic_convergence(self):
        opt = AdamW(lr=0.1, weight_decay=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        loss = lambda p: jnp.sum(p["w"] ** 2)
        for _ in range(200):
            g = jax.grad(loss)(params)
            params, state = opt.update(g, state, params)
        assert float(loss(params)) < 1e-3

    def test_adamw_matches_reference_formula(self):
        opt = AdamW(lr=0.01, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                    clip_norm=0.0)
        p = {"w": jnp.asarray([1.0, 2.0])}
        s = opt.init(p)
        g = {"w": jnp.asarray([0.5, -0.2])}
        p1, s1 = opt.update(g, s, p)
        m = 0.1 * np.asarray(g["w"])
        v = 0.001 * np.asarray(g["w"]) ** 2
        mhat = m / (1 - 0.9)
        vhat = v / (1 - 0.999)
        want = np.asarray(p["w"]) - 0.01 * mhat / (np.sqrt(vhat) + 1e-8)
        np.testing.assert_allclose(np.asarray(p1["w"]), want, rtol=1e-6)

    def test_weight_decay_is_decoupled(self):
        opt = AdamW(lr=0.01, weight_decay=0.1, clip_norm=0.0)
        p = {"w": jnp.asarray([4.0])}
        s = opt.init(p)
        p1, _ = opt.update({"w": jnp.asarray([0.0])}, s, p)
        np.testing.assert_allclose(float(p1["w"][0]), 4.0 * (1 - 0.001),
                                   rtol=1e-6)

    def test_clip_by_global_norm(self):
        g = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
        clipped = clip_by_global_norm(g, 1.0)
        assert abs(float(global_norm(clipped)) - 1.0) < 1e-6
        same = clip_by_global_norm(g, 100.0)
        np.testing.assert_allclose(np.asarray(same["a"]), [3.0])

    def test_warmup_cosine(self):
        sch = warmup_cosine(1.0, warmup=10, total=100, floor=0.1)
        assert float(sch(jnp.asarray(0))) == 0.0
        assert abs(float(sch(jnp.asarray(10))) - 1.0) < 1e-6
        assert abs(float(sch(jnp.asarray(100))) - 0.1) < 1e-6
        assert float(sch(jnp.asarray(55))) < 1.0

    def test_sgd_momentum(self):
        opt = SGD(lr=0.1, momentum=0.9)
        p = {"w": jnp.asarray([1.0])}
        s = opt.init(p)
        p, s = opt.update({"w": jnp.asarray([1.0])}, s, p)
        p, s = opt.update({"w": jnp.asarray([1.0])}, s, p)
        np.testing.assert_allclose(float(p["w"][0]), 1 - 0.1 - 0.1 * 1.9,
                                   rtol=1e-6)


class TestCheckpoint:
    def test_roundtrip_and_gc(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, async_save=False)
            tree = {"a": jnp.arange(5, dtype=jnp.float32),
                    "nest": {"b": jnp.ones((3, 2))}}
            for step in (1, 2, 3, 4):
                mgr.save(step, jax.tree.map(lambda x: x * step, tree))
            assert mgr.all_steps() == [3, 4]       # keep=2 gc'd the rest
            restored, man = mgr.restore_latest(tree)
            np.testing.assert_allclose(np.asarray(restored["a"]),
                                       np.arange(5) * 4)

    def test_async_save(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=True)
            mgr.save(7, {"x": jnp.zeros(3)})
            mgr.wait()
            assert mgr.latest_step() == 7

    def test_structure_mismatch_rejected(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(1, {"x": jnp.zeros(3)})
            with pytest.raises(AssertionError):
                mgr.restore(1, {"y": jnp.zeros(3)})

    def test_no_partial_checkpoint_visible(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(1, {"x": jnp.zeros(3)})
            os.makedirs(os.path.join(d, ".tmp-step_2"))  # crashed write
            assert mgr.all_steps() == [1]

    def test_uncommitted_step_skipped_by_other_instance(self):
        # The commit-marker handshake: a step directory that a DIFFERENT
        # manager instance has renamed into place but not yet marked
        # COMMITTED must be invisible to an already-live reader's
        # restore_latest.
        from repro.checkpoint import COMMIT_MARKER
        with tempfile.TemporaryDirectory() as d:
            writer = CheckpointManager(d, async_save=False)
            tree = {"x": jnp.arange(3, dtype=jnp.float32)}
            writer.save(1, tree)
            reader = CheckpointManager(d, async_save=False)  # live reader
            writer.save(2, jax.tree.map(lambda v: v * 2, tree))
            # simulate the writer mid-save of step 2: dir + manifest
            # visible, marker not yet written
            os.remove(os.path.join(d, "step_2", COMMIT_MARKER))
            assert reader.all_steps() == [1]
            restored, man = reader.restore_latest(tree)
            assert man["step"] == 1
            np.testing.assert_allclose(np.asarray(restored["x"]),
                                       np.arange(3))

    def test_checksum_detects_silent_corruption(self):
        # Flip array bytes AFTER commit, keeping the npz container valid:
        # the container parse succeeds, so only the per-leaf CRC in the
        # manifest can catch it. restore() must raise; restore_latest()
        # must fall back to the previous committed step and record it.
        from repro.checkpoint import ChecksumError
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=3, async_save=False)
            tree = {"x": jnp.arange(4, dtype=jnp.float32)}
            mgr.save(1, tree)
            mgr.save(2, jax.tree.map(lambda v: v * 2, tree))
            npz = os.path.join(d, "step_2", "arrays.npz")
            data = dict(np.load(npz))
            data["a0"] = data["a0"] + 1.0          # silent bit-rot stand-in
            np.savez(npz, **data)
            with pytest.raises(ChecksumError):
                mgr.restore(2, tree)
            restored, man = mgr.restore_latest(tree)
            assert man["step"] == 1
            np.testing.assert_allclose(np.asarray(restored["x"]),
                                       np.arange(4))
            assert ("checksum_fallback", 2) in mgr.events

    def test_pre_crc_checkpoints_still_restorable(self):
        # Manifests written before the crc32 field existed skip the
        # integrity gate instead of failing it.
        import json as json_mod
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            tree = {"x": jnp.arange(3, dtype=jnp.float32)}
            mgr.save(1, tree)
            mpath = os.path.join(d, "step_1", "manifest.json")
            with open(mpath) as f:
                man = json_mod.load(f)
            del man["crc32"]                       # old-format manifest
            with open(mpath, "w") as f:
                json_mod.dump(man, f)
            restored, man = mgr.restore_latest(tree)
            assert man["step"] == 1

    def test_premarker_checkpoints_backfilled_on_init(self):
        # Checkpoints written before the marker existed (manifest but no
        # COMMITTED file) must stay restorable: a new manager instance
        # stamps them at construction time.
        from repro.checkpoint import COMMIT_MARKER
        with tempfile.TemporaryDirectory() as d:
            writer = CheckpointManager(d, async_save=False)
            tree = {"x": jnp.arange(3, dtype=jnp.float32)}
            writer.save(5, tree)
            os.remove(os.path.join(d, "step_5", COMMIT_MARKER))  # old format
            mgr = CheckpointManager(d, async_save=False)
            assert mgr.all_steps() == [5]
            assert os.path.exists(os.path.join(d, "step_5", COMMIT_MARKER))


class TestFaultTolerance:
    def _quad_step(self):
        opt = SGD(lr=0.05, momentum=0.0)

        def step(params, opt_state, batch):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((p["w"] - batch) ** 2))(params)
            params, opt_state = opt.update(g, opt_state, params)
            return params, opt_state, {"loss": loss}
        p = {"w": jnp.asarray([10.0])}
        return step, p, opt.init(p)

    def test_failure_and_resume_deterministic(self):
        step, p0, s0 = self._quad_step()
        batch_at = lambda i: jnp.asarray([float(i % 3)])
        with tempfile.TemporaryDirectory() as d:
            rc = RunnerConfig(ckpt_dir=d, ckpt_every=4, max_steps=20)
            r1 = TrainingRunner(rc, step, batch_at, inject_failure_at=10)
            with pytest.raises(SimulatedFailure):
                r1.run(p0, s0)
            r2 = TrainingRunner(rc, step, batch_at)
            p_resumed, _, end = r2.run(p0, s0)
            assert end == 20
            assert ("resume", 8) in r2.events

            # ground truth: uninterrupted run
            with tempfile.TemporaryDirectory() as d2:
                rc2 = RunnerConfig(ckpt_dir=d2, ckpt_every=4, max_steps=20)
                p_clean, _, _ = TrainingRunner(rc2, step, batch_at).run(p0, s0)
            np.testing.assert_allclose(np.asarray(p_resumed["w"]),
                                       np.asarray(p_clean["w"]), rtol=1e-6)

    def test_nan_loss_triggers_rollback(self):
        # Regression: the spike guard compared `np.isfinite(loss) is
        # False` — np.bool_ is never identical to Python's False, so a
        # NaN loss sailed through. A one-shot NaN after the step-8
        # checkpoint must roll back to it and still finish the run.
        step, p0, s0 = self._quad_step()
        batch_at = lambda i: jnp.asarray([float(i % 3)])
        calls = {"n": 0}
        fired = {"done": False}

        def nan_step(params, opt_state, batch):
            params, opt_state, metrics = step(params, opt_state, batch)
            if not fired["done"] and calls["n"] >= 10:
                fired["done"] = True
                metrics = {"loss": jnp.asarray(float("nan"))}
            calls["n"] += 1
            return params, opt_state, metrics

        with tempfile.TemporaryDirectory() as d:
            rc = RunnerConfig(ckpt_dir=d, ckpt_every=4, max_steps=16)
            r = TrainingRunner(rc, nan_step, batch_at)
            p_end, _, end = r.run(p0, s0)
            assert end == 16
            assert ("rollback", 8) in r.events
            assert np.isfinite(np.asarray(p_end["w"])).all()


class TestSampler:
    def _adj(self, n=200, e=1600):
        return sbm_graph(n, e, seed=0)

    def test_static_shapes(self):
        adj = self._adj()
        s = NeighborSampler(adj, batch_nodes=8, fanout=(3, 2), seed=0)
        b1, b2 = s.sample(), s.sample()
        assert b1.senders.shape == b2.senders.shape == (s.max_edges,)
        assert b1.node_ids.shape == (s.max_nodes,)

    def test_edges_are_real(self):
        adj = self._adj().tocsr()
        s = NeighborSampler(adj, batch_nodes=8, fanout=(4, 3), seed=1)
        b = s.sample()
        for u, v in zip(b.senders[b.edge_mask], b.receivers[b.edge_mask]):
            gu, gv = b.node_ids[u], b.node_ids[v]
            assert adj[gv, gu] != 0 or adj[gu, gv] != 0

    @given(st.integers(1, 12), st.integers(1, 5), st.integers(1, 5))
    @settings(max_examples=10, deadline=None)
    def test_property_budget(self, batch, f1, f2):
        adj = self._adj()
        s = NeighborSampler(adj, batch_nodes=batch, fanout=(f1, f2), seed=2)
        b = s.sample()
        mn, me = max_sizes(batch, (f1, f2))
        assert int(b.node_mask.sum()) <= mn
        assert int(b.edge_mask.sum()) <= me
        # seeds come first and are valid
        assert b.node_mask[:batch].all()


class TestDataStreams:
    def test_token_stream_deterministic(self):
        s = TokenStream(1000, 4, 16, seed=3)
        b1, b2 = s.batch_at(7), s.batch_at(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(s.batch_at(8)["tokens"], b1["tokens"])
        np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])

    def test_click_stream(self):
        s = ClickStream((100, 50, 10), 32, seed=0)
        b = s.batch_at(0)
        assert b["idx"].shape == (32, 3)
        assert (b["idx"] < np.array([100, 50, 10])).all()
        assert set(np.unique(b["labels"])) <= {0.0, 1.0}

    def test_paper_dataset_identical_across_processes(self):
        # Python salts str hashes per process; two processes with
        # different salts must still synthesize the same pubmed
        import subprocess
        import sys
        code = ("import hashlib\n"
                "from repro.data.graphs import make_paper_dataset\n"
                "a, x, y, _ = make_paper_dataset('pubmed', seed=0)\n"
                "h = hashlib.sha256()\n"
                "for arr in (a.indptr, a.indices, a.data, x, y):\n"
                "    h.update(arr.tobytes())\n"
                "print(h.hexdigest())\n")
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        digests = set()
        for salt in ("1", "2"):
            env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src,
                       JAX_PLATFORMS="cpu")
            out = subprocess.run([sys.executable, "-c", code], env=env,
                                 capture_output=True, text=True,
                                 timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            digests.add(out.stdout.strip())
        assert len(digests) == 1, digests


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                        jnp.float32)
        q, scale = quantize_int8(x)
        err = jnp.abs(q.astype(jnp.float32) * scale - x).max()
        assert float(err) <= float(scale) * 0.5 + 1e-7

    def test_error_feedback_preserves_signal(self):
        """Sum of compressed gradients ~ sum of true gradients (EF-SGD's
        key invariant: the residual never grows unboundedly)."""
        rng = np.random.default_rng(0)
        g_true = [jnp.asarray(rng.standard_normal(64) * 0.01, jnp.float32)
                  for _ in range(50)]
        ef = ef_init({"w": g_true[0]})
        acc_c = jnp.zeros(64)
        for g in g_true:
            cg, ef = compress_with_error_feedback({"w": g}, ef)
            acc_c = acc_c + cg["w"]
        acc_t = sum(np.asarray(g) for g in g_true)
        resid = np.abs(np.asarray(acc_c) - acc_t).max()
        # residual bounded by one quantization step, not accumulating
        assert resid < 0.01


class TestFMIdentities:
    @given(st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def test_property_sum_square_trick(self, seed):
        cfg = get_arch("fm").smoke
        params = fm_m.fm_init(cfg, jax.random.PRNGKey(seed % 7))
        rng = np.random.default_rng(seed)
        idx = jnp.asarray(rng.integers(0, 10, (4, cfg.n_sparse)), jnp.int32)
        s1 = fm_m.fm_score(params, idx, cfg)
        s2 = fm_m.fm_score_ref(params, idx, cfg)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, atol=1e-5)

    def test_retrieval_decomposition(self):
        cfg = get_arch("fm").smoke
        params = fm_m.fm_init(cfg, KEY)
        rng = np.random.default_rng(1)
        offs = fm_m.field_offsets(cfg)
        n_user, m = 3, 50
        user_fields = np.arange(n_user)
        cand_fields = np.arange(n_user, cfg.n_sparse)
        raw = rng.integers(0, 10, (m, cfg.n_sparse)).astype(np.int32)
        raw[:, :n_user] = raw[0, :n_user]          # same user for all rows
        direct = fm_m.fm_score(params, jnp.asarray(raw), cfg)

        flat = raw + offs[None, :]
        user_idx = jnp.asarray(flat[0, :n_user])
        cand_idx = jnp.asarray(flat[:, n_user:])
        fast = fm_m.retrieval_score(params, user_idx, cand_idx, cfg, n_user)
        np.testing.assert_allclose(np.asarray(fast), np.asarray(direct),
                                   rtol=1e-4, atol=1e-5)
