"""Pipelined dispatch (ISSUE 5): ordering under in-flight reordering,
the drain_class quiesce barrier, admission wait over in-flight work,
latency segment accounting, the roofline EWMA prior, and the threaded
staging-pool/drainer path over the real engine.

Policy semantics run on SimClock + StubEngine (zero compiles,
deterministic); one threaded test drives the real Engine end to end.
"""
import numpy as np
import pytest

from repro.serving import (AdmissionError, AdmissionPolicy, LatencyModel,
                           RequestQueue, SimClock, StubEngine,
                           run_pipeline_smoke)

from conftest import make_heterogeneous_matrix


def _pipe_queue(clock=None, n_names=3, engine_kw=None, **kw):
    clock = clock or SimClock()
    engine = StubEngine(clock, **(engine_kw or {}))
    for i in range(n_names):
        engine.register(f"g{i}")
    kw.setdefault("target_batch", 2)
    kw.setdefault("default_deadline_ms", 500.0)
    kw.setdefault("pipelined", True)
    queue = RequestQueue(engine, clock=clock, **kw)
    return queue, engine, clock


def _x(v=1.0, f=3):
    return np.full((4, f), v, np.float32)


def _warm(engine, bss=(1, 2), f=3):
    for bs in bss:
        engine.serve_group([("g0", _x(f=f))] * bs)


class TestPipelineOrdering:
    def test_within_key_order_preserved_across_key_interleaving(self):
        queue, engine, clock = _pipe_queue(max_inflight=8)
        _warm(engine, bss=(2,))
        _warm(engine, bss=(2,), f=7)
        enqueues = []
        orig = engine.serve_group_async

        def spy(reqs, prepared=None):
            enqueues.append((engine.group_key(reqs[0][0], reqs[0][1]),
                             float(np.asarray(reqs[0][1]).ravel()[0])))
            return orig(reqs, prepared)

        engine.serve_group_async = spy
        # interleave closes across two keys: A1, B1, A2 — the pipeline
        # may overlap freely ACROSS keys, but within key A the second
        # batch must never enqueue (or resolve) before the first
        fa1 = [queue.submit("g0", _x(1.0)) for _ in range(2)]
        queue.pump()
        fb1 = [queue.submit("g0", _x(10.0, f=7)) for _ in range(2)]
        queue.pump()
        fa2 = [queue.submit("g0", _x(2.0)) for _ in range(2)]
        queue.pump()
        queue.drain()
        key_a = engine.group_key("g0", _x(1.0))
        a_vals = [v for k, v in enqueues if k == key_a]
        assert a_vals == [1.0, 2.0], \
            f"within-key enqueue order broken: {a_vals}"
        for f, want in [(fa1, 2.0), (fb1, 20.0), (fa2, 4.0)]:
            for fut in f:
                got = np.asarray(fut.result(timeout=0)).ravel()[0]
                assert got == want
        assert queue.stats.dispatch_errors == 0

    def test_outputs_and_dispatches_match_serial(self):
        def world(pipelined):
            clock = SimClock()
            engine = StubEngine(clock)
            for i in range(3):
                engine.register(f"g{i}")
            queue = RequestQueue(engine, clock=clock, target_batch=2,
                                 default_deadline_ms=500.0,
                                 pipelined=pipelined)
            _warm(engine, bss=(1, 2))
            futs = []
            for i in range(7):
                futs.append(queue.submit(f"g{i % 3}", _x(float(i))))
                queue.pump()
            queue.drain()
            outs = [np.asarray(f.result(timeout=0)) for f in futs]
            return outs, list(engine.dispatches)

        outs_s, disp_s = world(False)
        outs_p, disp_p = world(True)
        assert disp_s == disp_p, "dispatch plan must not depend on mode"
        for a, b in zip(outs_s, outs_p):
            np.testing.assert_array_equal(a, b)

    def test_window_bound_is_respected(self):
        queue, engine, clock = _pipe_queue(max_inflight=2,
                                           engine_kw={"base_s": 1.0})
        _warm(engine, bss=(2,))
        futs = [queue.submit("g0", _x(float(i))) for i in range(12)]
        queue.pump()   # 6 size-closes; slow device -> window backs up
        queue.drain()
        assert queue.stats.inflight_peak <= 2, \
            f"in-flight window exceeded: {queue.stats.inflight_peak}"
        assert queue.stats.inflight_peak >= 1
        assert all(f.done() for f in futs)
        assert queue.inflight() == 0


class TestDrainClassWithInflight:
    def test_quiesces_inflight_no_strand_no_double_dispatch(self):
        queue, engine, clock = _pipe_queue(max_inflight=8, target_batch=4)
        _warm(engine, bss=(1, 2, 4))
        sclass = engine.handle("g0").sclass
        mutated = []
        # a full batch goes IN FLIGHT (enqueued, device still busy) ...
        inflight_futs = [queue.submit("g0", _x(float(i)))
                         for i in range(4)]
        queue.pump()
        assert queue.inflight() >= 1
        assert not any(f.done() for f in inflight_futs)
        # ... plus a partial batch still PENDING in the scheduler
        pending_futs = [queue.submit("g1", _x(9.0)) for _ in range(2)]
        dispatches_before = len(engine.dispatches)
        n = queue.drain_class(sclass, action=lambda: mutated.append(True))
        assert mutated == [True], "action must run exactly once"
        assert queue.inflight() == 0, "quiesce point must be clean"
        for f in inflight_futs + pending_futs:
            assert f.done(), "drain_class stranded a future"
        # pending partial flushed as ONE batch; the in-flight batch was
        # completed, not re-dispatched
        assert len(engine.dispatches) == dispatches_before + 1
        assert n == 1
        assert queue.stats.close_reasons.get("retire") == 1
        for i, f in enumerate(inflight_futs):
            np.testing.assert_array_equal(f.result(timeout=0),
                                          _x(float(i)) * 2.0)

    def test_lifecycle_smoke_runs_pipelined(self):
        # the full serial-vs-pipelined comparison incl. bitwise equality
        snaps = run_pipeline_smoke(verbose=False)
        assert snaps["pipelined"]["deadline_misses"] == 0
        assert snaps["pipelined"]["overlap_ratio"] > 0.2


class TestAdmissionSeesInflight:
    def test_wait_budget_counts_inflight_window(self):
        lat = LatencyModel(default_s=1.0)
        queue, engine, clock = _pipe_queue(max_inflight=8,
                                           latency_model=lat)
        _warm(engine, bss=(2,))
        for i in range(6):
            queue.submit("g0", _x(float(i)))
        queue.pump()   # 3 batches staged+enqueued, none complete yet
        assert queue.inflight() == 3
        assert queue.depth() == 0, "scheduler must be empty"
        queue.admission = AdmissionPolicy(max_wait_ms=2500.0)
        # the scheduler sees nothing, but 3 in-flight batches at ~1s
        # each exceed the 2.5s wait budget (3s backlog + its own batch)
        with pytest.raises(AdmissionError) as ei:
            queue.submit("g0", _x())
        assert ei.value.reason == "wait"
        queue.drain()

    def test_no_inflight_admits(self):
        lat = LatencyModel(default_s=1.0)
        queue, engine, clock = _pipe_queue(
            admission=AdmissionPolicy(max_wait_ms=2500.0),
            latency_model=lat)
        _warm(engine, bss=(2,))
        queue.submit("g0", _x())   # 1 pending batch ~2s < 2.5s budget
        queue.drain()


class TestPipelineErrors:
    def test_staging_error_resolves_futures_queue_survives(self):
        queue, engine, clock = _pipe_queue()
        _warm(engine, bss=(2,))
        orig = engine.serve_group_async
        engine.serve_group_async = lambda reqs, prepared=None: \
            (_ for _ in ()).throw(RuntimeError("stage exploded"))
        futs = [queue.submit("g0", _x()) for _ in range(2)]
        queue.pump()
        for f in futs:
            assert f.done()
            with pytest.raises(RuntimeError):
                f.result(timeout=0)
        assert queue.stats.dispatch_errors == 1
        engine.serve_group_async = orig
        ok = [queue.submit("g0", _x()) for _ in range(2)]
        queue.pump()
        queue.drain()
        assert all(f.done() for f in ok)
        np.testing.assert_array_equal(ok[0].result(timeout=0), _x() * 2.0)


class TestLatencySegments:
    def test_segments_learned_and_total_consistent(self):
        queue, engine, clock = _pipe_queue(
            engine_kw={"base_s": 0.004, "per_item_s": 0.001,
                       "stage_s": 0.004})
        _warm(engine, bss=(2,))
        for i in range(4):
            queue.submit("g0", _x(float(i)))
            queue.pump()
        queue.drain()
        key = engine.group_key("g0", _x())
        stage, dev = queue.latency.estimate_segments(key, 2)
        assert stage > 0 and dev > 0
        assert queue.latency.estimate(key, 2) == \
            pytest.approx(stage + dev)
        assert queue.latency.snapshot()["split_entries"] >= 1

    def test_unsplit_observation_estimates_device_heavy(self):
        m = LatencyModel()
        m.observe("k", 4, 0.1)           # serial path: total only
        stage, dev = m.estimate_segments("k", 4)
        assert stage == 0.0 and dev == pytest.approx(0.1), \
            "unknown split must be charged to the unhidable segment"


class TestRooflinePrior:
    @staticmethod
    def _price_host_as_v5e(monkeypatch):
        """Give this host's device kind the v5e peaks, so the prior can
        be checked on a CPU (which has no published peaks)."""
        import jax
        from repro.analysis import roofline
        monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                            roofline.peaks_for(roofline.V5E))

    def _engine(self):
        from repro.core import csr_from_dense
        from repro.engine import Engine
        eng = Engine()
        rng = np.random.default_rng(0)
        ws = [(rng.standard_normal((16, 8)) * 0.1).astype(np.float32),
              (rng.standard_normal((8, 4)) * 0.1).astype(np.float32)]
        a = make_heterogeneous_matrix(300, seed=0)
        eng.register("g0", csr_from_dense(a), weights=ws)
        x = rng.standard_normal((300, 16)).astype(np.float32)
        return eng, x

    def test_prior_seeds_unseen_key_and_data_overrides(self, monkeypatch):
        self._price_host_as_v5e(monkeypatch)
        eng, x = self._engine()
        key = eng.group_key("g0", x)
        m = LatencyModel(default_s=0.05, prior=eng.latency_prior)
        want = eng.latency_prior(key, 1)
        assert want is not None and want != m.default_s
        assert m.estimate(key, 1) == pytest.approx(want)
        assert m.prior_hits == 1
        m.observe(key, 1, 0.123)
        assert m.estimate(key, 1) == pytest.approx(0.123), \
            "an observation must beat the prior"

    def test_prior_scales_with_batch_and_floors(self, monkeypatch):
        self._price_host_as_v5e(monkeypatch)
        eng, x = self._engine()
        key = eng.group_key("g0", x)
        t1, t8 = eng.latency_prior(key, 1), eng.latency_prior(key, 8)
        assert t8 >= t1 >= eng.LAUNCH_FLOOR_S

    def test_unknown_device_kind_has_no_prior(self, monkeypatch):
        import jax
        from repro.analysis import roofline
        kind = jax.devices()[0].device_kind
        monkeypatch.delitem(roofline.PEAKS, kind, raising=False)
        eng, x = self._engine()
        key = eng.group_key("g0", x)
        assert eng.latency_prior(key, 1) is None, \
            f"no peaks are published for {kind!r}: the model must learn"
        m = LatencyModel(default_s=0.05, prior=eng.latency_prior)
        assert m.estimate(key, 1) == 0.05 and m.prior_hits == 0

    def test_stub_classes_fall_through_to_default(self):
        clock = SimClock()
        engine = StubEngine(clock)
        engine.register("g0")
        m = LatencyModel(default_s=0.07,
                         prior=getattr(engine, "latency_prior", None))
        assert m.prior is None   # stub has no roofline surface
        assert m.estimate(engine.group_key("g0", _x()), 2) == 0.07

    def test_default_queue_model_wires_engine_prior(self):
        eng, x = self._engine()
        queue = RequestQueue(eng, attach=False)
        assert queue.latency.prior == eng.latency_prior


class TestThreadedPipelineRealEngine:
    def test_threaded_staging_pool_bitwise_equal_to_infer(self):
        from repro.core import csr_from_dense
        from repro.engine import Engine
        eng = Engine()
        rng = np.random.default_rng(0)
        xs = {}
        ws = [(rng.standard_normal((16, 8)) * 0.1).astype(np.float32),
              (rng.standard_normal((8, 4)) * 0.1).astype(np.float32)]
        for i, n in enumerate([300, 304, 308]):
            a = make_heterogeneous_matrix(n, seed=i)
            eng.register(f"g{i}", csr_from_dense(a), weights=ws)
            xs[f"g{i}"] = rng.standard_normal((n, 16)).astype(np.float32)
        # warm the executors the traffic can hit — compiles stay out of
        # the threaded path so the test bounds are about plumbing
        eng.infer("g0", xs["g0"])
        eng.serve_group([("g0", xs["g0"])] * 2)
        queue = RequestQueue(eng, target_batch=2, pipelined=True,
                             max_inflight=2, stage_workers=2,
                             default_deadline_ms=60_000.0)
        queue.start()
        try:
            futs = [(name, x, queue.submit(name, x))
                    for name, x in list(xs.items()) * 2]
            outs = [(name, x, f.result(timeout=30.0))
                    for name, x, f in futs]
        finally:
            queue.stop()
        for name, x, y in outs:
            np.testing.assert_array_equal(
                np.asarray(y), np.asarray(eng.infer(name, x)),
                err_msg=f"threaded pipelined output differs for {name}")
        snap = queue.stats.snapshot()
        assert snap["completed"] == 6
        assert snap["dispatch_errors"] == 0
        assert snap["pipelined"] is True
        assert queue.inflight() == 0


class TestAdaptiveInflight:
    def test_window_tracks_observed_overlap(self):
        queue, engine, clock = _pipe_queue(max_inflight=4,
                                           adaptive_inflight=True)
        pipe = queue.pipeline
        assert pipe.inflight_cap == 4 and pipe.max_inflight == 4
        # completion always blocked on the host: overlap 0 -> window
        # collapses to 1 (pipelining buys nothing, stop paying latency)
        for _ in range(20):
            pipe._observe_overlap(1.0, 1.0)
        assert pipe.max_inflight == 1
        assert pipe.overlap_ewma == pytest.approx(0.0)
        # compute fully hides staging again: window earns the cap back,
        # smoothly (EWMA), never overshooting [1, cap]
        seen = []
        for _ in range(20):
            pipe._observe_overlap(0.0, 1.0)
            seen.append(pipe.max_inflight)
        assert seen == sorted(seen)
        assert all(1 <= m <= 4 for m in seen)
        assert pipe.max_inflight == 4

    def test_overlap_clamped_to_unit_interval(self):
        queue, engine, clock = _pipe_queue(max_inflight=3,
                                           adaptive_inflight=True)
        pipe = queue.pipeline
        pipe._observe_overlap(5.0, 1.0)    # wait > device: clamp at 0
        assert pipe.overlap_ewma == 0.0 and pipe.max_inflight == 1
        pipe.overlap_ewma = None
        pipe._observe_overlap(-1.0, 1.0)   # clock skew: clamp at 1
        assert pipe.overlap_ewma == 1.0 and pipe.max_inflight == 3

    def test_disabled_by_default_window_stays_fixed(self):
        queue, engine, clock = _pipe_queue(max_inflight=4)
        _warm(engine, bss=(2,))
        for i in range(6):
            queue.submit("g0", _x(float(i)))
        queue.pump()
        queue.drain()
        pipe = queue.pipeline
        assert pipe.adaptive_inflight is False
        assert pipe.overlap_ewma is None
        assert pipe.max_inflight == pipe.inflight_cap == 4

    def test_end_to_end_adapts_and_completes(self):
        # a slow device with instant staging: real traffic must feed the
        # EWMA and keep the live window inside [1, cap], with every
        # future still resolving
        queue, engine, clock = _pipe_queue(max_inflight=4,
                                           adaptive_inflight=True,
                                           engine_kw={"base_s": 1.0})
        _warm(engine, bss=(2,))
        futs = [queue.submit("g0", _x(float(i))) for i in range(12)]
        queue.pump()
        queue.drain()
        pipe = queue.pipeline
        assert all(f.done() for f in futs)
        assert pipe.overlap_ewma is not None
        assert 1 <= pipe.max_inflight <= pipe.inflight_cap
        snap = pipe.snapshot()
        assert snap["adaptive_inflight"] is True
        assert snap["inflight_cap"] == 4
        assert snap["overlap_ewma"] == pytest.approx(pipe.overlap_ewma)
