"""Async serving frontend: deadline-based batch closing, admission
control, EWMA latency model, the deterministic simulation smoke, and the
engine-side satellites (stack LRU, executor-cache size, padded-MAC waste
telemetry).

Scheduler semantics are tested on a `SimClock` + `StubEngine` — no real
compiles, no wall-clock sleeps, bit-for-bit reproducible. One
integration test drives the queue over the real `Engine` and checks the
batched outputs bitwise against per-request ``infer``.
"""
import numpy as np
import pytest

from repro.serving import (AdmissionError, AdmissionPolicy, LatencyModel,
                           RequestQueue, Scheduler, SimClock, StubEngine,
                           pow2_ceil, run_smoke)

from conftest import make_heterogeneous_matrix


def _sim_queue(clock=None, **kw):
    clock = clock or SimClock()
    engine = StubEngine(clock)
    for i in range(3):
        engine.register(f"g{i}")
    kw.setdefault("target_batch", 4)
    kw.setdefault("default_deadline_ms", 500.0)
    queue = RequestQueue(engine, clock=clock, **kw)
    return queue, engine, clock


def _x(v=1.0):
    return np.full((4, 3), v, np.float32)


def _warm(engine, bss=(1, 2, 4)):
    for bs in bss:
        engine.serve_group([("g0", _x())] * bs)


class TestBatchClosing:
    def test_closes_on_pow2_size(self):
        queue, engine, clock = _sim_queue()
        _warm(engine)
        futs = [queue.submit("g0", _x(i)) for i in range(5)]
        queue.pump()
        # 4 == target_batch dispatched together; the 5th stays pending
        assert [f.done() for f in futs] == [True] * 4 + [False]
        assert queue.stats.close_reasons == {"size": 1}
        assert queue.stats.batch_hist == {4: 1}
        np.testing.assert_array_equal(futs[2].result(timeout=0), _x(2) * 2.0)

    def test_closes_early_on_deadline_slack(self):
        queue, engine, clock = _sim_queue()
        _warm(engine)
        fut = queue.submit("g0", _x(), deadline_ms=500.0)
        queue.pump()
        assert not fut.done(), "plenty of slack — batch must keep lingering"
        est = queue.latency.estimate(
            (engine.handle("g0").sclass, 3, ((2, 2),)), 1)
        # advance to just before the close point: still lingering
        clock.advance(0.5 - queue.scheduler.safety_factor * est - 0.01)
        queue.pump()
        assert not fut.done()
        clock.advance(0.02)   # now slack < safety * est -> must close
        queue.pump()
        assert fut.done()
        assert queue.stats.close_reasons == {"deadline": 1}
        assert queue.stats.deadline_misses == 0, \
            "closing on slack must land the result inside the deadline"

    def test_tighter_later_deadline_drives_close(self):
        queue, engine, clock = _sim_queue()
        _warm(engine)
        f_loose = queue.submit("g0", _x(), deadline_ms=60_000.0)
        f_tight = queue.submit("g0", _x(), deadline_ms=500.0)
        clock.advance(0.2)
        queue.pump()
        assert not f_tight.done()
        # FIFO head is the loose request; the close rule must key off
        # the MINIMUM deadline in the queue, not arrival order
        clock.advance(0.25)
        queue.pump()
        assert f_tight.done() and f_loose.done()
        assert queue.stats.close_reasons == {"deadline": 1}
        assert queue.stats.deadline_misses == 0

    def test_cancelled_future_is_skipped_not_resolved(self):
        queue, engine, clock = _sim_queue(target_batch=2)
        _warm(engine, bss=(2,))
        f1 = queue.submit("g0", _x())
        f2 = queue.submit("g0", _x(2.0))
        assert f1.cancel()
        queue.pump()
        assert f1.cancelled() and f2.done()
        np.testing.assert_array_equal(f2.result(timeout=0), _x(2.0) * 2.0)

    def test_deadline_miss_is_counted(self):
        queue, engine, clock = _sim_queue()
        _warm(engine)
        # deadline shorter than the service time itself: the scheduler
        # closes immediately (slack already below estimate) but the
        # dispatch cannot finish in time — that IS a miss, and it must
        # be visible in telemetry, not silently dropped.
        service = engine.service_s(1)
        fut = queue.submit("g0", _x(), deadline_ms=service * 1e3 / 2)
        queue.pump()
        assert fut.done()
        assert queue.stats.deadline_misses == 1

    def test_drain_closes_remainder(self):
        queue, engine, clock = _sim_queue()
        _warm(engine)
        futs = [queue.submit("g0", _x(i)) for i in range(3)]
        queue.pump()
        assert not any(f.done() for f in futs)
        queue.drain()
        assert all(f.done() for f in futs)
        assert queue.stats.close_reasons == {"drain": 1}
        assert queue.stats.batch_hist == {3: 1}
        # 3 live members dispatched in a pow2-4 vmap slot
        assert queue.stats.padded_slots == 4

    def test_max_linger_caps_waiting(self):
        queue, engine, clock = _sim_queue(max_linger_ms=50.0)
        _warm(engine)
        fut = queue.submit("g0", _x(), deadline_ms=10_000.0)
        clock.advance(0.049)
        queue.pump()
        assert not fut.done()
        clock.advance(0.002)
        queue.pump()
        assert fut.done(), "linger cap must close despite huge slack"

    def test_groups_split_by_feature_width(self):
        queue, engine, clock = _sim_queue(target_batch=2)
        _warm(engine, bss=(2,))
        f_a = queue.submit("g0", np.zeros((4, 3), np.float32))
        f_b = queue.submit("g0", np.zeros((4, 7), np.float32))
        queue.pump()
        # different f_in -> different group keys -> neither reaches
        # target size; both still pending
        assert not f_a.done() and not f_b.done()
        assert queue.depth() == 2
        queue.drain()
        assert f_a.done() and f_b.done()
        assert queue.stats.batch_hist == {1: 2}


class TestDispatchErrors:
    def test_error_resolves_futures_and_queue_survives(self):
        queue, engine, clock = _sim_queue(target_batch=2)
        _warm(engine, bss=(2,))
        orig = engine.serve_group
        engine.serve_group = lambda reqs: (_ for _ in ()).throw(
            RuntimeError("kernel exploded"))
        # two group keys close in the same pump: BOTH plans' futures
        # must carry the error (no abandoned siblings, no hang)
        futs = [queue.submit("g0", _x()), queue.submit("g0", _x()),
                queue.submit("g0", np.zeros((4, 7), np.float32)),
                queue.submit("g0", np.zeros((4, 7), np.float32))]
        queue.pump()
        for f in futs:
            assert f.done()
            with pytest.raises(RuntimeError):
                f.result(timeout=0)
        assert queue.stats.dispatch_errors == 2
        # the queue is still alive once the engine recovers
        engine.serve_group = orig
        ok = [queue.submit("g0", _x()), queue.submit("g0", _x())]
        queue.pump()
        assert all(f.done() for f in ok)
        np.testing.assert_array_equal(ok[0].result(timeout=0), _x() * 2.0)


class TestAdmission:
    def test_depth_budget_rejects_with_reason(self):
        queue, engine, clock = _sim_queue(
            admission=AdmissionPolicy(max_depth=2))
        queue.submit("g0", _x())
        queue.submit("g0", _x())
        with pytest.raises(AdmissionError) as ei:
            queue.submit("g0", _x())
        assert ei.value.reason == "depth"
        assert queue.stats.rejected == {"depth": 1}
        assert queue.stats.arrivals == 2, "rejects are not arrivals"
        queue.drain()

    def test_wait_budget_rejects_with_reason(self):
        lat = LatencyModel(default_s=1.0)   # every batch "takes" 1s
        queue, engine, clock = _sim_queue(
            admission=AdmissionPolicy(max_wait_ms=500.0),
            latency_model=lat)
        with pytest.raises(AdmissionError) as ei:
            queue.submit("g0", _x())
        assert ei.value.reason == "wait"
        assert queue.stats.rejected == {"wait": 1}

    def test_wait_estimate_includes_cross_key_backlog(self):
        # dispatch is serial in the pump thread, so a request's wait
        # includes OTHER keys' pending batches — the pre-fix estimate
        # let a flood on key A sail past the budget by arriving on B
        lat = LatencyModel(default_s=1.0)
        s = Scheduler(lat, target_batch=4)
        for _ in range(8):                      # 2 pending batches on A
            s.add("g", None, ("A",), now=0.0, deadline_s=100.0)
        # joining B stands behind A's 2 batches + its own fresh batch
        assert s.estimated_wait_s(("B",), 0.0) == pytest.approx(3.0)
        # joining A: 9 pending -> 3 batches
        assert s.estimated_wait_s(("A",), 0.0) == pytest.approx(3.0)

    def test_wait_budget_sees_other_keys_backlog(self):
        lat = LatencyModel(default_s=1.0)
        queue, engine, clock = _sim_queue(
            admission=AdmissionPolicy(max_wait_ms=2500.0),
            latency_model=lat, target_batch=4)
        for _ in range(8):                      # backlog on the f_in=3 key
            queue.submit("g0", _x())
        # a DIFFERENT group key must still be rejected: its wait is the
        # cross-key backlog (2 batches) + its own batch = ~3s > 2.5s
        with pytest.raises(AdmissionError) as ei:
            queue.submit("g0", np.zeros((4, 7), np.float32))
        assert ei.value.reason == "wait"
        queue.drain()

    def test_submit_after_stop_rejects(self):
        queue, engine, clock = _sim_queue()
        queue.start()
        queue.stop()
        with pytest.raises(AdmissionError) as ei:
            queue.submit("g0", _x())
        assert ei.value.reason == "stopped"
        assert queue.stats.rejected == {"stopped": 1}

    def test_default_policy_admits(self):
        queue, engine, clock = _sim_queue()
        for i in range(32):
            queue.submit("g0", _x(i))
        queue.drain()
        assert queue.stats.rejected == {}
        assert queue.stats.completed == 32


class TestLatencyModel:
    KEY = ("class", 3, ())

    def test_ewma_update(self):
        m = LatencyModel(alpha=0.5, default_s=9.9)
        m.observe(self.KEY, 4, 0.1)
        assert m.estimate(self.KEY, 4) == pytest.approx(0.1)
        m.observe(self.KEY, 4, 0.2)
        assert m.estimate(self.KEY, 4) == pytest.approx(0.15)

    def test_cold_samples_are_excluded(self):
        m = LatencyModel(default_s=0.05)
        m.observe(self.KEY, 4, 30.0, cold=True)   # a trace+compile
        assert m.cold_skipped == 1 and m.observed == 0
        assert m.estimate(self.KEY, 4) == 0.05, \
            "one compile must not poison the estimate"

    def test_estimate_scales_up_not_down(self):
        m = LatencyModel()
        m.observe(self.KEY, 2, 0.1)
        assert m.estimate(self.KEY, 8) == pytest.approx(0.4)
        # smaller batches keep the observed value: launch overhead
        # dominates there, linear down-scaling would close too late
        assert m.estimate(self.KEY, 1) == pytest.approx(0.1)
        assert m.estimate(("other", 0, ()), 4) == m.default_s

    def test_cold_detection_via_engine_miss_counter(self):
        queue, engine, clock = _sim_queue()
        queue.submit("g0", _x())
        queue.drain()           # first dispatch compiles -> cold sample
        assert queue.latency.cold_skipped == 1
        queue.submit("g0", _x())
        queue.drain()           # warm repeat -> folded into the EWMA
        assert queue.latency.observed == 1
        key = (engine.handle("g0").sclass, 3, ((2, 2),))
        assert queue.latency.known(key, 1)


class TestScheduler:
    def test_pow2_ceil(self):
        assert [pow2_ceil(n) for n in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]

    def test_target_batch_must_be_pow2(self):
        with pytest.raises(ValueError):
            Scheduler(LatencyModel(), target_batch=6)

    def test_next_due_forecast_matches_poll(self):
        m = LatencyModel(default_s=0.01)
        s = Scheduler(m, target_batch=8, safety_factor=2.0)
        s.add("g", None, ("k",), now=0.0, deadline_s=1.0)
        due = s.next_due_s(0.0)
        assert due == pytest.approx(1.0 - 2.0 * 0.01)
        assert s.poll(due - 1e-6) == []
        plans = s.poll(due)
        assert len(plans) == 1 and plans[0].reason == "deadline"

    def test_full_queue_is_due_immediately(self):
        m = LatencyModel(default_s=0.01)
        s = Scheduler(m, target_batch=2)
        s.add("g", None, ("k",), now=0.0, deadline_s=100.0)
        assert s.next_due_s(0.0) > 0.0, "lone request lingers"
        s.add("g", None, ("k",), now=0.0, deadline_s=100.0)
        # rule (a) is satisfiable NOW — a sleeping worker must not wait
        # out the deadline slack before dispatching a full batch
        assert s.next_due_s(0.0) == 0.0
        assert s.poll(0.0)[0].reason == "size"

    def test_smoke_runs(self):
        snap = run_smoke(verbose=False)
        assert snap["deadline_misses"] == 0
        assert snap["mean_batch"] > 1.0


# --------------------------------------------------------------------------
# Engine-side satellites + real-engine integration
# --------------------------------------------------------------------------

def _family_engine(n_graphs=3, f_in=16, hidden=8, classes=4, **kw):
    from repro.core import csr_from_dense
    from repro.engine import Engine
    eng = Engine(**kw)
    rng = np.random.default_rng(0)
    xs = {}
    for i in range(n_graphs):
        n = 300 + 4 * i
        a = make_heterogeneous_matrix(n, seed=i)
        ws = [(rng.standard_normal((f_in, hidden)) * 0.1).astype(np.float32),
              (rng.standard_normal((hidden, classes)) * 0.1
               ).astype(np.float32)]
        eng.register(f"g{i}", csr_from_dense(a), weights=ws)
        xs[f"g{i}"] = rng.standard_normal((n, f_in)).astype(np.float32)
    return eng, xs


class TestEngineSatellites:
    def test_executor_cache_size_is_public(self):
        eng, xs = _family_engine(1)
        assert len(eng.executors) == 0 == eng.executors.size
        eng.infer("g0", xs["g0"])
        assert eng.executors.size == 1 == len(eng.executors)
        assert eng.stats()["executors"] == 1

    def test_stack_cache_is_lru_not_fifo(self):
        eng, xs = _family_engine(3, max_stacks=2)
        pair = lambda a, b: [(a, xs[a]), (b, xs[b])]   # noqa: E731
        eng.serve_group(pair("g0", "g1"))     # stack A founded
        eng.serve_group(pair("g0", "g2"))     # stack B founded
        eng.serve_group(pair("g0", "g1"))     # A hit -> A becomes MRU
        assert eng.stack_hits == 1 and eng.stack_misses == 2
        eng.serve_group(pair("g1", "g2"))     # C founded -> evict LRU=B
        assert eng.stack_evictions == 1
        keys = {k[1:] for k in eng._stacks}    # drop the device slot
        assert ("g0", "g1") in keys, \
            "FIFO would have evicted the hottest stack A; LRU must keep it"
        assert ("g0", "g2") not in keys
        # A must still be a hit (no rebuild) after the eviction round
        eng.serve_group(pair("g0", "g1"))
        assert eng.stack_hits == 2 and eng.stack_misses == 3
        st = eng.stats()
        assert st["stack_hits"] == 2 and st["stack_misses"] == 3
        assert st["stack_evictions"] == 1 and st["stacks"] == 2

    def test_reregister_invalidates_stacks_keeps_lru(self):
        eng, xs = _family_engine(2)
        eng.serve_group([("g0", xs["g0"]), ("g1", xs["g1"])])
        assert len(eng._stacks) == 1
        a = make_heterogeneous_matrix(300, seed=9)
        from repro.core import csr_from_dense
        rng = np.random.default_rng(9)
        ws = [(rng.standard_normal((16, 8)) * 0.1).astype(np.float32),
              (rng.standard_normal((8, 4)) * 0.1).astype(np.float32)]
        eng.register("g0", csr_from_dense(a), weights=ws)
        assert len(eng._stacks) == 0, "stale stacks would serve old weights"
        assert hasattr(eng._stacks, "move_to_end"), \
            "re-register must preserve the LRU container type"

    def test_class_waste_telemetry(self):
        eng, xs = _family_engine(3)
        waste = eng.stats()["class_waste"]
        assert len(waste) == 1, "the family shares one shape class"
        w = next(iter(waste.values()))
        assert w["members"] == 3
        assert w["ell_capacity"] >= w["ell_nnz"] > 0
        assert w["dense_capacity"] >= w["dense_nnz"]
        assert w["coo_capacity"] >= w["coo_nnz"]
        assert 0.0 <= w["ell_waste_frac"] <= 1.0
        assert 0.0 <= w["padded_mac_waste_frac"] <= 1.0

    def test_serve_group_rejects_mixed_keys(self):
        eng, xs = _family_engine(2)
        with pytest.raises(ValueError):
            eng.serve_group([("g0", xs["g0"]),
                             ("g1", xs["g1"][:, :8])])   # f_in differs

    def test_serve_group_empty_is_empty(self):
        eng, xs = _family_engine(1)
        assert eng.serve_group([]) == []


class TestQueueOverRealEngine:
    def test_bitwise_equal_to_infer_and_stats_surface(self):
        clock = SimClock()
        eng, xs = _family_engine(3)
        queue = RequestQueue(eng, target_batch=2, clock=clock,
                             default_deadline_ms=60_000.0)
        reqs = [("g0", xs["g0"]), ("g1", xs["g1"]), ("g2", xs["g2"])]
        futs = [queue.submit(n, x) for n, x in reqs]
        queue.pump()    # size-closes the first pow2 pair
        assert futs[0].done() and futs[1].done()
        queue.drain()   # rule (c) flushes the remainder
        for (name, x), f in zip(reqs, futs):
            got = np.asarray(f.result(timeout=0))
            want = np.asarray(eng.infer(name, x))
            np.testing.assert_array_equal(got, want)
        st = eng.stats()
        assert st["serving"]["completed"] == 3
        assert st["serving"]["deadline_misses"] == 0
        assert st["serving"]["batches"] == 2
        assert queue.stats.close_reasons == {"size": 1, "drain": 1}
