"""Observability stack tests (ISSUE 8): ring tracer, typed metrics,
Chrome-trace export, offline critical-path report, and the
trace-completeness property over the serving frontend.

The property tests run on `SimClock` + `StubEngine` — zero real
compiles — and work with either real hypothesis or the offline stub
(tests/_hypothesis_stub.py).
"""
import json
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.obs.export import (DEVICE_PID, DEVICE_TID, HOST_PID,
                              chrome_trace, write_chrome_trace)
from repro.obs.metrics import (Counter, CounterFamily, Gauge, Histogram,
                               MetricsRegistry, percentile, percentile_ms)
from repro.obs.report import (check_complete, dominant_hist, instants,
                              measured_overlap, overlap_check, report,
                              spans, stage_table, waste_by_class)
from repro.obs.trace import NULL_TRACER, Tracer, label
from repro.serving import (AdmissionError, AdmissionPolicy, RequestQueue,
                           SimClock, StubEngine, bursty_trace, replay_trace)


# ------------------------------------------------------------- tracer -----

class TestTracer:
    def test_disabled_is_inert(self):
        tr = Tracer(capacity=8, enabled=False)
        assert tr.begin("x") == -1
        tr.end(-1)
        tr.instant("y")
        assert not tr.sample(0)
        assert tr.events() == []
        assert all(s is None for s in tr._slots), \
            "a disabled tracer must not touch the ring"

    def test_null_tracer_shared_sentinel(self):
        assert NULL_TRACER.enabled is False
        assert NULL_TRACER.begin("x") == -1
        assert NULL_TRACER.events() == []

    def test_begin_end_roundtrip(self):
        clock = SimClock()
        tr = Tracer(capacity=16, clock=clock)
        sid = tr.begin("work", "serving", req=7, args={"a": 1})
        clock.advance(0.5)
        tr.end(sid, args={"b": 2})
        evs = tr.events()
        assert [e["ph"] for e in evs] == ["B", "E"]
        assert evs[0]["sid"] == sid and evs[1]["sid"] == sid
        assert evs[0]["req"] == 7
        assert evs[1]["ts"] - evs[0]["ts"] == pytest.approx(0.5)

    def test_end_minus_one_is_noop(self):
        tr = Tracer(capacity=8)
        tr.end(-1)
        assert tr.events() == []

    def test_cross_thread_end(self):
        clock = SimClock()
        tr = Tracer(capacity=16, clock=clock)
        sid = tr.begin("hop", "serving")
        t = threading.Thread(target=lambda: tr.end(sid))
        t.start()
        t.join()
        evs = tr.events()
        assert [e["ph"] for e in evs] == ["B", "E"]
        assert evs[0]["tid"] != evs[1]["tid"]
        doc = chrome_trace(evs)
        (x,) = spans(doc)
        assert x["tid"] == evs[0]["tid"], \
            "a cross-thread span renders on the beginning thread's track"

    def test_sampling_deterministic(self):
        tr = Tracer(capacity=8, sample_every=3)
        assert [tr.sample(i) for i in range(7)] == \
            [True, False, False, True, False, False, True]
        tr.enabled = False
        assert not tr.sample(0)

    def test_ring_wrap_drops_oldest(self):
        tr = Tracer(capacity=4)
        sids = [tr.begin(f"s{i}") for i in range(6)]
        assert tr.wrapped()
        evs = tr.events()
        assert len(evs) == 4
        assert [e["sid"] for e in evs] == sids[2:], \
            "wrap must drop the OLDEST events"

    def test_no_wrap_under_capacity(self):
        tr = Tracer(capacity=8)
        tr.begin("a")
        assert not tr.wrapped()

    def test_reject_ids_negative_and_unique(self):
        tr = Tracer(capacity=8)
        ids = [tr.reject_id() for _ in range(4)]
        assert all(i < 0 for i in ids)
        assert len(set(ids)) == 4

    def test_clear(self):
        tr = Tracer(capacity=8)
        tr.begin("a")
        tr.clear()
        assert tr.events() == []
        assert not tr.wrapped()

    def test_label_prefers_summary(self):
        class HasSummary:
            def summary(self):
                return "sc[n<=64]"

        class BadSummary:
            def summary(self):
                raise RuntimeError("boom")

            def __str__(self):
                return "fallback"

        assert label(HasSummary()) == "sc[n<=64]"
        assert label(BadSummary()) == "fallback"
        assert label(3) == "3"


# ------------------------------------------------- percentile (sat. 1) ----

class TestPercentile:
    """Regression pin for the ONE shared percentile helper: linear
    interpolation (np.percentile default), empty-safe. Every latency
    percentile in ServerStats, the smokes, the benchmark drivers and
    trace_report flows through this function."""

    def test_empty_returns_zero(self):
        assert percentile([], 99) == 0.0
        assert percentile_ms([], 50) == 0.0

    @pytest.mark.parametrize("samples,q,want", [
        ([1.0, 2.0, 3.0, 4.0], 50, 2.5),      # midpoint interpolation
        ([1.0, 2.0, 3.0, 4.0], 0, 1.0),
        ([1.0, 2.0, 3.0, 4.0], 100, 4.0),
        ([0.0, 10.0], 75, 7.5),                # linear between samples
        ([1.0, 2.0, 3.0, 4.0, 5.0], 90, 4.6),  # (n-1)*q/100 fractional
        ([5.0], 99, 5.0),
    ])
    def test_linear_interpolation_pinned(self, samples, q, want):
        assert percentile(samples, q) == pytest.approx(want)

    def test_matches_numpy_reference(self):
        rng = np.random.default_rng(0)
        xs = rng.uniform(0, 1, 101).tolist()
        for q in (1, 25, 50, 75, 99):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)))

    def test_percentile_ms_scales(self):
        assert percentile_ms([0.001, 0.003], 50) == pytest.approx(2.0)


# ------------------------------------------------------------ metrics -----

class TestMetrics:
    def test_counter(self):
        reg = MetricsRegistry()
        c = Counter("c", reg)
        c.inc()
        c.inc(3)
        assert c.value == 4
        assert reg.snapshot() == {"c": 4}

    def test_counter_threaded_exact(self):
        c = Counter("c")
        n, per = 8, 1000

        def worker():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n * per

    def test_gauge(self):
        g = Gauge("g")
        g.set(2.0)
        g.set_max(1.0)
        assert g.value == 2.0
        g.set_max(5.0)
        assert g.value == 5.0
        g.add(1.0)
        assert g.value == 6.0

    def test_histogram_window_and_lifetime(self):
        h = Histogram("h", window=4)
        for v in range(8):
            h.observe(float(v))
        assert h.count == 8                 # lifetime count survives trim
        assert h.total == sum(range(8))
        assert h.values() == [4.0, 5.0, 6.0, 7.0]
        assert h.mean() == pytest.approx(sum(range(8)) / 8)
        assert h.percentile(50) == pytest.approx(5.5)
        snap = h.snapshot_value()
        assert set(snap) == {"count", "mean", "p50", "p99"}
        assert snap["count"] == 8

    def test_histogram_empty(self):
        h = Histogram("h")
        assert h.mean() == 0.0
        assert h.percentile(99) == 0.0
        assert h.snapshot_value()["p50"] == 0.0

    def test_counter_family(self):
        f = CounterFamily("f")
        f.inc("depth")
        f.inc("depth")
        f.inc("wait", 3)
        assert f.get("depth") == 2
        assert f.get("nope") == 0
        assert f.total() == 5
        assert f.as_dict() == {"depth": 2, "wait": 3}

    def test_registry_helpers_and_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("a").inc()
        reg.gauge("b").set(2.0)
        reg.histogram("c").observe(1.0)
        reg.family("d").inc("x")
        assert reg.names() == ["a", "b", "c", "d"]
        snap = reg.snapshot()
        assert snap["a"] == 1 and snap["b"] == 2.0
        assert snap["c"]["count"] == 1
        assert snap["d"] == {"x": 1}
        assert reg.get("a") is not None
        assert reg.get("zzz") is None


# ------------------------------------------------------------- export -----

def _traced_pair(clock, tr):
    """One host span + one device-cat child span, closed."""
    sid = tr.begin("staging", "serving", req=1, args={"reqs": [1]})
    clock.advance(0.001)
    dev = tr.begin("device", "device", parent=sid,
                   args={"reqs": [1], "live": 1, "padded": 2,
                         "sclass": "sc"})
    clock.advance(0.004)
    tr.end(dev)
    tr.end(sid)
    return sid, dev


class TestExport:
    def test_device_spans_route_to_virtual_track(self):
        clock = SimClock()
        tr = Tracer(capacity=32, clock=clock)
        _traced_pair(clock, tr)
        doc = chrome_trace(tr.events())
        by_name = {s["name"]: s for s in spans(doc)}
        assert by_name["device"]["pid"] == DEVICE_PID
        assert by_name["device"]["tid"] == DEVICE_TID
        assert by_name["staging"]["pid"] == HOST_PID

    def test_span_assembly_merges_args_and_injects_ids(self):
        clock = SimClock()
        tr = Tracer(capacity=32, clock=clock)
        sid = tr.begin("w", "serving", req=9, parent=5, args={"a": 1})
        clock.advance(0.002)
        tr.end(sid, args={"b": 2})
        doc = chrome_trace(tr.events())
        (x,) = spans(doc)
        assert x["ph"] == "X"
        assert x["args"]["a"] == 1 and x["args"]["b"] == 2
        assert x["args"]["sid"] == sid
        assert x["args"]["parent"] == 5 and x["args"]["req"] == 9
        assert x["ts"] == 0.0                      # relative to earliest
        assert x["dur"] == pytest.approx(2000.0)   # µs

    def test_unclosed_span_flagged_not_dropped(self):
        tr = Tracer(capacity=32)
        tr.begin("dangling", "serving")
        doc = chrome_trace(tr.events())
        (x,) = spans(doc)
        assert x["args"]["unclosed"] is True
        assert x["dur"] == 0.0

    def test_orphan_ends_counted(self):
        tr = Tracer(capacity=2)   # B falls off the ring, E survives
        sid = tr.begin("old")
        tr.begin("new")
        tr.end(sid)
        doc = chrome_trace(tr.events())
        assert doc["otherData"]["orphan_ends"] == 1

    def test_instants_exported(self):
        tr = Tracer(capacity=32)
        tr.instant("cache.hit", "engine", args={"kind": "spmm"})
        doc = chrome_trace(tr.events())
        (i,) = instants(doc)
        assert i["s"] == "t" and i["name"] == "cache.hit"
        assert i["args"]["kind"] == "spmm"

    def test_track_metadata_events(self):
        clock = SimClock()
        tr = Tracer(capacity=32, clock=clock)
        _traced_pair(clock, tr)
        doc = chrome_trace(tr.events())
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        names = {(e["name"], e["pid"], e["args"]["name"]) for e in meta}
        assert ("process_name", HOST_PID, "host") in names
        assert ("process_name", DEVICE_PID, "device") in names
        assert ("thread_name", DEVICE_PID, "device window") in names
        assert any(e["name"] == "thread_name" and e["pid"] == HOST_PID
                   for e in meta)

    def test_write_chrome_trace_records_ring_state(self, tmp_path):
        clock = SimClock()
        tr = Tracer(capacity=32, clock=clock)
        _traced_pair(clock, tr)
        path = tmp_path / "trace.json"
        doc = write_chrome_trace(str(path), tr, metadata={"k": "v"})
        on_disk = json.loads(path.read_text())
        assert on_disk == doc
        assert doc["otherData"]["ring_capacity"] == 32
        assert doc["otherData"]["ring_wrapped"] is False
        assert doc["otherData"]["k"] == "v"
        assert doc["displayTimeUnit"] == "ms"


# ------------------------------------------------------------- report -----

def _request_world(clock, tr, *, n_reqs=2, device_ms=4.0, wait_ms=0.0):
    """A minimal complete trace: per-request root+queue spans, one
    batch staging/device/wait_device chain."""
    roots, queues = [], []
    for r in range(n_reqs):
        root = tr.begin("request", "request", req=r, args={"name": "g"})
        q = tr.begin("queue", "queue", req=r, parent=root)
        roots.append(root)
        queues.append(q)
    clock.advance(0.002)
    for q in queues:
        tr.end(q, args={"reason": "size"})
    reqs = list(range(n_reqs))
    stage = tr.begin("staging", "serving", args={"reqs": reqs})
    clock.advance(0.001)
    tr.end(stage)
    dev = tr.begin("device", "device",
                   args={"reqs": reqs, "live": n_reqs,
                         "padded": 2 * n_reqs, "sclass": "sc"})
    wait = tr.begin("wait_device", "serving", parent=dev)
    clock.advance(wait_ms / 1e3)
    tr.end(wait)
    clock.advance(max(0.0, (device_ms - wait_ms) / 1e3))
    tr.end(dev)
    for root in roots:
        tr.end(root, args={"missed": False})


class TestReport:
    def _doc(self, **kw):
        clock = SimClock()
        tr = Tracer(capacity=256, clock=clock)
        _request_world(clock, tr, **kw)
        meta = kw.pop("metadata", {})
        return chrome_trace(tr.events(), metadata=meta)

    def test_complete_world_has_no_problems(self):
        assert check_complete(self._doc()) == []

    def test_unclosed_span_is_a_problem(self):
        clock = SimClock()
        tr = Tracer(capacity=64, clock=clock)
        tr.begin("request", "request", req=0)
        doc = chrome_trace(tr.events())
        probs = check_complete(doc)
        assert any("unclosed" in p for p in probs)

    def test_request_without_root_is_a_problem(self):
        clock = SimClock()
        tr = Tracer(capacity=64, clock=clock)
        # batch span names req 3 as a member, but req 3 has no root
        sid = tr.begin("device", "device", args={"reqs": [3]})
        tr.end(sid)
        probs = check_complete(chrome_trace(tr.events()))
        assert any("request 3" in p and "expected 1" in p for p in probs)

    def test_orphan_parent_is_a_problem(self):
        clock = SimClock()
        tr = Tracer(capacity=64, clock=clock)
        sid = tr.begin("queue", "queue", req=0, parent=999)
        tr.end(sid)
        root = tr.begin("request", "request", req=0)
        tr.end(root)
        probs = check_complete(chrome_trace(tr.events()))
        assert any("orphan span" in p for p in probs)

    def test_ring_wrap_is_a_problem(self):
        doc = {"traceEvents": [], "otherData": {"ring_wrapped": True}}
        assert any("ring wrapped" in p for p in check_complete(doc))

    def test_stage_table_and_dominant(self):
        doc = self._doc(device_ms=4.0)
        table = stage_table(doc)
        assert table["device"]["n"] == 1
        assert table["device"]["p50_ms"] == pytest.approx(4.0)
        assert table["queue"]["n"] == 2
        dom = dominant_hist(doc)
        assert dom == {"device": 2}   # both members dominated by device

    def test_overlap_full_hiding(self):
        doc = self._doc(device_ms=4.0, wait_ms=0.0)
        m = measured_overlap(doc)
        assert m["batches"] == 1
        assert m["ratio"] == pytest.approx(1.0)

    def test_overlap_serial_no_hiding(self):
        doc = self._doc(device_ms=4.0, wait_ms=4.0)
        assert measured_overlap(doc)["ratio"] == pytest.approx(0.0)

    def test_overlap_check_tolerance(self):
        clock = SimClock()
        tr = Tracer(capacity=256, clock=clock)
        _request_world(clock, tr, device_ms=4.0, wait_ms=0.0)
        good = chrome_trace(tr.events(),
                            metadata={"serving": {"overlap_ratio": 0.99}})
        assert overlap_check(good)["ok"]
        bad = chrome_trace(tr.events(),
                           metadata={"serving": {"overlap_ratio": 0.50}})
        assert not overlap_check(bad)["ok"]

    def test_waste_by_class(self):
        doc = self._doc(n_reqs=3)
        waste = waste_by_class(doc)
        assert waste["sc"]["live"] == 3 and waste["sc"]["padded"] == 6
        assert waste["sc"]["waste_frac"] == pytest.approx(0.5)

    def test_report_bundle(self):
        rep = report(self._doc())
        assert rep["problems"] == []
        assert rep["requests"] == 2
        assert "device" in rep["stage_table"]


# ------------------------------------- completeness property (sat. 3) -----

def _export(tracer, **meta):
    return chrome_trace(tracer.events(),
                        metadata={"ring_wrapped": tracer.wrapped(), **meta})


class TestSpanTreeProperty:
    """Every submitted request — admitted, rejected, deadline-missed,
    or drained by a shape-class retirement — yields exactly one closed
    `request` root span tree. Deterministic stub world, zero compiles."""

    @settings(max_examples=10, deadline=None)
    @given(n_bursts=st.integers(min_value=1, max_value=3),
           burst=st.integers(min_value=1, max_value=5),
           seed=st.integers(min_value=0, max_value=999),
           max_depth=st.integers(min_value=2, max_value=6),
           flood=st.integers(min_value=0, max_value=8),
           miss=st.booleans())
    def test_every_submission_yields_closed_tree(self, n_bursts, burst,
                                                 seed, max_depth, flood,
                                                 miss):
        clock = SimClock()
        engine = StubEngine(clock)
        names = ["a", "b"]
        for n in names:
            engine.register(n)
        xs = {n: np.full((4, 3), float(i + 1), np.float32)
              for i, n in enumerate(names)}
        tracer = Tracer(capacity=1 << 14, clock=clock)
        queue = RequestQueue(engine, target_batch=4,
                             default_deadline_ms=500.0, clock=clock,
                             admission=AdmissionPolicy(max_depth=max_depth),
                             tracer=tracer)
        trace = bursty_trace(n_bursts, burst, 0.5, names, seed=seed)
        # the trace itself can overrun max_depth: its rejections are
        # submissions too, each with its own root span
        _, trace_rejected = replay_trace(queue, trace, xs.__getitem__)
        rejected = sum(trace_rejected)
        for _ in range(flood):      # no pumping: may exceed max_depth
            try:
                queue.submit(names[0], xs[names[0]])
            except AdmissionError:
                rejected += 1
        queue.drain()
        if miss:
            # unseen feature width -> cold compile inside the deadline
            fut = queue.submit(names[0], np.full((4, 7), 1.0, np.float32),
                               deadline_ms=50.0)
            queue.drain()
            assert fut.done()
        assert not tracer.wrapped()
        doc = _export(tracer)
        assert check_complete(doc) == []
        roots = [s for s in spans(doc) if s["name"] == "request"]
        admitted = queue.stats.arrivals
        assert len(roots) == admitted + rejected
        assert sum(1 for s in roots if s["args"]["req"] < 0) == rejected
        if miss:
            assert any(s["args"].get("missed") for s in roots)

    def test_drained_during_retirement_closes(self):
        from repro.engine.lifecycle import (LifecycleConfig,
                                            LifecycleManager)
        clock = SimClock()
        engine = StubEngine(clock)
        tracer = Tracer(capacity=1 << 14, clock=clock)
        queue = RequestQueue(engine, target_batch=4,
                             default_deadline_ms=500.0, clock=clock,
                             tracer=tracer)
        cfg = LifecycleConfig(waste_budget=0.52, breach_windows=2,
                              max_retires_per_window=1,
                              max_recompiles_per_window=2, min_traffic=1,
                              cooldown_windows=2)
        mgr = LifecycleManager(engine, frontend=queue, config=cfg)
        big = [f"big{i}" for i in range(3)]
        for n in big:
            engine.register(n, size=100)
        x = np.full((4, 3), 1.0, np.float32)

        def serve(names):
            futs = [queue.submit(n, x) for n in names]
            queue.drain()
            assert all(f.done() for f in futs)

        serve(big)
        mgr.step()
        small = [f"small{i}" for i in range(4)]
        for n in small:
            engine.register(n, size=60)
        serve(big + small)
        mgr.step()                      # breach window 1: hysteresis
        serve(big + small)
        pending = [queue.submit(n, x) for n in small[:2]]
        w = mgr.step()                  # retires + drains the in-flights
        assert w["retired"], "the drift scenario must retire the class"
        assert all(f.done() for f in pending), \
            "retirement must not strand in-flight requests"
        assert queue.stats.close_reasons.get("retire", 0) >= 1
        assert not tracer.wrapped()
        doc = _export(tracer)
        assert check_complete(doc) == []
        assert any(e["name"] == "lifecycle.retire"
                   for e in instants(doc)), \
            "the retirement must emit its lifecycle instant"
        retire_reqs = {
            s["args"]["req"] for s in spans(doc)
            if s["name"] == "queue" and s["args"].get("reason") == "retire"}
        assert retire_reqs, \
            "drained members' queue spans must close with reason=retire"
