"""repro-lint: the static analysis passes catch their known-bad fixtures
and run clean on the repo itself.

Each pass gets a deliberately broken input — a per-K dispatch where
ragged mode promises one launch, an unmasked ragged kernel, an
oversized-VMEM BlockSpec, a lock-free cross-thread field write — and
must flag it; the whole-repo runs must stay at zero unwaived errors
(that is the CI gate `scripts/lint_repro.py` enforces).
"""
from __future__ import annotations

import functools
import json
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.static.bench_check import (check_bench_file,
                                               check_bench_files,
                                               flatten_metrics,
                                               write_bench_json)
from repro.analysis.static.concurrency_pass import (analyze_paths,
                                                    run_concurrency_pass)
from repro.analysis.static.fixtures import fixture_engine
from repro.analysis.static.jaxpr_pass import (check_dead_lanes,
                                              check_single_launch,
                                              kernel_name, pallas_eqns,
                                              run_jaxpr_pass,
                                              trace_gcn_executor)
from repro.analysis.static.kernel_pass import (check_contract,
                                               contracts_for_class,
                                               run_kernel_pass)
from repro.analysis.static.report import Report
from repro.kernels.ell_spmm import ragged_ell_contract
from repro.kernels.tile_matmul import matmul_contract


def _errors(findings):
    return [f for f in findings if f.severity == "error" and not f.waived]


def _rules(findings):
    return {f.rule for f in _errors(findings)}


# ------------------------------------------------------------- pass 1 -----

class TestJaxprPass:
    def test_repo_clean(self):
        assert _errors(run_jaxpr_pass()) == []

    def test_double_launch_dispatch_caught(self):
        # the legacy per-K dispatch traces one fixed-K launch per
        # distinct K — in ragged mode that is exactly the regression
        # the single-launch rule exists to catch
        engine = fixture_engine(backend="pallas", ell_dispatch="loop")
        closed, h = trace_gcn_executor(engine, "lint-fixture")
        findings = check_single_launch(closed, n_layers=len(h.weights))
        assert "single-launch" in _rules(findings)
        # and the messages name the per-K kernels it traced instead
        assert any("'ell_spmm'" in f.message for f in _errors(findings))

    def test_unmasked_kernel_fails_dead_lane_proof(self):
        # the same launch contract as the production ragged kernel, but
        # with the kk < unit_k value mask dropped: the store is no
        # longer provably zero for a dead unit, so the static sentinel
        # proof must reject it
        def unmasked(tile_col_ref, unit_k_ref, cols_ref, vals_ref, b_ref,
                     o_ref, *, kmax):
            del tile_col_ref, unit_k_ref
            b = b_ref[0]
            cols = cols_ref[0]
            vals = vals_ref[0].astype(jnp.float32)
            acc = jnp.zeros((cols.shape[0], b.shape[1]), jnp.float32)
            for kk in range(kmax):
                g = jnp.take(b, cols[:, kk], axis=0)
                acc = acc + vals[:, kk][:, None] * g.astype(jnp.float32)
            o_ref[0] = acc

        u, r, kmax, nct, t, f = 3, 4, 2, 2, 8, 16
        c = ragged_ell_contract(u, r, kmax, nct, t, f, bf=16)
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=c["num_scalar_prefetch"], grid=c["grid"],
            in_specs=c["in_specs"], out_specs=c["out_specs"][0])
        call = pl.pallas_call(
            functools.partial(unmasked, kmax=kmax), grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct(c["out_shapes"][0], jnp.float32),
            interpret=True, name="unmasked_ragged_ell")
        closed = jax.make_jaxpr(call)(
            jnp.zeros(u, jnp.int32), jnp.zeros(u, jnp.int32),
            jnp.zeros((u, r, kmax), jnp.int32),
            jnp.zeros((u, r, kmax), jnp.float32),
            jnp.zeros((nct, t, f), jnp.float32))
        (eqn,) = pallas_eqns(closed)
        findings = check_dead_lanes(eqn)
        assert _rules(findings) == {"sentinel-safety"}

    def test_masked_production_kernel_passes_dead_lane_proof(self):
        engine = fixture_engine(backend="pallas")
        closed, _ = trace_gcn_executor(engine, "lint-fixture")
        ragged = [e for e in pallas_eqns(closed)
                  if kernel_name(e) == "ragged_ell_spmm"]
        assert ragged, "fixture must trace a ragged launch"
        assert check_dead_lanes(ragged[0]) == []


# ------------------------------------------------------------- pass 2 -----

class TestKernelPass:
    def test_repo_clean(self):
        assert _errors(run_kernel_pass()) == []

    def test_oversized_vmem_blockspec_caught(self):
        # 3 * (2048*2048*4B) * 2 buffers + scratch >> the 16 MiB budget
        bad = matmul_contract(8192, 8192, 8192, bm=2048, bn=2048, bk=2048)
        assert "vmem-budget" in _rules(check_contract(bad))

    def test_default_matmul_contract_fits(self):
        assert _errors(check_contract(matmul_contract(512, 512, 512))) == []

    def test_out_of_range_tile_col_caught(self):
        # a scalar-prefetch tile_col addressing one past the last B tile
        # must trip the grid-corner bounds evaluation
        u, r, kmax, nct, t, f = 4, 8, 3, 2, 8, 32
        c = ragged_ell_contract(u, r, kmax, nct, t, f, bf=32)
        tile_col = np.full((u,), nct, np.int32)          # out of range
        unit_k = np.full((u,), kmax, np.int32)
        findings = check_contract(c, scalar_args=(tile_col, unit_k))
        assert "index-map-bounds" in _rules(findings)

    def test_oversized_buffer_depth_blows_vmem(self):
        # the ragged contract is legal at the default pipeline depth but
        # a runaway buffer_depth multiplies the resident working set
        # past the 16 MiB budget — exactly the candidate class the
        # autotuner must reject before ever timing it
        u, r, kmax, nct, t, f = 6, 8, 5, 3, 64, 32
        scalars = (np.full((u,), nct - 1, np.int32),
                   np.full((u,), kmax, np.int32))
        good = ragged_ell_contract(u, r, kmax, nct, t, f, bf=32)
        assert _errors(check_contract(good, scalar_args=scalars,
                                      backend="tpu")) == []
        bad = ragged_ell_contract(u, r, kmax, nct, t, f, bf=32,
                                  buffer_depth=4096)
        assert "vmem-budget" in _rules(check_contract(
            bad, scalar_args=scalars, backend="tpu"))

    def test_fixture_class_contracts_clean(self):
        engine = fixture_engine()
        h = engine.handle("lint-fixture")
        pairs = contracts_for_class(h.sclass, (48, 32, 128))
        assert pairs, "fixture class must imply at least one ELL contract"
        for contract, scalars in pairs:
            assert _errors(check_contract(contract,
                                          scalar_args=scalars)) == []


# ------------------------------------------------------------- pass 3 -----

RACY_SERVICE = textwrap.dedent("""\
    import threading

    class Svc:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._worker, daemon=True)

        def _worker(self):
            while True:
                self.count += 1{waiver}

        def snapshot(self):
            return {{"count": self.count}}
""")


class TestConcurrencyPass:
    def test_repo_clean(self):
        assert _errors(run_concurrency_pass()) == []

    def test_lock_free_field_write_caught(self, tmp_path):
        mod = tmp_path / "svc.py"
        mod.write_text(RACY_SERVICE.format(waiver=""))
        findings = analyze_paths([mod], entry_classes={"Svc"})
        errs = _errors(findings)
        assert _rules(findings) == {"field-race"}
        assert any("Svc.count" in f.message for f in errs)

    def test_waiver_suppresses_the_race(self, tmp_path):
        mod = tmp_path / "svc.py"
        mod.write_text(RACY_SERVICE.format(
            waiver="  # lint: racy-ok(test counter)"))
        findings = analyze_paths([mod], entry_classes={"Svc"})
        assert _errors(findings) == []
        waived = [f for f in findings if f.waived]
        assert waived and waived[0].waive_reason == "test counter"

    def test_locked_write_is_clean(self, tmp_path):
        mod = tmp_path / "svc.py"
        mod.write_text(textwrap.dedent("""\
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                    self._t = threading.Thread(target=self._worker,
                                               daemon=True)

                def _worker(self):
                    with self._lock:
                        self.count += 1

                def snapshot(self):
                    with self._lock:
                        return {"count": self.count}
        """))
        assert _errors(analyze_paths([mod], entry_classes={"Svc"})) == []

    def test_unlocked_histogram_write_caught(self, tmp_path):
        # Known-bad obs fixture: a Histogram-like ring whose worker
        # stores samples without the lock the public snapshot takes.
        # Subscript stores are writes to the lint — this pins that the
        # obs scope extension actually bites on the shape of bug the
        # metrics primitives could regress into.
        mod = tmp_path / "hist.py"
        mod.write_text(textwrap.dedent("""\
            import threading

            class Hist:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.window = [0.0] * 64
                    self.n = 0
                    self._t = threading.Thread(target=self._worker,
                                               daemon=True)

                def _worker(self):
                    while True:
                        self.window[self.n % 64] = 1.0
                        self.n += 1

                def snapshot(self):
                    with self._lock:
                        return {"n": self.n, "window": list(self.window)}
        """))
        findings = analyze_paths([mod], entry_classes={"Hist"})
        errs = _errors(findings)
        assert "field-race" in _rules(findings)
        assert any("Hist.window" in f.message for f in errs)

    def test_obs_dir_in_default_scope(self):
        from repro.analysis.static.concurrency_pass import (LOCK_ORDER,
                                                            SCOPE_DIRS)
        assert "src/repro/obs" in SCOPE_DIRS
        # metric locks are declared leaves: after every component lock
        for comp in ("RequestQueue._lock", "ExecutorCache._lock",
                     "LatencyModel._lock"):
            for leaf in ("Counter._lock", "Histogram._lock",
                         "Tracer._lock"):
                assert LOCK_ORDER.index(comp) < LOCK_ORDER.index(leaf)

    def test_lock_order_inversion_caught(self, tmp_path):
        mod = tmp_path / "inv.py"
        mod.write_text(textwrap.dedent("""\
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._gate = threading.Lock()

                def forward(self):
                    with self._lock:
                        with self._gate:
                            pass

                def backward(self):
                    with self._gate:
                        with self._lock:
                            pass
        """))
        findings = analyze_paths(
            [mod], entry_classes={"Svc"},
            lock_order=("Svc._lock", "Svc._gate"))
        assert "lock-order" in _rules(findings)
        assert any("inversion" in f.message for f in _errors(findings))

    def test_replicas_file_in_default_scope(self):
        from repro.analysis.static.concurrency_pass import (LOCK_ORDER,
                                                            SCOPE_DIRS)
        assert "src/repro/serving/replicas.py" in SCOPE_DIRS
        # The router lock sits between the frontend locks and the
        # per-replica pipeline lock it routes batches into, and above
        # every metric leaf it updates while routing.
        assert (LOCK_ORDER.index("RequestQueue._dispatch_gate")
                < LOCK_ORDER.index("ReplicaSet._lock")
                < LOCK_ORDER.index("DispatchPipeline._lock"))
        for leaf in ("Counter._lock", "CounterFamily._lock",
                     "GaugeFamily._lock"):
            assert LOCK_ORDER.index("ReplicaSet._lock") < LOCK_ORDER.index(leaf)

    def test_scope_file_entry_is_linted_once(self):
        # replicas.py appears in SCOPE_DIRS both via its directory glob
        # and as an explicit file entry; run_concurrency_pass must
        # dedupe rather than double-report (or crash globbing a file).
        from repro.analysis.static.concurrency_pass import (SCOPE_DIRS,
                                                            _repo_root)
        root = _repo_root()
        scoped = set()
        for d in SCOPE_DIRS:
            target = root / d
            if d.endswith(".py"):
                assert target.is_file()
                scoped.add(target)
            else:
                scoped.update(target.glob("*.py"))
        assert root / "src/repro/serving/replicas.py" in scoped

    def test_unlocked_replica_depth_read_caught(self, tmp_path):
        # Known-bad router fixture: the dispatch worker updates a
        # replica-depth field under the router lock, but the routing
        # path reads it lock-free to score replicas. That torn read is
        # exactly the bug class ReplicaSet._score avoids by routing
        # under self._lock.
        mod = tmp_path / "router.py"
        mod.write_text(textwrap.dedent("""\
            import threading

            class Router:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.depth = 0
                    self._t = threading.Thread(target=self._drain,
                                               daemon=True)

                def _drain(self):
                    while True:
                        with self._lock:
                            self.depth -= 1

                def route(self):
                    return self.depth

                def enroll(self):
                    with self._lock:
                        self.depth += 1
        """))
        findings = analyze_paths([mod], entry_classes={"Router"})
        errs = _errors(findings)
        assert "field-race" in _rules(findings)
        assert any("Router.depth" in f.message for f in errs)

    def test_resilience_files_in_default_scope(self):
        from repro.analysis.static.concurrency_pass import (LOCK_ORDER,
                                                            SCOPE_DIRS)
        assert "src/repro/serving/chaos.py" in SCOPE_DIRS
        assert "src/repro/serving/resilience.py" in SCOPE_DIRS
        # The coordinator's handler runs from the pipeline's failure
        # path, so its lock nests inside the pipeline's; the injector
        # is polled inside the executor-cache miss path.
        assert (LOCK_ORDER.index("DispatchPipeline._lock")
                < LOCK_ORDER.index("ResilienceCoordinator._lock"))
        assert (LOCK_ORDER.index("ExecutorCache._lock")
                < LOCK_ORDER.index("ChaosInjector._lock"))
        for name in ("ResilienceCoordinator._lock", "DispatchWatchdog._lock",
                     "BrownoutController._lock", "ChaosInjector._lock"):
            for leaf in ("Counter._lock", "Histogram._lock"):
                assert LOCK_ORDER.index(name) < LOCK_ORDER.index(leaf)

    def test_unlocked_retry_counter_caught(self, tmp_path):
        # Known-bad resilience fixture: a retry loop bumps its attempt
        # counter lock-free while the public snapshot reads it under
        # the lock — the shape of bug ResilienceCoordinator avoids by
        # counting retries through the locked ServerStats hooks.
        mod = tmp_path / "res.py"
        mod.write_text(textwrap.dedent("""\
            import threading

            class Coordinator:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.retries = 0
                    self._t = threading.Thread(target=self._retry_loop,
                                               daemon=True)

                def _retry_loop(self):
                    while True:
                        self.retries += 1

                def snapshot(self):
                    with self._lock:
                        return {"retries": self.retries}
        """))
        findings = analyze_paths([mod], entry_classes={"Coordinator"})
        errs = _errors(findings)
        assert "field-race" in _rules(findings)
        assert any("Coordinator.retries" in f.message for f in errs)


# -------------------------------------------------------------- bench -----

class TestBenchCheck:
    def test_flatten(self):
        flat = flatten_metrics({"a": {"ms": 1.5, "ok": True, "note": "x"},
                                "n": 3})
        assert flat == {"a.ms": 1.5, "n": 3}

    def test_roundtrip_is_clean(self, tmp_path):
        path = tmp_path / "BENCH_test.json"
        write_bench_json(path, "bench_test", "bench_test --smoke",
                         "2026-08-08", {"cora": {"ms": 2.0}})
        assert check_bench_file(path) == []
        assert check_bench_files(tmp_path) == []

    @pytest.mark.parametrize("doc", [
        "not json {",
        json.dumps([1, 2]),
        # schema 1 (pre-provenance) files must fail until reseeded
        json.dumps({"bench": "b", "schema": 1, "created": "d",
                    "command": "c", "metrics": {"m": 1}}),
        # schema 2 without the provenance block
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c", "metrics": {"m": 1}}),
        # provenance present but not an object
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c", "provenance": "b93d566",
                    "metrics": {"m": 1}}),
        # provenance with a missing / empty / non-string key
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c",
                    "provenance": {"git_sha": "x", "jax_version": "y"},
                    "metrics": {"m": 1}}),
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c",
                    "provenance": {"git_sha": "", "jax_version": "y",
                                   "backend": "cpu"},
                    "metrics": {"m": 1}}),
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c",
                    "provenance": {"git_sha": 7, "jax_version": "y",
                                   "backend": "cpu"},
                    "metrics": {"m": 1}}),
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c",
                    "provenance": {"git_sha": "x", "jax_version": "y",
                                   "backend": "cpu"},
                    "metrics": {"m": "fast"}}),
        json.dumps({"bench": "b", "schema": 2, "created": "d",
                    "command": "c",
                    "provenance": {"git_sha": "x", "jax_version": "y",
                                   "backend": "cpu"},
                    "metrics": {"m": True}}),
        json.dumps({"schema": 2, "created": "d", "command": "c",
                    "provenance": {"git_sha": "x", "jax_version": "y",
                                   "backend": "cpu"},
                    "metrics": {"m": 1}}),
    ])
    def test_malformed_files_fail(self, tmp_path, doc):
        path = tmp_path / "BENCH_bad.json"
        path.write_text(doc)
        assert _errors(check_bench_file(path))

    def test_provenance_collected_automatically(self, tmp_path):
        path = tmp_path / "BENCH_test.json"
        doc = write_bench_json(path, "bench_test", "bench_test --smoke",
                               "2026-08-08", {"ms": 1.0})
        prov = doc["provenance"]
        assert set(prov) == {"git_sha", "jax_version", "backend"}
        assert all(isinstance(v, str) and v for v in prov.values())

    def test_required_metrics_enforced(self, tmp_path):
        # a bench_spmm trajectory missing one of the kernel-health
        # metrics regressed its reporting contract -> schema error
        path = tmp_path / "BENCH_spmm.json"
        write_bench_json(path, "bench_spmm", "bench_spmm --smoke",
                         "2026-08-08",
                         {"cora": {"launches_per_spmm": 1,
                                   "ell_pad_waste_x": 6.0}})
        (finding,) = _errors(check_bench_file(path))
        assert "achieved_roofline_frac" in finding.message
        write_bench_json(path, "bench_spmm", "bench_spmm --smoke",
                         "2026-08-08",
                         {"cora": {"launches_per_spmm": 1,
                                   "ell_pad_waste_x": 6.0,
                                   "achieved_roofline_frac": 0.004}})
        assert check_bench_file(path) == []

    def test_required_metrics_scoped_to_bench(self, tmp_path):
        # other benches carry no required set — the suffix match must
        # not leak bench_spmm's contract onto them
        path = tmp_path / "BENCH_other.json"
        write_bench_json(path, "bench_other", "bench_other", "2026-08-08",
                         {"ms": 1.0})
        assert check_bench_file(path) == []

    def test_committed_trajectories_valid(self, repo_root):
        findings = check_bench_files(repo_root)
        assert _errors(findings) == []


# ---------------------------------------------------------- repo gate -----

@pytest.fixture(scope="module")
def repo_root():
    from repro.analysis.static.concurrency_pass import _repo_root
    return _repo_root()


def test_whole_repo_lint_is_clean():
    """The exact gate scripts/lint_repro.py applies in tier-1 CI."""
    report = Report()
    report.extend(run_jaxpr_pass())
    report.extend(run_kernel_pass())
    report.extend(run_concurrency_pass())
    assert report.ok, "\n" + report.render(verbose=True)
    err, warn, _ = report.counts()
    assert (err, warn) == (0, 0), report.render(verbose=True)
