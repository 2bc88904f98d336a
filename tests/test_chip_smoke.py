"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases, rehearsed here at a tiny scale on the CPU (Pallas kernels in
interpret mode), reach the float32 reference."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from test_distributed import run_in_subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

SCALE = 0.02          # pubmed -> 394 vertices, flickr -> 1,785


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _prints_a_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            pass
    return False


def test_refuses_without_a_tpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not _prints_a_result(out.stdout)


def test_fails_without_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert not _prints_a_result(out.stdout)


@pytest.fixture(scope="module")
def tiny():
    graphs = cs.build_graphs(cs.GRAPHS, seed=0, scale=SCALE)
    requests = cs.make_requests(graphs, cs.N_REQUESTS, seed=0)
    refs = {name: cs.reference_logits(csr, x, w)
            for name, (csr, x, w) in graphs.items()}
    req_refs = [cs.reference_logits(graphs[n][0], x, graphs[n][2])
                for n, x in requests]
    return graphs, refs, requests, req_refs


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_infer_and_queue_phases(backend, tiny):
    from repro.engine import Engine
    graphs, refs, requests, req_refs = tiny
    engine = Engine(backend=backend)
    cs.infer_phase(engine, graphs, refs)
    cs.queue_phase(engine, requests, req_refs)
    if backend == "pallas":
        for name, (_, x, _) in graphs.items():
            ragged, _ = cs.kernel_launches(engine, name, x)
            assert ragged == cs.CONFIG.n_layers


def test_check_rejects_a_wrong_output(tiny):
    graphs, refs, _, _ = tiny
    ref = refs["pubmed"]
    with pytest.raises(RuntimeError, match="tolerance"):
        cs.check("wrong", ref * (1 + 4 * cs.TOLERANCE), ref)


def test_replica_phase_on_four_devices():
    out = run_in_subprocess(textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.engine import Engine
        graphs = cs.build_graphs(cs.REPLICA_GRAPHS, seed=0, scale={SCALE})
        requests = cs.make_requests(graphs, cs.N_REQUESTS, seed=0)
        refs = [cs.reference_logits(graphs[n][0], x, graphs[n][2])
                for n, x in requests]
        cs.replica_phase(Engine(backend="pallas"), graphs, requests, refs, 4)
        """))
    assert "bitwise-equal to replicas=1" in out
    for lane in range(4):
        assert f"lane {lane} -> TFRT_CPU_{lane}: 4 dispatches" in out
