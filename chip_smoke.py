"""Run the paper's GCN end to end on a TPU, through the serving entry points.

    python chip_smoke.py [--seed N]     # one chip: every phase below
    python chip_smoke.py --replicas 4   # only the replica phase, 4 chips

The model is the paper's own (2-layer GCN, hidden 128, weights drawn
from ``--seed``) on Table I graphs synthesized at their published size
(pubmed, flickr). Phases, each printing its first-call (compile) time
and a warm time, both ending in ``block_until_ready``:

  infer/xla, infer/pallas  ``Engine.register`` then ``Engine.infer`` per
                           graph under both backends; the pallas
                           executor must compile to Mosaic kernels
                           (``tpu_custom_call``) with one ragged ELL
                           launch per layer.
  queue                    16 requests mixed over both graphs through a
                           started, pipelined ``RequestQueue``, twice.
  replicas (--replicas N)  16 requests over four graphs through
                           ``RequestQueue(replicas=N)`` and ``replicas=1``:
                           bitwise-equal outputs, lane i on device i.

Every output is compared with a plain float32 reference that never
touches the partition code (``reference_logits``). The last line of
standard output is one JSON object naming the device; it is printed
only after every phase passed. Without a TPU the script exits 1 before
any phase runs.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis.static.jaxpr_pass import (RAGGED_KERNEL,  # noqa: E402
                                              kernel_name, pallas_eqns)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.gcn_paper import CONFIG  # noqa: E402
from repro.data.graphs import make_paper_dataset  # noqa: E402
from repro.engine import Engine  # noqa: E402
from repro.serving import RequestQueue  # noqa: E402

GRAPHS = ("pubmed", "flickr")
# the replica phase needs one group key per lane in flight at once (a
# key stays pinned to its lane while that lane is busy), so four graphs
REPLICA_GRAPHS = ("cora", "citeseer", "pubmed", "flickr")
N_REQUESTS = 16
RESULT_TIMEOUT_S = 900.0

# TPU default precision rounds both operands of an f32 matmul to
# bfloat16 (unit roundoff 2^-8). Each of the 2 layers has two such
# matmuls on the served path (X.W and the dense-tile A.B), each with
# two rounded operands: 8 roundings of relative size 2^-8, measured
# against the output's scale.
BF16_UNIT_ROUNDOFF = 2.0 ** -8
TOLERANCE = 2 * CONFIG.n_layers * 2 * BF16_UNIT_ROUNDOFF


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def timed(fn):
    """(result, seconds) of ``fn()``, waited out on the device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def make_weights(f_in: int, seed: int) -> list:
    """gcn-paper weights [f_in, hidden], [hidden, classes], Glorot."""
    rng = np.random.default_rng(seed)
    dims = [f_in] + [CONFIG.d_hidden] * (CONFIG.n_layers - 1) \
        + [CONFIG.n_classes]
    return [(rng.standard_normal((a, b)) * np.sqrt(2.0 / (a + b)))
            .astype(np.float32) for a, b in zip(dims[:-1], dims[1:])]


def build_graphs(names, seed: int, scale: float = 1.0) -> dict:
    """name -> (normalized adjacency CSR, features, weights)."""
    out = {}
    for name in names:
        csr, x, _, _ = make_paper_dataset(name, scale=scale, seed=seed)
        out[name] = (csr, x, make_weights(x.shape[1], seed))
    return out


def make_requests(graphs: dict, n: int, seed: int) -> list:
    """``n`` (name, features) requests cycling over ``graphs``; each
    request keeps a random 90% of its graph's feature rows."""
    rng = np.random.default_rng(seed)
    names = list(graphs)
    reqs = []
    for i in range(n):
        name = names[i % len(names)]
        x = graphs[name][1]
        keep = (rng.random(x.shape[0]) < 0.9).astype(np.float32)
        reqs.append((name, x * keep[:, None]))
    return reqs


def reference_logits(csr, x, weights):
    """The GCN forward in plain float32: ``segment_sum`` over the CSR's
    (row, col, val) triples and ``X @ W`` at HIGHEST precision."""
    n = csr.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(csr.indptr))
    return _reference(jnp.asarray(rows), jnp.asarray(csr.indices),
                      jnp.asarray(csr.data), jnp.asarray(x),
                      [jnp.asarray(w) for w in weights], n)


@functools.partial(jax.jit, static_argnames=("n",))
def _reference(rows, cols, vals, x, weights, n):
    h = x
    for i, w in enumerate(weights):
        hw = jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST)
        h = jax.ops.segment_sum(vals[:, None] * hw[cols], rows,
                                num_segments=n)
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return h


def rel_err(y, ref) -> float:
    """max |y - ref| over max |ref|: error on the output's own scale."""
    y, ref = np.asarray(y), np.asarray(ref)
    require(y.shape == ref.shape, f"shape {y.shape} != reference {ref.shape}")
    require(bool(np.isfinite(y).all()), "non-finite output")
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def check(label: str, y, ref) -> float:
    err = rel_err(y, ref)
    require(err <= TOLERANCE,
            f"{label}: max rel err {err:.3e} > tolerance {TOLERANCE:.3e}")
    return err


def register_all(engine: Engine, graphs: dict, label: str) -> None:
    for name, (csr, _, weights) in graphs.items():
        t0 = time.perf_counter()
        h = engine.register(name, csr, weights=weights)
        log(f"{label}: register {name} ({csr.shape[0]} vertices, "
            f"{csr.indptr[-1]} nnz) in {time.perf_counter() - t0:.3f}s, "
            f"class {h.sclass.summary()}")


def kernel_launches(engine: Engine, name: str, x) -> tuple:
    """(ragged ELL launches traced, tpu_custom_call count compiled) for
    the executor ``infer`` runs on ``name``."""
    h = engine.handle(name)
    fn = engine.executors.gcn(h.sclass, x.shape[1],
                              tuple(tuple(w.shape) for w in h.weights))
    args = (h.part, engine.prepare_x(name, x), h.weights)
    ragged = sum(kernel_name(e) == RAGGED_KERNEL
                 for e in pallas_eqns(jax.make_jaxpr(fn)(*args)))
    text = fn.lower(*args).compile().as_text()
    return ragged, text.count("tpu_custom_call")


def infer_phase(engine: Engine, graphs: dict, refs: dict) -> None:
    """Register every graph, then ``infer`` each twice (cold, warm)."""
    label = f"infer/{engine.executors.backend}"
    register_all(engine, graphs, label)
    for name, (_, x, _) in graphs.items():
        y, t_first = timed(lambda: engine.infer(name, x))
        _, t_warm = timed(lambda: engine.infer(name, x))
        err = check(f"{label}/{name}", y, refs[name])
        log(f"{label}/{name}: compile+first {t_first:.3f}s warm "
            f"{t_warm:.6f}s max_rel_err {err:.3e} (tolerance "
            f"{TOLERANCE:.3e})")


def check_kernels(engine: Engine, graphs: dict) -> None:
    """The pallas executor compiled Mosaic kernels, one ragged ELL
    launch per layer."""
    for name, (_, x, _) in graphs.items():
        ragged, custom = kernel_launches(engine, name, x)
        log(f"kernels/{name}: ragged ELL launches {ragged} (layers "
            f"{CONFIG.n_layers}), tpu_custom_call {custom}")
        require(ragged == CONFIG.n_layers,
                f"{name}: {ragged} ragged launches for "
                f"{CONFIG.n_layers} layers")
        require(custom > 0, f"{name}: no Mosaic kernel in the compiled "
                f"executor (interpret mode?)")


def serve(engine: Engine, requests: list, *, target_batch: int,
          replicas=None) -> tuple:
    """Every request through one pipelined queue; returns the outputs
    in request order and the queue's stats.

    The requests are queued before the pump starts, so its first poll
    closes and routes them all in one pass: batches and replica lanes
    do not depend on thread timing.
    """
    queue = RequestQueue(engine, pipelined=True, target_batch=target_batch,
                         replicas=replicas, attach=False)
    futures = [queue.submit(name, x) for name, x in requests]
    queue.start()
    try:
        outs = jax.block_until_ready(
            [f.result(timeout=RESULT_TIMEOUT_S) for f in futures])
    finally:
        queue.stop()
    return outs, queue.stats


def queue_phase(engine: Engine, requests: list, refs: list) -> None:
    for rnd in ("compile+first", "warm"):
        (outs, stats), t = timed(lambda: serve(engine, requests,
                                               target_batch=4))
        errs = [check(f"queue/{rnd}/{i}", y, r)
                for i, (y, r) in enumerate(zip(outs, refs))]
        snap = stats.snapshot()
        log(f"queue/{rnd}: {len(outs)} requests in {t:.3f}s, batch_hist "
            f"{snap['batch_hist']}, max_rel_err {max(errs):.3e}")


def replica_phase(engine: Engine, graphs: dict, requests: list,
                  refs: list, n: int) -> None:
    require(len(jax.devices()) >= n,
            f"--replicas {n} needs {n} devices, JAX sees "
            f"{len(jax.devices())}")
    register_all(engine, graphs, "replicas")
    # one request per dispatch, so both runs execute the same programs
    one, _ = serve(engine, requests, target_batch=1, replicas=1)
    (many, stats), t = timed(lambda: serve(engine, requests,
                                           target_batch=1, replicas=n))
    for i, (a, b, r) in enumerate(zip(one, many, refs)):
        check(f"replicas/{i}", b, r)
        require(np.array_equal(np.asarray(a), np.asarray(b)),
                f"request {i}: replicas={n} output differs from "
                f"replicas=1")
    lanes = stats.replica_snapshot()["per_replica"]
    for rid in range(n):
        lane = lanes.get(rid, {})
        log(f"replicas: lane {rid} -> {jax.devices()[rid]}: "
            f"{lane.get('batches', 0)} dispatches")
    devices = sorted({d for y in many for d in y.devices()},
                     key=lambda d: d.id)
    log(f"replicas: {len(many)} requests in {t:.3f}s over {n} lanes; "
        f"output devices {devices}; bitwise-equal to replicas=1")
    require(devices == list(jax.devices()[:n]),
            f"outputs landed on {devices}, want one lane per device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="run only the N-replica phase (needs N chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    log(f"devices: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    log(f"tolerance: max|y-ref|/max|ref| <= {TOLERANCE:.6f} "
        f"(2 layers x 2 matmuls x 2 bf16-rounded operands x 2^-8)")

    names = GRAPHS if args.replicas is None else REPLICA_GRAPHS
    graphs = build_graphs(names, args.seed)
    requests = make_requests(graphs, N_REQUESTS, args.seed)
    req_refs = [reference_logits(graphs[name][0], x, graphs[name][2])
                for name, x in requests]
    if args.replicas is not None:
        replica_phase(Engine(backend="pallas"), graphs, requests, req_refs,
                      args.replicas)
    else:
        refs = {name: reference_logits(csr, x, w)
                for name, (csr, x, w) in graphs.items()}
        infer_phase(Engine(backend="xla"), graphs, refs)
        pallas = Engine(backend="pallas")
        infer_phase(pallas, graphs, refs)
        check_kernels(pallas, graphs)
        queue_phase(pallas, requests, req_refs)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
