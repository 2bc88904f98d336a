"""The paper's GCN as the benchmark makes, counts and checks it.

A configuration's ``model`` names its kind (``"kind": "gcn"``), and the
harness loads this file by that name. ``model`` also gives
``n_layers`` and ``d_hidden``; each graph's entry gives ``n_features``
and ``n_classes``. The layers are ``[f, d_hidden, ..., classes]`` wide,
with a ReLU after every layer but the last:
``A_tilde relu(A_tilde X W1) W2`` for two.

``make_params``  Glorot-normal float32 weights, one matrix a layer;
``flops``        operations of one request at its unpadded shapes;
``forward``      the plain forward that `bench.lib.reference` runs at
                 each of its precisions;
``register``     the graph and its weights handed to ``Engine.register``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _widths(model: dict, spec: dict) -> list:
    """[f_in, hidden..., classes] of one graph."""
    return ([spec["n_features"]]
            + [model["d_hidden"]] * (model["n_layers"] - 1)
            + [spec["n_classes"]])


def make_params(key, model: dict, spec: dict) -> list:
    """Glorot-normal float32 weights [[f, h], ..., [h, c]]; layer ``i``
    draws from ``fold_in(key, i)``. Traced inside the harness's one
    jitted generator."""
    ws = _widths(model, spec)
    return [jax.random.normal(jax.random.fold_in(key, li), (a, b),
                              jnp.float32)
            * np.float32(np.sqrt(2.0 / (a + b)))
            for li, (a, b) in enumerate(zip(ws[:-1], ws[1:]))]


def gcn_flops(n_vertices: int, nnz: int, widths) -> float:
    """FLOPs of one forward; ``widths`` = [f_in, hidden..., classes].

    Per layer: ``2 N F_in F_out`` for the combination ``X W`` and
    ``2 nnz F_out`` for the aggregation ``A_tilde (X W)``, where ``nnz``
    counts the nonzeros of A_tilde (self loops included). Padding,
    shape class headroom and the program's partition never count as
    work."""
    total = 0.0
    for f_in, f_out in zip(widths[:-1], widths[1:]):
        total += 2.0 * n_vertices * f_in * f_out + 2.0 * nnz * f_out
    return total


def flops(csr, model: dict, spec: dict) -> float:
    """FLOPs of one request over A_tilde ``csr`` (scipy)."""
    return gcn_flops(csr.shape[0], csr.nnz, _widths(model, spec))


@functools.partial(jax.jit, static_argnames=("n", "dtype", "precision",
                                             "operands"))
def forward(rows, cols, vals, x, params, *, n, dtype, precision,
            operands=None):
    """Logits [n, classes] over A_tilde's (row, col, value) triples:
    ``X W`` at ``precision`` with its operands rounded to ``operands``
    (None: not rounded), the aggregation a ``segment_sum``, every array
    in ``dtype``; float32 out."""
    vals = vals.astype(dtype)
    h = x.astype(dtype)

    def rounded(a):
        return a if operands is None else a.astype(operands).astype(dtype)

    for i, w in enumerate(params):
        hw = jnp.dot(rounded(h), rounded(w.astype(dtype)),
                     precision=precision)
        h = jax.ops.segment_sum(vals[:, None] * hw[cols], rows,
                                num_segments=n)
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)


def register(engine, name: str, csr, *, reorder, params):
    """Register the program's CSR ``csr`` of A_tilde with its weights."""
    return engine.register(name, csr, reorder=reorder, weights=params)
