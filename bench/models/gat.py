"""GAT (Velickovic et al., ICLR 2018) as the benchmark makes, counts and
checks it.

A configuration's ``model`` names this file (``"kind": "gat"``) and
gives ``n_layers``, ``heads`` (a layer), ``d_head`` (a layer; the string
``"classes"`` stands for the graph's ``n_classes``), ``concat`` (a
layer), ``activation`` and ``negative_slope``. The program serves the
paper's transductive shape only: every layer but the last concatenates
its heads and applies an ELU, the last averages its heads, slope 0.2;
``make_params`` refuses any other. A layer, with ``z = h W`` split into
heads of ``F'``:

    e_ij  = LeakyReLU(a_l . z_i + a_r . z_j)   per head
    out_i = sum_{j in N(i)} softmax_j(e_ij) z_j

over N(i), the pattern of A_tilde's row i (its neighbours and i). No
bias, as in the paper's equations; no dropout at inference.

``make_params``  Glorot-normal float32 ``w``, ``a_l``, ``a_r`` a layer;
``flops``        operations of one request at its unpadded shapes;
``forward``      the plain forward that `bench.lib.reference` runs at
                 each of its precisions;
``register``     the graph and its parameters handed to
                 ``Engine.register`` with ``model="gat"``;
``attn_cost``    the least operations and bytes of the attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SERVED = {"activation": "elu", "negative_slope": 0.2}


def _layers(model: dict, spec: dict) -> list:
    """[(f_in, heads, d_head, concat), ...] of one graph's layers."""
    d_head = [spec["n_classes"] if d == "classes" else d
              for d in model["d_head"]]
    f_in, out = spec["n_features"], []
    for heads, d, concat in zip(model["heads"], d_head, model["concat"]):
        out.append((f_in, heads, d, concat))
        f_in = heads * d if concat else d
    if len(out) != model["n_layers"]:
        raise ValueError(f"GAT: {model['n_layers']} layers, "
                         f"{len(out)} given")
    return out


def _glorot(key, shape, fans: int):
    """Glorot-normal: standard deviation sqrt(2 / (fan in + fan out))."""
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(np.sqrt(2.0 / fans)))


def make_params(key, model: dict, spec: dict) -> list:
    """One ``{"w": [f_in, H*F'], "a_l": [H, F'], "a_r": [H, F']}`` a
    layer, Glorot-normal float32; layer ``i`` draws from ``fold_in(key,
    i)``. Each head's ``a_l`` and ``a_r`` are [F', 1] vectors, so their
    fans add to ``F' + 1``."""
    layers = _layers(model, spec)
    concat = [c for *_, c in layers]
    if (concat != [True] * (len(layers) - 1) + [False]
            or any(model[k] != v for k, v in SERVED.items())):
        raise ValueError(f"the program serves GAT with every layer but "
                         f"the last concatenated and {SERVED}; the "
                         f"configuration asks for {model}")
    params = []
    for li, (f_in, heads, d, _) in enumerate(layers):
        kw, kl, kr = jax.random.split(jax.random.fold_in(key, li), 3)
        params.append({
            "w": _glorot(kw, (f_in, heads * d), f_in + heads * d),
            "a_l": _glorot(kl, (heads, d), d + 1),
            "a_r": _glorot(kr, (heads, d), d + 1)})
    return params


def gat_flops(n_vertices: int, nnz: int, layers) -> float:
    """FLOPs of one forward, 2 a multiply-add, at unpadded shapes, a
    layer ``(f_in, H, F', concat)``: ``2 N f_in H F'`` for ``X W``,
    ``4 N H F'`` for the two projections ``z . a_l`` and ``z . a_r``,
    and ``2 nnz H (F' + 1)`` for the aggregation of ``[z | 1]`` (the
    softmax's numerator and denominator) over A_tilde's ``nnz``
    nonzeros. The exp and the LeakyReLU are not counted; nor are
    padding, shape-class headroom or the program's partition."""
    total = 0.0
    for f_in, heads, d, _ in layers:
        total += (2.0 * n_vertices * f_in * heads * d
                  + 4.0 * n_vertices * heads * d
                  + 2.0 * nnz * heads * (d + 1))
    return total


def flops(csr, model: dict, spec: dict) -> float:
    """FLOPs of one request over A_tilde ``csr`` (scipy)."""
    return gat_flops(csr.shape[0], csr.nnz, _layers(model, spec))


def attn_cost(n_vertices: int, nnz: int, model: dict,
              spec: dict) -> tuple:
    """(FLOPs, bytes) that any implementation of the attention (the
    program's scopes ``attn.dense``, ``attn.ell`` and ``attn.coo``) must
    spend on one request, at unpadded shapes.

    FLOPs: the aggregation term of `gat_flops`, ``2 nnz H (F' + 1)`` a
    layer. Bytes, float32 and int32, a layer: read ``[z | 1]``
    (``N H (F' + 1)``), ``s_l``, ``s_r`` and the row max (``3 N H``)
    once a vertex; read one column index a nonzero (``nnz``) and one
    row offset a vertex (``N``); write the layer's output once (``N H
    F'`` concatenated, ``N F'`` averaged). A lower bound: a share of the
    roofline from it cannot pass 100%."""
    fl = by = 0.0
    for _, heads, d, concat in _layers(model, spec):
        fl += 2.0 * nnz * heads * (d + 1)
        by += 4.0 * (n_vertices * heads * (d + 1) + 3 * n_vertices * heads
                     + nnz + n_vertices
                     + n_vertices * (heads * d if concat else d))
    return fl, by


@functools.partial(jax.jit, static_argnames=("n", "dtype", "precision",
                                             "operands"))
def forward(rows, cols, vals, x, params, *, n, dtype, precision,
            operands=None):
    """Logits [n, classes] over A_tilde's (row, col) pattern (``vals``
    is not used): ``X W`` and the projections ``z . a`` at
    ``precision`` with their operands rounded to ``operands`` (None:
    not rounded), the row max a ``segment_max``, the sums
    ``segment_sum``s, every array in ``dtype``; float32 out."""
    del vals
    h = x.astype(dtype)

    def rounded(a):
        return a if operands is None else a.astype(operands).astype(dtype)

    for i, p in enumerate(params):
        heads, d = p["a_l"].shape
        z = jnp.dot(rounded(h), rounded(p["w"].astype(dtype)),
                    precision=precision).reshape(n, heads, d)
        s_l, s_r = (jnp.einsum("nhf,hf->nh", rounded(z),
                               rounded(p[k].astype(dtype)),
                               precision=precision)
                    for k in ("a_l", "a_r"))
        e = jax.nn.leaky_relu(s_l[rows] + s_r[cols], SERVED["negative_slope"])
        e = jnp.exp(e - jax.ops.segment_max(e, rows, num_segments=n)[rows])
        den = jax.ops.segment_sum(e, rows, num_segments=n)
        num = jax.ops.segment_sum(e[:, :, None] * z[cols], rows,
                                  num_segments=n)
        out = num / den[:, :, None]
        if i < len(params) - 1:
            h = jax.nn.elu(out.reshape(n, heads * d))
        else:
            h = out.mean(axis=1)
    # the answer is stored in ``dtype``: without the barrier XLA may
    # fuse the last layer into the float32 cast and skip the rounding
    return jax.lax.optimization_barrier(h).astype(jnp.float32)


def register(engine, name: str, csr, *, reorder, params):
    """Register the program's CSR ``csr`` of A_tilde with the GAT's
    parameters."""
    return engine.register(name, csr, reorder=reorder, model="gat",
                           params=params)
