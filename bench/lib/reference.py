"""The plain GCN forward that decides ``correct``, and its controls.

``reference`` is the paper's 2-layer GCN, ``A_tilde relu(A_tilde X W1)
W2``, written with nothing but ``jax.numpy``: ``X W`` at HIGHEST
precision and the aggregation as a ``segment_sum`` over the CSR's
(row, col, value) triples, all in float32. It knows nothing of the
program's partition, padding, reordering or batching.

The configurations state float32 storage and the TPU's default matmul
precision, which rounds both operands of every matmul to bfloat16 (one
pass, float32 accumulation). ``reference_stated`` is the same forward
at that stated precision: the operands of ``X W`` rounded to bfloat16,
their products and sums and the whole aggregation in float32.

Two numbers are compared for each answer:

``max_err_ratio``     the widest gap between the answer and
                      ``reference``, over the widest gap between
                      ``reference_stated`` and ``reference``: about 1
                      for a program that rounds as the configuration
                      states, on every seed, where the widest gap
                      alone swings with the weights' scale. Its
                      control, ``reference_fp8``, rounds the matmul
                      operands to float8 (e4m3), one step below the
                      stated bfloat16 operands.
``bf16_exact_share``  the share of the answer's logits that a bfloat16
                      holds exactly. Its control, ``reference_bf16``,
                      keeps every array and intermediate in bfloat16,
                      one step below the stated float32 storage, and
                      reads 1; a float32 answer reads the share of its
                      exact zeros. No gap to a reference separates
                      this control: the program's own matmuls round
                      the same operands, and its dense tiles, matmuls
                      at the stated precision, round a share of the
                      aggregation's operands as bfloat16 storage would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _triples(a) -> tuple:
    n = a.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(a.indptr))
    return (jnp.asarray(rows), jnp.asarray(a.indices.astype(np.int32)),
            jnp.asarray(a.data.astype(np.float32)))


@functools.partial(jax.jit, static_argnames=("n", "dtype", "precision",
                                             "operands"))
def _forward(rows, cols, vals, x, weights, *, n, dtype, precision,
             operands=None):
    vals = vals.astype(dtype)
    h = x.astype(dtype)

    def rounded(a):
        return a if operands is None else a.astype(operands).astype(dtype)

    for i, w in enumerate(weights):
        hw = jnp.dot(rounded(h), rounded(w.astype(dtype)),
                     precision=precision)
        h = jax.ops.segment_sum(vals[:, None] * hw[cols], rows,
                                num_segments=n)
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)


def reference(a, x, weights) -> np.ndarray:
    """float32 logits [n, classes] of the GCN over CSR ``a`` (scipy),
    features ``x`` [n, f] and ``weights`` [[f, h], [h, c]]."""
    return np.asarray(_forward(*_triples(a), jnp.asarray(x), list(weights),
                               n=a.shape[0], dtype=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST))


def reference_stated(a, x, weights) -> np.ndarray:
    """The forward at the stated precision: ``X W`` operands rounded
    to bfloat16, float32 everywhere else."""
    return np.asarray(_forward(*_triples(a), jnp.asarray(x), list(weights),
                               n=a.shape[0], dtype=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST,
                               operands=jnp.bfloat16))


def reference_bf16(a, x, weights) -> np.ndarray:
    """The control: the same forward computed in bfloat16."""
    return np.asarray(_forward(*_triples(a), jnp.asarray(x), list(weights),
                               n=a.shape[0], dtype=jnp.bfloat16,
                               precision=jax.lax.Precision.DEFAULT))


def reference_fp8(a, x, weights) -> np.ndarray:
    """The control: matmul operands rounded to float8 e4m3."""
    return np.asarray(_forward(*_triples(a), jnp.asarray(x), list(weights),
                               n=a.shape[0], dtype=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST,
                               operands=jnp.float8_e4m3fn))


def max_err_ratio(y, ref: np.ndarray, stated: np.ndarray) -> float:
    """max |y - ref| / max |stated - ref|; infinite for a wrong shape
    or a non-finite answer."""
    y = np.asarray(y)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    return float(np.abs(y - ref).max() / np.abs(stated - ref).max())


def bf16_exact_share(y) -> float:
    """Share of the logits whose float32 value a bfloat16 holds exactly
    (the low 16 bits of the float32 are zero)."""
    bits = np.asarray(y, np.float32).view(np.uint32)
    return float(((bits & 0xFFFF) == 0).mean())
