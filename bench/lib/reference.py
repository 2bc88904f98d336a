"""The plain references that decide ``correct``, and their controls.

Each configuration's model file (``models/<kind>.py``) has a
``forward(rows, cols, vals, x, params, *, n, dtype, precision,
operands)``: the model written with nothing but ``jax.numpy`` over the
CSR's (row, col, value) triples. It knows nothing of the program's
partition, padding, reordering or batching. This module runs it at four
precisions, the same for every model:

``reference``         float32 throughout, matmuls at HIGHEST;
``reference_stated``  the configurations' stated precision: float32
                      storage and the TPU's default matmul precision,
                      which rounds both operands of every matmul to
                      bfloat16 (one pass, float32 accumulation); the
                      operands are rounded to bfloat16, their products
                      and sums and everything else stay in float32;
``reference_fp8``     a control: matmul operands rounded to float8
                      e4m3, one step below the stated bfloat16;
``reference_bf16``    a control: every array and intermediate in
                      bfloat16, one step below the stated float32
                      storage.

Two numbers are compared for each answer:

``max_err_ratio``     the widest gap between the answer and
                      ``reference``, over the widest gap between
                      ``reference_stated`` and ``reference``: about 1
                      for a program that rounds as the configuration
                      states, on every seed, where the widest gap
                      alone swings with the weights' scale. Its
                      control is ``reference_fp8``.
``bf16_exact_share``  the share of the answer's logits that a bfloat16
                      holds exactly. Its control, ``reference_bf16``,
                      reads 1; a float32 answer reads the share of its
                      exact zeros. No gap to a reference separates
                      this control: the program's own matmuls round
                      the same operands, and its dense tiles, matmuls
                      at the stated precision, round a share of the
                      aggregation's operands as bfloat16 storage would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _triples(a) -> tuple:
    n = a.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(a.indptr))
    return (jnp.asarray(rows), jnp.asarray(a.indices.astype(np.int32)),
            jnp.asarray(a.data.astype(np.float32)))


def _run(forward, a, x, params, *, dtype, precision,
         operands=None) -> np.ndarray:
    return np.asarray(forward(*_triples(a), jnp.asarray(x), params,
                              n=a.shape[0], dtype=dtype, precision=precision,
                              operands=operands))


def reference(forward, a, x, params) -> np.ndarray:
    """float32 logits of the model ``forward`` over CSR ``a`` (scipy),
    features ``x`` [n, f] and parameters ``params``."""
    return _run(forward, a, x, params, dtype=jnp.float32, precision=HIGHEST)


def reference_stated(forward, a, x, params) -> np.ndarray:
    """The forward at the stated precision: matmul operands rounded
    to bfloat16, float32 everywhere else."""
    return _run(forward, a, x, params, dtype=jnp.float32, precision=HIGHEST,
                operands=jnp.bfloat16)


def reference_bf16(forward, a, x, params) -> np.ndarray:
    """The control: the same forward computed in bfloat16."""
    return _run(forward, a, x, params, dtype=jnp.bfloat16,
                precision=jax.lax.Precision.DEFAULT)


def reference_fp8(forward, a, x, params) -> np.ndarray:
    """The control: matmul operands rounded to float8 e4m3."""
    return _run(forward, a, x, params, dtype=jnp.float32, precision=HIGHEST,
                operands=jnp.float8_e4m3fn)


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
