"""The tri-engine heterogeneous SpMM executor (paper §IV-A/§IV-D/§IV-E).

Computes ``Y = A @ B`` where A is a TriPartition, dispatching each
component to its engine:

  dense tiles -> MXU batched matmul        (dense systolic tensor array)
  ELL buckets -> gather + FMA, static K    (sparse systolic tensor array)
  COO residual-> take + segment_sum        (PL row-wise SpMM)

Two backends:
  * ``xla``    — pure jnp ops; used for CPU measurement and inside pjit'd
                 distributed programs.
  * ``pallas`` — routes dense tiles + ELL buckets through the Pallas
                 kernels in ``repro.kernels`` (interpret=True on CPU,
                 compiled Mosaic on TPU).

All three partial products are exact; their sum equals A @ B bit-for-bit
up to float addition order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .formats import (PartitionMeta, TriPartition, ell_buckets,
                      pad_b_to_tiles, scatter_ell_partials)


def dense_tiles_matmul(part: TriPartition, b: jnp.ndarray,
                       meta: PartitionMeta) -> jnp.ndarray:
    """Dense-engine partial product, as padded [nrt*T, F]."""
    T = meta.tile
    nrt = meta.n_row_tiles
    f = b.shape[1]
    if part.dense.tiles.shape[0] == 0:
        return jnp.zeros((nrt * T, f), b.dtype)
    bt = pad_b_to_tiles(b, meta).reshape(meta.n_col_tiles, T, f)
    rhs = jnp.take(bt, part.dense.tile_col, axis=0)          # [n_t, T, F]
    prod = jnp.einsum("tij,tjf->tif", part.dense.tiles.astype(b.dtype), rhs,
                      preferred_element_type=jnp.float32)
    out = jax.ops.segment_sum(prod, part.dense.tile_row,
                              num_segments=nrt)               # [nrt, T, F]
    return out.reshape(nrt * T, f).astype(b.dtype)


def _ell_bucket_partials(bucket, bt: jnp.ndarray) -> jnp.ndarray:
    """One bucket's gather+FMA partial products, flattened to [U*R, F]."""
    u, r, k = bucket.cols.shape
    f = bt.shape[-1]
    btile = jnp.take(bt, bucket.tile_col, axis=0)             # [U, T, F]
    acc = jnp.zeros((u, r, f), jnp.float32)
    for kk in range(k):  # K is static per bucket — fixed trip count
        gathered = jnp.take_along_axis(
            btile, bucket.cols[:, :, kk][:, :, None], axis=1)  # [U,R,F]
        acc = acc + bucket.vals[:, :, kk][:, :, None] * gathered
    return acc.reshape(u * r, f)


def _ragged_partials(ell, bt: jnp.ndarray) -> jnp.ndarray:
    """All units' gather+FMA partials in one masked Kmax pass, [U*R, F].

    Delegates to the kernel oracle so the XLA path and the Pallas
    kernel's validation target are one implementation (the
    mask-the-values structure there keeps live lanes bit-identical to
    the "fused" dispatch).
    """
    from repro.kernels.ref import ragged_ell_spmm_ref
    u, r, _ = ell.cols.shape
    prod = ragged_ell_spmm_ref(ell.cols, ell.vals, ell.tile_col,
                               ell.unit_k, bt)
    return prod.reshape(u * r, bt.shape[-1])


def ell_matmul(part: TriPartition, b: jnp.ndarray, meta: PartitionMeta,
               *, dispatch: str = "ragged") -> jnp.ndarray:
    """Sparse-engine partial product, as padded [nrt*T, F].

    ``dispatch="ragged"`` (default) runs ONE masked Kmax pass over the
    concatenated unit array — the XLA mirror of the single-launch Pallas
    kernel. ``"fused"`` / ``"loop"`` are the legacy per-K paths kept for
    A/B parity (buckets derived from the ragged array): "fused" emits one
    scatter-add over all buckets, "loop" one per bucket. All three
    produce identical results up to float addition order.
    """
    if dispatch not in ("ragged", "fused", "loop"):
        raise ValueError(f"unknown ell dispatch {dispatch!r}")
    f = b.shape[1]
    if part.ell.cols.shape[0] == 0:
        return jnp.zeros((meta.n_padded_rows, f), jnp.float32)
    bt = pad_b_to_tiles(b, meta).reshape(meta.n_col_tiles, meta.tile, f)
    if dispatch == "ragged":
        return scatter_ell_partials(part.ell.rows.reshape(-1),
                                    _ragged_partials(part.ell, bt), meta)
    buckets = ell_buckets(part.ell, meta.ell_segments)
    partials = [_ell_bucket_partials(bucket, bt) for bucket in buckets]
    rows = [bucket.rows.reshape(-1) for bucket in buckets]
    if dispatch == "fused":
        return scatter_ell_partials(jnp.concatenate(rows),
                                    jnp.concatenate(partials), meta)
    return scatter_ell_partials(rows, partials, meta)


def coo_matmul(part: TriPartition, b: jnp.ndarray,
               meta: PartitionMeta) -> jnp.ndarray:
    """Flexible-engine partial product (row-wise product SpMM), [nrt*T, F]."""
    T = meta.tile
    nrt = meta.n_row_tiles
    f = b.shape[1]
    if part.coo.vals.shape[0] == 0:
        return jnp.zeros((nrt * T, f), jnp.float32)
    bp = pad_b_to_tiles(b, meta)
    msgs = part.coo.vals[:, None] * jnp.take(bp, part.coo.cols, axis=0)
    return jax.ops.segment_sum(msgs, part.coo.rows, num_segments=nrt * T)


def hybrid_spmm(part: TriPartition, b: jnp.ndarray, *, meta: PartitionMeta,
                backend: str = "xla", ell_dispatch: str = "ragged",
                ell_tune: dict = None) -> jnp.ndarray:
    """Y = A @ B via the three engines. Returns [n_rows, F].

    ``ell_tune`` optionally carries an autotuned ragged-kernel
    configuration (pallas backend only — the XLA mirror has no launch
    tunables); tuned outputs are bitwise-equal to defaults.
    """
    if backend == "pallas":
        from repro.kernels import ops as kops
        dense = kops.dense_tiles_matmul
        ell = functools.partial(kops.ell_matmul, dispatch=ell_dispatch,
                                ell_tune=ell_tune)
    elif backend == "xla":
        dense = dense_tiles_matmul
        ell = functools.partial(ell_matmul, dispatch=ell_dispatch)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    # One named scope per engine, whatever the backend: the device
    # trace's ops carry them (docs/TRACING.md, "Named scopes").
    with jax.named_scope("agg.dense"):
        yd = dense(part, b, meta)
    with jax.named_scope("agg.ell"):
        ye = ell(part, b, meta)
    with jax.named_scope("agg.coo"):
        yc = coo_matmul(part, b, meta)
    y = yd.astype(jnp.float32) + ye + yc
    return y[: meta.n_rows].astype(b.dtype)


def hybrid_spmm_ref(a_dense: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Oracle: plain dense matmul."""
    return a_dense @ b


# ---------------------------------------------------------------------------
# Combination-first chained SpMM with intra-layer pipelining (paper §IV-E).
# ---------------------------------------------------------------------------

def gcn_layer(part: TriPartition, x: jnp.ndarray, w: jnp.ndarray, *,
              meta: PartitionMeta, backend: str = "xla",
              block_cols: int = 0, activation=None,
              ell_dispatch: str = "ragged",
              ell_tune: dict = None) -> jnp.ndarray:
    """One GCN layer  sigma(A @ (X @ W))  in combination-first order.

    ``block_cols > 0`` enables the paper's fine-grained pipelining: W's
    output columns are processed in blocks, and ``A @ (X @ W[:, blk])``
    is emitted per block so the aggregation of block i never waits for
    combination of block i+1 — on ACAP this overlaps the dense array with
    the sparse array + PL; under XLA it makes the overlap structural so
    the scheduler can interleave the two matmul families.
    """
    h = w.shape[1]
    if block_cols and block_cols < h:
        nblk = -(-h // block_cols)
        pads = nblk * block_cols - h
        wp = jnp.pad(w, ((0, 0), (0, pads)))
        outs = []
        for i in range(nblk):  # static unroll: each block is independent
            wi = jax.lax.slice_in_dim(wp, i * block_cols, (i + 1) * block_cols,
                                      axis=1)
            with jax.named_scope("combine"):
                bi = x @ wi                               # combination (dense)
            outs.append(hybrid_spmm(part, bi, meta=meta, backend=backend,
                                    ell_dispatch=ell_dispatch,
                                    ell_tune=ell_tune))
        y = jnp.concatenate(outs, axis=1)[:, :h]
    else:
        with jax.named_scope("combine"):
            xw = x @ w
        y = hybrid_spmm(part, xw, meta=meta, backend=backend,
                        ell_dispatch=ell_dispatch, ell_tune=ell_tune)
    return activation(y) if activation is not None else y


def gcn_forward(part: TriPartition, x: jnp.ndarray, weights, *,
                meta: PartitionMeta, backend: str = "xla",
                block_cols: int = 0, ell_dispatch: str = "ragged",
                ell_tune: dict = None) -> jnp.ndarray:
    """The paper's 2-layer vanilla GCN:  softmax-free inference logits
    X2 = A·relu(A·X·W1)·W2   (activation on hidden layer only)."""
    h = x
    for i, w in enumerate(weights):
        act = jax.nn.relu if i < len(weights) - 1 else None
        h = gcn_layer(part, h, w, meta=meta, backend=backend,
                      block_cols=block_cols, activation=act,
                      ell_dispatch=ell_dispatch, ell_tune=ell_tune)
    return h
