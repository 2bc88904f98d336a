"""Dense blocked matmul Pallas kernel — the dense systolic tensor array.

On ACAP the dense AIE array computes X @ W with 32x32 tiles flowing
through a chain of tensor PEs. On TPU the MXU *is* the systolic array;
the chain dataflow becomes the k-innermost grid iteration of pallas_call,
and the tile size is re-picked for VMEM/MXU alignment (multiples of 128).

Grid: (M/bm, N/bn, K/bk), k innermost so the f32 VMEM accumulator is
revisited across the contraction; blocks are (bm,bk) x (bk,bn) -> (bm,bn).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def matmul_contract(m: int, k: int, n: int, *, bm: int = DEFAULT_BM,
                    bn: int = DEFAULT_BN, bk: int = DEFAULT_BK) -> dict:
    """The exact launch contract ``tile_matmul`` uses for these shapes.

    Single source of truth for grid, BlockSpecs, scratch, and padded
    operand shapes — the wrapper below launches from this dict and the
    static kernel-contract checker (``repro.analysis.static``) audits
    it, so the two can never drift.
    """
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    mp, np_, kp = (-(-m // bm_) * bm_, -(-n // bn_) * bn_, -(-k // bk_) * bk_)
    return {
        "name": "tile_matmul",
        "grid": (mp // bm_, np_ // bn_, kp // bk_),
        "num_scalar_prefetch": 0,
        "in_specs": [
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        "out_specs": [pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j))],
        "scratch_shapes": [pltpu.VMEM((bm_, bn_), jnp.float32)],
        "in_shapes": [(mp, kp), (kp, np_)],
        "out_shapes": [(mp, np_)],
        "elem_bytes": 4,
    }


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tile_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                interpret: bool = False) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N]; M,K,N need not be multiples of the blocks
    (inputs are zero-padded — zeros contribute nothing to the contraction)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    c = matmul_contract(m, k, n, bm=bm, bn=bn, bk=bk)
    (mp, kp), (_, np_) = c["in_shapes"]
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else b

    out = pl.pallas_call(
        _matmul_kernel,
        grid=c["grid"],
        in_specs=c["in_specs"],
        out_specs=c["out_specs"][0],
        out_shape=jax.ShapeDtypeStruct(c["out_shapes"][0], a.dtype),
        scratch_shapes=c["scratch_shapes"],
        interpret=interpret,
        name=c["name"],
    )(a_p, b_p)
    return out[:m, :n]
