"""Dense systolic tensor array applied to A's tightly-clustered tiles.

Block-sparse (BSR-stack) x dense matmul with scalar-prefetched B-tile
selection: grid (n_tiles, F/bf); each step computes
``tiles[t] @ b_tiles[tile_col[t]][:, blk]`` on the MXU. The caller
segment-sums the per-tile products over tile_row (paper Fig. 7: results
of STPE rows are accumulated into the output row band).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BF = 128


def _bsr_kernel(tile_col_ref, tiles_ref, b_ref, o_ref):
    del tile_col_ref
    o_ref[0] = jnp.dot(tiles_ref[0], b_ref[0],
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def bsr_spmm(tiles: jnp.ndarray, tile_col: jnp.ndarray,
             b_tiles: jnp.ndarray, *, bf: int = DEFAULT_BF,
             interpret: bool = False) -> jnp.ndarray:
    """tiles [n_t, T, T], tile_col [n_t] int32, b_tiles [nct, T, F]
    -> [n_t, T, F] float32 per-tile products."""
    n_t, t, t2 = tiles.shape
    nct, t3, f = b_tiles.shape
    assert t == t2 == t3
    bf_ = min(bf, f)
    fp = -(-f // bf_) * bf_
    b_p = jnp.pad(b_tiles, ((0, 0), (0, 0), (0, fp - f))) if fp != f else b_tiles

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_t, fp // bf_),
        in_specs=[
            pl.BlockSpec((1, t, t), lambda i, j, tc: (i, 0, 0)),
            pl.BlockSpec((1, t, bf_), lambda i, j, tc: (tc[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((1, t, bf_), lambda i, j, tc: (i, 0, j)),
    )
    out = pl.pallas_call(
        _bsr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_t, t, fp), jnp.float32),
        interpret=interpret,
        name="bsr_spmm",
    )(tile_col, tiles, b_p)
    return out[:, :, :f]
