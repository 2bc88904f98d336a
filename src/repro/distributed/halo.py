"""Locality-aware distributed gather/scatter for 1-D sharded graph tensors.

The paper's graph reordering (§IV-B) concentrates edges near the diagonal;
in distributed terms: after reordering, an edge's endpoints live in the
same or a neighboring shard. Generic SPMD lowers ``jnp.take`` on a sharded
operand to an ALL-GATHER of the whole table (measured: 13 live copies of a
29.5 GiB edge-message tensor on dimenet/ogb_products). These halo ops
exchange only the two neighboring shards via ``ppermute``:

  memory   per device: 3 shards instead of the full table  (256x less)
  traffic  per device: 2 shards instead of n-1              (~128x less)

Contract: after reordering, every gathered index lies within one shard of
its consumer's position (indices are clamped to the halo; the offline
partitioner validates the bound and widens the halo if needed).
Both ops are differentiable (clip/take/segment_sum transpose cleanly).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _nshards(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def make_halo_ops(mesh, axes):
    """Returns (take_fn, segment_sum_fn) bound to ``mesh`` over ``axes``."""
    names = tuple(axes)
    n = _nshards(mesh, names)
    fwd = [(i, (i + 1) % n) for i in range(n)]   # send to right neighbor
    bwd = [(i, (i - 1) % n) for i in range(n)]   # send to left neighbor

    def take(x, idx):
        """x [N, ...] sharded over axes on dim 0; idx [M] sharded same way.
        Returns x[idx] assuming halo locality."""
        shard = x.shape[0] // n
        tail = (P(names),) if x.ndim == 1 else (P(names, *([None] * (x.ndim - 1))),)

        def f(xl, il):
            me = jax.lax.axis_index(names)
            left = jax.lax.ppermute(xl, names, fwd)    # from left neighbor
            right = jax.lax.ppermute(xl, names, bwd)   # from right neighbor
            halo = jnp.concatenate([left, xl, right], axis=0)
            base = me * shard - shard
            loc = jnp.clip(il - base, 0, 3 * shard - 1)
            return jnp.take(halo, loc, axis=0)

        return shard_map(
            f, mesh=mesh,
            in_specs=(tail[0], P(names)),
            out_specs=(P(names) if x.ndim == 1
                       else P(names, *([None] * (x.ndim - 1)))),
        )(x, idx)

    def segment_sum(vals, idx, num_segments):
        """segment_sum(vals [M, ...], idx [M]) -> [num_segments, ...] with
        both sides sharded over ``axes`` and halo locality on idx."""
        shard = num_segments // n

        def f(vl, il):
            me = jax.lax.axis_index(names)
            base = me * shard - shard
            loc = jnp.clip(il - base, 0, 3 * shard - 1)
            acc = jax.ops.segment_sum(vl, loc, num_segments=3 * shard)
            left, center, right = (acc[:shard], acc[shard: 2 * shard],
                                   acc[2 * shard:])
            # my 'left' block belongs to my left neighbor and vice versa
            from_right = jax.lax.ppermute(left, names, bwd)
            from_left = jax.lax.ppermute(right, names, fwd)
            return center + from_left + from_right

        tail_in = P(names) if vals.ndim == 1 \
            else P(names, *([None] * (vals.ndim - 1)))
        tail_out = P(names) if vals.ndim == 1 \
            else P(names, *([None] * (vals.ndim - 1)))
        return shard_map(f, mesh=mesh, in_specs=(tail_in, P(names)),
                         out_specs=tail_out)(vals, idx)

    return take, segment_sum


def validate_locality(idx: np.ndarray, positions: np.ndarray, n_total: int,
                      nshards: int) -> float:
    """Offline check: fraction of references outside the +-1-shard halo
    (the partitioner warns/widens if > 0)."""
    shard = n_total // nshards
    return float(np.mean(np.abs(idx - positions) > shard))
