"""Expert-parallel MoE dispatch via shard_map (the §Perf iteration that
replaces GSPMD's one-hot-matmul lowering of the dispatch gather).

Layout: tokens are data-sharded and REPLICATED across the model axis;
experts are sharded across the model axis (E/|model| per rank). Each
model rank therefore already holds every token it could need — it simply
compacts the tokens routed to ITS experts into a local capacity buffer
(plain local gather, no one-hot matmul, no all-to-all), runs its experts,
scatters back, and a single psum over the model axis combines the
partial outputs (each token's experts live on exactly `top_k` ranks).

Collective cost per layer: one psum of the token activations over the
model axis — versus GSPMD's measured ~100x HLO-flop inflation from
lowering `take` on the sharded token table.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P


def moe_ffn_ep(x, p, cfg, mesh, *, dp_axes, mdl_axis,
               capacity: Optional[int] = None):
    """x [T, D] (T data-sharded, replicated over model) -> [T, D]."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_mdl = int(mesh.shape[mdl_axis])
    e_local = e // n_mdl
    n_dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    t_local = t // n_dp
    if capacity is None:
        capacity = int(np.ceil(t_local * k / e * cfg.capacity_factor))
    c = max(capacity, 1)

    def body(xl, router, w_gate, w_up, w_down):
        # xl [t_local, D]; router [D, E]; w_* [e_local, ...]
        me = jax.lax.axis_index(mdl_axis)
        logits = xl.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)                # [t_local, k]
        topv = topv / topv.sum(axis=-1, keepdims=True)

        # my experts are [me*e_local, (me+1)*e_local)
        e_flat = topi.reshape(-1)
        w_flat = topv.reshape(-1)
        tok_flat = jnp.repeat(jnp.arange(t_local, dtype=jnp.int32), k)
        local_e = e_flat - me * e_local
        mine = (local_e >= 0) & (local_e < e_local)

        onehot = jax.nn.one_hot(jnp.where(mine, local_e, e_local),
                                e_local + 1, dtype=jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - 1
        rank = jnp.sum(rank * onehot, axis=-1)
        keep = mine & (rank < c)
        dest = jnp.where(keep, local_e * c + rank, e_local * c)

        slot_tok = jnp.zeros((e_local * c + 1,), jnp.int32) \
            .at[dest].set(tok_flat)
        slot_w = jnp.zeros((e_local * c + 1,), jnp.float32) \
            .at[dest].set(w_flat)
        slot_tok = slot_tok[:-1].reshape(e_local, c)
        slot_w = slot_w[:-1].reshape(e_local, c)

        xs = jnp.take(xl, slot_tok, axis=0)                 # local gather!
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate)) \
            * jnp.einsum("ecd,edf->ecf", xs, w_up)
        y = jnp.einsum("ecf,efd->ecd", h, w_down)
        y = (y * slot_w[..., None].astype(y.dtype)).reshape(e_local * c, d)
        out = jax.ops.segment_sum(y, slot_tok.reshape(-1),
                                  num_segments=t_local)
        # each token was processed by top_k experts spread over ranks
        return jax.lax.psum(out.astype(xl.dtype), mdl_axis)

    dp = tuple(dp_axes)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None), P(), P(mdl_axis, None, None),
                  P(mdl_axis, None, None), P(mdl_axis, None, None)),
        out_specs=P(dp, None),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
