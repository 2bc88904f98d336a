"""Graph attention (GAT) over the tri-partition's three engines.

One GAT layer (Velickovic et al., ICLR 2018, arXiv:1710.10903) with H
heads of F' features, over the nonzero pattern N(i) of A's row i:

    z_j        = h_j W                           [H, F'] a vertex
    e_ij       = LeakyReLU_0.2(a_l . z_i + a_r . z_j)   per head
    alpha_ij   = exp(e_ij - m_i) / sum_{j in N(i)} exp(e_ij - m_i)
    out_i      = sum_{j in N(i)} alpha_ij z_j    per head

then the heads concatenated (and an ELU) in a hidden layer, averaged in
the last. A_tilde's pattern is N(i) and i. A's values are not used: a
slot takes attention where its value is nonzero, and every padding slot
of a partition (shape-class padding, empty dense-tile entries, ELL slots
past a unit's K) carries 0.

A row's pattern spans the dense tiles, the ELL units and the COO
triples, so each engine scores its own slots from the per-vertex
projections ``s_l = z . a_l`` and ``s_r = z . a_r`` ([N, H]):

``attn.score``  the projections and the exact row max ``m_i``:
                LeakyReLU increases, so ``max_j e_ij = LeakyReLU(s_l[i]
                + max_j s_r[j])``, a masked max of ``s_r`` over the
                pattern (a masked max on the dense tiles and the ELL
                units, a ``segment_max`` on COO), combined across the
                engines;
``attn.dense``, ``attn.ell``, ``attn.coo``
                each engine's ``p_ij = exp(e_ij - m_i)`` on its slots
                and ``sum_j p_ij [z_j | 1]``: the ones column makes the
                softmax's denominator in the same pass as its numerator.

The three partial sums are added and divided outside every scope. A row
with no slot (class padding) gets 0, never a NaN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .formats import PartitionMeta, TriPartition, scatter_ell_partials

NEGATIVE_SLOPE = 0.2


def gat_param_shapes(params) -> tuple:
    """``((w.shape, a_l.shape), ...)`` of GAT parameters, a list of
    ``{"w": [f_in, H*F'], "a_l": [H, F'], "a_r": [H, F']}``, one a
    layer; a ValueError names what does not fit."""
    if not params:
        raise ValueError("GAT parameters: no layers")
    shapes, f_in = [], None
    for i, p in enumerate(params):
        if set(p) != {"w", "a_l", "a_r"}:
            raise ValueError(f"GAT layer {i}: keys {sorted(p)}, want "
                             f"['a_l', 'a_r', 'w']")
        w, a_l, a_r = (tuple(p[k].shape) for k in ("w", "a_l", "a_r"))
        if (len(w) != 2 or len(a_l) != 2 or a_l != a_r
                or w[1] != a_l[0] * a_l[1]
                or (f_in is not None and w[0] != f_in)):
            raise ValueError(f"GAT layer {i}: w {w}, a_l {a_l}, a_r {a_r} "
                             f"do not chain as [f_in, H*F'], [H, F']")
        shapes.append((w, a_l))
        f_in = w[1]
    return tuple(shapes)


def _leaky(e):
    return jax.nn.leaky_relu(e, NEGATIVE_SLOPE)


def _ell_take(table, cols):
    """``table`` [U, T, ...] gathered at the units' tile-local ``cols``
    [U, R, K]: [U, R, K, ...]."""
    u, r, k = cols.shape
    idx = cols.reshape((u, r * k) + (1,) * (table.ndim - 2))
    g = jnp.take_along_axis(table, idx, axis=1)
    return g.reshape((u, r, k) + table.shape[2:])


def _at_unit_rows(a, rows):
    """[N, H] ``a`` at the ELL units' output ``rows`` [U, R]; the
    sentinel row of padded unit rows (N) reads zeros."""
    a = jnp.pad(a, ((0, 1), (0, 0)))
    return jnp.take(a, rows.reshape(-1), axis=0).reshape(rows.shape
                                                         + a.shape[1:])


def _by_tile(a, meta: PartitionMeta):
    """[n_tiles * T, ...] as [n_tiles, T, ...]."""
    return a.reshape((-1, meta.tile) + a.shape[1:])


# --------------------------------------------------------- row max ----
def _dense_max(part: TriPartition, s_r, meta: PartitionMeta):
    d = part.dense
    heads = s_r.shape[1]
    if d.tiles.shape[0] == 0:
        return jnp.full((meta.n_padded_rows, heads), -jnp.inf, s_r.dtype)
    sr = jnp.take(_by_tile(s_r, meta), d.tile_col, axis=0)     # [n_t,T,H]
    mask = (d.tiles != 0)[..., None]                          # [n_t,i,j,1]
    top = jnp.where(mask, sr[:, None, :, :], -jnp.inf).max(axis=2)
    top = jax.ops.segment_max(top, d.tile_row,
                              num_segments=meta.n_row_tiles)
    return top.reshape(meta.n_padded_rows, heads)


def _ell_max(part: TriPartition, s_r, meta: PartitionMeta):
    ell = part.ell
    heads = s_r.shape[1]
    if ell.cols.shape[0] == 0:
        return jnp.full((meta.n_padded_rows, heads), -jnp.inf, s_r.dtype)
    srt = jnp.take(_by_tile(s_r, meta), ell.tile_col, axis=0)  # [U,T,H]
    sr = _ell_take(srt, ell.cols)                             # [U,R,K,H]
    top = jnp.where((ell.vals != 0)[..., None], sr, -jnp.inf).max(axis=2)
    out = jnp.full((meta.ell_sentinel_row + 1, heads), -jnp.inf, s_r.dtype)
    out = out.at[ell.rows.reshape(-1)].max(top.reshape(-1, heads))
    return out[: meta.n_padded_rows]


def _coo_max(part: TriPartition, s_r, meta: PartitionMeta):
    coo = part.coo
    sr = jnp.where((coo.vals != 0)[:, None], s_r[coo.cols], -jnp.inf)
    return jax.ops.segment_max(sr, coo.rows,
                               num_segments=meta.n_padded_rows)


# -------------------------------------------------- per-engine sums ----
def _dense_attn(part: TriPartition, s_l, s_r, m, z1, meta: PartitionMeta):
    """sum_j p_ij [z_j | 1] over the dense tiles, [N, H, F'+1]."""
    d = part.dense
    if d.tiles.shape[0] == 0:
        return jnp.zeros((meta.n_padded_rows,) + z1.shape[1:], z1.dtype)
    sl = jnp.take(_by_tile(s_l, meta), d.tile_row, axis=0)    # [n_t,T,H]
    mt = jnp.take(_by_tile(m, meta), d.tile_row, axis=0)
    sr = jnp.take(_by_tile(s_r, meta), d.tile_col, axis=0)
    e = _leaky(sl[:, :, None, :] + sr[:, None, :, :])         # [n_t,i,j,H]
    p = jnp.where((d.tiles != 0)[..., None],
                  jnp.exp(e - mt[:, :, None, :]), 0.0)
    zt = jnp.take(_by_tile(z1, meta), d.tile_col, axis=0)   # [n_t,T,H,F1]
    prod = jnp.einsum("tijh,tjhf->tihf", p, zt,
                      preferred_element_type=jnp.float32)
    out = jax.ops.segment_sum(prod, d.tile_row,
                              num_segments=meta.n_row_tiles)
    return out.reshape((meta.n_padded_rows,) + z1.shape[1:])


def _ell_attn(part: TriPartition, s_l, s_r, m, z1, meta: PartitionMeta):
    """sum_j p_ij [z_j | 1] over the ELL units, in Kmax masked trips."""
    ell = part.ell
    u, r, kmax = ell.cols.shape
    if u == 0:
        return jnp.zeros((meta.n_padded_rows,) + z1.shape[1:], z1.dtype)
    heads, f1 = z1.shape[1:]
    sl, mu = (_at_unit_rows(a, ell.rows) for a in (s_l, m))   # [U,R,H]
    srt = jnp.take(_by_tile(s_r, meta), ell.tile_col, axis=0)   # [U,T,H]
    e = _leaky(sl[:, :, None, :] + _ell_take(srt, ell.cols))  # [U,R,K,H]
    p = jnp.where((ell.vals != 0)[..., None],
                  jnp.exp(e - mu[:, :, None, :]), 0.0)
    zt = jnp.take(_by_tile(z1.reshape(z1.shape[0], heads * f1), meta),
                  ell.tile_col, axis=0)                      # [U,T,H*F1]
    acc = jnp.zeros((u, r, heads, f1), jnp.float32)
    for kk in range(kmax):   # static Kmax trips, padding masked by p = 0
        g = jnp.take_along_axis(zt, ell.cols[:, :, kk][:, :, None], axis=1)
        acc = acc + p[:, :, kk, :, None] * g.reshape(u, r, heads, f1)
    out = scatter_ell_partials(ell.rows.reshape(-1),
                               acc.reshape(u * r, heads * f1), meta)
    return out.reshape((meta.n_padded_rows,) + z1.shape[1:])


def _coo_attn(part: TriPartition, s_l, s_r, m, z1, meta: PartitionMeta):
    """sum_j p_ij [z_j | 1] over the COO triples (segment_sum)."""
    coo = part.coo
    e = _leaky(s_l[coo.rows] + s_r[coo.cols])                   # [nnz,H]
    p = jnp.where((coo.vals != 0)[:, None],
                  jnp.exp(e - m[coo.rows]), 0.0)
    return jax.ops.segment_sum(p[:, :, None] * z1[coo.cols], coo.rows,
                               num_segments=meta.n_padded_rows)


# ----------------------------------------------------------- layers ----
def gat_layer(part: TriPartition, h, p: dict, *, meta: PartitionMeta,
              concat: bool):
    """One GAT layer over a square partition: [N, H*F'] with the heads
    concatenated, or [N, F'] averaged (``concat`` False); N, the rows
    of ``h``, is the class's padded rows."""
    heads, f = p["a_l"].shape
    n = meta.n_padded_rows
    with jax.named_scope("combine"):
        z = (h @ p["w"]).reshape(n, heads, f)
        z1 = jnp.concatenate([z, jnp.ones((n, heads, 1), z.dtype)], axis=2)
    with jax.named_scope("attn.score"):
        s_l = jnp.einsum("nhf,hf->nh", z, p["a_l"])
        s_r = jnp.einsum("nhf,hf->nh", z, p["a_r"])
        # -inf on a row with no slot, whose p every engine masks to 0
        m = _leaky(s_l + jnp.maximum(jnp.maximum(
            _dense_max(part, s_r, meta), _ell_max(part, s_r, meta)),
            _coo_max(part, s_r, meta)))
    with jax.named_scope("attn.dense"):
        yd = _dense_attn(part, s_l, s_r, m, z1, meta)
    with jax.named_scope("attn.ell"):
        ye = _ell_attn(part, s_l, s_r, m, z1, meta)
    with jax.named_scope("attn.coo"):
        yc = _coo_attn(part, s_l, s_r, m, z1, meta)
    y = yd + ye + yc
    den = y[:, :, f:]
    out = y[:, :, :f] / jnp.where(den > 0, den, 1.0)
    return out.reshape(n, heads * f) if concat else out.mean(axis=1)


def gat_forward(part: TriPartition, x, params, *, meta: PartitionMeta):
    """GAT logits [N, classes]: every layer but the last concatenates
    its heads and applies an ELU, the last averages its heads (the
    paper's transductive models). ``params`` as `gat_param_shapes`
    takes them."""
    h = x
    for i, p in enumerate(params):
        last = i == len(params) - 1
        h = gat_layer(part, h, p, meta=meta, concat=not last)
        if not last:
            h = jax.nn.elu(h)
    return h
