"""Sparse systolic tensor engine — ragged single-launch ELL SpMM kernel.

H-GCN's sparse tensor array maps ELL groups of *differing* K onto one
systolic array by making K a per-tile parameter, not a per-kernel one.
The TPU translation (``ragged_ell_spmm``): ONE kernel launch over the
concatenated unit array with a per-unit mask ``kk < unit_k[u]`` —
``unit_k`` rides the scalar-prefetch path next to ``tile_col``, so both
the B-tile choice and the live trip count are known before each grid
step's body runs.

Mosaic has no in-kernel row gather, so a unit's product is formed on
the MXU: the unit's [R, K] slab is densified on the VPU into its
[R, T] row block of A (a one-hot compare per slab column), which then
multiplies the unit's [T, bf] B tile at HIGHEST precision (the f32
gather it replaces is exact, and so stays the densified product).

v2 grid structure (density-aware):

  * **K bands** — units arrive sorted by K descending (the partition
    emits them that way; ``segments`` carries the (K, n_units) runs).
    The runs are merged to at most ``max_bands`` bands and the kernel
    selects, per unit, the densify chain of its band via ``lax.switch``
    — short units stop paying the full-Kmax trip count. The densified
    block does not depend on the chain length (the band chains only
    drop slab columns the value mask already zeroed), so every band
    plan is bitwise-identical to the fixed-K path.
  * **Unit batching** (``gu > 1``) — process ``gu`` units per grid step
    against the whole padded B resident in VMEM (block index maps drop
    the per-unit ``tile_col`` lookup; each unit reads its B tile at a
    scalar ``tile_col`` from SMEM). Cuts grid steps — and their fixed
    overhead — by ``gu``× at the cost of ``nct*T*bf`` VMEM for B, so it
    is only legal for small graphs: the default resolves via
    ``auto_gu`` (the largest VMEM-legal batch), the autotuner proposes
    overrides, and the kernel contract oracle
    (``repro.analysis.static.kernel_pass``) rejects any candidate whose
    working set blows the VMEM budget.
  * **Multi-buffering** (``buffer_depth``) — the contract carries the
    HBM→VMEM pipeline depth and ``dimension_semantics`` so DMA for grid
    step i+1 overlaps step i's compute; the feature axis is declared
    ``parallel`` (steps independent), the unit axis ``arbitrary``.

The legacy fixed-K kernel (``ell_spmm``) is retained for the
"fused"/"loop" A/B dispatches: one launch per distinct K with a fully
static trip count (the pre-ragged layout).

Grid: (n_units / gu, F / bf). Output is per-unit [U, R, bf] partial
products; the caller scatter-adds them over the unit row ids (the
flexible engine's job — on ACAP the PL collects STPE results the same
way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BF = 128
# Band-merge cap: more bands = tighter trip counts but a deeper
# lax.switch; 4 captures most of the padded-trip savings on real graphs.
DEFAULT_MAX_BANDS = 4
# HBM->VMEM pipeline depth (double-buffered by default, quad is the
# autotuner's other legal choice).
DEFAULT_BUFFER_DEPTH = 2
# Scoped-VMEM limit every ELL launch passes to Mosaic
# (``vmem_limit_bytes``), and the budget the kernel pass audits the
# contracts' multi-buffered working sets against.
VMEM_BUDGET_BYTES = 16 * 2 ** 20


def _pad_f(f: int, bf: int) -> tuple:
    """(bf_, fp): the clamped feature block and padded feature width."""
    bf_ = min(bf, f)
    return bf_, -(-f // bf_) * bf_


def merge_bands(runs, max_bands: int) -> tuple:
    """Merge descending-K (K, n_units) runs down to ``max_bands`` bands.

    Adjacent runs merge into the wider K; the pair chosen at each step
    is the one adding the least padded-MAC waste
    ``(K_left - K_right) * n_right``. Deterministic (first minimum
    wins), returns a tuple of (K, n_units) with K strictly descending.
    """
    merged: list = []
    for k, n in runs:
        if n <= 0:
            continue
        if merged and merged[-1][0] == int(k):
            merged[-1][1] += int(n)
        else:
            merged.append([int(k), int(n)])
    while len(merged) > max_bands:
        best = min(range(len(merged) - 1),
                   key=lambda i: (merged[i][0] - merged[i + 1][0])
                   * merged[i + 1][1])
        merged[best][1] += merged[best + 1][1]
        del merged[best + 1]
    return tuple((k, n) for k, n in merged)


def _bands_of(segments, u: int, kmax: int, max_bands: int) -> tuple:
    """Normalize ``segments`` into the kernel's K-descending band plan.

    Empty segments (or any non-descending legacy order) collapse to one
    Kmax-wide band — exactly the v1 kernel. Band Ks are clamped to the
    slab width; a band covering units whose slab columns past K are all
    zero is trip-equivalent to the full-width chain.
    """
    if u == 0:
        return ()
    segs = tuple((int(k), int(n)) for k, n in segments if int(n) > 0)
    if not segs or sum(n for _, n in segs) != u:
        return ((kmax, u),)
    ks = [k for k, _ in segs]
    if any(ks[i] < ks[i + 1] for i in range(len(ks) - 1)):
        return ((kmax, u),)     # legacy ascending order: no banding
    segs = tuple((min(k, kmax), n) for k, n in segs)
    return merge_bands(segs, max_bands)


def _band_tables(bands) -> tuple:
    """(band_ks, band_offs): static switch tables.

    ``band_offs`` holds the starting unit index of every band past the
    first; the kernel's band selector is ``sum(i >= off)``.
    """
    band_ks = tuple(k for k, _ in bands)
    offs, at = [], 0
    for _, n in bands[:-1]:
        at += n
        offs.append(at)
    return band_ks, tuple(offs)


def _spec_block_bytes(specs, elem_bytes: int) -> int:
    total = 0
    for spec in specs:
        n = elem_bytes
        for d in spec.block_shape:
            n *= int(d)
        total += n
    return total


def ell_contract(u: int, r: int, k: int, nct: int, t: int, f: int,
                 *, bf: int = DEFAULT_BF,
                 buffer_depth: int = DEFAULT_BUFFER_DEPTH) -> dict:
    """The exact launch contract ``ell_spmm`` uses for these shapes.

    Single source of truth for grid, BlockSpecs, and padded operand
    shapes — the kernel wrapper below launches from this dict and the
    static kernel-contract checker (``repro.analysis.static``) audits
    it, so the two can never drift. All operands are 4-byte elements
    (int32 indices, float32 values).
    """
    bf_, fp = _pad_f(f, bf)
    in_specs = [
        pl.BlockSpec((1, r, k), lambda i, j, tc: (i, 0, 0)),
        pl.BlockSpec((1, r, k), lambda i, j, tc: (i, 0, 0)),
        pl.BlockSpec((1, t, bf_), lambda i, j, tc: (tc[i], 0, j)),
    ]
    out_specs = [pl.BlockSpec((1, r, bf_), lambda i, j, tc: (i, 0, j))]
    return {
        "name": "ell_spmm",
        "grid": (u, fp // bf_),
        "num_scalar_prefetch": 1,
        "in_specs": in_specs,
        "out_specs": out_specs,
        "scratch_shapes": [],
        "in_shapes": [(u, r, k), (u, r, k), (nct, t, fp)],
        "out_shapes": [(u, r, fp)],
        "elem_bytes": 4,
        "buffer_depth": buffer_depth,
        "dimension_semantics": ("arbitrary", "parallel"),
        "vmem_limit_bytes": VMEM_BUDGET_BYTES,
    }


def ragged_ell_contract(u: int, r: int, kmax: int, nct: int, t: int, f: int,
                        *, bf: int = DEFAULT_BF, segments: tuple = (),
                        max_bands: int = DEFAULT_MAX_BANDS,
                        buffer_depth: int = DEFAULT_BUFFER_DEPTH,
                        gu: int = 1) -> dict:
    """The exact launch contract ``ragged_ell_spmm`` uses (see
    ``ell_contract``); scalar-prefetch operands are (tile_col, unit_k).

    Tunables (all audited by the kernel pass, all defaulting to the v1
    behavior): ``segments`` — the (K, n_units) descending runs of the
    unit axis, merged to ``max_bands`` K bands; ``buffer_depth`` — the
    HBM→VMEM pipeline depth; ``gu`` — units per grid step (``gu > 1``
    switches the B operand to whole-array VMEM residency).
    """
    if gu < 1:
        raise ValueError(f"gu must be >= 1, got {gu}")
    if buffer_depth < 1:
        raise ValueError(f"buffer_depth must be >= 1, got {buffer_depth}")
    bf_, fp = _pad_f(f, bf)
    bands = _bands_of(segments, u, kmax, max_bands)
    band_ks, band_offs = _band_tables(bands)
    if gu == 1:
        up = u
        grid = (u, fp // bf_)
        in_specs = [
            pl.BlockSpec((1, r, kmax), lambda i, j, tc, ks: (i, 0, 0)),
            pl.BlockSpec((1, r, kmax), lambda i, j, tc, ks: (i, 0, 0)),
            pl.BlockSpec((1, t, bf_), lambda i, j, tc, ks: (tc[i], 0, j)),
        ]
        out_specs = [pl.BlockSpec((1, r, bf_),
                                  lambda i, j, tc, ks: (i, 0, j))]
    else:
        # gu units per step against the WHOLE padded B in VMEM: the
        # B block ignores the unit axis (index maps can't read gu
        # different tile_cols), so each unit picks its tile inside the
        # body.
        up = -(-u // gu) * gu
        grid = (up // gu, fp // bf_)
        in_specs = [
            pl.BlockSpec((gu, r, kmax), lambda i, j, tc, ks: (i, 0, 0)),
            pl.BlockSpec((gu, r, kmax), lambda i, j, tc, ks: (i, 0, 0)),
            pl.BlockSpec((nct, t, bf_), lambda i, j, tc, ks: (0, 0, j)),
        ]
        out_specs = [pl.BlockSpec((gu, r, bf_),
                                  lambda i, j, tc, ks: (i, 0, j))]
    return {
        "name": "ragged_ell_spmm",
        "grid": grid,
        "num_scalar_prefetch": 2,
        "in_specs": in_specs,
        "out_specs": out_specs,
        "scratch_shapes": [],
        "in_shapes": [(up, r, kmax), (up, r, kmax), (nct, t, fp)],
        "out_shapes": [(up, r, fp)],
        "elem_bytes": 4,
        "segments": tuple((int(k), int(n)) for k, n in segments),
        "band_ks": band_ks,
        "band_offs": band_offs,
        "buffer_depth": buffer_depth,
        "gu": gu,
        "dimension_semantics": ("arbitrary", "parallel"),
        "vmem_limit_bytes": VMEM_BUDGET_BYTES,
    }


def contract_cost(c: dict) -> dict:
    """Analytic per-launch cost of a contract: HBM bytes + MXU FLOPs.

    ``hbm_bytes`` counts every block the grid moves (in + out, once per
    step — multi-buffering overlaps the transfers, it does not remove
    them); ``flops`` counts the MXU products the kernel issues, one
    [R, T] x [T, F] product per (padded) unit whatever its K (2 ops per
    MAC; the K bands shorten only the VPU densify, not counted here).
    Benchmarks divide these by the roofline constants to report the
    DMA-vs-compute split and the achieved-roofline fraction; this
    module deliberately knows bytes and FLOPs only.
    """
    n_steps = 1
    for g in c["grid"]:
        n_steps *= int(g)
    step_bytes = _spec_block_bytes(
        list(c["in_specs"]) + list(c["out_specs"]), c["elem_bytes"])
    hbm_bytes = step_bytes * n_steps
    units, rows, _ = c["in_shapes"][0]               # (up, r, k)
    _, t, fp = c["in_shapes"][2]                     # (nct, t, fp)
    flops = 2.0 * units * rows * t * fp
    return {"hbm_bytes": float(hbm_bytes), "flops": flops}


def auto_gu(u: int, r: int, kmax: int, nct: int, t: int, f: int,
            *, bf: int = DEFAULT_BF,
            buffer_depth: int = DEFAULT_BUFFER_DEPTH) -> int:
    """Largest legal unit batch for these shapes.

    ``gu > 1`` makes the whole padded B VMEM-resident, so it is only
    legal while the multi-buffered working set stays inside the VMEM
    budget — the same bound the static contract oracle enforces
    (``repro.analysis.static.kernel_pass.estimate_vmem_bytes``). Big
    graphs therefore resolve to 1 and keep the per-unit B-tile path;
    the autotuner may still override with an explicitly checked value.
    """
    for g in (8, 4, 2):
        if u < g:
            continue
        c = ragged_ell_contract(u, r, kmax, nct, t, f, bf=bf,
                                buffer_depth=buffer_depth, gu=g)
        block = _spec_block_bytes(c["in_specs"] + c["out_specs"],
                                  c["elem_bytes"])
        if block * buffer_depth <= VMEM_BUDGET_BYTES:
            return g
    return 1


def _unit_product(cols, vals, b, k: int, ku=None):
    """One unit's [R, bf] product over its first ``k`` slab columns.

    Mosaic has no in-kernel row gather, so the unit is densified on the
    VPU into its [R, T] row block (column ``cols[:, kk]`` of row r gets
    ``vals[r, kk]``) and multiplied with the B tile on the MXU. Rows of
    an ELL slab hold distinct columns and padding is value-zero, so the
    densified block is exact and independent of K; HIGHEST precision
    keeps the f32 product free of bf16 input rounding. ``ku`` (the
    unit's live K) masks the VALUES: lanes at or past it contribute
    nothing, whatever the slab holds there.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (cols.shape[0], b.shape[0]),
                                    1)
    dense = jnp.zeros(iota.shape, jnp.float32)
    for kk in range(k):                              # static trip count
        v = vals[:, kk:kk + 1]                       # [R, 1]
        if ku is not None:
            v = jnp.where(kk < ku, v, 0.0)
        dense = dense + jnp.where(cols[:, kk:kk + 1] == iota, v, 0.0)
    return jnp.dot(dense, b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _ell_kernel(tile_col_ref, cols_ref, vals_ref, b_ref, o_ref, *, k: int):
    del tile_col_ref  # consumed by the index maps
    o_ref[0] = _unit_product(cols_ref[0], vals_ref[0].astype(jnp.float32),
                             b_ref[0], k)


@functools.partial(jax.jit, static_argnames=("bf", "buffer_depth",
                                             "interpret"))
def ell_spmm(cols: jnp.ndarray, vals: jnp.ndarray, tile_col: jnp.ndarray,
             b_tiles: jnp.ndarray, *, bf: int = DEFAULT_BF,
             buffer_depth: int = DEFAULT_BUFFER_DEPTH,
             interpret: bool = False) -> jnp.ndarray:
    """Per-unit ELL products.

    cols [U, R, K] int32 (tile-local), vals [U, R, K], tile_col [U] int32,
    b_tiles [nct, T, F]  ->  [U, R, F] float32.
    """
    u, r, k = cols.shape
    nct, t, f = b_tiles.shape
    bf_, fp = _pad_f(f, bf)
    b_p = jnp.pad(b_tiles, ((0, 0), (0, 0), (0, fp - f))) if fp != f else b_tiles

    c = ell_contract(u, r, k, nct, t, f, bf=bf, buffer_depth=buffer_depth)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=c["num_scalar_prefetch"],
        grid=c["grid"],
        in_specs=c["in_specs"],
        out_specs=c["out_specs"][0],
    )
    out = pl.pallas_call(
        functools.partial(_ell_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c["out_shapes"][0], jnp.float32),
        interpret=interpret,
        name=c["name"],
        **_compiler_kw(c, interpret),
    )(tile_col, cols, vals, b_p)
    return out[:, :, :f]


def _compiler_kw(c: dict, interpret: bool) -> dict:
    """Mosaic pipelining knobs from the contract (compiled path only —
    interpret mode takes no compiler params)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=c["dimension_semantics"],
        vmem_limit_bytes=c["vmem_limit_bytes"])}


def _ragged_ell_kernel(tile_col_ref, unit_k_ref, cols_ref, vals_ref, b_ref,
                       o_ref, *, band_ks: tuple, band_offs: tuple, gu: int):
    """Band-switched masked unit products over gu units per grid step.

    Every unit's whole product runs inside this one body execution, at
    the K of its own band (units are K-descending, so the band bounds
    its unit_k), so each unit's result is independent of ``gu``, the
    band plan and the feature blocking: every launch configuration is
    bitwise-equal to every other and to the fixed-K kernel.
    """
    i = pl.program_id(0)

    def unit(g, b):
        """Unit ``i*gu + g`` against its [T, bf] B tile ``b``."""
        at = i * gu + g
        ku = unit_k_ref[at]                          # this unit's live K
        cols = cols_ref[g]                           # [R, Kmax]
        vals = vals_ref[g].astype(jnp.float32)       # [R, Kmax]
        chains = [functools.partial(_unit_product, cols, vals, b, k, ku)
                  for k in band_ks]
        if len(chains) == 1:
            return chains[0]()
        band = sum((at >= off).astype(jnp.int32) for off in band_offs)
        return jax.lax.switch(band, chains)

    if gu == 1:
        del tile_col_ref  # consumed by the index maps
        o_ref[0] = unit(0, b_ref[0])
        return

    # gu > 1: the whole padded B is resident; each unit reads its own
    # tile with a scalar tile_col read (SMEM holds scalars only).
    for g in range(gu):
        o_ref[g] = unit(g, b_ref[tile_col_ref[i * gu + g]])


@functools.partial(jax.jit, static_argnames=("bf", "segments", "max_bands",
                                             "buffer_depth", "gu",
                                             "interpret"))
def ragged_ell_spmm(cols: jnp.ndarray, vals: jnp.ndarray,
                    tile_col: jnp.ndarray, unit_k: jnp.ndarray,
                    b_tiles: jnp.ndarray, *, bf: int = DEFAULT_BF,
                    segments: tuple = (),
                    max_bands: int = DEFAULT_MAX_BANDS,
                    buffer_depth: int = DEFAULT_BUFFER_DEPTH,
                    gu: int = None, interpret: bool = False) -> jnp.ndarray:
    """Per-unit ELL products over the concatenated ragged unit array.

    cols [U, R, Kmax] int32 (tile-local), vals [U, R, Kmax],
    tile_col [U] int32, unit_k [U] int32, b_tiles [nct, T, F]
    ->  [U, R, F] float32.  ONE launch covers every K width.

    ``segments`` (the meta's descending (K, n_units) runs) enables the
    K-band grid; ``gu``/``buffer_depth`` are the autotuner's knobs (see
    module docstring). ``gu=None`` (the default) resolves via
    ``auto_gu`` — the largest VMEM-legal unit batch for these shapes.
    Every configuration is bitwise-equal to every other because
    per-unit chains never split across body executions.
    """
    u, r, kmax = cols.shape
    nct, t, f = b_tiles.shape
    if u == 0 or kmax == 0:
        return jnp.zeros((u, r, f), jnp.float32)
    if gu is None:
        gu = auto_gu(u, r, kmax, nct, t, f, bf=bf,
                     buffer_depth=buffer_depth)
    bf_, fp = _pad_f(f, bf)
    b_p = jnp.pad(b_tiles, ((0, 0), (0, 0), (0, fp - f))) if fp != f else b_tiles

    c = ragged_ell_contract(u, r, kmax, nct, t, f, bf=bf, segments=segments,
                            max_bands=max_bands, buffer_depth=buffer_depth,
                            gu=gu)
    up = c["in_shapes"][0][0]
    if up != u:
        # dead tail units (unit_k == 0 -> all-masked -> zero output)
        cols = jnp.pad(cols, ((0, up - u), (0, 0), (0, 0)))
        vals = jnp.pad(vals, ((0, up - u), (0, 0), (0, 0)))
        tile_col = jnp.pad(tile_col, (0, up - u))
        unit_k = jnp.pad(unit_k, (0, up - u))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=c["num_scalar_prefetch"],
        grid=c["grid"],
        in_specs=c["in_specs"],
        out_specs=c["out_specs"][0],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_ell_kernel, band_ks=c["band_ks"],
                          band_offs=c["band_offs"], gu=gu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c["out_shapes"][0], jnp.float32),
        interpret=interpret,
        name=c["name"],
        **_compiler_kw(c, interpret),
    )(tile_col, unit_k, cols, vals, b_p)
    return out[:u, :, :f]
