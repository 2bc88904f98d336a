"""Pass 2 — kernel contract checker (the pre-compile legality oracle).

Audits the launch contracts exported by ``repro.kernels`` (grid,
BlockSpecs, scratch — see ``ell_contract`` / ``ragged_ell_contract`` /
``matmul_contract``, the same dicts the kernel wrappers launch from)
WITHOUT tracing or compiling anything:

- **vmem-budget**: the pipelined working set (every in/out block double-
  buffered, scratch single-buffered) must fit the per-backend VMEM
  budget. Catches an oversized BlockSpec before Mosaic does, with a
  byte-level accounting instead of a compile error.
- **index-map-arity**: every index map must take exactly
  ``len(grid) + num_scalar_prefetch`` arguments — a mismatch is a
  guaranteed trace failure, reported here with the operand named.
- **index-map-bounds**: index maps are evaluated at every grid corner
  (with caller-supplied worst-case scalar-prefetch stand-ins); each
  resulting block must lie inside the padded operand. Catches e.g. a
  ``tile_col`` that can address past the B-tile array.
- **block-divisibility**: padded operand dims must be exact multiples of
  their block dims — the repo's wrappers pad to guarantee this, so a
  violation means the contract and the padding math drifted.
- **class-fit / mac-amortization**: an independent restatement of the
  shape-class waste bound (`repro.engine.shape_class.class_fits`): a
  class whose unit capacity or slab width the member could never
  amortize is rejected here even if the runtime fit logic regresses.
  This is the legality oracle the ROADMAP item-2 autotuner will query.
"""
from __future__ import annotations

import inspect
import itertools
import math
from typing import List, Optional, Sequence

import numpy as np

from repro.analysis.static.report import Finding
from repro.engine.shape_class import (ClassNeed, ShapeClass, ShapePolicy,
                                      class_fits)
from repro.kernels.ell_spmm import (DEFAULT_BF, VMEM_BUDGET_BYTES as
                                    ELL_VMEM_LIMIT, ragged_ell_contract)
from repro.kernels.tile_matmul import matmul_contract

# VMEM a launch contract may assume, by backend: on TPU the scoped
# limit the ELL kernels pass to Mosaic (``vmem_limit_bytes``) — Mosaic
# needs the whole multi-buffered working set resident inside it.
VMEM_BUDGET_BYTES = {"tpu": ELL_VMEM_LIMIT}
# Default in/out block buffering when a contract carries no
# ``buffer_depth`` (the pipeline double-buffers); scratch is not
# multiplied.
PIPELINE_BUFFERS = 2


def _nbytes(shape: Sequence[int], elem_bytes: int) -> int:
    return int(math.prod(shape)) * elem_bytes


def estimate_vmem_bytes(contract: dict) -> int:
    """Static VMEM working-set estimate for one launch contract.

    Honors the contract's tuned ``buffer_depth`` (HBM→VMEM pipeline
    depth — quad-buffering doubles the block working set relative to
    the default double-buffering).
    """
    elem = contract["elem_bytes"]
    depth = int(contract.get("buffer_depth", PIPELINE_BUFFERS))
    total = 0
    for spec in contract["in_specs"] + contract["out_specs"]:
        total += _nbytes(spec.block_shape, elem) * depth
    for ref in contract["scratch_shapes"]:
        total += _nbytes(ref.shape, np.dtype(ref.dtype).itemsize)
    return total


def check_contract(contract: dict, *, scalar_args: Sequence = (),
                   backend: str = "tpu") -> List[Finding]:
    """All structural checks for one launch contract.

    ``scalar_args`` are worst-case stand-ins for the scalar-prefetch
    operands (e.g. a ``tile_col`` array of the largest legal tile
    index) — the bounds check evaluates the index maps against them.
    """
    name = contract["name"]
    grid = contract["grid"]
    nsp = contract["num_scalar_prefetch"]
    findings: List[Finding] = []

    def err(rule: str, msg: str) -> None:
        findings.append(Finding("kernel", rule, "error", name, msg))

    if any(g < 1 for g in grid):
        err("grid", f"grid {grid} has a non-positive dimension")
        return findings
    if len(scalar_args) != nsp:
        err("scalar-prefetch-arity",
            f"contract declares {nsp} scalar-prefetch operand(s) but "
            f"{len(scalar_args)} stand-in(s) were supplied")
        return findings

    specs = ([("in", i, s) for i, s in enumerate(contract["in_specs"])]
             + [("out", i, s) for i, s in enumerate(contract["out_specs"])])
    shapes = contract["in_shapes"] + contract["out_shapes"]
    want_arity = len(grid) + nsp
    for (kind, i, spec), full in zip(specs, shapes):
        label = f"{kind}[{i}]"
        arity = len(inspect.signature(spec.index_map).parameters)
        if arity != want_arity:
            err("index-map-arity",
                f"{label} index map takes {arity} args, grid+prefetch "
                f"needs {want_arity}")
            continue
        block = spec.block_shape
        if len(block) != len(full):
            err("block-rank",
                f"{label} block {block} vs operand {full}: rank mismatch")
            continue
        if any(f % b for f, b in zip(full, block)):
            err("block-divisibility",
                f"{label} operand {full} not a multiple of block {block} "
                f"(the wrapper's padding must make this exact)")
        for corner in itertools.product(*[(0, g - 1) for g in grid]):
            idx = spec.index_map(*corner, *scalar_args)
            idx = tuple(int(v) for v in idx)
            for d, (ix, b, f) in enumerate(zip(idx, block, full)):
                if ix < 0 or (ix + 1) * b > f:
                    err("index-map-bounds",
                        f"{label} index map at grid corner {corner} "
                        f"selects block {ix} on dim {d}: bytes "
                        f"[{ix * b}, {(ix + 1) * b}) exceed operand "
                        f"extent {f}")

    budget = VMEM_BUDGET_BYTES.get(backend)
    if budget is not None:
        est = estimate_vmem_bytes(contract)
        if est > budget:
            err("vmem-budget",
                f"working set ~{est / 2**20:.1f} MiB exceeds the "
                f"{backend} budget of {budget / 2**20:.0f} MiB "
                f"(blocks double-buffered + scratch)")
    return findings


# ----------------------------------------------------------- class fit -----

def check_class_fit(need: ClassNeed, sc: ShapeClass,
                    policy: ShapePolicy = ShapePolicy()) -> List[Finding]:
    """Legality oracle: may ``need`` be served out of class ``sc``?

    Deliberately re-derives the waste bounds instead of delegating to
    `class_fits`, then ALSO cross-checks against it — if the two ever
    disagree, the runtime fit logic regressed (or this oracle did), and
    either way the lint should fail loudly.
    """
    loc = sc.summary()
    findings: List[Finding] = []

    def err(rule: str, msg: str) -> None:
        findings.append(Finding("kernel", rule, "error", loc, msg))

    slack = policy.fit_slack
    if need.ell_units > sc.ell_units or need.ell_kmax > sc.ell_kmax:
        err("class-capacity",
            f"need (Kmax={need.ell_kmax}, units={need.ell_units}) "
            f"overflows class (Kmax={sc.ell_kmax}, units={sc.ell_units})")
    if need.ell_units:
        if sc.ell_kmax > slack * need.ell_kmax:
            err("slab-width",
                f"class slab Kmax={sc.ell_kmax} > {slack}x the member's "
                f"widest unit K={need.ell_kmax}: every unit's masked "
                f"tail becomes dead trips")
        # padded-MAC amortization: the banded kernel executes each
        # capacity slot at its band's K width, so banded MACs beyond
        # slack*Kmax*need_units + granule*Kmax is work the member can
        # never amortize
        class_macs = sum(k * n for k, n in sc.bands)
        budget = (slack * sc.ell_kmax * need.ell_units
                  + policy.unit_granule * sc.ell_kmax)
        if class_macs > budget:
            err("mac-amortization",
                f"class runs {class_macs} banded MAC slots/row for a "
                f"member needing {need.ell_units} units: padded-MAC "
                f"budget allows at most {budget:.0f} (slack={slack}, "
                f"granule={policy.unit_granule})")
        # band slot dominance: unit i of the member must fit the K of
        # class slot i (pad_to_class keeps unit order)
        profile = (need.ell_band_profile
                   or ((need.ell_kmax, need.ell_units),))
        slots = np.repeat([k for k, _ in sc.bands],
                          [n for _, n in sc.bands]).astype(np.int64)
        needs = np.repeat([k for k, _ in profile],
                          [n for _, n in profile]).astype(np.int64)
        if needs.size > slots.size:
            err("band-slot",
                f"member has {needs.size} units but the class bands "
                f"expose {slots.size} slots")
        elif needs.size and not (needs <= slots[: needs.size]).all():
            bad = int(np.flatnonzero(needs > slots[: needs.size])[0])
            err("band-slot",
                f"member unit {bad} (K={int(needs[bad])}) exceeds class "
                f"band slot K={int(slots[bad])}")
    oracle_ok = not findings
    runtime_ok = class_fits(need, sc, policy)
    # The oracle only covers the ELL waste bounds; runtime class_fits
    # also checks tile/dense/coo fields. Disagreement in the direction
    # "oracle rejects but runtime accepts" is the dangerous one.
    if not oracle_ok and runtime_ok:
        err("fit-oracle-drift",
            "class_fits accepts a fit the static waste bounds reject — "
            "runtime fit logic and the lint oracle have drifted")
    return findings


# ------------------------------------------------------ repo-level run -----

def contracts_for_class(sc: ShapeClass, f_widths: Sequence[int],
                        bf: int = DEFAULT_BF, **tune) -> List[tuple]:
    """(contract, scalar_args) pairs the engine would launch for ``sc``
    at each feature width, with worst-case scalar stand-ins: every unit
    addressing the LAST B tile at its band slot's FULL K width.
    Extra ``tune`` kwargs (``buffer_depth``, ``gu``, ``max_bands``)
    build the contract a tuned launch would use — the autotuner audits
    candidates through exactly this path."""
    out = []
    for f in f_widths:
        if sc.ell_units and sc.ell_kmax:
            c = ragged_ell_contract(sc.ell_units, sc.r_block, sc.ell_kmax,
                                    sc.n_col_tiles, sc.tile, f, bf=bf,
                                    segments=sc.bands, **tune)
            tile_col = np.full((sc.ell_units,), sc.n_col_tiles - 1, np.int32)
            unit_k = np.repeat(
                [k for k, _ in sc.bands],
                [n for _, n in sc.bands]).astype(np.int32)
            out.append((c, (tile_col, unit_k)))
    return out


def run_kernel_pass(engine=None, *, backend: str = "tpu",
                    policy: Optional[ShapePolicy] = None) -> List[Finding]:
    """Repo-level entry: audit every contract the fixture engine's
    registered classes imply, the default dense-matmul contract, and
    every (member, class) fit in the engine."""
    from repro.analysis.static.fixtures import (FIXTURE_F_HID, FIXTURE_F_IN,
                                                fixture_engine)
    if engine is None:
        engine = fixture_engine(backend="xla")
    policy = policy or engine.policy
    findings: List[Finding] = []
    f_widths = (FIXTURE_F_IN, FIXTURE_F_HID, 128)
    seen = set()
    for h in engine._graphs.values():
        if h.sclass not in seen:
            seen.add(h.sclass)
            for contract, scalars in contracts_for_class(h.sclass, f_widths):
                findings.extend(check_contract(contract,
                                               scalar_args=scalars,
                                               backend=backend))
        if h.need is not None:
            findings.extend(check_class_fit(h.need, h.sclass, policy))
    # the dense weight-GEMM / blocked matmul contract at its defaults
    # and at a representative padded class size
    for m, k, n in ((512, 512, 512), (2048, 1024, 256)):
        findings.extend(check_contract(matmul_contract(m, k, n),
                                       backend=backend))
    return findings
