"""Pass 1 — jaxpr analyzer: structural proofs over the traced dispatch.

Traces the engine's real executors (`ExecutorCache.gcn` over the fixture
graph — tracing only, nothing compiles or runs) and checks:

- **single-launch**: ragged dispatch mode collapses each SpMM's ELL work
  into exactly ONE ``pallas_call`` of the ragged kernel — per GCN layer,
  one ragged launch and zero legacy fixed-K launches. The pre-ragged
  layout's one-launch-per-K regression would show up here before any
  kernel runs.
- **no-host-sync**: the traced region of ``serve_group_async`` (the
  executor jaxpr) must contain no callback/transfer primitives — a
  ``debug_callback`` or ``device_put`` inside the trace would stall the
  async dispatch pipeline on every batch.
- **dtype/shape flow**: the executor traces at exactly the shapes
  ``prepare_x`` produces (class-padded input rows), emits float32
  logits of the class's padded row count, and no float64/complex aval
  appears anywhere in the trace; every member's true ``n_rows`` must be
  coverable by the class output (the unpad slice reads garbage
  otherwise).
- **sentinel-safety**: a static proof that padded ELL lanes cannot
  reach live output rows. Two halves: (a) layout — the scatter sentinel
  row equals ``n_padded_rows`` (one past the last live row, sliced off)
  and every dead unit (``unit_k == 0``) targets only sentinel rows with
  all-zero padded values; (b) kernel — an abstract interpretation of
  the ragged kernel's jaxpr under the *dead-unit state* (every scalar-
  prefetch read returns 0) proving the value stored to the output ref
  is identically zero **without assuming anything about the cols/vals
  data**. That is exactly the masked-FMA structure: if the
  ``kk < unit_k`` mask is dropped, the store value becomes unprovable
  and the check fails — the static form of the bitwise padding tests in
  ``tests/test_ragged_ell.py``.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax
from jax.extend import core as jex_core

from repro.analysis.static.report import Finding

# Primitives that would force host synchronization (or host round-trips)
# inside the traced region of ``serve_group_async``.
FORBIDDEN_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "debug_print", "infeed", "outfeed", "device_put",
})

# Launch names (the ``name=`` each kernel wrapper passes to pallas_call).
RAGGED_KERNEL = "ragged_ell_spmm"
FIXED_KERNEL = "ell_spmm"


# -------------------------------------------------------- jaxpr walking -----

def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (jit bodies,
    cond branches, pallas kernel bodies, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for sub in _as_jaxprs(val):
            yield sub


def _as_jaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif hasattr(val, "eqns"):           # a raw Jaxpr (pallas kernel body)
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _as_jaxprs(item)


def pallas_eqns(closed) -> list:
    return [e for e in iter_eqns(closed.jaxpr)
            if e.primitive.name == "pallas_call"]


def kernel_name(eqn) -> str:
    return eqn.params["name"]


# ---------------------------------------------- dead-lane abstract interp ----

# Abstract values: ("int", v) known scalar int, ("bool", b) known bool,
# "zero" provably all-zero array/scalar, None unknown.
ZERO = "zero"

_PROPAGATE = frozenset({
    "broadcast_in_dim", "convert_element_type", "reshape", "squeeze",
    "expand_dims", "transpose", "slice", "dynamic_slice", "copy", "neg",
    "reduce_sum", "rev",
})


def _abs_literal(val):
    arr = np.asarray(val)
    if arr.dtype == bool and arr.size == 1:
        return ("bool", bool(arr.reshape(-1)[0]))
    if np.issubdtype(arr.dtype, np.integer) and arr.size == 1:
        return ("int", int(arr.reshape(-1)[0]))
    if arr.size == 0 or not np.any(arr):
        return ZERO
    return None


def _is_zero(v) -> bool:
    return v == ZERO or v == ("int", 0)


def _cmp(prim: str, a, b):
    if not (isinstance(a, tuple) and a[0] == "int"
            and isinstance(b, tuple) and b[0] == "int"):
        return None
    x, y = a[1], b[1]
    return ("bool", {"gt": x > y, "lt": x < y, "ge": x >= y,
                     "le": x <= y, "eq": x == y, "ne": x != y}[prim])


class DeadLaneInterp:
    """Abstract interpreter for one pallas kernel jaxpr under the
    dead-unit state: every scalar-prefetch read yields 0 (padded units
    carry ``unit_k == 0`` and ``tile_col == 0``), all tensor operands
    stay unknown. Collects the abstract value of every store to an
    output ref."""

    def __init__(self, kernel_jaxpr, grid_mapping):
        nsp = grid_mapping.num_index_operands
        nin = grid_mapping.num_inputs
        nout = grid_mapping.num_outputs
        invars = kernel_jaxpr.invars
        self.scalar_refs = set(invars[:nsp])
        self.out_refs = set(invars[nsp + nin: nsp + nin + nout])
        self.jaxpr = kernel_jaxpr
        self.stores: list = []       # abstract values stored to out refs

    def run(self) -> None:
        self._eval(self.jaxpr, {})

    def _read(self, env, atom):
        if isinstance(atom, jex_core.Literal):
            return _abs_literal(atom.val)
        return env.get(atom)

    def _eval(self, jaxpr, env) -> None:
        for eqn in jaxpr.eqns:
            vals = [self._read(env, a) for a in eqn.invars]
            out = self._apply(eqn, vals, env)
            for var in eqn.outvars:
                env[var] = out

    def _apply(self, eqn, vals, env):
        prim = eqn.primitive.name
        if prim == "get":
            ref = eqn.invars[0]
            return ("int", 0) if ref in self.scalar_refs else None
        if prim in ("swap", "addupdate"):
            ref = eqn.invars[0]
            if ref in self.out_refs:
                self.stores.append((vals[1], eqn))
            return None
        sub = [s for s in _sub_jaxprs(eqn)]
        if sub and prim == "cond":
            # lax.switch / lax.cond — the ragged kernel's K-band selector.
            # invars[0] is the branch index, the rest feed every branch.
            # An output is provably zero iff EVERY branch's output at
            # that position is zero under the dead-unit state (each band
            # chain is the same masked FMA at a different trip count).
            per_branch = []
            for inner in sub:
                for op, iv in zip(eqn.invars[1:], inner.invars):
                    if (not isinstance(op, jex_core.Literal)
                            and op in self.scalar_refs):
                        self.scalar_refs.add(iv)
                sub_env = dict(zip(inner.invars, vals[1:]))
                self._eval(inner, sub_env)
                per_branch.append(
                    [self._read(sub_env, v) for v in inner.outvars])
            which = vals[0]
            if (isinstance(which, tuple) and which[0] == "int"
                    and 0 <= which[1] < len(per_branch)):
                outs = per_branch[which[1]]
            else:
                outs = [ZERO if all(_is_zero(v) for v in pos)
                        else (pos[0] if len(set(map(repr, pos))) == 1
                              else None)
                        for pos in zip(*per_branch)]
            if len(outs) == 1:
                return outs[0]
            return outs[0] if len(set(map(repr, outs))) == 1 else None
        if sub and prim in ("jit", "closed_call", "custom_jvp_call",
                            "custom_vjp_call", "remat", "checkpoint"):
            inner = sub[0]
            sub_env = dict(zip(inner.invars, vals))
            self._eval(inner, sub_env)
            outs = [self._read(sub_env, v) for v in inner.outvars]
            # jaxpr eqns are single-valued abstractly here; a multi-out
            # call collapses to its first out unless all agree
            if len(outs) == 1:
                return outs[0]
            return outs[0] if len(set(map(repr, outs))) == 1 else None
        if prim in _PROPAGATE:
            return vals[0]
        if prim in ("mul", "dot_general", "and"):
            return ZERO if any(_is_zero(v) for v in vals) else None
        if prim in ("add", "sub", "or", "add_any", "max", "min"):
            return ZERO if all(_is_zero(v) for v in vals) else None
        if prim in ("gt", "lt", "ge", "le", "eq", "ne"):
            return _cmp(prim, vals[0], vals[1])
        if prim == "select_n":
            which, cases = vals[0], vals[1:]
            if isinstance(which, tuple) and which[0] == "bool":
                return cases[int(which[1])]
            if all(_is_zero(c) for c in cases):
                return ZERO
            return None
        if prim in ("gather", "take"):
            return ZERO if vals[0] == ZERO else None
        return None


def check_dead_lanes(eqn) -> List[Finding]:
    """Prove one ragged pallas_call's output is zero for a dead unit."""
    name = kernel_name(eqn)
    interp = DeadLaneInterp(eqn.params["jaxpr"],
                            eqn.params["grid_mapping"])
    interp.run()
    findings: List[Finding] = []
    if not interp.stores:
        findings.append(Finding(
            "jaxpr", "sentinel-safety", "error", name,
            "no store to an output ref found — cannot prove dead lanes"))
    for val, store_eqn in interp.stores:
        if val != ZERO:
            findings.append(Finding(
                "jaxpr", "sentinel-safety", "error", name,
                f"store via {store_eqn.primitive.name} is not provably "
                f"zero under the dead-unit state (unit_k==0): a padded "
                f"ELL lane could reach live output rows — is the "
                f"kk < unit_k value mask intact?"))
    return findings


# --------------------------------------------------------------- checks -----

def check_single_launch(closed, n_layers: int,
                        label: str = "gcn") -> List[Finding]:
    """Ragged mode: one ragged ELL launch per layer, zero fixed-K ones."""
    names = [kernel_name(e) for e in pallas_eqns(closed)]
    ragged = names.count(RAGGED_KERNEL)
    fixed = names.count(FIXED_KERNEL)
    findings: List[Finding] = []
    if ragged != n_layers:
        findings.append(Finding(
            "jaxpr", "single-launch", "error", label,
            f"expected {n_layers} ragged ELL launch(es) "
            f"(one per SpMM), traced {ragged}: {names}"))
    if fixed:
        findings.append(Finding(
            "jaxpr", "single-launch", "error", label,
            f"{fixed} legacy fixed-K ELL launch(es) in ragged mode: "
            f"{names}"))
    return findings


def check_no_host_sync(closed, label: str) -> List[Finding]:
    hits = [(e.primitive.name, e) for e in iter_eqns(closed.jaxpr)
            if e.primitive.name in FORBIDDEN_PRIMS]
    return [Finding(
        "jaxpr", "no-host-sync", "error", label,
        f"forbidden primitive {name!r} inside the traced dispatch "
        f"region — this host-syncs every async batch")
        for name, _ in hits]


def check_dtype_flow(closed, *, n_in_rows: int, n_out_rows: int,
                     f_out: int, label: str) -> List[Finding]:
    findings: List[Finding] = []

    def err(rule, msg):
        findings.append(Finding("jaxpr", rule, "error", label, msg))

    for eqn in iter_eqns(closed.jaxpr):
        for var in eqn.outvars:
            dt = getattr(var.aval, "dtype", None)
            if dt is not None and dt in (np.float64, np.complex64,
                                         np.complex128):
                err("dtype-flow", f"{dt} aval in trace at "
                    f"{eqn.primitive.name} — breaks f32 kernel parity")
                break
    outs = closed.jaxpr.outvars
    if len(outs) != 1:
        err("shape-flow", f"executor emits {len(outs)} outputs, want 1")
    else:
        aval = outs[0].aval
        if tuple(aval.shape) != (n_out_rows, f_out):
            err("shape-flow",
                f"executor output {tuple(aval.shape)} != class-padded "
                f"({n_out_rows}, {f_out})")
        elif aval.dtype != np.float32:
            err("dtype-flow", f"executor output dtype {aval.dtype}, "
                f"want float32")
    x_avals = [v.aval for v in closed.jaxpr.invars
               if getattr(v.aval, "ndim", 0) == 2
               and v.aval.shape[0] == n_in_rows]
    if not x_avals:
        err("shape-flow",
            f"no executor input matches prepare_x's padded row count "
            f"{n_in_rows} — padding and trace shapes drifted")
    return findings


def check_sentinel_layout(handle) -> List[Finding]:
    """Static layout facts the scatter's slice-off depends on."""
    findings: List[Finding] = []
    loc = f"graph:{handle.name}"

    def err(msg):
        findings.append(Finding("jaxpr", "sentinel-safety", "error",
                                loc, msg))

    meta = handle.padded_meta
    if meta.ell_sentinel_row != meta.n_padded_rows:
        err(f"sentinel row {meta.ell_sentinel_row} != n_padded_rows "
            f"{meta.n_padded_rows}: padding writes would land INSIDE "
            f"the live slice")
    if handle.meta.n_rows > meta.n_padded_rows:
        err(f"true n_rows {handle.meta.n_rows} exceeds class-padded "
            f"rows {meta.n_padded_rows}: the unpad slice truncates "
            f"live rows")
    ell = handle.part.ell
    uk = np.asarray(ell.unit_k)
    if uk.size:
        rows = np.asarray(ell.rows)
        vals = np.asarray(ell.vals)
        dead = uk == 0
        if dead.any() and not (rows[dead] == meta.ell_sentinel_row).all():
            err("a dead unit (unit_k==0) targets a non-sentinel row")
        kmax = vals.shape[-1]
        kk = np.arange(kmax)[None, None, :]
        padded_lane = kk >= uk[:, None, None]
        if vals[np.broadcast_to(padded_lane, vals.shape)].any():
            err("non-zero values in masked lanes (kk >= unit_k): fused "
                "dispatch bitwise parity relies on zero padding")
        live_rows = rows[~dead] if (~dead).any() else rows[:0]
        if live_rows.size and (live_rows.max() > meta.ell_sentinel_row
                               or live_rows.min() < 0):
            err("live unit row ids outside [0, sentinel]")
    return findings


# ------------------------------------------------------ repo-level run -----

def trace_gcn_executor(engine, name: str):
    """jaxpr of the executor ``serve_group_async`` would dispatch for
    one request on ``name`` (trace only; nothing compiles)."""
    from repro.analysis.static.fixtures import fixture_x
    h = engine.handle(name)
    w_shapes = tuple(tuple(w.shape) for w in h.weights)
    f_in = int(h.weights[0].shape[0])
    fn = engine.executors.gcn(h.sclass, f_in, w_shapes)
    x = engine.prepare_x(name, fixture_x(h.meta.n_cols, f_in))
    return jax.make_jaxpr(fn)(h.part, x, h.weights), h


def run_jaxpr_pass(engine=None, name: str = "lint-fixture") -> List[Finding]:
    """Repo-level entry: trace the fixture engine's pallas dispatch path
    and run every structural check."""
    from repro.analysis.static.fixtures import fixture_engine
    if engine is None:
        engine = fixture_engine(backend="pallas")
    closed, h = trace_gcn_executor(engine, name)
    n_layers = len(h.weights)
    findings = []
    findings += check_single_launch(closed, n_layers)
    findings += check_no_host_sync(closed, label="gcn-executor")
    findings += check_dtype_flow(
        closed,
        n_in_rows=h.sclass.n_col_tiles * h.sclass.tile,
        n_out_rows=h.padded_meta.n_padded_rows,
        f_out=int(h.weights[-1].shape[1]),
        label="gcn-executor")
    findings += check_sentinel_layout(h)
    ragged = [e for e in pallas_eqns(closed)
              if kernel_name(e) == RAGGED_KERNEL]
    if ragged:
        findings += check_dead_lanes(ragged[0])
    elif h.sclass.ell_units:
        findings.append(Finding(
            "jaxpr", "sentinel-safety", "error", "gcn-executor",
            "class has ELL units but no ragged launch traced — "
            "cannot run the dead-lane proof"))
    return findings
