"""HLO/jaxpr trace inspection: collective ops, bytes, kernel launches.

``compiled.cost_analysis()`` has no collective-byte entry, so we parse the
optimized HLO: every all-reduce / all-gather / reduce-scatter / all-to-all
/ collective-permute op, with bytes computed from the result (and operand)
array shapes and ring-algorithm traffic factors. ``count_pallas_calls``
walks a traced jaxpr instead — the launch-count oracle for the ragged
single-launch ELL guarantee (tests + benchmarks share it).
"""
from __future__ import annotations

import dataclasses
import re


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` eqns in a jaxpr, including sub-jaxprs.

    Accepts an open ``Jaxpr`` (``jax.make_jaxpr(fn)(x).jaxpr``); walks
    every nested jaxpr (jit bodies, control flow branches, ...) with the
    static analyzer's walker.
    """
    from repro.analysis.static.jaxpr_pass import iter_eqns
    return sum(1 for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == "pallas_call")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64"
                       r"|f64|c64|c128)\[([0-9,]*)\]")

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# ring-algorithm bytes-on-wire per participating device, as a multiple of
# the per-device *result/operand* size (n = group size; n-1/n ~ 1):
#   all-reduce: 2x (reduce-scatter + all-gather phases)
#   all-gather: 1x result-shard gathered from others ~ result bytes
#   reduce-scatter: 1x operand bytes
#   all-to-all: 1x operand bytes
#   collective-permute: 1x operand bytes
_TRAFFIC_FACTOR = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}


def shape_bytes(text: str) -> int:
    """Sum of sizes of every array literal in an HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    traffic_bytes: float
    line: str


def parse_collectives(hlo_text: str) -> list:
    """Extract collectives from optimized HLO module text."""
    out = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start|-done)?\(", ls)
        if not m:
            continue
        if "-done(" in ls:
            continue  # count the -start only (async pairs)
        result_type, kind = m.groups()
        rb = shape_bytes(result_type)
        out.append(CollectiveOp(kind, rb, rb * _TRAFFIC_FACTOR[kind], ls))
    return out


def collective_summary(hlo_text: str) -> dict:
    ops = parse_collectives(hlo_text)
    by_kind = {}
    for op in ops:
        d = by_kind.setdefault(op.kind, {"count": 0, "bytes": 0.0})
        d["count"] += 1
        d["bytes"] += op.traffic_bytes
    total = sum(d["bytes"] for d in by_kind.values())
    return {"by_kind": by_kind, "total_traffic_bytes": total,
            "n_ops": len(ops)}
