"""Perf-trajectory file schema: writers for benchmarks, checker for lint.

``BENCH_*.json`` files at the repo root record one benchmark run each so
re-anchors (and humans) can diff perf across PRs without re-running
anything. The schema is deliberately flat and tiny:

    {
      "bench":   "bench_spmm",           # which benchmark wrote it
      "schema":  2,                      # format version
      "created": "2026-08-08",           # ISO date of the run
      "command": "bench_spmm --smoke",   # how to reproduce
      "provenance": {                    # where the numbers came from
        "git_sha":     "b93d566...",     #   (schema 2: a trajectory
        "jax_version": "0.9.0",          #   point without its code +
        "backend":     "cpu"             #   runtime identity cannot be
      },                                 #   compared across PRs)
      "metrics": {"spmm.ragged_ms": 1.9, ...}   # flat str -> number
    }

``lint_repro.py --bench-check`` fails the lint if a committed trajectory
file does not parse or violates this schema — a malformed file is worse
than no file, because a future regression gate would silently skip it.
Schema 2 added the ``provenance`` block; ``write_bench_json`` collects
it automatically (a writer that cannot read the JAX backend fails
rather than record a guess), and schema-1 files fail the check until
reseeded.
"""
from __future__ import annotations

import json
import numbers
import subprocess
from pathlib import Path
from typing import List

from repro.analysis.static.report import Finding

SCHEMA_VERSION = 2

PROVENANCE_KEYS = ("git_sha", "jax_version", "backend")

# Per-bench required metric names (suffix-matched against the flat
# dotted keys): a trajectory file for that bench missing one of these
# regressed its reporting contract, not just its numbers. bench_spmm
# must carry the kernel-health trio the regression gates read.
REQUIRED_METRICS = {
    "bench_spmm": ("launches_per_spmm", "ell_pad_waste_x",
                   "achieved_roofline_frac"),
    "bench_serving": ("replica_speedup_x", "chaos_rescued", "chaos_shed"),
}


def flatten_metrics(obj, prefix: str = "") -> dict:
    """Collapse a nested results dict to flat dotted keys, numeric
    leaves only (bools and non-numeric leaves are dropped).

    >>> flatten_metrics({"a": {"b": 1.5, "note": "hi"}, "n": 3})
    {'a.b': 1.5, 'n': 3}
    """
    out: dict = {}
    if isinstance(obj, dict):
        for key, val in obj.items():
            dotted = f"{prefix}.{key}" if prefix else str(key)
            out.update(flatten_metrics(val, dotted))
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, numbers.Real):
        out[prefix] = obj
    return out


def collect_provenance() -> dict:
    """Run provenance for a trajectory file.

    Every value is a non-empty string — the schema check requires that.
    A checkout without git records ``"unknown"`` for the sha; the JAX
    version and backend are read from JAX itself, and a failure to read
    them raises: a number filed under a guessed backend is worse than
    no number.
    """
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    import jax
    return {"git_sha": sha or "unknown",
            "jax_version": jax.__version__,
            "backend": jax.default_backend()}


def write_bench_json(path, bench: str, command: str, created: str,
                     results: dict) -> dict:
    """Flatten ``results`` and write a schema-2 trajectory file
    (provenance auto-collected; callers pass only the run facts)."""
    doc = {
        "bench": bench,
        "schema": SCHEMA_VERSION,
        "created": created,
        "command": command,
        "provenance": collect_provenance(),
        "metrics": flatten_metrics(results),
    }
    Path(path).write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return doc


def check_bench_file(path) -> List[Finding]:
    """Validate one trajectory file against the schema."""
    path = Path(path)
    loc = str(path)

    def err(msg: str) -> Finding:
        return Finding("bench", "trajectory-schema", "error", loc, msg)

    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        return [err(f"unreadable or invalid JSON: {e}")]
    if not isinstance(doc, dict):
        return [err("top level must be an object")]
    findings: List[Finding] = []
    for key, typ in (("bench", str), ("created", str), ("command", str)):
        if not isinstance(doc.get(key), typ) or not doc.get(key):
            findings.append(err(f"missing or non-{typ.__name__} field "
                                f"{key!r}"))
    if doc.get("schema") != SCHEMA_VERSION:
        findings.append(err(f"schema must be {SCHEMA_VERSION}, "
                            f"got {doc.get('schema')!r}"))
    prov = doc.get("provenance")
    if not isinstance(prov, dict):
        findings.append(err("missing provenance object (schema 2: "
                            "git_sha / jax_version / backend)"))
    else:
        for key in PROVENANCE_KEYS:
            if not isinstance(prov.get(key), str) or not prov.get(key):
                findings.append(err(
                    f"provenance.{key} must be a non-empty string, "
                    f"got {prov.get(key)!r}"))
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        findings.append(err("metrics must be a non-empty object"))
    else:
        for key, val in metrics.items():
            if not isinstance(key, str):
                findings.append(err(f"metric key {key!r} is not a string"))
            if isinstance(val, bool) or not isinstance(val, numbers.Real):
                findings.append(
                    err(f"metric {key!r} must be a number, got {val!r}"))
        for want in REQUIRED_METRICS.get(doc.get("bench"), ()):
            if not any(isinstance(k, str) and k.split(".")[-1] == want
                       for k in metrics):
                findings.append(err(
                    f"bench {doc.get('bench')!r} must report a "
                    f"{want!r} metric (reporting contract regressed)"))
    return findings


def check_bench_files(root) -> List[Finding]:
    """Validate every BENCH_*.json under ``root`` (non-recursive)."""
    root = Path(root)
    findings: List[Finding] = []
    for path in sorted(root.glob("BENCH_*.json")):
        findings.extend(check_bench_file(path))
    return findings
