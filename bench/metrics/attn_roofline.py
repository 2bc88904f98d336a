"""The GAT attention's share of its roofline, in %: for the requests
answered in the traced window, the least time the chip needs for the
attention's work, ``max(FLOPs / peak bf16 FLOP/s, bytes / peak HBM
bytes/s)`` a request from ``attn_cost`` of ``models/gat.py`` (a lower
bound, so the share stays under 100%), over the device time of the
three scopes that do that work, ``attn.dense``, ``attn.ell`` and
``attn.coo`` (`bench.lib.marks`).

A `Run` carries no graph, so this reader takes N (``n_vertices``) and
A_tilde's nnz (``n_edges + n_vertices``: the edges and one self loop a
vertex) from ``configs/gat.pubmed.json``, the one configuration whose
cell lists it.
"""
from bench.lib import harness, marks, peaks

SCOPES = ("attn.dense", "attn.ell", "attn.coo")
CONFIG = "gat.pubmed"


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    spent = sum(m.scope_s(s) or 0.0 for s in SCOPES)
    if spent <= 0:
        return None
    import jax
    config = harness.load_json(harness.BENCH_DIR, "configs", CONFIG)
    model = harness.load_model(harness.BENCH_DIR, config)
    (spec,) = config["graphs"]
    n = spec["n_vertices"]
    fl, by = model.attn_cost(n, spec["n_edges"] + n, config["model"], spec)
    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    least = max(fl / peak.bf16_flops, by / peak.hbm_bytes)
    return 100.0 * len(run.answered) * least / spent
