"""Table I graphs synthesized at their published size.

The program's synthesis (stochastic block model with power-law
degrees, then the paper's normalized adjacency ``D^-1/2 (A + I)
D^-1/2``), kept with the benchmark so that the graphs a cell measures
cannot change with the program, and drawn to the published graph's
exact edge count, where the program's copy merges repeated draws.
Features are binary at a fixed density, drawn from the run's seed.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def sbm_graph(n: int, n_edges: int, *, seed: int,
              intra_frac: float = 0.9) -> sp.csr_matrix:
    """Undirected SBM with power-law-ish degrees and exactly ``n_edges``
    directed nonzeros (``n_edges // 2`` distinct undirected edges),
    communities of ~112 vertices, no self loops. Pairs are drawn in
    rounds until that many distinct ones exist; a repeated pair or a
    self loop is drawn again, and the first ``n_edges // 2`` distinct
    pairs in draw order are kept."""
    rng = np.random.default_rng(seed)
    n_communities = max(n // 112, 2)
    comm = rng.integers(0, n_communities, n)
    m = n_edges // 2
    w = (np.arange(n) + 2.0) ** -0.8
    rng.shuffle(w)
    w /= w.sum()
    order = np.argsort(comm, kind="stable")
    comm_sorted = comm[order]
    starts = np.searchsorted(comm_sorted, np.arange(n_communities))
    ends = np.searchsorted(comm_sorted, np.arange(n_communities),
                           side="right")
    keys = np.empty(0, np.int64)          # lo * n + hi, in draw order
    while keys.size < m:
        draw = max(2 * (m - keys.size), 1024)
        n_intra = int(draw * intra_frac)
        src = rng.choice(n, size=n_intra, p=w)
        cs = comm[src]
        lo, hi = starts[cs], ends[cs]
        dst = order[(lo + rng.random(n_intra) * (hi - lo)).astype(np.int64)]
        src2 = rng.choice(n, size=draw - n_intra, p=w)
        dst2 = rng.integers(0, n, draw - n_intra)
        mix = rng.permutation(draw)
        a = np.concatenate([src, src2])[mix]
        b = np.concatenate([dst, dst2])[mix]
        keep = a != b
        new = (np.minimum(a, b) * n + np.maximum(a, b))[keep]
        keys = np.concatenate([keys, new.astype(np.int64)])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:m]
    rows, cols = keys // n, keys % n
    a = sp.coo_matrix((np.ones(2 * m, np.float32),
                       (np.concatenate([rows, cols]),
                        np.concatenate([cols, rows]))),
                      shape=(n, n)).tocsr()
    a.sort_indices()
    return a


def normalized_adjacency(a: sp.csr_matrix) -> sp.csr_matrix:
    """The paper's A_tilde = D^-1/2 (A + I) D^-1/2, float32 CSR."""
    n = a.shape[0]
    abar = (a + sp.eye(n, format="csr", dtype=np.float32)).tocsr()
    deg = np.asarray(abar.sum(axis=1)).ravel()
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    out = (sp.diags(dinv) @ abar @ sp.diags(dinv)).tocsr().astype(np.float32)
    out.sort_indices()
    return out


def make_graph(spec: dict, graph_seed: int) -> sp.csr_matrix:
    """A_tilde of one graph of a configuration: ``n_vertices`` and
    ``n_edges`` (directed nonzeros of A, each undirected edge twice)
    from ``spec``; the structure depends on ``graph_seed`` alone, so
    every run of a configuration registers the same graph."""
    return normalized_adjacency(sbm_graph(
        int(spec["n_vertices"]), int(spec["n_edges"]), seed=graph_seed))


def make_features(n: int, f: int, density: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Binary float32 features [n, f] with ``density`` ones."""
    return (rng.random((n, f), dtype=np.float32) < density).astype(np.float32)
