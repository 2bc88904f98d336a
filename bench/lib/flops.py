"""Operations of one GCN request, from the unpadded shapes.

Per layer: ``2 N F_in F_out`` for the combination ``X W`` and
``2 nnz F_out`` for the aggregation ``A_tilde (X W)``, where ``nnz``
counts the nonzeros of A_tilde (self loops included). Padding, shape
class headroom and the program's partition never count as work.
"""
from __future__ import annotations


def gcn_flops(n_vertices: int, nnz: int, widths) -> float:
    """FLOPs of one forward; ``widths`` = [f_in, hidden..., classes]."""
    total = 0.0
    for f_in, f_out in zip(widths[:-1], widths[1:]):
        total += 2.0 * n_vertices * f_in * f_out + 2.0 * nnz * f_out
    return total
