"""The benchmark's plain reference: an exact triangle count on the host.

Independent of the program: every edge is oriented from the lower to the
higher (degree, id) rank, and ``(U @ U) .* U`` is summed in row blocks
with scipy, so each triangle is counted once, at its lowest-ranked
vertex.

:func:`control_count` is the control of the comparison: the same count
with the exactness guarantee broken the way a fixed-width intersection
window would break it.  The benchmark's own runs never call it.
"""
from __future__ import annotations

import numpy as np

ROWS = 1 << 16  # rows per masked-SpGEMM block
CONTROL_QUANTILE = 0.99


def _upper(n: int, edges: np.ndarray):
    """``U``: each edge oriented from lower to higher (degree, id) rank."""
    import scipy.sparse as sp

    deg = np.bincount(edges.ravel(), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    u, v = rank[edges[:, 0]], rank[edges[:, 1]]
    return sp.csr_matrix(
        (np.ones(edges.shape[0], np.int64), (np.minimum(u, v), np.maximum(u, v))),
        shape=(n, n),
    )


def _masked_sum(upper, windows) -> int:
    """``sum((U @ W) .* W)`` in blocks of :data:`ROWS` rows: over every
    task ``(i, j)`` of ``U``, the size of the intersection of the rows
    ``W_i`` and ``W_j``."""
    total = 0
    for r0 in range(0, upper.shape[0], ROWS):
        w = windows[r0 : r0 + ROWS]
        total += int((upper[r0 : r0 + ROWS] @ windows).multiply(w).sum())
    return total


def count(n: int, edges: np.ndarray) -> int:
    """Exact triangle count of the simple graph ``(n, edges)``."""
    edges = np.asarray(edges)
    if edges.shape[0] == 0:
        return 0
    upper = _upper(n, edges)
    return _masked_sum(upper, upper)


def control_count(n: int, edges: np.ndarray) -> int:
    """The count with every oriented row cut to its first ``cap``
    neighbours, ``cap`` being the :data:`CONTROL_QUANTILE` of the
    non-empty row lengths: what a kernel with a fixed intersection width
    and no long-row fallback would count.  The triangles that only the
    tails of the longer rows close are lost."""
    edges = np.asarray(edges)
    if edges.shape[0] == 0:
        return 0
    upper = _upper(n, edges)
    lens = np.diff(upper.indptr)
    cap = int(np.quantile(lens[lens > 0], CONTROL_QUANTILE))
    pos = np.arange(upper.nnz) - np.repeat(upper.indptr[:-1], lens)
    windows = upper.copy()
    windows.data[pos >= cap] = 0
    windows.eliminate_zeros()
    return _masked_sum(upper, windows)
