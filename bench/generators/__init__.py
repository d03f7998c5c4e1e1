"""Seeded graph generators the benchmark owns.

Each module here is one generator, found by the ``generator`` key of a
configuration file: ``sample(cfg, rng)`` returns ``(n, src, dst)``, the
vertex count and the raw edge samples, which :func:`simple_edges` turns
into the simple undirected edge list the program takes.
"""
from __future__ import annotations

import numpy as np


def simple_edges(n: int, src, dst) -> np.ndarray:
    """Drop self loops and duplicates; each edge once as ``(min, max)``,
    sorted by ``min * n + max`` (the canonical form of the program's
    ``Graph.from_edges``)."""
    src = np.asarray(src, dtype=np.int64).ravel()
    dst = np.asarray(dst, dtype=np.int64).ravel()
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * np.int64(n) + hi)
    return np.stack([key // n, key % n], axis=1)
