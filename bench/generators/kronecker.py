"""GAP's Kron graph: the Graph500 Kronecker (R-MAT) sampler.

``edge_factor * 2**scale`` directed samples; each picks one quadrant of
the ``initiator`` ``(a, b, c, d)`` per bit level, the first bit of the
quadrant going to the source and the second to the destination.  The
initiator is the same at every level (no Graph500 per-level noise) and
no vertex permutation is applied here: the same sampling, draw for
draw, as the program's ``rmat`` generator.
"""
from __future__ import annotations

import numpy as np


def sample(cfg: dict, rng: np.random.Generator):
    scale = int(cfg["scale"])
    n = 1 << scale
    m = int(cfg["edge_factor"]) * n
    a, b, c, _ = cfg["initiator"]
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        u = rng.random(m)
        quad = (u >= a).astype(np.int64) + (u >= a + b) + (u >= a + b + c)
        src |= (quad >> 1) << level
        dst |= (quad & 1) << level
    return n, src, dst
