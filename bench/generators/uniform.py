"""GAP's Urand graph: ``edge_factor * 2**scale`` edge samples with both
endpoints drawn uniformly, as the GAP generator makes it (self loops and
duplicates are then dropped, nothing is cut).

The program's ``erdos_renyi(n, 16)`` draws the same samples from the same
seed, then keeps only the first ``8 n`` edges in sorted order, which
favours low vertex ids; this generator keeps them all.
"""
from __future__ import annotations

import numpy as np


def sample(cfg: dict, rng: np.random.Generator):
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    return n, src, dst
