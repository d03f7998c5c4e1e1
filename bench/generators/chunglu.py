"""The Chung–Lu expected-degree graph (Chung & Lu, PNAS 99(25), 2002).

``edge_factor * 2**scale`` pairs; each endpoint is drawn independently
with probability ∝ ``(v + 1)^(-1/(alpha - 1))``, the expected-degree
sequence of a power law with exponent ``alpha``, so low ids are the
hubs.  Self loops and duplicates are then dropped; nothing is cut.

The program's ``powerlaw`` fixture draws endpoints by the same law but
oversamples and keeps only the first ``m`` edges in sorted order, which
favours the hubs' rows; this generator keeps every pair.
"""
from __future__ import annotations

import numpy as np


def weights(n: int, alpha: float) -> np.ndarray:
    """The endpoint law: ``p[v] ∝ (v + 1)^(-1/(alpha - 1))``."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1.0))
    return w / w.sum()


def sample(cfg: dict, rng: np.random.Generator):
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    p = weights(n, float(cfg["alpha"]))
    src = rng.choice(n, size=m, p=p)
    dst = rng.choice(n, size=m, p=p)
    return n, src, dst
