"""Jitted public wrapper for the tc_tile kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .tc_tile import tile_triple_counts

__all__ = ["tile_pair_count"]


def tile_pair_count(
    triples, a_tiles, b_tiles, m_tiles, *, mode="popcount", interpret
):
    """Total masked-intersection count for one block pair.

    Sums the per-triple partial counts produced by the kernel.  ``mode``
    selects the VPU popcount path or the MXU unpack-matmul path (identical
    results; the roofline decides which wins on hardware).
    """
    per = tile_triple_counts(
        triples, a_tiles, b_tiles, m_tiles, mode=mode, interpret=interpret
    )
    return jnp.sum(per, dtype=jnp.int32)
