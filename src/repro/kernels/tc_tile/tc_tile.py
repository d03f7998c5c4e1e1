"""Pallas TPU kernel: bit-packed tile set-intersection for triangle counting.

The paper's inner loop — "hash Adj(v_j), probe Adj(v_i), count hits with
k > j" — becomes, on TPU, a *bitmap tile* operation (DESIGN.md §2): the
adjacency fragments of 128 consecutive local rows are packed into a
128x128-bit tile (4 uint32 words per row).  For an active triple
(A-tile (ti,tk), B-tile (tj,tk), mask-tile (ti,tj)) the contribution is::

    sum_{i, j} M[i, j] * popcount(A_bits[i, :] & B_bits[j, :])

Two compute modes, selected statically:

* ``mode="popcount"`` — VPU integer path: AND + population count, one
  32-bit word of k at a time.
  A bitmap is a collision-free hash table, so this is the paper's "direct
  bitwise AND without probing" optimization promoted to the only mode.
* ``mode="mxu"``      — unpack both tiles to ``bf16`` 0/1 matrices and use
  the MXU: ``counts = A ⋅ Bᵀ`` (exact: partial sums ≤ 128 < 2^8, fp32
  accumulation).  Preferable when tiles are dense enough that the matmul
  beats 4-word popcounting.

The grid runs over a *scalar-prefetched* list of active tile triples
(the doubly-compressed sparsity structure computed by the planner):
``triples[g] = (a_slot, b_slot, m_slot, valid)``.  ``BlockSpec`` index maps
read the prefetched slots so only live tiles are ever staged into VMEM.

B tiles enter word-major (``(W, T)``, transposed by the wrapper) so the
popcount mode reads word k of every row j as one lane row and the MXU
mode unpacks them straight into the ``(k, j)`` operand.  Each grid
step's count is stored through :mod:`repro.kernels.step_counts`.

VMEM working set per grid step: 3 x 128x4 uint32 tiles (6 KiB) + a few
128x128 int32/fp32 intermediates (64 KiB each) — comfortably within
v5e's ~16 MiB scoped VMEM with full double-buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ..step_counts import (
    block_sum,
    step_counts,
    step_counts_out,
    store_step_count,
)

TILE = 128
WORDS = TILE // 32

__all__ = ["tile_triple_counts", "TILE", "WORDS", "unpack_bits_tile"]


def unpack_bits_tile(words, dtype=jnp.bfloat16):
    """(T, W) uint32 -> (T, T) 0/1 matrix; column c = bit c%32 of word c//32."""
    t, w = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(t, w * 32).astype(dtype)


def _bits_along_lanes(words):
    """(T, W) words -> (T, 32W) int32 0/1 with column c = bit c%32 of word
    c//32 — built from lane broadcasts, shifts and selects only (Mosaic
    has no lane-merging reshape)."""
    t, w = words.shape
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, 32 * w), 1)
    sel = jnp.broadcast_to(words[:, 0:1], (t, 32 * w))
    for k in range(1, w):
        sel = jnp.where(col // 32 == k, words[:, k : k + 1], sel)
    return jax.lax.shift_right_logical(sel, col % 32) & 1


def _bits_along_sublanes(words_t):
    """(W, T) words -> (32W, T) int32 0/1 with row c = bit c%32 of word
    c//32: the transpose of :func:`_bits_along_lanes`."""
    w, t = words_t.shape
    words_t = jax.lax.bitcast_convert_type(words_t, jnp.int32)
    row = jax.lax.broadcasted_iota(jnp.int32, (32 * w, t), 0)
    sel = jnp.broadcast_to(words_t[0:1, :], (32 * w, t))
    for k in range(1, w):
        sel = jnp.where(row // 32 == k, words_t[k : k + 1, :], sel)
    return jax.lax.shift_right_logical(sel, row % 32) & 1


def _kernel_popcount(triples_ref, a_ref, bt_ref, m_ref, out_ref):
    g = pl.program_id(0)
    valid = triples_ref[4 * g + 3] > 0
    a = a_ref[0]  # (T, W) uint32 — rows i, k-bits
    bt = bt_ref[0]  # (W, T) uint32 — k-words x rows j
    # per (i, j): popcount over the k-words of (A_i & B_j), one word at
    # a time so the live intermediate stays (T, T)
    counts = jnp.zeros((TILE, TILE), jnp.int32)
    for k in range(WORDS):
        both = a[:, k : k + 1] & bt[k : k + 1, :]
        counts = counts + jax.lax.population_count(both).astype(jnp.int32)
    mask = _bits_along_lanes(m_ref[0])  # (T, T) over (i, j)
    store_step_count(out_ref, jnp.where(valid, block_sum(counts * mask), 0))


def _kernel_mxu(triples_ref, a_ref, bt_ref, m_ref, out_ref):
    g = pl.program_id(0)
    valid = triples_ref[4 * g + 3] > 0
    # bits go through int32 and f32 to bf16: Mosaic refuses a direct
    # uint32 -> bf16 cast
    a = _bits_along_lanes(a_ref[0]).astype(jnp.float32).astype(jnp.bfloat16)
    bt = _bits_along_sublanes(bt_ref[0]).astype(jnp.float32).astype(
        jnp.bfloat16
    )  # (T, T) k x j
    counts = jax.lax.dot_general(
        a,
        bt,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (T, T) exact integers (<= 128 per entry)
    mask = _bits_along_lanes(m_ref[0]).astype(jnp.float32)
    total = block_sum(counts * mask).astype(jnp.int32)
    store_step_count(out_ref, jnp.where(valid, total, 0))


@functools.partial(
    jax.jit, static_argnames=("mode", "interpret")
)
def tile_triple_counts(
    triples, a_tiles, b_tiles, m_tiles, *, mode="popcount", interpret
):
    """Per-triple masked intersection counts.

    Args:
      triples: (G, 4) int32 — (a_slot, b_slot, m_slot, valid).
      a_tiles/b_tiles/m_tiles: (N*, T, W) uint32 packed tile stores.
      mode: "popcount" (VPU) or "mxu".
      interpret: run the kernel body in interpret mode (CPU validation);
        on TPU pass ``interpret=False``.  No default: the caller decides.

    Returns: (G,) int32 per-triple counts (sum for the block-pair total).
    """
    g = triples.shape[0]
    kern = _kernel_popcount if mode == "popcount" else _kernel_mxu
    out_shape, out_spec = step_counts_out(g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, TILE, WORDS), lambda i, t: (t[4 * i], 0, 0)),
            # B tiles word-major, so word k of every row j is one lane row
            pl.BlockSpec((1, WORDS, TILE), lambda i, t: (t[4 * i + 1], 0, 0)),
            pl.BlockSpec((1, TILE, WORDS), lambda i, t: (t[4 * i + 2], 0, 0)),
        ],
        out_specs=out_spec,
    )
    # flat: SMEM pads a 2-D array's last dim to 128 words
    flat = triples.reshape(-1).astype(jnp.int32)
    b_t = jnp.swapaxes(b_tiles, 1, 2)
    # traced with x64 off: Mosaic has no 64-bit types, and under x64 the
    # body's Python ints would trace as int64
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name="tc_count_tile",
        )(flat, a_tiles, b_t, m_tiles)
    return step_counts(out, g)
