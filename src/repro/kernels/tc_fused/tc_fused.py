"""Pallas TPU kernel: fused device-step intersection for short tasks.

One grid step processes a *tile* of ``TS`` short tasks end to end —
probe-gather, intersection, and count-accumulate fused in VMEM
(DESIGN.md §5.1) — instead of the lax path's gather → searchsorted →
segment-sum chain that round-trips every intermediate through HBM:

1. the wrapper turns the task lists into per-task fragment
   ``(start, length)`` pairs, blocked ``TS`` tasks at a time into SMEM — so
   neither the task lists nor the ``nb+1`` row pointers have to fit in
   SMEM whole;
2. both CSR index arrays stay in HBM as ``(rows, 128)`` lane rows; each
   task DMAs the ``ceil(d/128) + 1`` lane rows that cover its fragment
   into a VMEM window (every task's copy is in flight before the first
   wait);
3. each window is re-aligned with a lane rotation by ``start % 128``
   and masked to the fragment, padding with distinct sentinels (−1
   A-side / ``int32.max`` B-side, shared with ``ref.py``), into two
   ``(TS, dp)`` panels, ``dp = 128·ceil(d/128)``;
4. ``d`` column-broadcast equality passes over the panels accumulate
   the tile's triangle contribution (CSR fragments are duplicate-free,
   so equal pairs = intersection size; no searchsorted, no key encoding
   — also valid on the 1D ring's global column ids), stored per tile
   through :mod:`repro.kernels.step_counts`.

Only *short* tasks (both fragments ≤ ``d`` under the planner's maxfrag
split) come here; long rows take the chunked two-level fallback in
``ops.count_pair_fused``.  ``interpret=True`` runs the same body under
the Pallas interpreter for CPU parity against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ..step_counts import (
    LANES,
    SUBLANES,
    block_sum,
    step_counts,
    step_counts_out,
    store_step_count,
)
from .ref import SENTINEL_A, SENTINEL_B

__all__ = ["fused_short_counts", "fused_window_rows"]

def fused_window_rows(d: int) -> int:
    """Lane rows one task's DMA window spans: a ``d``-long fragment
    starting anywhere in a lane row covers at most ``ceil(d/128) + 1``."""
    return -(-d // LANES) + 1


def _fused_panel_kernel(
    # SMEM (1, 4, TS) block: A start, A length, B start, B length per task
    frag_ref,
    # HBM (rows, 128) CSR index arrays
    a_idx_hbm,
    b_idx_hbm,
    # output + scratch
    out_ref,
    wa_ref,
    wb_ref,
    pa_ref,
    pb_ref,
    sem,
    *,
    ts: int,
    d: int,
):
    nr = wa_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def window_copy(t, idx_hbm, win_ref, side):
        row = frag_ref[0, 2 * side, t] // LANES
        return pltpu.make_async_copy(
            idx_hbm.at[pl.ds(row, nr)], win_ref.at[t], sem.at[side]
        )

    def copies(t):
        return (
            window_copy(t, a_idx_hbm, wa_ref, 0),
            window_copy(t, b_idx_hbm, wb_ref, 1),
        )

    def start_all(t, carry):
        for c in copies(t):
            c.start()
        return carry

    def wait_all(t, carry):
        for c in copies(t):
            c.wait()
        return carry

    jax.lax.fori_loop(0, ts, start_all, 0)
    jax.lax.fori_loop(0, ts, wait_all, 0)

    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def align(win_ref, t, side, sentinel):
        """Task ``t``'s fragment as ``nr - 1`` lane chunks: the window is
        rotated left by ``start % 128`` so chunk ``c`` takes its lanes
        from window rows c and c+1, then masked to the fragment."""
        off = frag_ref[0, 2 * side, t] % LANES
        length = frag_ref[0, 2 * side + 1, t]
        shift = (LANES - off) % LANES
        rows = [
            pltpu.roll(win_ref[t, pl.ds(r, 1), :], shift, 1)
            for r in range(nr)
        ]
        first = lane < LANES - off
        return [
            jnp.where(
                lane + c * LANES < length,
                jnp.where(first, rows[c], rows[c + 1]),
                jnp.int32(sentinel),
            )
            for c in range(nr - 1)
        ]

    def fill(g, carry):
        # Mosaic stores panel rows 8 at a time (one aligned sublane
        # group): tasks are merged into their sublane by select
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        for win_ref, panel_ref, side, sentinel in (
            (wa_ref, pa_ref, 0, SENTINEL_A),
            (wb_ref, pb_ref, 1, SENTINEL_B),
        ):
            group = [jnp.zeros((SUBLANES, LANES), jnp.int32)] * (nr - 1)
            for r in range(SUBLANES):
                chunks = align(win_ref, base + r, side, sentinel)
                group = [
                    jnp.where(sub == r, ch, acc)
                    for ch, acc in zip(chunks, group)
                ]
            for c, rows8 in enumerate(group):
                panel_ref[pl.ds(base, SUBLANES), pl.ds(c * LANES, LANES)] = (
                    rows8
                )
        return carry

    jax.lax.fori_loop(0, ts // SUBLANES, fill, 0)

    pa = pa_ref[...]
    pb = pb_ref[...]
    acc = jnp.zeros(pb.shape, jnp.int32)
    for k in range(d):
        acc = acc + (pa[:, k : k + 1] == pb).astype(jnp.int32)
    store_step_count(out_ref, block_sum(acc))


def _fragments(indptr, rows, valid):
    """Per-task fragment (start, length); invalid tasks get length 0."""
    indptr = indptr.astype(jnp.int32)
    start = jnp.where(valid, indptr[rows], 0)
    length = jnp.where(valid, indptr[rows + 1] - indptr[rows], 0)
    return start, length


def _lane_rows(indices, nr: int):
    """``(rows, 128)`` view of a CSR index array, padded by ``nr`` lane
    rows so every task's window copy stays in bounds."""
    n = indices.shape[0]
    rows = -(-n // LANES) + nr
    flat = jnp.pad(indices.astype(jnp.int32), (0, rows * LANES - n))
    return flat.reshape(rows, LANES)


@functools.partial(
    jax.jit, static_argnames=("tile", "d", "interpret")
)
def fused_short_counts(
    a_indptr,
    a_indices,
    b_indptr,
    b_indices,
    ti,
    tj,
    tcount,
    *,
    tile: int,
    d: int,
    interpret: bool,
):
    """Per-tile fused intersection counts for the short-task list.

    Args:
      a_indptr/b_indptr: (nb+1,) CSR row pointers.
      a_indices/b_indices: (npad,) CSR column ids (stay in HBM).
      ti, tj: (tmax,) short-task row ids; first ``tcount`` are real.
      tile: tasks per grid step (``ops.fused_tile_for`` sizes this).
      d: fragment bound — every real fragment must fit (maxfrag
        contract).
      interpret: Pallas interpreter mode (CPU); ``False`` on TPU.

    Returns: (ntile,) int32 per-tile counts (sum for the step total).
    """
    if tile % SUBLANES:
        raise ValueError(f"tile must be a multiple of {SUBLANES}, got {tile}")
    tmax = ti.shape[0]
    ntile = max(1, -(-tmax // tile))
    pad = ntile * tile - tmax
    if pad:
        ti = jnp.concatenate([ti, jnp.zeros((pad,), ti.dtype)])
        tj = jnp.concatenate([tj, jnp.zeros((pad,), tj.dtype)])
    valid = jnp.arange(ntile * tile) < tcount
    frags = jnp.stack(
        _fragments(a_indptr, ti.astype(jnp.int32), valid)
        + _fragments(b_indptr, tj.astype(jnp.int32), valid)
    )
    frags = frags.reshape(4, ntile, tile).transpose(1, 0, 2)
    nr = fused_window_rows(d)
    a_rows, b_rows = _lane_rows(a_indices, nr), _lane_rows(b_indices, nr)
    # traced with x64 off: Mosaic has no 64-bit types, and under x64 the
    # body's Python ints (loop counters, divisors) would trace as int64
    with jax.enable_x64(False):
        out = _fused_call(frags, a_rows, b_rows, d=d, interpret=interpret)
    return step_counts(out, ntile)


def _fused_call(frags, a_rows, b_rows, *, d, interpret):
    ntile, _, tile = frags.shape
    nr = fused_window_rows(d)
    dp = (nr - 1) * LANES
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_shape, out_spec = step_counts_out(ntile)
    return pl.pallas_call(
        functools.partial(_fused_panel_kernel, ts=tile, d=d),
        grid=(ntile,),
        in_specs=[
            pl.BlockSpec(
                (1, 4, tile), lambda g: (g, 0, 0), memory_space=pltpu.SMEM
            ),
            hbm,
            hbm,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tile, nr, LANES), jnp.int32),
            pltpu.VMEM((tile, nr, LANES), jnp.int32),
            pltpu.VMEM((tile, dp), jnp.int32),
            pltpu.VMEM((tile, dp), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name="tc_count_fused",
    )(frags, a_rows, b_rows)
