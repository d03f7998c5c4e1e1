"""Per-grid-step counts out of a Pallas TPU kernel.

Mosaic refuses a rank-1 ``(1,)`` output block per grid step, and a
reduction to a scalar lowers through ``jnp.sum``, which promotes to
int64 under x64.  So a kernel sums its step's count to a ``(1, 1)``
block (:func:`block_sum`) and writes it into its slot of a resident
``(8, 128)`` int32 output block that holds 1024 consecutive steps
(:func:`store_step_count`); :func:`step_counts_out` gives the matching
``out_shape``/``out_specs`` and :func:`step_counts` unpacks the result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["block_sum", "step_counts", "step_counts_out", "store_step_count"]

SUBLANES, LANES = 8, 128
STEPS_PER_BLOCK = SUBLANES * LANES


def block_sum(x):
    """Sum of a 2-D block, as a ``(1, 1)`` block."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def store_step_count(out_ref, total):
    """Write this grid step's ``(1, 1)`` count into its slot of the
    resident output block (zeroed by the block's first step)."""
    slot = pl.program_id(0) % STEPS_PER_BLOCK

    @pl.when(slot == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    shape = out_ref.shape
    here = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) == slot // LANES) & (
        jax.lax.broadcasted_iota(jnp.int32, shape, 1) == slot % LANES
    )
    out_ref[...] = jnp.where(here, total, out_ref[...])


def step_counts_out(n_steps: int):
    """``(out_shape, out_specs)`` for ``n_steps`` grid steps' counts; the
    index map also takes (and ignores) scalar-prefetch refs."""
    nblk = -(-n_steps // STEPS_PER_BLOCK)
    return (
        jax.ShapeDtypeStruct((nblk * SUBLANES, LANES), jnp.int32),
        pl.BlockSpec(
            (SUBLANES, LANES), lambda i, *_: (i // STEPS_PER_BLOCK, 0)
        ),
    )


def step_counts(out, n_steps: int):
    """The ``(n_steps,)`` int32 counts held in a kernel's output."""
    return out.reshape(-1)[:n_steps]
