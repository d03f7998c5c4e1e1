"""Per-shift block-pair counting — the compute hot spot, three paths.

All paths compute, for a static task list ``(ti, tj)`` (the nonzeros of the
device's mask block), ``sum_t |row_A(ti_t)  ∩  row_B(tj_t)|`` where A and B
are the two CSR blocks the device holds at the current Cannon/SUMMA step.

Paths (DESIGN.md §2):

* ``dense``   — ``sum((A @ Bᵀ) ⊙ M)``; MXU-shaped; oracle + small blocks.
* ``search``  — vectorized intersection, chunked over tasks; the scalable
  path for hyper-sparse giant blocks.  On CPU a row-wise binary search:
  ``probe_shorter=True`` probes the shorter fragment into the longer (the
  paper's ⟨j,i,k⟩ hash-the-longer-list rule).  On TPU, where every binary
  search step is a slow element gather, each fragment is fetched as a
  contiguous window: the lane rows it spans are gathered from the index
  array viewed as ``(rows, 128)``, shifted left by the start's lane
  offset, and intersected by a dense equality compare.  (A ``vmap`` of
  ``lax.dynamic_slice`` would compile to one serial loop trip per task.)
* ``tile``    — bit-packed 128×128 tile kernel (``repro.kernels.tc_tile``),
  wired in by :mod:`repro.core.cannon` when the plan carries tile stores.
* ``fused``   — the Pallas probe-gather + intersection + accumulate
  mega-kernel (``repro.kernels.tc_fused``, DESIGN.md §5.1); its long-row
  fallback reuses :func:`count_pair_search` /
  :func:`count_pair_search_global` from this module, so the fused path
  stays count-equivalent to ``search2`` by construction.

Everything here is pure ``jnp`` and shape-static, usable inside
``shard_map`` and under ``lax.scan``.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import compat

__all__ = [
    "aug_key_dtype",
    "count_pair_dense",
    "count_pair_search",
    "gather_rows",
]


def aug_key_dtype(base: int):
    """Dtype wide enough for row-encoded keys ``row * base + col``.

    Rows and cols are block-local (``< base``), so the largest key is
    ``base**2 - 1``.  int32 covers ``base <= 46340``; beyond that the key
    needs int64 — and if x64 is off, jax would *silently truncate* the
    ``astype(int64)`` back to int32, wrapping keys into collisions and
    corrupting counts (the historical bug this guard exists for).  Fail
    loudly instead of returning garbage.
    """
    if base * base - 1 <= np.iinfo(np.int32).max:
        return jnp.int32
    if not compat.x64_enabled():
        raise OverflowError(
            f"row-encoded intersection keys for block size nb={base - 1} "
            "exceed int32 (row * base + col needs int64); enable x64 "
            "(jax.config.update('jax_enable_x64', True)) to use the "
            "'global'/'search2' count paths on blocks this large"
        )
    return jnp.int64


def count_pair_dense(a_dense, b_dense, m_dense, *, acc_dtype=jnp.float32):
    """``sum((A @ Bᵀ) ⊙ M)`` — exact for 0/1 blocks.

    ``A: (nb, nb)`` rows=i cols=k; ``B: (nb, nb)`` rows=j cols=k;
    ``M: (nb, nb)`` mask at (i_local, j_local).
    """
    prod = jax.lax.dot_general(
        a_dense,
        b_dense,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc_dtype,
    )
    return jnp.sum(prod * m_dense, dtype=acc_dtype)


def gather_rows(indptr, indices, rows, dpad: int, sentinel: int):
    """Gather padded adjacency fragments ``(T, dpad)`` for ``rows`` (T,).

    Padding positions are filled with ``sentinel`` (greater than any valid
    local column id) so each returned row stays sorted — required by the
    binary-search probe.
    """
    start = indptr[rows]
    length = indptr[rows + 1] - start
    offs = jnp.arange(dpad, dtype=indptr.dtype)
    idx = start[:, None] + offs[None, :]
    valid = offs[None, :] < length[:, None]
    vals = indices[jnp.clip(idx, 0, indices.shape[0] - 1)]
    return jnp.where(valid, vals, sentinel), length


# per-chunk element bound of the TPU path's (chunk, dpad, dpad) compare
_EQUALITY_ELEMS = 1 << 26


def _equality_intersect() -> bool:
    """Intersect by dense equality (TPU) instead of binary search: on a
    TPU each binary-search step is an element gather, ~29x slower on the
    default Cannon count of Graph500 scale 20 (one v5e chip)."""
    return jax.default_backend() == "tpu"


# lanes of one vreg row: the TPU path views each index array as (rows, 128)
_LANES = 128


def _lane_rows(dpad: int) -> int:
    """Lane rows one ``dpad`` window spans, wherever it starts in a row."""
    return -(-dpad // _LANES) + 1


def _lane_view(indices, dpad: int, sentinel: int):
    """``indices`` padded with ``sentinel`` to whole lane rows, plus
    :func:`_lane_rows` spare rows so no window is clamped, as
    ``(rows, 128)``."""
    rows = -(-indices.shape[0] // _LANES) + _lane_rows(dpad)
    pad = rows * _LANES - indices.shape[0]
    return jnp.concatenate(
        [indices, jnp.full((pad,), sentinel, indices.dtype)]
    ).reshape(rows, _LANES)


def _window_rows(indptr, lanes, rows, dpad: int, sentinel: int):
    """Like :func:`gather_rows`, but each fragment is one contiguous
    ``dpad`` window of the lane view ``lanes`` (:func:`_lane_view`): the
    lane rows it spans, gathered whole and shifted left by the start's
    lane offset.  (A ``vmap`` of ``lax.dynamic_slice`` over the flat
    array compiles to a serial loop of one slice per task.)"""
    start = indptr[rows]
    length = indptr[rows + 1] - start
    r = start // _LANES
    panel = jnp.concatenate(
        [jnp.take(lanes, r + c, axis=0) for c in range(_lane_rows(dpad))],
        axis=1,
    )
    shift = start % _LANES
    for bit in range(_LANES.bit_length() - 1):  # one roll per offset bit
        panel = jnp.where(
            ((shift >> bit) & 1)[:, None] == 1,
            jnp.roll(panel, -(1 << bit), axis=1),
            panel,
        )
    offs = jnp.arange(dpad, dtype=indptr.dtype)
    valid = offs[None, :] < length[:, None]
    return jnp.where(valid, panel[:, :dpad], sentinel), length


def _searchsorted_rows(keys, queries):
    """Row-wise searchsorted: keys (T, Dk) sorted rows; queries (T, Dq)."""
    return jax.vmap(
        lambda k, q: jnp.searchsorted(k, q, side="left")
    )(keys, queries)


def count_pair_search(
    a_indptr,
    a_indices,
    b_indptr,
    b_indices,
    ti,
    tj,
    tcount,
    *,
    dpad: int,
    chunk: int,
    probe_shorter: bool = True,
    count_dtype=jnp.int32,
    sentinel: Optional[int] = None,
):
    """Chunked vectorized set-intersection over the device's task list.

    ``ti, tj: (tmax,)`` local row ids into A / B; only the first ``tcount``
    are real (the rest are padding and masked out).  Tasks are processed in
    ``tmax / chunk`` chunks under ``lax.scan`` so the working set stays at
    ``O(chunk * dpad)`` regardless of block size — on TPU ``O(chunk *
    dpad²)``, with ``chunk`` shrunk to keep that under
    ``_EQUALITY_ELEMS``.
    """
    equality = _equality_intersect()
    if equality:
        chunk = max(1, min(chunk, _EQUALITY_ELEMS // (dpad * dpad)))
    tmax = ti.shape[0]
    nchunk = -(-tmax // chunk)
    pad = nchunk * chunk - tmax
    if pad:
        ti = jnp.concatenate([ti, jnp.zeros((pad,), ti.dtype)])
        tj = jnp.concatenate([tj, jnp.zeros((pad,), tj.dtype)])
    ti_c = ti.reshape(nchunk, chunk)
    tj_c = tj.reshape(nchunk, chunk)
    base = jnp.arange(nchunk)[:, None] * chunk + jnp.arange(chunk)[None, :]
    tvalid_c = base < tcount

    if sentinel is None:
        sentinel = a_indptr.shape[0]  # nb + 1 > any local col id
    offs = jnp.arange(dpad)

    if equality:
        a_win = _lane_view(a_indices, dpad, sentinel)
        b_win = _lane_view(b_indices, dpad, sentinel)

    def one_chunk(acc, args):
        rows_i, rows_j, valid = args
        if equality:
            # CSR rows are duplicate-free: equal pairs = |A ∩ B|; the
            # sentinel padding only matches where the probe mask is off
            a_vals, a_len = _window_rows(
                a_indptr, a_win, rows_i, dpad, sentinel
            )
            b_vals, _ = _window_rows(b_indptr, b_win, rows_j, dpad, sentinel)
            eq = (a_vals[:, :, None] == b_vals[:, None, :]) & (
                offs[None, :, None] < a_len[:, None, None]
            )
            per_task = jnp.sum(eq, axis=(1, 2), dtype=count_dtype)
            per_task = jnp.where(valid, per_task, 0)
            return acc + jnp.sum(per_task, dtype=count_dtype), None
        a_vals, a_len = gather_rows(a_indptr, a_indices, rows_i, dpad, sentinel)
        b_vals, b_len = gather_rows(b_indptr, b_indices, rows_j, dpad, sentinel)
        if probe_shorter:
            swap = (a_len > b_len)[:, None]
            probe = jnp.where(swap, b_vals, a_vals)
            keys = jnp.where(swap, a_vals, b_vals)
            probe_len = jnp.minimum(a_len, b_len)
        else:
            probe, keys, probe_len = a_vals, b_vals, a_len
        pos = _searchsorted_rows(keys, probe)
        hit = (
            jnp.take_along_axis(
                keys, jnp.clip(pos, 0, keys.shape[1] - 1), axis=1
            )
            == probe
        )
        hit &= offs[None, :] < probe_len[:, None]
        per_task = jnp.sum(hit, axis=1, dtype=count_dtype)
        per_task = jnp.where(valid, per_task, 0)
        return acc + jnp.sum(per_task, dtype=count_dtype), None

    acc0 = jnp.zeros((), dtype=count_dtype)
    acc, _ = jax.lax.scan(one_chunk, acc0, (ti_c, tj_c, tvalid_c))
    return acc


def count_pair_search_global(
    a_indptr,
    a_indices,
    b_indptr,
    b_indices,
    ti,
    tj,
    tcount,
    *,
    dpad: int,
    chunk: int,
    count_dtype=jnp.int32,
    aug_b=None,
    row_base: Optional[int] = None,
):
    """Gather-free-keys intersection: probe A fragments into a row-encoded
    *global* sorted view of B (``aug_b[e] = row(e) * (nb+1) + col(e)``).

    Only the probe side is gathered (padded to ``dpad``); the keys side is
    searched in place regardless of row length — so probe padding can be
    sized to the PROBE distribution alone (the §Perf H1a bucketing lever),
    and truncation bugs on long key rows are structurally impossible.
    """
    nb = b_indptr.shape[0] - 1
    base = row_base or (nb + 1)
    if aug_b is None:
        aug_b = build_aug_keys(b_indptr, b_indices)
    tmax = ti.shape[0]
    nchunk = -(-tmax // chunk)
    pad = nchunk * chunk - tmax
    if pad:
        ti = jnp.concatenate([ti, jnp.zeros((pad,), ti.dtype)])
        tj = jnp.concatenate([tj, jnp.zeros((pad,), tj.dtype)])
    ti_c = ti.reshape(nchunk, chunk)
    tj_c = tj.reshape(nchunk, chunk)
    pos0 = jnp.arange(nchunk)[:, None] * chunk + jnp.arange(chunk)[None, :]
    tvalid_c = pos0 < tcount
    sentinel = base - 1  # never a valid column id

    key_dtype = aug_key_dtype(base)

    def one_chunk(acc, args):
        rows_i, rows_j, valid = args
        a_vals, a_len = gather_rows(a_indptr, a_indices, rows_i, dpad, sentinel)
        keys = rows_j[:, None].astype(key_dtype) * base + a_vals.astype(
            key_dtype
        )
        pos = jnp.searchsorted(aug_b, keys.reshape(-1)).reshape(keys.shape)
        hit = (
            aug_b[jnp.clip(pos, 0, aug_b.shape[0] - 1)] == keys
        )
        hit &= jnp.arange(dpad)[None, :] < a_len[:, None]
        per_task = jnp.sum(hit, axis=1, dtype=count_dtype)
        per_task = jnp.where(valid, per_task, 0)
        return acc + jnp.sum(per_task, dtype=count_dtype), None

    acc0 = jnp.zeros((), dtype=count_dtype)
    acc, _ = jax.lax.scan(one_chunk, acc0, (ti_c, tj_c, tvalid_c))
    return acc


def build_aug_keys(b_indptr, b_indices):
    """Row-encoded global key array for count_pair_search_global."""
    nb = b_indptr.shape[0] - 1
    base = nb + 1
    key_dtype = aug_key_dtype(base)
    nnz = b_indices.shape[0]
    row_of = (
        jnp.searchsorted(
            b_indptr, jnp.arange(nnz, dtype=b_indptr.dtype), side="right"
        )
        - 1
    )
    return row_of.astype(key_dtype) * base + b_indices.astype(key_dtype)


_TWO_LEVEL_KW_WARNED = False


def _warn_two_level_kwargs(probe_shorter, sentinel) -> None:
    """One-time notice that the two-level path ignores search-only knobs.

    The global-key formulation *always* probes the A side into the
    row-encoded B keys and needs no padding sentinel, so
    ``probe_shorter``/``sentinel`` are accepted for signature
    compatibility with :func:`count_pair_search` but have no effect —
    callers porting from ``search`` must not believe the flags are
    honored.
    """
    global _TWO_LEVEL_KW_WARNED
    if _TWO_LEVEL_KW_WARNED:
        return
    ignored = []
    if probe_shorter is not True:
        ignored.append(f"probe_shorter={probe_shorter!r}")
    if sentinel is not None:
        ignored.append(f"sentinel={sentinel!r}")
    if ignored:
        _TWO_LEVEL_KW_WARNED = True
        warnings.warn(
            "count_pair_search_two_level ignores "
            + ", ".join(ignored)
            + ": the global-key path always probes the A side and needs "
            "no sentinel (this notice is emitted once per process)",
            UserWarning,
            stacklevel=3,
        )


def count_pair_search_two_level(
    a_indptr,
    a_indices,
    b_indptr,
    b_indices,
    ti,
    tj,
    tcount,
    n_long,
    *,
    dpad_long: int,
    dpad_short: int,
    chunk: int,
    probe_shorter: bool = True,
    count_dtype=jnp.int32,
    sentinel: Optional[int] = None,
    aug_b=None,
):
    """Length-bucketed intersection (§Perf hillclimb H1a).

    The planner statically reorders each device's task list so the
    ``n_long`` tasks whose *probe* fragment can exceed ``dpad_short``
    (under any Cannon pairing) come first; long chunks run at
    ``dpad_long`` probe padding, the rest at ``dpad_short``.  Both buckets
    use the gather-free-keys global search, so the keys side needs no
    padding at all.  For power-law graphs this removes the
    ``dmax/avg_len`` probe-padding waste on >90% of tasks
    (measured in EXPERIMENTS.md §Perf).

    ``probe_shorter``/``sentinel`` are search-path knobs the global-key
    formulation structurally ignores — passing non-defaults emits a
    one-time warning rather than silently dropping them.  ``aug_b``
    accepts planner-staged keys (DESIGN.md §5); when ``None`` the keys
    are built on device per call.
    """
    _warn_two_level_kwargs(probe_shorter, sentinel)
    tmax = ti.shape[0]
    n_long_c = -(-max(1, n_long) // chunk) * chunk
    n_long_c = min(n_long_c, tmax)

    long_count = jnp.minimum(tcount, n_long_c)
    short_count = jnp.maximum(tcount - n_long_c, 0)

    if aug_b is None:
        aug_b = build_aug_keys(b_indptr, b_indices)
    acc_long = count_pair_search_global(
        a_indptr,
        a_indices,
        b_indptr,
        b_indices,
        ti[:n_long_c],
        tj[:n_long_c],
        long_count,
        dpad=dpad_long,
        chunk=chunk,
        count_dtype=count_dtype,
        aug_b=aug_b,
    )
    if n_long_c >= tmax:
        return acc_long
    acc_short = count_pair_search_global(
        a_indptr,
        a_indices,
        b_indptr,
        b_indices,
        ti[n_long_c:],
        tj[n_long_c:],
        short_count,
        dpad=dpad_short,
        chunk=chunk,
        count_dtype=count_dtype,
        aug_b=aug_b,
    )
    return acc_long + acc_short
