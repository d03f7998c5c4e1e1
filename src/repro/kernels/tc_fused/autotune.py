"""Measured-roofline autotune table for the fused kernel (DESIGN.md §4.6).

The PR 5 percentile autotune is purely *analytic* — it derives shapes
from the probe-length distribution without ever running a kernel.  This
module adds the *measured* mode: time a handful of candidate
``(tile, chunk, d_small)`` shapes of the fused panel against the
incumbent two-level search on the plan's busiest device block, sanity-
check the verdict against the published peaks of the device it ran on
(:data:`repro.launch.roofline.PEAKS`; no roofline verdict on a device
kind the table does not know), and persist the result so every later run with the
same (backend, dtype, shape-bucket) resolves ``method="auto"`` straight
from the table.

Keying mirrors the plan cache's content-addressed style
(:func:`repro.pipeline.cache.graph_digest`): a blake2b over the table
version, backend, index dtype, power-of-two buckets of the block
shapes, and the split parameters.  Bucketing (rather than exact shapes)
is what makes the table reusable across graphs of the same size class —
and what makes a warm table possible at all under batched serving.

Entries are single JSON files under :func:`default_table_dir`
(``$REPRO_TC_MEASURED_DIR`` overrides; tests point it at a tmpdir).
"""
from __future__ import annotations

import hashlib
import json
import math
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.count import (
    build_aug_keys,
    count_pair_search,
    count_pair_search_two_level,
)
from ...launch.roofline import PEAKS
from .ops import count_pair_fused, fused_tile_for, resolve_fused_impl

__all__ = [
    "TABLE_VERSION",
    "default_table_dir",
    "measured_entry",
    "measured_table_key",
    "predict_fused_wins",
    "roofline_predict",
]

TABLE_VERSION = 1
_REPS = 3  # min-of-k timing


def default_table_dir() -> str:
    env = os.environ.get("REPRO_TC_MEASURED_DIR")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "tc_measured"
    )


def _bucket(x: int) -> int:
    """Next power of two — the shape-bucket that makes entries reusable
    across graphs of the same size class."""
    return 1 << max(0, int(math.ceil(math.log2(max(1, int(x))))))


def measured_table_key(
    *,
    kind: str,
    backend: str,
    dtype: str,
    nb: int,
    nnz_pad: int,
    tmax: int,
    dmax: int,
    d_small: int,
    tail_heavy: bool,
) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(
        repr(
            (
                TABLE_VERSION,
                kind,
                backend,
                dtype,
                _bucket(nb),
                _bucket(nnz_pad),
                _bucket(tmax),
                _bucket(dmax),
                int(d_small),
                bool(tail_heavy),
            )
        ).encode()
    )
    return h.hexdigest()


def roofline_predict(
    *, tshort: int, d_small: int, dpad: int, nnz: int, peaks: dict
) -> dict:
    """Roofline time model for the short-task bucket (the long bucket
    runs the same fallback on both paths and cancels out).

    search2's short bucket gathers the probe panel (``dpad`` ids per
    task, where ``dpad`` = the baseline's short padding) and then runs a
    binary search whose ~log2(nnz) dependent levels each touch HBM,
    plus the key encode — all charged to ``peaks['hbm_bw']``.  The fused
    kernel's HBM traffic is the two fragment gathers ONLY: the (d, d)
    equality panel lives in VMEM/registers and never reaches HBM (the
    point of the fusion), so it is charged to the *compute* ceiling
    instead and the fused time is the max of the two terms.  The model
    ranks the paths; the measured table is the ground truth it is
    sanity-checked against.
    """
    lg = max(1.0, math.log2(max(2, nnz)))
    bytes_search = tshort * dpad * 4.0 * (2.0 + lg)
    bytes_fused = tshort * 2.0 * d_small * 4.0
    ops_fused = tshort * float(d_small) ** 2
    t_fused = max(
        bytes_fused / peaks["hbm_bw"], ops_fused / peaks["peak_flops"]
    )
    t_search = bytes_search / peaks["hbm_bw"]
    return dict(
        t_search=t_search,
        t_fused=t_fused,
        hbm_bw=peaks["hbm_bw"],
        peak_flops=peaks["peak_flops"],
        predicted_winner="fused" if t_fused < t_search else "search2",
    )


def predict_fused_wins(entry: dict) -> bool:
    """The table's verdict: does the measured fused best beat the
    measured baseline on this shape bucket?"""
    return bool(entry.get("winner") == "fused")


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def _time_once(fn, *args) -> float:
    """Min-of-k warm wall time of a jitted ``fn(*args)``.

    The operands MUST be passed as jit arguments, not closures: a
    zero-argument jitted callable is all-constant, so XLA would fold the
    entire count at compile time and the "measurement" would time a
    buffer fetch.  The first call compiles + warms; production pays
    exactly this warm-dispatch cost inside the engine.
    """
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _busiest_arrays(plan) -> Tuple:
    """(a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt, sentinel, kind) of the
    device with the most tasks — the block the measurement represents."""
    if hasattr(plan, "t_cnt"):  # OneDPlan
        p = plan.p
        flat = int(np.argmax(np.asarray(plan.t_cnt)))
        d0, o = flat // p, flat % p
        return (
            plan.indptr[d0], plan.indices[d0],
            plan.indptr[o], plan.indices[o],
            plan.t_i[d0, o], plan.t_j[d0, o],
            int(plan.t_cnt[d0, o]), plan.n + 1, "oned",
        )
    cnts = np.asarray(plan.m_cnt)
    flat = int(np.argmax(cnts))
    x, y = flat // cnts.shape[1], flat % cnts.shape[1]
    if plan.b_indptr.ndim == 4:  # SummaPlan: measure against panel 0
        return (
            plan.a_indptr[x, y], plan.a_indices[x, y],
            plan.b_indptr[x, y, 0], plan.b_indices[x, y, 0],
            plan.m_ti[x, y], plan.m_tj[x, y],
            int(cnts[x, y]), plan.nb_c + 1, "summa",
        )
    return (
        plan.a_indptr[x, y], plan.a_indices[x, y],
        plan.b_indptr[x, y], plan.b_indices[x, y],
        plan.m_ti[x, y], plan.m_tj[x, y],
        int(cnts[x, y]), plan.nb + 1, "cannon",
    )


def _candidates(d_small: int, chunk: int, dmax: int):
    """Candidate (tile, chunk, d_small) shapes: the analytic pick, a
    half-size tile (less VMEM pressure), and a widened panel that pulls
    borderline-long tasks out of the fallback."""
    t0 = fused_tile_for(d_small)
    cands = [(t0, chunk, d_small)]
    if t0 > 8:
        cands.append((t0 // 2, chunk, d_small))
    d2 = min(-(-d_small * 2 // 8) * 8, dmax)
    if d2 > d_small:
        cands.append((fused_tile_for(d2), chunk, d2))
    return cands


def measured_entry(
    plan,
    *,
    backend: Optional[str] = None,
    table_dir: Optional[str] = None,
    force: bool = False,
) -> Tuple[dict, bool]:
    """Measured verdict for ``plan``'s shape bucket: ``(entry, hit)``.

    ``hit`` is True when the entry came off disk.  Requires a
    maxfrag-split plan (``n_long``/``d_small`` set by the two-sided
    autotune stage) — measuring the fused kernel under a probe-only
    split would time a kernel that miscounts.
    """
    n_long = getattr(plan, "n_long", None)
    d_small = getattr(plan, "d_small", None)
    if n_long is None or d_small is None:
        raise ValueError(
            "measured autotune needs a maxfrag-split plan: re-plan with "
            "autotune='fused' (two-sided split) first"
        )
    report = getattr(plan, "autotune", None) or {}
    backend = backend or jax.default_backend()
    a_ptr, a_idx, b_ptr, b_idx, ti, tj, cnt, sentinel, kind = (
        _busiest_arrays(plan)
    )
    key = measured_table_key(
        kind=kind,
        backend=backend,
        dtype=str(np.asarray(a_idx).dtype),
        nb=a_ptr.shape[0] - 1,
        nnz_pad=a_idx.shape[0],
        tmax=ti.shape[0],
        dmax=plan.dmax,
        d_small=d_small,
        tail_heavy=bool(report.get("tail_heavy", False)),
    )
    table_dir = table_dir or default_table_dir()
    path = os.path.join(table_dir, key + ".json")
    if not force and os.path.exists(path):
        with open(path) as fh:
            return json.load(fh), True

    a_ptr = jnp.asarray(a_ptr)
    a_idx = jnp.asarray(a_idx)
    b_ptr = jnp.asarray(b_ptr)
    b_idx = jnp.asarray(b_idx)
    ti = jnp.asarray(ti)
    tj = jnp.asarray(tj)
    chunk = int(plan.chunk)
    impl = resolve_fused_impl("auto")
    long_fallback = "search" if kind == "oned" else "global"

    arrs = (a_ptr, a_idx, b_ptr, b_idx, ti, tj)
    if kind == "oned":
        baseline_name = "search"
        base_jit = jax.jit(
            lambda ap, ai, bp, bi, t1, t2: count_pair_search(
                ap, ai, bp, bi, t1, t2, cnt,
                dpad=plan.dmax, chunk=chunk, sentinel=sentinel,
            )
        )
    else:
        baseline_name = "search2"
        aug = build_aug_keys(b_ptr, b_idx)
        base_jit = jax.jit(
            lambda ap, ai, bp, bi, t1, t2, aug=aug:
            count_pair_search_two_level(
                ap, ai, bp, bi, t1, t2, cnt, n_long,
                dpad_long=plan.dmax, dpad_short=d_small, chunk=chunk,
                aug_b=aug,
            )
        )

    t_base = _time_once(base_jit, *arrs)
    cands = []
    for tile, ch, d in _candidates(d_small, chunk, plan.dmax):
        fused_jit = jax.jit(
            lambda ap, ai, bp, bi, t1, t2, tile=tile, ch=ch, d=d:
            count_pair_fused(
                ap, ai, bp, bi, t1, t2, cnt,
                n_long=n_long, d_small=d, dpad_long=plan.dmax,
                chunk=ch, tile=tile, impl=impl,
                long_fallback=long_fallback, sentinel=sentinel,
            )
        )
        t = _time_once(fused_jit, *arrs)
        cands.append(dict(tile=tile, chunk=ch, d_small=d, seconds=t))
    best = min(cands, key=lambda c: c["seconds"])

    tshort = max(0, cnt - n_long)
    # the baseline's short bucket runs at d_small padding too (search2's
    # dpad_short) — the paths differ in traffic pattern, not padding;
    # a device kind without published peaks gets no roofline verdict
    kind = _device_kind()
    predict = None
    if kind in PEAKS:
        predict = roofline_predict(
            tshort=max(1, tshort), d_small=d_small, dpad=d_small,
            nnz=int(b_idx.shape[0]), peaks=PEAKS[kind],
        )
    entry = dict(
        version=TABLE_VERSION,
        key=key,
        kind=kind,
        backend=backend,
        impl=impl,
        baseline=baseline_name,
        t_baseline=t_base,
        t_fused=best["seconds"],
        best=dict(tile=best["tile"], chunk=best["chunk"],
                  d_small=best["d_small"]),
        candidates=cands,
        winner="fused" if best["seconds"] < t_base else baseline_name,
        roofline=predict,
        created=time.time(),
    )
    os.makedirs(table_dir, exist_ok=True)
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(entry, fh, indent=1)
    os.replace(tmp, path)
    return entry, False
