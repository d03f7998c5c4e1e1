"""Cannon-schedule distributed triangle counting (the paper's §5.1).

Device ``(x, y)`` of the ``q x q`` grid (mesh axes ``(row_axis, col_axis)``)
starts with the pre-skewed blocks ``A = U_{x,(x+y)%q}`` and
``B = U_{y,(x+y)%q}`` (see :mod:`repro.core.plan`) and performs ``q`` steps
of {count local pair against the static task list, shift A left, shift B
up}.  Shifts are single-blob ``ppermute`` collectives (paper's
serialization optimization); the next blocks are requested *before* the
local count so XLA can overlap communication with compute.

Multi-pod (2.5D, beyond-paper): with ``npods`` pods the blocks are
replicated across the ``pod`` axis, pod ``t`` starts at skew offset ``t``
and executes every ``npods``-th shift; the final count is a global psum.
Memory ×npods, shift traffic ÷npods — the communication-avoiding trade.

This module is a thin *configuration* of :mod:`repro.core.engine`: every
builder below just composes an OperandStore (CSR blob / dense / bit-tile),
the :class:`~repro.core.engine.CannonSchedule`, a count kernel, and a
Reduction — the scan/ppermute schedule body lives in the engine, once.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from . import engine
from .engine import (
    CannonSchedule,
    CSRStore,
    DenseStore,
    GridAxes,
    Reduction,
    TileStore,
    make_csr_kernel,
)

__all__ = [
    "build_cannon_fn",
    "build_cannon_stepper",
    "build_cannon_tile_fn",
    "build_cannon_dense_fn",
    "cannon_in_specs",
    "pod_stack_arrays",
]


def cannon_in_specs(
    row_axis: str, col_axis: str, pod_axis: Optional[str] = None
) -> Dict:
    """PartitionSpecs for the plan's stacked device arrays."""
    axes = GridAxes(row_axis, col_axis, pod_axis)
    return CSRStore(kernel=None).in_specs(axes)


def pod_stack_arrays(arrays: Dict, npods: int, q: int) -> Dict:
    """Stack A/B operands with per-pod skew offsets (numpy, host side).

    ``A0_t[x, y] = A0[x, (y+t) % q]`` and ``B0_t[x, y] = B0[(x+t) % q, y]``
    put pod ``t`` at Cannon skew offset ``t`` so it can execute shifts
    ``t, t+npods, ...`` only.  The planner's ``step_keep`` mask is
    pod-strided the same way: pod ``t``'s local step ``s`` is global
    shift ``t + s * npods``, so its mask slice is ``step_keep[..., t::npods]``.
    """
    import numpy as np

    out = dict(arrays)
    for key in ("a_indptr", "a_indices"):
        out[key] = np.stack(
            [np.roll(arrays[key], -t, axis=1) for t in range(npods)]
        )
    for key in ("b_indptr", "b_indices", "b_aug"):
        if key not in arrays:
            continue
        out[key] = np.stack(
            [np.roll(arrays[key], -t, axis=0) for t in range(npods)]
        )
    if "step_keep" in arrays:
        out["step_keep"] = np.stack(
            [arrays["step_keep"][:, :, t::npods] for t in range(npods)]
        )
    return out


def _cannon_parts(plan, mesh, *, row_axis, col_axis, pod_axis,
                  double_buffer=True, live_steps=None, elide_shifts=False):
    axes = GridAxes(row_axis, col_axis, pod_axis)
    npods = mesh.shape[pod_axis] if pod_axis else 1
    return axes, CannonSchedule(
        q=plan.q, axes=axes, npods=npods, double_buffer=double_buffer,
        live_steps=live_steps, elide_shifts=elide_shifts,
    )


def _coerce(plan):
    from .plan import as_plan

    return as_plan(plan)


def build_cannon_fn(
    plan,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    pod_axis: Optional[str] = None,
    method: str = "search",
    probe_shorter: bool = True,
    count_dtype=jnp.int32,
    use_blob: bool = True,
    reduce_global: bool = True,
    tile_kernel_mode: Optional[str] = None,
    compress_lengths: bool = False,
    batched: bool = False,
    use_step_mask: Optional[bool] = None,
    double_buffer: bool = True,
    compact: Optional[bool] = None,
    elide_shifts: bool = False,
    reduce_strategy: str = "auto",
    fused_impl: str = "auto",
    fused_tile: Optional[int] = None,
):
    """Build the jitted SPMD counting function for ``plan`` on ``mesh``.

    ``plan`` may be a raw :class:`~repro.core.plan.TCPlan` or a pipeline
    :class:`~repro.pipeline.artifact.PlanArtifact`.  Returns a callable
    ``fn(**device_arrays)`` yielding the global triangle count (scalar)
    or per-device counts if ``reduce_global=False``; with
    ``batched=True`` the arrays carry a leading batch axis and the call
    returns per-graph counts (see ``engine.build_engine_fn``).
    ``method``: any registered CSR kernel — ``"search"`` (flat padding),
    ``"search2"`` (two-level length-bucketed — §Perf H1a; requires
    ``bucketize_plan``), ``"global"`` (gather-free keys), ``"fused"``
    (Pallas equality-panel + long fallback, DESIGN.md §5.1; requires a
    maxfrag-split plan from ``autotune='fused'``, and ``fused_impl``
    picks its backend: ``auto``/``pallas``/``pallas-interpret``/
    ``lax``).
    ``compress_lengths`` (§Perf H1b) ships row *lengths as uint16 pairs*
    instead of the int32 indptr inside the shift blob, cutting shifted
    bytes by ~(nb*2)/(nb*4+nnz*4).
    ``use_step_mask=None`` auto-enables sparsity-aware step skipping
    when the plan carries ``step_keep``; ``double_buffer`` selects the
    communication-overlapped two-generation scan body (default on).
    ``compact=None`` auto-enables the compacted kept-step schedule
    (dead-shift elision + fused multi-hop ppermutes, DESIGN.md §4.4)
    when the plan staged one that elides a step; the global/search2
    kernels additionally pick up planner-staged ``b_aug`` intersection
    keys when the plan carries them.  ``elide_shifts`` is a timing probe
    (counts are wrong for q > 1) used by the benchmark's shift/count
    attribution.  ``reduce_strategy`` selects the final reduction:
    ``"flat"`` (one psum per mesh axis), ``"tree"`` (the 2.5D staged
    reduce — joint grid psum + cross-pod binomial ppermute tree,
    DESIGN.md §4.5), or ``"auto"`` (tree whenever a power-of-two pod
    axis is present).
    """
    del tile_kernel_mode  # tile path has its own builder below
    plan = _coerce(plan)
    from .plan import resolve_compact_steps, resolve_step_mask

    use_step_mask = resolve_step_mask(plan, use_step_mask)
    npods = mesh.shape[pod_axis] if pod_axis else 1
    live = resolve_compact_steps(plan, compact, batched=batched, npods=npods)
    axes, schedule = _cannon_parts(
        plan, mesh, row_axis=row_axis, col_axis=col_axis, pod_axis=pod_axis,
        double_buffer=double_buffer, live_steps=live,
        elide_shifts=elide_shifts,
    )
    if method == "fused":
        engine.check_fused_split(plan)
    kernel = make_csr_kernel(
        method,
        dpad=plan.dpad,
        chunk=plan.chunk,
        probe_shorter=probe_shorter,
        count_dtype=count_dtype,
        n_long=getattr(plan, "n_long", None),
        d_small=getattr(plan, "d_small", None),
        fused_impl=fused_impl,
        fused_tile=fused_tile,
    )
    # fused consumes staged keys only in its long-row fallback — with
    # n_long == 0 shipping the aug blob would be pure shift bytes
    fused_wants_aug = (
        method == "fused" and (getattr(plan, "n_long", None) or 0) > 0
    )
    store = CSRStore(
        kernel,
        use_blob=use_blob,
        compress_lengths=compress_lengths,
        dmax=plan.dmax,
        with_aug=(
            (method in ("global", "search2") or fused_wants_aug)
            and getattr(plan, "b_aug", None) is not None
        ),
    )
    return engine.build_engine_fn(
        mesh, axes, store, schedule,
        count_dtype=count_dtype,
        reduction=Reduction(
            global_sum=reduce_global, strategy=reduce_strategy
        ),
        batched=batched,
        use_step_mask=use_step_mask,
        hub=engine.HubCount.from_plan(plan, probe_shorter=probe_shorter),
    )


def build_cannon_stepper(
    plan,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    method: str = "search",
    probe_shorter: bool = True,
    count_dtype=jnp.int32,
    use_step_mask: Optional[bool] = None,
    double_buffer: bool = True,
    compact: Optional[bool] = None,
):
    """Shift-at-a-time Cannon for fault-tolerant runs.

    Returns ``one_shift(state, masks, step=s) -> state`` (jitted SPMD)
    where ``state = (*carry_arrays, partial_counts)`` — with the default
    double-buffered schedule the carry is two payload generations
    ``(a_ptr, a_idx, b_ptr, b_idx) x 2``, built once from the plan
    arrays by ``one_shift.prime`` (which issues the prologue shift).
    The host loop owns the shift index, checkpointing state between
    shifts so a restarted job resumes mid-loop (EXPERIMENTS.md
    §Fault-tolerance).  Same engine body as :func:`build_cannon_fn` —
    only the loop owner differs.

    With a compacted plan (``compact=None`` auto, DESIGN.md §4.4) the
    host loop iterates ``one_shift.live_steps`` only — still passing
    original step indices, so checkpointed indices round-trip unchanged
    — and the carry is a *single* payload generation (4 arrays): each
    call's fused multi-hop shift lands exactly on the next live step, so
    there is no in-flight second buffer to keep.
    """
    plan = _coerce(plan)
    from .plan import resolve_compact_steps, resolve_step_mask

    if getattr(plan, "hub", None) is not None:
        raise ValueError(
            "the checkpointed stepper counts one schedule shift at a "
            "time and has no slot for the hub-split partial; plan with "
            "hub_split=False for fault-tolerant runs"
        )
    use_step_mask = resolve_step_mask(plan, use_step_mask)
    live = resolve_compact_steps(plan, compact)
    axes, schedule = _cannon_parts(
        plan, mesh, row_axis=row_axis, col_axis=col_axis, pod_axis=None,
        double_buffer=double_buffer and live is None, live_steps=live,
    )
    kernel = make_csr_kernel(
        method,
        dpad=plan.dpad,
        chunk=plan.chunk,
        probe_shorter=probe_shorter,
        count_dtype=count_dtype,
    )
    store = CSRStore(kernel, use_blob=False)
    # count_dtype binds the kernel and the masked-step zero; the
    # accumulator dtype follows the caller's acc array (the checkpointed
    # state owns it)
    return engine.build_engine_stepper(
        mesh, axes, store, schedule,
        count_dtype=count_dtype, use_step_mask=use_step_mask,
    )


def build_cannon_tile_fn(
    plan,
    tile_plan,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    mode: str = "popcount",
    interpret: bool,
    count_dtype=jnp.int32,
    reduce_global: bool = True,
    use_step_mask: Optional[bool] = None,
    double_buffer: bool = True,
    compact: Optional[bool] = None,
    reduce_strategy: str = "auto",
):
    """Cannon schedule with the Pallas bit-tile kernel as the count path.

    Tile stores shift exactly like the CSR blobs; the per-(device, shift)
    active-triple lists are static (planner-joined) and drive the kernel's
    scalar-prefetch grid.  ``interpret`` has no default: ``True``
    validates on CPU, ``False`` runs the Mosaic-lowered kernel on TPU.  The skip mask
    comes from the *CSR* plan (``plan.step_keep``); callers stage it
    alongside the tile arrays.  Under a compacted schedule the unrolled
    body selects each live step's triple list with a *static* index.
    """
    del tile_plan  # shapes travel with the device arrays
    plan = _coerce(plan)
    from .plan import resolve_compact_steps, resolve_step_mask

    if getattr(plan, "hub", None) is not None:
        raise ValueError(
            "the bit-tile path stages its own arrays and would drop the "
            "hub-split partial; plan with hub_split=False for method "
            "'tile'"
        )
    use_step_mask = resolve_step_mask(plan, use_step_mask)
    live = resolve_compact_steps(plan, compact)
    axes, schedule = _cannon_parts(
        plan, mesh, row_axis=row_axis, col_axis=col_axis, pod_axis=None,
        double_buffer=double_buffer, live_steps=live,
    )
    store = TileStore(mode=mode, interpret=interpret, count_dtype=count_dtype)
    return engine.build_engine_fn(
        mesh, axes, store, schedule,
        count_dtype=count_dtype,
        reduction=Reduction(
            global_sum=reduce_global, strategy=reduce_strategy
        ),
        use_step_mask=use_step_mask,
    )


def build_cannon_dense_fn(
    plan,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    pod_axis: Optional[str] = None,
    acc_dtype=jnp.float32,
    reduce_global: bool = True,
    use_step_mask: Optional[bool] = None,
    double_buffer: bool = True,
    compact: Optional[bool] = None,
    reduce_strategy: str = "auto",
):
    """Dense-operand Cannon (oracle path): blocks as 0/1 float matrices."""
    plan = _coerce(plan)
    from .plan import resolve_compact_steps, resolve_step_mask

    if getattr(plan, "hub", None) is not None:
        raise ValueError(
            "the dense oracle path stages its own blocks and would drop "
            "the hub-split partial; plan with hub_split=False for "
            "method 'dense'"
        )
    use_step_mask = resolve_step_mask(plan, use_step_mask)
    npods = mesh.shape[pod_axis] if pod_axis else 1
    live = resolve_compact_steps(plan, compact, npods=npods)
    axes, schedule = _cannon_parts(
        plan, mesh, row_axis=row_axis, col_axis=col_axis, pod_axis=pod_axis,
        double_buffer=double_buffer, live_steps=live,
    )
    store = DenseStore(acc_dtype=acc_dtype)
    return engine.build_engine_fn(
        mesh, axes, store, schedule,
        count_dtype=acc_dtype,
        reduction=Reduction(
            global_sum=reduce_global, strategy=reduce_strategy
        ),
        use_step_mask=use_step_mask,
    )
