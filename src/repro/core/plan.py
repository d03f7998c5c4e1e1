"""Host-side execution plan for the 2D (Cannon/SUMMA/2.5D) algorithm.

The planner turns a degree-ordered :class:`~repro.core.graph.Graph` into
fixed-shape, device-ready numpy arrays, stacked over the processor grid so
that ``shard_map`` with ``P(row_axis, col_axis)`` hands each device exactly
its blocks:

* ``a_*``  — Cannon "A" operand, pre-skewed: device ``(x, y)`` starts with
  block ``U_{x, (x+y) % q}``  (rows *i*, columns *k*);
* ``b_*``  — Cannon "B" operand, pre-skewed: device ``(x, y)`` starts with
  block ``U_{y, (x+y) % q}``  (rows *j*, columns *k*; this is
  ``L_{(x+y)%q, y}`` stored transposed — see DESIGN.md §2);
* ``m_*``  — the static task list: nonzeros ``(i, j)`` of ``U_{x, y}``.

All ragged structures are padded to plan-wide maxima (XLA needs static
shapes), rounded up a coarse ladder (:func:`ladder_nnz`, :func:`ladder_dpad`)
so that graphs of one size class share one compiled program; the padding
fractions are part of the plan report because they are *measured overhead*
of the TPU adaptation (DESIGN.md §10.4).

The pre-skew implements Cannon's initial alignment at data-distribution
time (the paper performs it as its first communication step; in an SPMD
framework the initial placement is free — we simply *feed* the aligned
blocks).  ``skew=0`` (SUMMA placement) is also available.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from .decomp import BlockCSR, cyclic_blocks
from .graph import Graph

__all__ = [
    "TCPlan",
    "build_plan",
    "analytic_plan",
    "PlanStats",
    "StepStats",
    "CompactSchedule",
    "compact_live_steps",
    "as_plan",
    "resolve_step_mask",
    "resolve_compact_steps",
    "host_aug_keys",
    "ladder_nnz",
    "ladder_dpad",
]


def as_plan(obj):
    """Coerce a pipeline :class:`~repro.pipeline.artifact.PlanArtifact`
    (or a raw plan) to its plan object — every engine builder accepts
    either."""
    inner = getattr(obj, "plan", None)
    return obj if inner is None else inner


@dataclasses.dataclass(frozen=True)
class CompactSchedule:
    """Globally-live steps of a skip-masked schedule (DESIGN.md §4.4).

    A schedule step is *globally dead* when ``step_keep`` is False on
    every device — no device can contribute, so the whole scan iteration
    (cond *and* collective) is removable.  The compacted engine executes
    only ``live_steps`` (original step indices, strictly increasing),
    replacing the elided unit shifts with fused multi-hop ``ppermute``\\ s
    whose distances are :attr:`hops`.  Keeping a dead step live is always
    correct (its count is provably zero), so any superset of the true
    live set is a valid ``live_steps`` — the stepper tests rely on this.
    """

    n_total: int  # schedule steps before compaction
    live_steps: Tuple[int, ...]  # original indices of the kept steps

    @property
    def n_live(self) -> int:
        return len(self.live_steps)

    @property
    def n_elided(self) -> int:
        return self.n_total - self.n_live

    @property
    def hops(self) -> Tuple[int, ...]:
        """Fused shift distances: ``hops[0]`` is the prologue hop from
        the initial placement to the first live step; ``hops[i]`` moves
        live step ``i-1``'s payload to live step ``i``."""
        prev, out = 0, []
        for s in self.live_steps:
            out.append(s - prev)
            prev = s
        return tuple(out)


def compact_live_steps(step_keep: np.ndarray) -> CompactSchedule:
    """Derive the compacted schedule from a staged skip mask.

    ``step_keep`` is any ``(..., nsteps)`` per-(device, step) bool array;
    a step survives iff *any* device keeps it.
    """
    keep = np.asarray(step_keep, dtype=bool)
    nsteps = keep.shape[-1]
    live = np.flatnonzero(keep.reshape(-1, nsteps).any(axis=0))
    return CompactSchedule(
        n_total=int(nsteps), live_steps=tuple(int(s) for s in live)
    )


def resolve_compact_steps(
    plan, compact, *, batched: bool = False, npods: int = 1
) -> Optional[Tuple[int, ...]]:
    """Resolve a builder's ``compact`` request against the plan.

    ``None`` auto-enables compaction iff the planner staged a
    :class:`CompactSchedule` that actually elides something and the
    build is a plain (non-batched, single-pod) engine — batched engines
    take the union of per-graph masks (not staged) and multi-pod runs
    stride the mask per pod, so both keep the uniform scan body.  An
    explicit ``True`` that cannot be honored is an error.
    """
    cs = getattr(as_plan(plan), "compact", None)
    if compact is None:
        if cs is None or batched or npods != 1 or cs.n_elided == 0:
            return None
    elif not compact:
        return None
    else:
        if cs is None:
            raise ValueError(
                "plan carries no compacted schedule; re-plan through the "
                "pipeline with step_masks=True (or leave compact=None)"
            )
        if batched or npods != 1:
            raise ValueError(
                "compact=True is not supported for batched or multi-pod "
                "engines; pass compact=False (or None for auto)"
            )
    return cs.live_steps


def resolve_broadcast(plan, broadcast, *, batched: bool = False) -> str:
    """Resolve a SUMMA builder's ``broadcast`` request against the plan.

    ``None`` defers to the strategy the plan was staged for (its
    ``broadcast`` field; ``"auto"`` for plans predating the knob).
    ``"auto"`` resolves to the ppermute ``"chain"`` for plain engines —
    half the one-hot psum's bytes, DESIGN.md §4.5 — and to ``"onehot"``
    for batched ones: chain rounds need static round indices (ppermute
    pairs are trace constants), i.e. the unrolled body, which the
    batched engine's shared scan rules out.  An explicit ``"chain"``
    that cannot be honored is an error.
    """
    b = broadcast
    if b is None:
        b = getattr(as_plan(plan), "broadcast", None) or "auto"
    if b == "auto":
        return "onehot" if batched else "chain"
    if b not in ("onehot", "chain"):
        raise ValueError(
            f"unknown broadcast strategy {b!r}; "
            "expected 'onehot', 'chain', or 'auto'"
        )
    if b == "chain" and batched:
        raise ValueError(
            "broadcast='chain' is not supported for batched engines "
            "(chain rounds need the unrolled body); pass 'onehot' "
            "(or 'auto')"
        )
    return b


def resolve_step_mask(plan, use_step_mask) -> bool:
    """Resolve a builder's ``use_step_mask`` request against the plan.

    ``None`` auto-enables skipping iff the planner staged ``step_keep``
    masks; an explicit ``True`` on a mask-less plan is an error (the
    engine would have nothing to consume).
    """
    has = getattr(plan, "step_keep", None) is not None
    if use_step_mask is None:
        return has
    if use_step_mask and not has:
        raise ValueError(
            "plan carries no step_keep masks; re-plan with step_masks=True"
        )
    return bool(use_step_mask)

INT = np.int32

# plan-shape ladder: a block's padded length is a multiple of 2^(b - 7)
# for b = ceil(log2 nnz) (< 1/64 added), a fragment's padded width a
# multiple of 8 (one sublane tile) that is not a whole number of 128-lane
# rows
_NNZ_STEPS = 1 << 7
_DPAD_STEP = 8
_LANES = 128


def ladder_nnz(nnz: int) -> int:
    """Padded length of a block holding at most ``nnz`` entries: ``nnz``
    rounded up to a multiple of ``2^(ceil(log2 nnz) - 7)``.

    The static shapes of a Cannon plan come from this ladder, not from
    the exact maxima, so graphs of one size class (the relabelings of
    one graph among them, whose per-block maxima differ by a few
    entries) share one compiled program."""
    nnz = max(1, int(nnz))
    step = max(1, (1 << (nnz - 1).bit_length()) // _NNZ_STEPS)
    return -(-nnz // step) * step


def ladder_dpad(dmax: int) -> int:
    """The count kernels' padded fragment width for a longest fragment
    of ``dmax``: rounded up to a multiple of 8, one step further where
    that is a multiple of 128.

    On a TPU v5e, ``search``'s kernel at a width of whole 128-lane rows
    ran 2.1x slower at 128 than at 129 and 1.5x slower at 256 than at
    248 (one block of ~896k tasks), so those widths are skipped:
    fragments of 121 to 136 all pad to 136."""
    dpad = -(-max(1, int(dmax)) // _DPAD_STEP) * _DPAD_STEP
    return dpad + _DPAD_STEP if dpad % _LANES == 0 else dpad


def ladder_task_share(nnz_max: int, tmax: int) -> float:
    """Tasks the ladder adds per device, over the largest block's."""
    return float(tmax / max(1, nnz_max) - 1.0)


def ladder_dpad2_share(dmax: int) -> float:
    """``dpad²`` work the ladder adds per task, over ``dmax²``."""
    dmax = max(1, int(dmax))
    return float((ladder_dpad(dmax) / dmax) ** 2 - 1.0)


def host_aug_keys(
    indptr: np.ndarray, indices: np.ndarray
) -> Optional[np.ndarray]:
    """Host-side row-encoded intersection keys for stacked CSR blocks.

    The numpy twin of :func:`repro.core.count.build_aug_keys`, applied
    once per block at pack time: for every ``(..., nb + 1)`` indptr /
    ``(..., nnz_pad)`` indices pair, emits ``aug[e] = row(e) * (nb + 1)
    + col(e)`` with padding positions landing on the maximal key (their
    row resolves past the last row and their column holds the ``nb``
    sentinel), so each block's key array is sorted exactly like the
    on-device build.  Returns ``None`` when the key range needs int64
    but x64 is off (the device copy would be silently truncated) — the
    kernels then fall back to building keys on device, which fails
    loudly via :func:`~repro.core.count.aug_key_dtype`.
    """
    from .count import aug_key_dtype

    nb = indptr.shape[-1] - 1
    base = nb + 1
    try:
        key_dtype = np.dtype(aug_key_dtype(base))
    except OverflowError:
        return None
    flat_ptr = indptr.reshape(-1, nb + 1)
    flat_idx = indices.reshape(-1, indices.shape[-1])
    nnz_pad = flat_idx.shape[1]
    # row of entry e per block: searchsorted(indptr, e, 'right') - 1,
    # vectorized over blocks (indptr rows are independently sorted)
    e = np.arange(nnz_pad, dtype=np.int64)
    row_of = (
        np.apply_along_axis(np.searchsorted, 1, flat_ptr, e, side="right")
        - 1
    )
    aug = row_of.astype(key_dtype) * base + flat_idx.astype(key_dtype)
    return aug.reshape(indices.shape)


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,), fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


@dataclasses.dataclass
class PlanStats:
    """Balance statistics (paper Tables 3/4 analogues), host-computed."""

    tasks_per_device: np.ndarray  # (q, q) int64 — nonzero tasks owned
    nnz_per_block: np.ndarray  # (q, q) int64
    probe_work_per_device_shift: np.ndarray  # (q, q, q) int64
    task_imbalance: float  # max/avg of tasks_per_device
    probe_imbalance: float  # max/avg of per-shift probe work
    intersection_tasks_total: int  # paper Table 4 metric
    padding_fraction_indices: float
    padding_fraction_tasks: float
    # per-(device, shift) intersection-task counts (the summands of
    # ``intersection_tasks_total``).  Staged so the delta path
    # (DESIGN.md §4.7) can update the total exactly from dirty cells
    # alone; None on plans packed by the loop reference.
    itasks_per_cell: Optional[np.ndarray] = None  # (q, q, q) int64
    # work the shape ladder adds (:func:`ladder_nnz`, :func:`ladder_dpad`):
    # padded tasks over the largest block's, and the kernel's padded
    # dpad² over dmax², each minus one
    ladder_task_share: float = 0.0
    ladder_dpad2_share: float = 0.0


@dataclasses.dataclass
class StepStats:
    """Per-(device, step) probe work for the non-Cannon schedules.

    The lean sibling of :class:`PlanStats`: just enough for the
    skip-aware rebalancer's masked-critical-path cost model (DESIGN.md
    §4.3) — SUMMA broadcast rounds carry a ``(r, c, c)`` array, the 1D
    ring a ``(p, p)`` one; the last axis is always the schedule step.
    """

    probe_work_per_device_shift: np.ndarray  # (..., nsteps) int64
    probe_imbalance: float  # max/avg of per-device total probe work


@dataclasses.dataclass
class TCPlan:
    """Device-ready arrays + metadata for one grid factorization."""

    n: int
    m: int
    q: int  # square grid dimension (Cannon); SUMMA reuses q x q here
    nb: int  # local rows/cols per block = ceil(n / q)
    nnz_pad: int  # padded nnz per block (ladder_nnz of the largest)
    tmax: int  # padded tasks per device
    dmax: int  # max adjacency-fragment length over all blocks
    chunk: int  # tasks per searchsorted chunk

    # stacked [q, q, ...] arrays; *_indptr (q,q,nb+1), *_indices (q,q,nnz_pad)
    a_indptr: np.ndarray
    a_indices: np.ndarray
    b_indptr: np.ndarray
    b_indices: np.ndarray
    m_ti: np.ndarray  # (q, q, tmax) task row (local i)
    m_tj: np.ndarray  # (q, q, tmax) task row of B (local j)
    m_cnt: np.ndarray  # (q, q) valid task count

    stats: Optional[PlanStats] = None
    # canonical (un-skewed) blocks kept for SUMMA / 1D comparisons
    blocks: Optional[List[List[BlockCSR]]] = None
    # (q, q, q) bool per-(device, shift) skip mask: True = the incoming
    # block pair can contribute (sparsity-aware step skipping); None for
    # un-skewed (SUMMA-placement) or analytic plans
    step_keep: Optional[np.ndarray] = None
    # (q, q, nnz_pad) host-staged row-encoded intersection keys of the B
    # placement (DESIGN.md §5) — shifted alongside the B blob so the
    # global/search2 kernels skip the per-step on-device key build
    b_aug: Optional[np.ndarray] = None
    # visit-order permutation σ of Cannon's initial alignment: step s
    # hands device (x, y) the k-panel z = σ[(x + y + s) % q] (identity
    # when None).  Chosen by the compaction stage to concentrate live
    # work onto few steps (DESIGN.md §4.4).
    skew_perm: Optional[Tuple[int, ...]] = None
    # globally-live steps + fused hop vector (compaction stage)
    compact: Optional[CompactSchedule] = None
    # deterministic kernel-shape autotune report (chunk, d_small/n_long,
    # tail_heavy) when the plan went through the autotune stage
    autotune: Optional[dict] = None
    # long/short task split from bucketize_plan / the autotune stage:
    # the first ``n_long`` tasks on every device need probes padded to
    # dmax, the rest fit in ``d_small``.  None = plan not bucketized.
    n_long: Optional[int] = None
    d_small: Optional[int] = None
    # padded-probe waste accounting from bucketize_plan
    bucket_stats: Optional[dict] = None
    # hub-split side (repro.pipeline.hubsplit.HubSide) when the planner
    # split the heavy-tailed suffix off the 2D path (DESIGN.md §4.8);
    # its arrays join device_arrays() and the engine folds its partial
    # into the reduction.  The plan's own arrays then cover only the
    # residual graph.
    hub: Optional[object] = None

    # ------------------------------------------------------------------
    @property
    def dpad(self) -> int:
        """The count kernels' padded fragment width: ``dmax`` up the
        shape ladder (:func:`ladder_dpad`)."""
        return ladder_dpad(self.dmax)

    def shape_key(self) -> Tuple:
        """Everything of the plan a compiled Cannon engine depends on:
        the device arrays' shapes and dtypes and the kernel's static
        parameters.  Plans with equal keys run one compiled program."""
        hub = self.hub
        return (
            self.q,
            self.dpad,
            self.chunk,
            self.n_long,
            self.d_small,
            self.compact.live_steps if self.compact is not None else None,
            None if hub is None else (hub.dpad, hub.chunk, hub.sentinel),
            tuple(
                (k, v.shape, v.dtype.str)
                for k, v in sorted(self.device_arrays().items())
            ),
        )

    def device_arrays(self) -> Dict[str, np.ndarray]:
        out = dict(
            a_indptr=self.a_indptr,
            a_indices=self.a_indices,
            b_indptr=self.b_indptr,
            b_indices=self.b_indices,
            m_ti=self.m_ti,
            m_tj=self.m_tj,
            m_cnt=self.m_cnt,
        )
        if self.step_keep is not None:
            out["step_keep"] = self.step_keep
        if self.b_aug is not None:
            out["b_aug"] = self.b_aug
        if self.hub is not None:
            out.update(self.hub.device_arrays())
        return out

    def shape_structs(self):
        """jax.ShapeDtypeStruct stand-ins for every device array.

        For analytic (shape-only) plans this reflects the *padded* sizes
        without ever allocating them.
        """
        import jax

        shape_only = getattr(self, "_shape_only", None)
        if shape_only is not None:
            return {
                k: jax.ShapeDtypeStruct(shape, dtype)
                for k, (shape, dtype) in shape_only.items()
            }
        return {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in self.device_arrays().items()
        }

    def dense_blocks(self) -> Dict[str, np.ndarray]:
        """Materialize dense block operands (oracle path, small n only)."""
        q, nb = self.q, self.nb
        a = np.zeros((q, q, nb, nb), dtype=np.float32)
        b = np.zeros((q, q, nb, nb), dtype=np.float32)
        msk = np.zeros((q, q, nb, nb), dtype=np.float32)
        for x in range(q):
            for y in range(q):
                for name, arr in (("a", a), ("b", b)):
                    indptr = getattr(self, f"{name}_indptr")[x, y]
                    indices = getattr(self, f"{name}_indices")[x, y]
                    for r in range(nb):
                        lo, hi = indptr[r], indptr[r + 1]
                        cols = indices[lo:hi]
                        arr[x, y, r, cols] = 1.0
                cnt = self.m_cnt[x, y]
                msk[x, y, self.m_ti[x, y, :cnt], self.m_tj[x, y, :cnt]] = 1.0
        out = dict(a_dense=a, b_dense=b, m_dense=msk)
        if self.step_keep is not None:
            out["step_keep"] = self.step_keep
        return out


def _stack_blocks(
    blocks: List[List[BlockCSR]],
    placement,  # (x, y) -> BlockCSR
    q: int,
    nb: int,
    nnz_pad: int,
) -> Tuple[np.ndarray, np.ndarray]:
    indptr = np.zeros((q, q, nb + 1), dtype=INT)
    indices = np.zeros((q, q, nnz_pad), dtype=INT)
    for x in range(q):
        for y in range(q):
            blk = placement(x, y)
            indptr[x, y] = blk.indptr.astype(INT)
            indices[x, y, : blk.nnz] = blk.indices.astype(INT)
            indices[x, y, blk.nnz :] = nb  # sentinel beyond any local col
    return indptr, indices


def build_plan(
    graph: Graph,
    q: int,
    *,
    skew: bool = True,
    chunk: int = 512,
    with_stats: bool = True,
    keep_blocks: bool = True,
    step_masks: bool = True,
    skew_perm: Optional[Tuple[int, ...]] = None,
    aug_keys: bool = False,
) -> TCPlan:
    """Plan the 2D-cyclic execution of a *degree-ordered* graph on q x q.

    ``skew=True`` applies Cannon's initial alignment at placement time;
    ``skew=False`` yields the canonical placement used by SUMMA (A at
    ``(x, y) -> U_{x,y}``, B at ``(x, y) -> U_{y,x}``).  ``skew_perm``
    generalizes the alignment with a visit-order permutation σ (device
    ``(x, y)`` sees panel ``z = σ[(x+y+s) % q]`` at step ``s`` — any σ
    is a correct Cannon schedule; the compaction stage picks one that
    concentrates live work, DESIGN.md §4.4).  ``aug_keys`` stages the
    row-encoded B intersection keys host-side for the global/search2
    kernels.

    The implementation is the pipeline's vectorized packer
    (:func:`repro.pipeline.stages.pack_tc_plan`): one lexsorted pass
    emits the stacked arrays directly.  :func:`_build_plan_loops` keeps
    the original per-block loop semantics as the byte-level reference
    the packer is tested against.
    """
    from ..pipeline.stages import pack_tc_plan

    return pack_tc_plan(
        graph,
        q,
        skew=skew,
        chunk=chunk,
        with_stats=with_stats,
        keep_blocks=keep_blocks,
        step_masks=step_masks,
        skew_perm=skew_perm,
        aug_keys=aug_keys,
    )


def _build_plan_loops(
    graph: Graph,
    q: int,
    *,
    skew: bool = True,
    chunk: int = 512,
    with_stats: bool = True,
    keep_blocks: bool = True,
    step_masks: bool = True,
    skew_perm: Optional[Tuple[int, ...]] = None,
    aug_keys: bool = False,
) -> TCPlan:
    """Loop-based reference planner (the pre-pipeline implementation).

    Retained verbatim so ``tests/test_pipeline.py`` can pin the
    vectorized packer to byte-identical output; not used on any runtime
    path.
    """
    n, m = graph.n, graph.m
    nb = -(-n // q)
    blocks = cyclic_blocks(graph, q, q)

    nnz_max = max(blocks[x][y].nnz for x in range(q) for y in range(q))
    nnz_pad = ladder_nnz(nnz_max)
    tmax = nnz_pad  # tasks per device == nnz of its mask block

    assert skew_perm is None or skew, "skew_perm is a Cannon-placement knob"
    sp = list(skew_perm) if skew_perm is not None else list(range(q))
    if skew:
        a_place = lambda x, y: blocks[x][sp[(x + y) % q]]
        b_place = lambda x, y: blocks[y][sp[(x + y) % q]]
    else:
        a_place = lambda x, y: blocks[x][y]
        b_place = lambda x, y: blocks[y][x]

    a_indptr, a_indices = _stack_blocks(blocks, a_place, q, nb, nnz_pad)
    b_indptr, b_indices = _stack_blocks(blocks, b_place, q, nb, nnz_pad)

    m_ti = np.zeros((q, q, tmax), dtype=INT)
    m_tj = np.full((q, q, tmax), 0, dtype=INT)
    m_cnt = np.zeros((q, q), dtype=INT)
    for x in range(q):
        for y in range(q):
            blk = blocks[x][y]
            # expand CSR -> COO (ti = local i in grid-row x, tj = local j in
            # grid-row y of the B operand; j's *local* index is j // q which
            # is exactly the stored column's block-local row id)
            rows = np.repeat(
                np.arange(blk.n_rows, dtype=INT), np.diff(blk.indptr)
            )
            cols = blk.indices.astype(INT)
            m_ti[x, y, : rows.shape[0]] = rows
            m_tj[x, y, : cols.shape[0]] = cols
            m_cnt[x, y] = rows.shape[0]

    dmax = max(1, max(blocks[x][y].max_row_len() for x in range(q) for y in range(q)))

    probe = None
    stats = None
    if with_stats:
        tasks = np.array(
            [[blocks[x][y].nnz for y in range(q)] for x in range(q)],
            dtype=np.int64,
        )
        # probe work per (x, y, shift): for each task (i, j) with both
        # fragments non-empty, the map-based intersection is "performed"
        # (paper Table 4 counts these tasks; we also weight by min-fragment
        # length for the imbalance measure of Table 3).
        probe = np.zeros((q, q, q), dtype=np.int64)
        itasks = 0
        rowlen = {
            (x, y): np.diff(blocks[x][y].indptr) for x in range(q) for y in range(q)
        }
        for x in range(q):
            for y in range(q):
                blk = blocks[x][y]
                rows = np.repeat(np.arange(blk.n_rows), np.diff(blk.indptr))
                cols = blk.indices
                for s in range(q):
                    z = sp[(x + y + s) % q] if skew else (x + y + s) % q
                    la = rowlen[(x, z)][rows]
                    lb = rowlen[(y, z)][cols]
                    both = (la > 0) & (lb > 0)
                    itasks += int(both.sum())
                    probe[x, y, s] = int(np.minimum(la, lb)[both].sum())
        tot_idx = q * q * nnz_pad
        stats = PlanStats(
            tasks_per_device=tasks,
            nnz_per_block=tasks.copy(),
            probe_work_per_device_shift=probe,
            task_imbalance=float(tasks.max() / max(1.0, tasks.mean())),
            probe_imbalance=float(
                probe.sum(axis=2).max() / max(1.0, probe.sum(axis=2).mean())
            ),
            intersection_tasks_total=itasks,
            padding_fraction_indices=float(1.0 - m / max(1, tot_idx)),
            padding_fraction_tasks=float(1.0 - m / max(1, q * q * tmax)),
            ladder_task_share=ladder_task_share(nnz_max, tmax),
            ladder_dpad2_share=ladder_dpad2_share(dmax),
        )

    # per-(device, shift) skip mask — loop reference of the vectorized
    # derivation in pipeline.stages (see DESIGN.md §4): device (x, y) at
    # shift s holds A = U_{x,z} and B = U_{y,z} with z = (x+y+s) % q, so
    # the step contributes only if the task list and both incoming
    # blocks are non-empty (refined to exact per-shift probe work when
    # stats were computed).
    step_keep = None
    if skew and step_masks:
        step_keep = np.zeros((q, q, q), dtype=bool)
        for x in range(q):
            for y in range(q):
                for s in range(q):
                    z = sp[(x + y + s) % q]
                    k = (
                        m_cnt[x, y] > 0
                        and blocks[x][z].nnz > 0
                        and blocks[y][z].nnz > 0
                    )
                    if probe is not None:
                        k = k and probe[x, y, s] > 0
                    step_keep[x, y, s] = k

    b_aug = host_aug_keys(b_indptr, b_indices) if aug_keys else None

    return TCPlan(
        n=n,
        m=m,
        q=q,
        nb=nb,
        nnz_pad=nnz_pad,
        tmax=tmax,
        dmax=dmax,
        chunk=min(chunk, tmax),
        a_indptr=a_indptr,
        a_indices=a_indices,
        b_indptr=b_indptr,
        b_indices=b_indices,
        m_ti=m_ti,
        m_tj=m_tj,
        m_cnt=m_cnt,
        stats=stats,
        blocks=blocks if keep_blocks else None,
        step_keep=step_keep,
        b_aug=b_aug,
        skew_perm=tuple(sp) if skew_perm is not None else None,
    )


def bucketize_plan(plan: TCPlan, d_small: int = 32) -> TCPlan:
    """§Perf H1a: statically reorder each device's tasks into long|short.

    A task is *long* iff under ANY Cannon pairing its probe needs padding
    beyond ``d_small`` (max over shifts of min-fragment length).  The
    planner reorders (m_ti, m_tj) so long tasks come first and records the
    per-plan maximum long-count; the two-level count path then runs long
    chunks at ``dmax`` and the rest at ``d_small``, eliminating the
    ``dmax / avg_len`` padded-probe waste on power-law graphs.
    Returns a new plan with ``n_long``/``d_small`` attributes set.
    """
    plan = as_plan(plan)
    assert plan.blocks is not None
    q = plan.q
    rowlen = {
        (x, y): np.diff(plan.blocks[x][y].indptr)
        for x in range(q)
        for y in range(q)
    }
    m_ti = plan.m_ti.copy()
    m_tj = plan.m_tj.copy()
    n_long_max = 0
    waste_before = 0
    waste_after = 0
    for x in range(q):
        for y in range(q):
            cnt = int(plan.m_cnt[x, y])
            ti = plan.m_ti[x, y, :cnt]
            tj = plan.m_tj[x, y, :cnt]
            # probe side is the A fragment (row i); keys side is searched
            # globally and needs no padding (count_pair_search_global)
            need = np.zeros(cnt, dtype=np.int64)
            for z in range(q):
                need = np.maximum(need, rowlen[(x, z)][ti])
            long_mask = need > d_small
            order = np.argsort(~long_mask, kind="stable")  # long first
            m_ti[x, y, :cnt] = ti[order]
            m_tj[x, y, :cnt] = tj[order]
            n_long = int(long_mask.sum())
            n_long_max = max(n_long_max, n_long)
            waste_before += cnt * plan.dmax
            waste_after += n_long * plan.dmax + (cnt - n_long) * d_small
    return dataclasses.replace(
        plan,
        m_ti=m_ti,
        m_tj=m_tj,
        n_long=n_long_max,
        d_small=d_small,
        bucket_stats=dict(
            padded_probe_before=float(waste_before * q),  # x shifts
            padded_probe_after=float(waste_after * q),
            reduction=float(waste_before / max(1, waste_after)),
        ),
    )


def analytic_plan(
    n: int,
    m: int,
    q: int,
    *,
    dmax_block: int,
    nnz_slack: float = 1.25,
    chunk: int = 512,
    name: str = "analytic",
) -> TCPlan:
    """Shape-only plan for dry runs on graphs too large to materialize.

    Uses the paper's balance argument (cyclic distribution => per-block nnz
    ~ m / p with small slack; Table 3 measured <= 6% imbalance, we budget
    ``nnz_slack``) to size the padded arrays.  Arrays are allocated as
    zero-filled placeholders only if requested via ``device_arrays``; dry
    runs should use :meth:`TCPlan.shape_structs` (no allocation).
    """
    nb = -(-n // q)
    nnz_pad = max(1, int(np.ceil(m / (q * q) * nnz_slack)))
    tmax = nnz_pad
    empty = np.zeros((q, q, 0), dtype=INT)
    plan = TCPlan(
        n=n,
        m=m,
        q=q,
        nb=nb,
        nnz_pad=nnz_pad,
        tmax=tmax,
        dmax=max(1, dmax_block),
        chunk=min(chunk, tmax),
        a_indptr=empty,
        a_indices=empty,
        b_indptr=empty,
        b_indices=empty,
        m_ti=empty,
        m_tj=empty,
        m_cnt=np.zeros((q, q), dtype=INT),
        stats=None,
        blocks=None,
    )
    plan._shape_only = dict(  # type: ignore[attr-defined]
        a_indptr=((q, q, nb + 1), INT),
        a_indices=((q, q, nnz_pad), INT),
        b_indptr=((q, q, nb + 1), INT),
        b_indices=((q, q, nnz_pad), INT),
        m_ti=((q, q, tmax), INT),
        m_tj=((q, q, tmax), INT),
        m_cnt=((q, q), INT),
    )
    return plan
