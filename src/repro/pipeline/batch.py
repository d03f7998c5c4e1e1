"""Batched front-end: count many graphs in one compiled engine call.

``count_triangles_many`` pads a list of graphs onto shared shapes —
vertex counts lifted to the batch maximum (isolated vertices are free),
index/task arrays padded to the batch-wide maxima — stacks every device
array on an unsharded leading batch axis, and runs the whole batch
through the engine's batched builder: **one** compile and **one**
dispatch for the batch, versus one of each per graph in a Python loop.

The assembled program (stacked staged arrays + compiled fn) is itself
cached under the tuple of graph digests, so a serving process that sees
the same batch again skips planning, padding, staging, *and* retracing.
The padding overhead of batching is measured and reported
(``ManyResult.padding_overhead``, DESIGN.md §10.5), never hidden.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import compat
from ..core.graph import Graph
from ..core.spans import count_scope, launch, span
from .cache import PlanCache, default_cache, digest_memoized, graph_digest
from .planner import relabel_cached
from .stages import pack_oned_plan, pack_summa_plan, pack_tc_plan

__all__ = ["ManyResult", "count_triangles_many"]

_CSR_METHODS = ("search", "search2", "global")


@dataclasses.dataclass
class ManyResult:
    """Per-graph triangle counts plus batch accounting."""

    triangles: List[int]
    schedule: str
    method: str
    grid: tuple
    batch: int
    plan_seconds: float  # planning + padding + staging (0-ish on cache hit)
    count_seconds: float
    padding_overhead: float  # stacked cells / sum(per-graph cells) - 1
    cache_hit: bool


@dataclasses.dataclass
class _BatchProgram:
    fn: object
    staged: Dict
    grid: tuple
    padding_overhead: float


def _pad_last(arr: np.ndarray, size: int, fill) -> np.ndarray:
    """Pad the last axis of ``arr`` up to ``size`` with ``fill``."""
    if arr.shape[-1] == size:
        return arr
    out = np.full(arr.shape[:-1] + (size,), fill, dtype=arr.dtype)
    out[..., : arr.shape[-1]] = arr
    return out


def _stack(plans, pads: Dict[str, tuple]) -> Dict[str, np.ndarray]:
    """Stack per-graph device arrays, padding each named array's last
    axis to the batch-wide size with its sentinel/zero fill."""
    out = {}
    for name, (size, fill) in pads.items():
        out[name] = np.stack(
            [_pad_last(p.device_arrays()[name], size, fill) for p in plans]
        )
    return out


def _padding_overhead(stacked: Dict, plans) -> float:
    batched = sum(v.size for v in stacked.values())
    single = sum(
        a.size for p in plans for a in p.device_arrays().values()
    )
    return float(batched / max(1, single) - 1.0)


def _lifted(graphs, digests, *, reorder, cyclic_p, cache) -> List[Graph]:
    """Relabel each graph on its own vertex set (degree order must not
    see the padding vertices), then lift all graphs to the shared n."""
    relabeled = [
        relabel_cached(
            g, d, reorder=reorder, cyclic_p=cyclic_p, cache=cache
        )[0]
        for g, d in zip(graphs, digests)
    ]
    n_max = max(g.n for g in relabeled)
    return [
        g if g.n == n_max else Graph(n=n_max, edges=g.edges, name=g.name)
        for g in relabeled
    ]


def _build_batch_program(
    lifted: Sequence[Graph],
    mesh,
    *,
    q: int,
    schedule: str,
    method: str,
    chunk: int,
    probe_shorter: bool,
    count_dtype,
):
    """Pack the lifted graphs on shared shapes and build the batched
    engine: ``(fn, stacked host arrays, grid, padding overhead)``."""
    n_max = lifted[0].n
    if schedule == "cannon":
        from ..core.cannon import build_cannon_fn
        from ..core.plan import bucketize_plan

        plans = [
            pack_tc_plan(
                g, q, skew=True, chunk=chunk, with_stats=False,
                keep_blocks=(method == "search2"),
                aug_keys=(method in ("global", "search2")),
            )
            for g in lifted
        ]
        if method == "search2":
            plans = [bucketize_plan(p) for p in plans]
        nnz_pad = max(p.nnz_pad for p in plans)
        tmax = max(p.tmax for p in plans)
        nb = plans[0].nb
        pads = dict(
            a_indptr=(nb + 1, 0),
            a_indices=(nnz_pad, nb),
            b_indptr=(nb + 1, 0),
            b_indices=(nnz_pad, nb),
            m_ti=(tmax, 0),
            m_tj=(tmax, 0),
            m_cnt=(plans[0].m_cnt.shape[-1], 0),
        )
        if plans[0].step_keep is not None:
            pads["step_keep"] = (q, False)  # (q, q, q) per graph, same q
        if plans[0].b_aug is not None:
            # tail-pad with the maximal key (row nb, col nb) so every
            # block's staged key array stays sorted after batch padding
            pads["b_aug"] = (nnz_pad, (nb + 1) * (nb + 1) - 1)
        stacked = _stack(plans, pads)
        rep = dataclasses.replace(
            plans[0],
            nnz_pad=nnz_pad,
            tmax=tmax,
            dmax=max(p.dmax for p in plans),
            chunk=min(chunk, tmax),
            stats=None,
            blocks=None,
        )
        if method == "search2":
            rep.n_long = max(p.n_long for p in plans)
            rep.d_small = plans[0].d_small
        fn = build_cannon_fn(
            rep, mesh, method=method, probe_shorter=probe_shorter,
            count_dtype=count_dtype, batched=True,
        )
        grid = (q, q)
    elif schedule == "summa":
        from ..core.summa import build_summa_fn

        names = list(mesh.axis_names)
        r, c = mesh.shape[names[-2]], mesh.shape[names[-1]]
        plans = [pack_summa_plan(g, r, c, chunk=chunk) for g in lifted]
        a_nnz_pad = max(p.a_nnz_pad for p in plans)
        b_nnz_pad = max(p.b_nnz_pad for p in plans)
        tmax = max(p.tmax for p in plans)
        nb_c = plans[0].nb_c
        pads = dict(
            a_indptr=(plans[0].nb_r + 1, 0),
            a_indices=(a_nnz_pad, nb_c),
            b_indptr=(nb_c + 1, 0),
            b_indices=(b_nnz_pad, nb_c),
            m_ti=(tmax, 0),
            m_tj=(tmax, 0),
            m_cnt=(plans[0].m_cnt.shape[-1], 0),
        )
        if plans[0].step_keep is not None:
            pads["step_keep"] = (c, False)  # (r, c, c) per graph
        stacked = _stack(plans, pads)
        rep = dataclasses.replace(
            plans[0],
            a_nnz_pad=a_nnz_pad,
            b_nnz_pad=b_nnz_pad,
            tmax=tmax,
            dmax=max(p.dmax for p in plans),
            chunk=min(chunk, tmax),
        )
        fn = build_summa_fn(
            rep, mesh, method=method, probe_shorter=probe_shorter,
            count_dtype=count_dtype, batched=True,
        )
        grid = (r, c)
    elif schedule == "oned":
        from ..core.onedim import build_oned_fn

        p_ring = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        flat_mesh = compat.make_mesh((p_ring,), ("flat",))
        plans = [pack_oned_plan(g, p_ring, chunk=chunk) for g in lifted]
        nnz_pad = max(p.nnz_pad for p in plans)
        gmax = max(p.gmax for p in plans)
        pads = dict(
            indptr=(plans[0].nb + 1, 0),
            indices=(nnz_pad, n_max + 1),
            t_i=(gmax, 0),
            t_j=(gmax, 0),
            t_cnt=(plans[0].t_cnt.shape[-1], 0),
        )
        if plans[0].step_keep is not None:
            pads["step_keep"] = (p_ring, False)  # (p, p) per graph
        stacked = _stack(plans, pads)
        rep = dataclasses.replace(
            plans[0],
            nnz_pad=nnz_pad,
            gmax=gmax,
            dmax=max(p.dmax for p in plans),
            chunk=min(chunk, gmax),
        )
        fn = build_oned_fn(
            rep, flat_mesh, method=method, probe_shorter=probe_shorter,
            count_dtype=count_dtype, batched=True,
        )
        grid = (p_ring,)
    else:
        raise ValueError(
            f"count_triangles_many supports schedules cannon/summa/oned, "
            f"got {schedule!r}"
        )

    return fn, stacked, grid, _padding_overhead(stacked, plans)


def _plan_batch(
    graphs, digests, mesh, *, reorder, cyclic_p, cache, **build
) -> _BatchProgram:
    """A batch's program on a plan-cache miss: relabel, pack and build,
    then stage the stacked arrays."""
    import jax.numpy as jnp

    with span("tc.plan.relabel"):
        lifted = _lifted(
            graphs, digests, reorder=reorder, cyclic_p=cyclic_p, cache=cache
        )
    with span("tc.plan.pack"):
        fn, stacked, grid, overhead = _build_batch_program(
            lifted, mesh, **build
        )
    with span("tc.stage"):
        staged = {k: jnp.asarray(v) for k, v in stacked.items()}
    return _BatchProgram(
        fn=fn, staged=staged, grid=grid, padding_overhead=overhead
    )


@count_scope()
def count_triangles_many(
    graphs: Sequence[Graph],
    mesh=None,
    *,
    q: Optional[int] = None,
    schedule: str = "cannon",
    method: str = "search",
    chunk: int = 512,
    reorder: bool = True,
    cyclic_p: Optional[int] = None,
    probe_shorter: bool = True,
    count_dtype=None,
    cache: Optional[PlanCache] = None,
) -> ManyResult:
    """Count triangles of many graphs with one compiled engine call.

    Results are exactly the per-graph ``count_triangles`` totals (padding
    to shared shapes never changes a count, only adds measured overhead).
    ``method`` must be a CSR kernel (``search``/``search2``/``global``);
    the dense and tile operand stores are per-graph paths.
    """
    graphs = list(graphs)
    assert graphs, "count_triangles_many needs at least one graph"
    if method not in _CSR_METHODS:
        raise ValueError(
            f"batched counting supports CSR methods {_CSR_METHODS}, "
            f"got {method!r}"
        )
    if method == "search2" and schedule != "cannon":
        raise ValueError("method 'search2' is a cannon-schedule path")

    from ..runtime import faultinject

    faultinject.fire("plan_stage", kind="many")
    seconds: Dict[str, float] = {}
    with span("tc.plan", seconds, "plan"):
        if mesh is None:
            from ..core.api import make_grid_mesh

            q = q or 1
            mesh = make_grid_mesh(q)
        else:
            names = list(mesh.axis_names)
            q = mesh.shape[names[-1]]
        if count_dtype is None:
            count_dtype = compat.default_count_dtype()
        cache = cache if cache is not None else default_cache()

        memo = int(all(digest_memoized(g) for g in graphs))
        with span("tc.plan.digest", memo=memo):
            digests = tuple(graph_digest(g) for g in graphs)
        key = (
            "many", schedule, method, mesh, q, chunk, reorder, cyclic_p,
            probe_shorter, str(np.dtype(count_dtype)), digests,
        )
        prog = cache.get(key)
        cache_hit = prog is not None
        if not cache_hit:
            prog = _plan_batch(
                graphs, digests, mesh,
                q=q, schedule=schedule, method=method, chunk=chunk,
                reorder=reorder, cyclic_p=cyclic_p,
                probe_shorter=probe_shorter, count_dtype=count_dtype,
                cache=cache,
            )
            cache.put(key, prog)

    faultinject.fire("device_stage")
    totals = launch(prog.fn, prog.staged, seconds, fetch=np.asarray)
    counts = [
        compat.check_count_overflow(int(t), count_dtype) for t in totals
    ]

    return ManyResult(
        triangles=counts,
        schedule=schedule,
        method=method,
        grid=prog.grid,
        batch=len(graphs),
        plan_seconds=seconds["plan"],
        count_seconds=seconds["dispatch"] + seconds["wait"] + seconds["fetch"],
        padding_overhead=prog.padding_overhead,
        cache_hit=cache_hit,
    )
