"""Top-level triangle-counting API.

``count_triangles(graph, mesh=...)`` runs the full pipeline of the paper:
host planning (ingest → relabel → decompose → pack → stage, cached —
DESIGN.md §3) -> schedule -> global count, on whatever mesh is supplied
(including a 1x1 mesh for single-device use).  The bundled runners plan
through :mod:`repro.pipeline`, so repeated counts of an already-seen
graph hit the content-addressed plan cache and skip planning, staging,
and retracing entirely; ``count_triangles_many`` batches several graphs
into one compiled engine call.

Schedules resolve via a registry: :func:`register_schedule` makes a new
schedule one registration away (DESIGN.md §6) — the bundled ones are
``cannon`` (the paper), ``summa`` (rectangular/elastic), and ``oned``
(the 1D baseline the paper beats).  The per-block count path is selected
with ``method`` (any registered CSR kernel, plus the ``dense`` and
``tile`` operand-store paths on the Cannon schedule).  Runners receive
the *raw* graph plus the relabel options on the :class:`RunContext`
(``reorder``/``cyclic_p``) — relabeling happens inside the pipeline so
the cache can skip it.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from .. import compat
from . import cannon as cannon_mod
from .graph import Graph
from .plan import TCPlan
from .spans import count_scope, launch, span

__all__ = [
    "TCResult",
    "count_triangles",
    "count_triangles_delta",
    "count_triangles_many",
    "make_grid_mesh",
    "register_schedule",
    "get_schedule",
    "available_schedules",
]


@dataclasses.dataclass
class TCResult:
    triangles: int
    plan: TCPlan
    preprocess_seconds: float
    count_seconds: float
    method: str
    schedule: str
    grid: tuple
    # skip-aware rebalance search report (set when rebalance_trials > 0
    # and the schedule plans through the pipeline): best seed, baseline/
    # best masked critical path, improvement, skipped steps
    rebalance: Optional[dict] = None
    # hub-split report (DESIGN.md §4.8) when the plan carries a hub
    # side: hub_rows / hub_nnz_frac / hub_tasks plus residual_mcp (the
    # masked critical path of the residual the 2D path actually runs)
    hub: Optional[dict] = None
    # which autotune flavor governed kernel-shape selection for this run
    # ("percentile" | "measured"; None when the method was explicit and
    # no autotune stage ran — DESIGN.md §4.6)
    autotune_mode: Optional[str] = None
    # measured mode only: did the shape-bucket entry come off disk?
    measured_table_hit: Optional[bool] = None
    # the PlanArtifact this count ran from (None for caller-supplied raw
    # plans or schedules registered without plans_itself) — streaming
    # callers thread it into the next count_triangles_delta call
    artifact: Optional[object] = None
    # apply_delta report (level, dirty blocks/cells, replanned stages,
    # rebased) when the count came through count_triangles_delta
    delta: Optional[dict] = None
    # structured attempt/demotion/regrid record attached by
    # repro.runtime.supervisor.supervised_count; None on unsupervised
    # runs (DESIGN.md §8)
    supervision: Optional[dict] = None
    # the staged plan arrays the CSR engine counted from (name -> device
    # array); on a multi-device mesh each is sharded as the engine takes
    # it.  None on the dense/tile operand-store paths.
    staged: Optional[dict] = None


def make_grid_mesh(q: int, row_axis="data", col_axis="model", npods=1, pod_axis="pod"):
    """A q x q (optionally x pods) mesh from the available devices."""
    import jax

    n_needed = q * q * npods
    devs = jax.devices()
    assert len(devs) >= n_needed, f"need {n_needed} devices, have {len(devs)}"
    if npods > 1:
        return compat.make_mesh((npods, q, q), (pod_axis, row_axis, col_axis))
    return compat.make_mesh((q, q), (row_axis, col_axis))


# ----------------------------------------------------------------------
# schedule registry
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """One registered schedule: how to plan and how to run.

    ``runner(graph, mesh, ctx) -> (total, plan)`` does planning + array
    staging + engine-fn build + execution; ``ctx`` is the
    :class:`RunContext` of the current ``count_triangles`` call.
    ``build_fn`` exposes the raw engine-fn builder for dry runs /
    lowering-only callers (benchmarks, roofline).

    ``plans_itself`` marks runners that route the *raw* graph through
    :mod:`repro.pipeline` themselves (reading ``ctx.reorder`` /
    ``ctx.cyclic_p`` / ``ctx.cache``), which is what lets cache hits
    skip the relabel too.  Runners registered without it keep the
    pre-pipeline contract: ``count_triangles`` relabels the graph
    before dispatch and hands them the preprocessed graph.
    """

    name: str
    runner: Callable
    build_fn: Optional[Callable] = None
    plans_itself: bool = False


@dataclasses.dataclass
class RunContext:
    q: int
    npods: int
    method: str
    chunk: int
    probe_shorter: bool
    count_dtype: object
    plan: Optional[TCPlan] = None
    # engine knobs: sparsity-aware step skipping (None = auto from the
    # plan's staged masks), the double-buffered Cannon scan body, and
    # schedule compaction (None = auto from the plan's staged live list)
    use_step_mask: Optional[bool] = None
    double_buffer: bool = True
    compact: Optional[bool] = None
    # communication-avoiding collective strategies (DESIGN.md §4.5):
    # the final reduction ("auto" = 2.5D tree when a power-of-two pod
    # axis is present, else flat psums) and SUMMA's panel broadcast
    # (None/"auto" = ppermute chain for plain engines, one-hot psum for
    # batched)
    reduce_strategy: str = "auto"
    broadcast: Optional[str] = None
    # pipeline options: runners plan the *raw* graph through
    # repro.pipeline with these, so cache hits skip the relabel too
    reorder: bool = True
    cyclic_p: Optional[int] = None
    # skip-aware rebalance (DESIGN.md §4.3): search this many relabeling
    # seeds for the lowest masked critical path (0 = off)
    rebalance_trials: int = 0
    # hub-split stage (DESIGN.md §4.8): False = off, True = default
    # threshold, a number = the threshold multiplier c
    hub_split: object = False
    cache: Optional[object] = None  # PlanCache; None -> default_cache()
    # autotune flavor for method 'auto'/'fused' (DESIGN.md §4.6):
    # "percentile" = the analytic PR 5 stage; "measured" = consult (and
    # populate) the persisted timing table keyed per shape bucket
    autotune: str = "percentile"
    measured_dir: Optional[str] = None  # measured-table dir override
    # fused-kernel backend ("auto" | "pallas" | "pallas-interpret" |
    # "lax") and an optional tile override (measured mode feeds the
    # table's best shape through here)
    fused_impl: str = "auto"
    fused_tile: Optional[int] = None
    # resolved reporting fields (land on TCResult)
    autotune_mode: Optional[str] = None
    measured_table_hit: Optional[bool] = None
    artifact: Optional[object] = None  # PlanArtifact set by the runner
    staged: Optional[dict] = None  # device arrays the engine counted from
    # span wall times of this count: "plan" (the tc.plan span, reported
    # as preprocess time) and the engine call's dispatch/wait/fetch
    seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    # holds the open tc.plan span until mark_counting() closes it
    plan_span: contextlib.ExitStack = dataclasses.field(
        default_factory=contextlib.ExitStack, repr=False
    )

    def mark_counting(self, plan=None) -> None:
        """Host planning/staging is done; counting starts now: closes the
        ``tc.plan`` span.  Also the fault-injection window for this
        count: ``device_stage`` fires here, and with a ``plan`` each live
        original step index fires a ``step`` point before dispatch — so a
        fault armed at an elided step never fires, composing with
        schedule compaction."""
        from ..runtime import faultinject

        if faultinject.is_armed():
            faultinject.fire("device_stage")
            if plan is not None:
                compacted = self.compact is not False
                for s in faultinject.live_step_indices(plan, compacted):
                    faultinject.fire("step", step=s)
        self.plan_span.close()

    def memo(self, key, build: Callable):
        """Per-artifact build-once helper (falls through when the runner
        has no artifact, e.g. a caller-supplied plan)."""
        if self.artifact is None:
            return build()
        return self.artifact.memo(key, build)


_SCHEDULES: Dict[str, ScheduleSpec] = {}


def register_schedule(
    name: str,
    runner: Callable,
    *,
    build_fn: Optional[Callable] = None,
    plans_itself: bool = False,
) -> None:
    """Register a schedule; ``count_triangles(..., schedule=name)`` then
    resolves to ``runner``.  Overwrites any previous registration.

    Pass ``plans_itself=True`` only if the runner plans the raw graph
    through :mod:`repro.pipeline` (honoring ``ctx.reorder`` /
    ``ctx.cyclic_p``); otherwise it receives the already-relabeled
    graph, as before the pipeline existed.
    """
    _SCHEDULES[name] = ScheduleSpec(
        name=name, runner=runner, build_fn=build_fn, plans_itself=plans_itself
    )


def get_schedule(name: str) -> ScheduleSpec:
    try:
        return _SCHEDULES[name]
    except KeyError:
        raise ValueError(
            f"unknown schedule {name!r}; registered: {available_schedules()}"
        ) from None


def available_schedules():
    return sorted(_SCHEDULES)


# ----------------------------------------------------------------------
# bundled schedule runners
# ----------------------------------------------------------------------
def _resolve_auto_method(plan, fallback: str = "search") -> str:
    """Resolve ``method='auto'`` from the plan's autotune report:
    ``search2`` when the probe-length tail is heavy (and the plan
    carries the two-level split), plain ``search`` otherwise."""
    at = getattr(plan, "autotune", None)
    if (
        at
        and at.get("tail_heavy")
        and getattr(plan, "n_long", None) is not None
    ):
        return "search2"
    return fallback


def _consult_measured(ctx: RunContext, plan) -> Optional[dict]:
    """Measured-autotune table lookup for a maxfrag-split plan: records
    ``autotune_mode``/``measured_table_hit`` on the context and returns
    the entry (timing it into the table on a miss — the one-time cost
    measured mode trades for shape-bucket-warm later runs)."""
    from ..kernels.tc_fused import measured_entry

    entry, hit = measured_entry(plan, table_dir=ctx.measured_dir)
    ctx.autotune_mode = "measured"
    ctx.measured_table_hit = hit
    return entry


def _placement(mesh, fn) -> Optional[dict]:
    """Per-input shardings of the engine ``fn`` on a multi-device mesh;
    ``None`` on one device, where default staging already fits."""
    return fn.shardings if mesh.devices.size > 1 else None


def _stage(host: dict, placement: Optional[dict] = None) -> dict:
    """Host plan arrays on the device (``tc.stage``): placed by
    ``placement`` (only the inputs it names), else on the default
    device."""
    import jax

    with span("tc.stage"):
        if placement is None:
            return {k: jnp.asarray(v) for k, v in host.items()}
        return {k: jax.device_put(host[k], s) for k, s in placement.items()}


def _stage_plan(ctx: RunContext, plan, mesh, fn) -> dict:
    """The plan arrays the engine ``fn`` counts from, staged through the
    artifact's memo when there is one; recorded on ``ctx.staged``."""
    placement = _placement(mesh, fn)
    if ctx.artifact is not None:
        ctx.staged = ctx.artifact.staged(placement)
    else:
        ctx.staged = _stage(plan.device_arrays(), placement)
    return ctx.staged


def _shared_engine(ctx: RunContext, key, build: Callable):
    """The engine fn for ``key`` from the plan cache, built on a miss:
    plans with one shape key share one traced and compiled program."""
    from ..pipeline.cache import default_cache

    cache = ctx.cache if ctx.cache is not None else default_cache()
    return cache.memo(("engine",) + key, build)


def _run_cannon(graph: Graph, mesh, ctx: RunContext):
    plan = ctx.plan  # a caller-supplied plan is already relabeled and
    if plan is None:  # wins over the pipeline (reorder/cyclic_p unused)
        from ..pipeline import plan_cannon

        def plan_with(aug: bool, method: str):
            # the fused panel needs the two-sided maxfrag split; the
            # measured table is only defined over such plans, so
            # method='auto' under measured mode plans the same way
            fused_split = method == "fused" or (
                method == "auto" and ctx.autotune == "measured"
            )
            return plan_cannon(
                graph,
                ctx.q,
                chunk=ctx.chunk,
                reorder=ctx.reorder,
                cyclic_p=ctx.cyclic_p,
                # blocks are only consumed by the tile join (and
                # search2's bucketizer, which the planner forces);
                # skipping them keeps cached artifacts lean on the
                # common CSR paths
                keep_blocks=(method == "tile"),
                bucketize=(method == "search2"),
                rebalance_trials=ctx.rebalance_trials,
                compact=ctx.compact is not False,
                autotune="fused" if fused_split else (method == "auto"),
                aug_keys=aug,
                hub_split=ctx.hub_split,
                cache=ctx.cache,
            )

        ctx.artifact = plan_with(
            ctx.method in ("global", "search2"), ctx.method
        )
        plan = ctx.artifact.plan
        if ctx.method in ("auto", "fused") and ctx.autotune_mode is None:
            ctx.autotune_mode = "percentile"
        if ctx.method == "auto":
            if ctx.autotune == "measured":
                entry = _consult_measured(ctx, plan)
                from ..kernels.tc_fused import predict_fused_wins

                if predict_fused_wins(entry):
                    ctx.method = "fused"
                    ctx.fused_tile = entry["best"]["tile"]
                else:
                    ctx.method = _resolve_auto_method(plan)
            else:
                ctx.method = _resolve_auto_method(plan)
            if ctx.method == "search2":
                # auto resolved to a key-consuming kernel: re-plan with
                # staged aug keys (deterministic, so only aug differs;
                # its own cache entry serves repeat counts warm) — the
                # common search resolution never pays for unused keys
                ctx.artifact = plan_with(True, "auto")
                plan = ctx.artifact.plan
        elif ctx.method == "fused" and ctx.autotune == "measured":
            entry = _consult_measured(ctx, plan)
            ctx.fused_tile = entry["best"]["tile"]
        if ctx.method == "fused" and (plan.n_long or 0) > 0:
            # only the long-row fallback consumes staged keys: re-plan
            # with aug like the search2 resolution above, but skip it
            # entirely on panel-only plans (n_long == 0)
            ctx.artifact = plan_with(True, "fused")
            plan = ctx.artifact.plan
    elif ctx.method == "auto":
        ctx.method = _resolve_auto_method(plan)

    if ctx.method == "dense":
        from .cannon import build_cannon_dense_fn

        dense = ctx.memo("dense_blocks", plan.dense_blocks)
        staged = ctx.memo("dense_staged", lambda: _stage(dense))
        ctx.mark_counting(plan)
        fn = ctx.memo(
            ("dense_fn", mesh, ctx.use_step_mask, ctx.double_buffer,
             ctx.compact, ctx.reduce_strategy),
            lambda: build_cannon_dense_fn(
                plan, mesh,
                use_step_mask=ctx.use_step_mask,
                double_buffer=ctx.double_buffer,
                compact=ctx.compact,
                reduce_strategy=ctx.reduce_strategy,
            ),
        )
        return launch(fn, staged, ctx.seconds), plan
    if ctx.method == "tile":
        import jax

        from .cannon import build_cannon_tile_fn
        from .tiles import build_tile_plan

        tp = ctx.memo("tile_plan", lambda: build_tile_plan(plan))
        staged = ctx.memo("tile_staged", lambda: _stage(tp.device_arrays()))
        # interpret mode only off-TPU: Mosaic lowering needs real hardware,
        # and silently interpreting on TPU would be orders of magnitude slow
        interpret = jax.default_backend() != "tpu"
        ctx.mark_counting(plan)
        fn = ctx.memo(
            ("tile_fn", mesh, interpret, str(ctx.count_dtype),
             ctx.use_step_mask, ctx.double_buffer, ctx.compact),
            lambda: build_cannon_tile_fn(
                plan, tp, mesh, interpret=interpret,
                count_dtype=ctx.count_dtype,
                use_step_mask=ctx.use_step_mask,
                double_buffer=ctx.double_buffer,
                compact=ctx.compact,
            ),
        )
        return launch(fn, staged, ctx.seconds), plan

    if ctx.method == "search2" and not hasattr(plan, "n_long"):
        from .plan import bucketize_plan

        plan = bucketize_plan(plan)

    pod_axis = "pod" if ctx.npods > 1 else None
    fn_key = (
        "fn", mesh, ctx.method, ctx.probe_shorter, str(ctx.count_dtype),
        pod_axis, ctx.use_step_mask, ctx.double_buffer, ctx.compact,
        ctx.reduce_strategy, ctx.fused_impl, ctx.fused_tile,
    )
    fn = ctx.memo(
        fn_key,
        lambda: _shared_engine(
            ctx, ("cannon", plan.shape_key()) + fn_key,
            lambda: cannon_mod.build_cannon_fn(
                plan,
                mesh,
                pod_axis=pod_axis,
                method=ctx.method,
                probe_shorter=ctx.probe_shorter,
                count_dtype=ctx.count_dtype,
                use_step_mask=ctx.use_step_mask,
                double_buffer=ctx.double_buffer,
                compact=ctx.compact,
                reduce_strategy=ctx.reduce_strategy,
                fused_impl=ctx.fused_impl,
                fused_tile=ctx.fused_tile,
            ),
        ),
    )
    if pod_axis is not None:
        staged = ctx.staged = ctx.memo(
            ("pod_staged", ctx.npods, mesh),
            lambda: _stage(
                cannon_mod.pod_stack_arrays(
                    plan.device_arrays(), ctx.npods, plan.q
                ),
                _placement(mesh, fn),
            ),
        )
    else:
        staged = _stage_plan(ctx, plan, mesh, fn)
    ctx.mark_counting(plan)
    return launch(fn, staged, ctx.seconds), plan


def _run_summa(graph: Graph, mesh, ctx: RunContext):
    from ..pipeline import plan_summa
    from .summa import build_summa_fn

    names = list(mesh.axis_names)
    r, c = mesh.shape[names[-2]], mesh.shape[names[-1]]
    splan = ctx.plan  # a caller-supplied plan (or delta-derived
    if splan is None:  # artifact) wins over the pipeline, like Cannon's
        fused_split = ctx.method == "fused" or (
            ctx.method == "auto" and ctx.autotune == "measured"
        )
        ctx.artifact = plan_summa(
            graph, r, c, chunk=ctx.chunk, reorder=ctx.reorder,
            cyclic_p=ctx.cyclic_p, rebalance_trials=ctx.rebalance_trials,
            compact=ctx.compact is not False,
            autotune="fused" if fused_split else (ctx.method == "auto"),
            broadcast=ctx.broadcast or "auto",
            hub_split=ctx.hub_split,
            cache=ctx.cache,
        )
        splan = ctx.artifact.plan
        if ctx.method in ("auto", "fused") and ctx.autotune_mode is None:
            ctx.autotune_mode = "percentile"
        if ctx.method == "auto":
            if ctx.autotune == "measured":
                entry = _consult_measured(ctx, splan)
                from ..kernels.tc_fused import predict_fused_wins

                if predict_fused_wins(entry):
                    ctx.method = "fused"
                    ctx.fused_tile = entry["best"]["tile"]
                else:
                    ctx.method = _resolve_auto_method(splan)
            else:
                ctx.method = _resolve_auto_method(splan)
        elif ctx.method == "fused" and ctx.autotune == "measured":
            entry = _consult_measured(ctx, splan)
            ctx.fused_tile = entry["best"]["tile"]
    elif ctx.method == "auto":
        ctx.method = _resolve_auto_method(splan)
    fn = ctx.memo(
        ("fn", mesh, ctx.method, ctx.probe_shorter, str(ctx.count_dtype),
         ctx.use_step_mask, ctx.compact, ctx.broadcast,
         ctx.reduce_strategy, ctx.fused_impl, ctx.fused_tile),
        lambda: build_summa_fn(
            splan,
            mesh,
            method=ctx.method,
            probe_shorter=ctx.probe_shorter,
            count_dtype=ctx.count_dtype,
            use_step_mask=ctx.use_step_mask,
            compact=ctx.compact,
            broadcast=ctx.broadcast,
            fused_impl=ctx.fused_impl,
            fused_tile=ctx.fused_tile,
        ),
    )
    staged = _stage_plan(ctx, splan, mesh, fn)
    ctx.mark_counting(splan)
    return launch(fn, staged, ctx.seconds), splan


def _run_oned(graph: Graph, mesh, ctx: RunContext):
    from ..pipeline import plan_oned
    from .onedim import build_oned_fn

    p = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    flat_mesh = compat.make_mesh((p,), ("flat",))
    oplan = ctx.plan  # caller-supplied plan / delta artifact wins
    if oplan is None:
        fused_split = ctx.method == "fused" or (
            ctx.method == "auto" and ctx.autotune == "measured"
        )
        ctx.artifact = plan_oned(
            graph, p, chunk=ctx.chunk, reorder=ctx.reorder,
            cyclic_p=ctx.cyclic_p, rebalance_trials=ctx.rebalance_trials,
            compact=ctx.compact is not False,
            autotune="fused" if fused_split else (ctx.method == "auto"),
            hub_split=ctx.hub_split,
            cache=ctx.cache,
        )
        oplan = ctx.artifact.plan
        if ctx.method in ("auto", "fused") and ctx.autotune_mode is None:
            ctx.autotune_mode = "percentile"
        if ctx.method == "auto":
            if ctx.autotune == "measured":
                entry = _consult_measured(ctx, oplan)
                from ..kernels.tc_fused import predict_fused_wins

                if predict_fused_wins(entry):
                    ctx.method = "fused"
                    ctx.fused_tile = entry["best"]["tile"]
                else:
                    # the ring's global-id columns rule out the two-level
                    # kernel; the percentile fallback is plain search
                    ctx.method = "search"
            else:
                # the ring's global-id columns rule out the two-level
                # kernel
                ctx.method = "search"
        elif ctx.method == "fused" and ctx.autotune == "measured":
            entry = _consult_measured(ctx, oplan)
            ctx.fused_tile = entry["best"]["tile"]
    elif ctx.method == "auto":
        # the ring's global-id columns rule out the two-level kernel
        ctx.method = "search"
    fn = ctx.memo(
        ("fn", flat_mesh, ctx.method, ctx.probe_shorter,
         str(ctx.count_dtype), ctx.use_step_mask, ctx.compact,
         ctx.reduce_strategy, ctx.fused_impl, ctx.fused_tile),
        lambda: build_oned_fn(
            oplan,
            flat_mesh,
            method=ctx.method,
            probe_shorter=ctx.probe_shorter,
            count_dtype=ctx.count_dtype,
            use_step_mask=ctx.use_step_mask,
            compact=ctx.compact,
            reduce_strategy=ctx.reduce_strategy,
            fused_impl=ctx.fused_impl,
            fused_tile=ctx.fused_tile,
        ),
    )
    staged = _stage_plan(ctx, oplan, flat_mesh, fn)
    ctx.mark_counting(oplan)
    return launch(fn, staged, ctx.seconds), oplan


def _register_bundled():
    from .cannon import build_cannon_fn
    from .onedim import build_oned_fn
    from .summa import build_summa_fn

    register_schedule(
        "cannon", _run_cannon, build_fn=build_cannon_fn, plans_itself=True
    )
    register_schedule(
        "summa", _run_summa, build_fn=build_summa_fn, plans_itself=True
    )
    register_schedule(
        "oned", _run_oned, build_fn=build_oned_fn, plans_itself=True
    )


_register_bundled()


# ----------------------------------------------------------------------
# top-level entry point
# ----------------------------------------------------------------------
@count_scope()
def count_triangles(
    graph: Graph,
    mesh=None,
    *,
    q: Optional[int] = None,
    method: str = "search",
    schedule: str = "cannon",
    npods: int = 1,
    probe_shorter: bool = True,
    chunk: int = 512,
    reorder: bool = True,
    cyclic_p: Optional[int] = None,
    count_dtype=None,
    plan: Optional[TCPlan] = None,
    use_step_mask: Optional[bool] = None,
    double_buffer: bool = True,
    compact: Optional[bool] = None,
    reduce_strategy: str = "auto",
    broadcast: Optional[str] = None,
    rebalance_trials: int = 0,
    hub_split: object = False,
    cache=None,
    autotune: str = "percentile",
    measured_dir: Optional[str] = None,
    fused_impl: str = "auto",
    fault_plan=None,
) -> TCResult:
    """Count triangles with the paper's 2D algorithm.

    With no mesh, a 1x1 grid on the default device is used (degenerate but
    identical code path).  ``schedule`` resolves via the registry (see
    :func:`available_schedules`); ``method`` picks the count kernel
    ("search", "search2", "global", and on Cannon also "dense"/"tile");
    ``method="auto"`` plans through the deterministic autotune stage and
    resolves to ``search2`` when the probe-length tail is heavy
    (``TCResult.method`` reports the resolution).
    ``cyclic_p`` enables the paper's initial cyclic redistribution
    (§5.3 step 1) as the pipeline's first relabel stage.
    ``use_step_mask`` controls sparsity-aware step skipping (None =
    auto: on when the plan staged ``step_keep`` masks; False forces the
    unmasked engine); ``double_buffer`` selects Cannon's
    communication-overlapped scan body; ``compact`` controls the
    compacted kept-step schedule (None = auto: on when the planner's
    compaction stage elided a step — DESIGN.md §4.4; False keeps the
    full scan body).  ``reduce_strategy`` selects the final reduction
    (``"flat"`` psums per axis, ``"tree"`` = the 2.5D staged reduce,
    ``"auto"`` = tree whenever a power-of-two pod axis is present) and
    ``broadcast`` SUMMA's panel broadcast (``"onehot"`` psum,
    ``"chain"`` ppermute chains, ``None``/``"auto"`` = chain for plain
    engines) — DESIGN.md §4.5.  ``rebalance_trials > 0`` runs
    the skip-aware rebalance stage (DESIGN.md §4.3) during planning —
    it needs a pipeline-backed schedule and a pipeline-made plan, so it
    is rejected alongside a caller-supplied ``plan`` or a schedule
    registered without ``plans_itself``.  ``hub_split`` turns on the
    hub-split stage (DESIGN.md §4.8) for heavy-tailed graphs: hub rows
    above ``c ×`` the average degree (``True`` = the default ``c``, a
    number = an explicit ``c``) are counted as replicated column-strided
    fragments outside the 2D schedule and the residual flows through the
    normal path — same pipeline requirement as the rebalancer, so it too
    needs ``plans_itself`` and no caller plan.  Planning goes
    through the content-addressed plan cache (``cache=None`` uses the
    process-wide default — pass a ``repro.pipeline.PlanCache`` to
    isolate, or one with ``maxsize=0`` to disable): repeated counts of
    an already-seen graph skip relabel/plan/stage/compile entirely.

    ``method="fused"`` runs the Pallas equality-panel kernel with its
    long-row fallback (DESIGN.md §5.1) — planning switches to the
    two-sided maxfrag autotune split it requires; ``fused_impl`` picks
    its backend (``"auto"`` = Pallas on TPU, the lax reference
    elsewhere; ``"pallas-interpret"`` for CPU parity checks).
    ``autotune`` selects the shape-selection flavor for
    ``method in ("auto", "fused")``: ``"percentile"`` (the analytic
    stage) or ``"measured"`` (consult/populate the persisted timing
    table of DESIGN.md §4.6, under which ``method="auto"`` resolves to
    ``fused`` exactly where measurement says it beats the incumbent;
    ``measured_dir`` overrides the table directory).

    ``fault_plan`` arms a :class:`repro.runtime.FaultPlan` of
    deterministic typed faults for the duration of this call (testing
    the recovery paths without real hardware faults — DESIGN.md §8);
    recovery itself lives in
    :func:`repro.runtime.supervisor.supervised_count`, which retries,
    demotes and regrids around this function.
    """
    if autotune not in ("percentile", "measured"):
        raise ValueError(
            f"unknown autotune mode {autotune!r}: "
            "expected percentile | measured"
        )
    if autotune == "measured" and plan is not None:
        raise ValueError(
            "autotune='measured' needs pipeline planning (the table is "
            "keyed off the planned shape bucket); drop the "
            "caller-supplied plan"
        )
    artifact = None
    if plan is not None and hasattr(plan, "staged") and hasattr(plan, "plan"):
        # a PlanArtifact (e.g. from apply_delta) supplied as the plan:
        # run its plan and reuse its staged device buffers / fn memos
        artifact = plan
        plan = artifact.plan
    # host planning and staging (the paper's ppt) are the tc.plan span:
    # open from here until the runner's mark_counting(), or its end
    seconds: Dict[str, float] = {}
    plan_span = contextlib.ExitStack()
    plan_span.enter_context(span("tc.plan", seconds, "plan"))
    with plan_span:
        if mesh is None:
            q = q or 1
            mesh = make_grid_mesh(q, npods=npods)
        else:
            names = list(mesh.axis_names)
            if "pod" in names:
                npods = mesh.shape["pod"]
            q = mesh.shape[names[-1]]

        if count_dtype is None:
            count_dtype = compat.default_count_dtype()

        spec = get_schedule(schedule)
        if rebalance_trials and (plan is not None or not spec.plans_itself):
            raise ValueError(
                "rebalance_trials requires planning through the pipeline: "
                "drop the caller-supplied plan and use a schedule registered "
                "with plans_itself=True"
            )
        from ..pipeline.hubsplit import normalize_hub_split

        if normalize_hub_split(hub_split) is not None and (
            plan is not None or not spec.plans_itself
        ):
            raise ValueError(
                "hub_split requires planning through the pipeline: drop "
                "the caller-supplied plan (it already carries — or lacks — "
                "its hub side) and use a schedule registered with "
                "plans_itself=True"
            )
        if not spec.plans_itself and (reorder or cyclic_p is not None):
            # pre-pipeline runner contract: hand it the relabeled graph
            from ..pipeline import relabel_stage

            graph, _ = relabel_stage(graph, reorder=reorder, cyclic_p=cyclic_p)
            reorder, cyclic_p = False, None
        ctx = RunContext(
            q=q,
            npods=npods,
            method=method,
            chunk=chunk,
            probe_shorter=probe_shorter,
            count_dtype=count_dtype,
            plan=plan,
            use_step_mask=use_step_mask,
            double_buffer=double_buffer,
            compact=compact,
            reduce_strategy=reduce_strategy,
            broadcast=broadcast,
            reorder=reorder,
            cyclic_p=cyclic_p,
            rebalance_trials=rebalance_trials,
            hub_split=hub_split,
            cache=cache,
            autotune=autotune,
            measured_dir=measured_dir,
            fused_impl=fused_impl,
            seconds=seconds,
            plan_span=plan_span,
        )
        if artifact is not None:
            ctx.artifact = artifact
        from ..runtime import faultinject

        with faultinject.armed(fault_plan):
            total, out_plan = spec.runner(graph, mesh, ctx)
    total = compat.check_count_overflow(total, count_dtype)

    hub_side = getattr(out_plan, "hub", None)
    hub_rep = None
    if hub_side is not None:
        hub_rep = hub_side.report()
        rb = getattr(ctx.artifact, "rebalance", None)
        stats = getattr(out_plan, "stats", None)
        if rb is not None:
            hub_rep["residual_mcp"] = rb.get("best_masked_critical_path")
        elif stats is not None:
            from ..pipeline.rebalance import masked_critical_path

            hub_rep["residual_mcp"] = masked_critical_path(
                stats.probe_work_per_device_shift,
                getattr(out_plan, "step_keep", None),
            )
        else:
            hub_rep["residual_mcp"] = None

    return TCResult(
        triangles=total,
        plan=out_plan,
        preprocess_seconds=seconds["plan"],
        count_seconds=sum(
            seconds.get(k, 0.0) for k in ("dispatch", "wait", "fetch")
        ),
        method=ctx.method,  # "auto" reports its per-schedule resolution
        schedule=schedule,
        grid=(npods, q, q) if npods > 1 else (q, q),
        rebalance=getattr(ctx.artifact, "rebalance", None),
        hub=hub_rep,
        autotune_mode=ctx.autotune_mode,
        measured_table_hit=ctx.measured_table_hit,
        artifact=ctx.artifact,
        staged=ctx.staged,
    )


@count_scope()
def count_triangles_delta(
    graph: Graph,
    delta,
    mesh=None,
    *,
    artifact=None,
    cache=None,
    rebase_every: int = 8,
    **kwargs,
) -> TCResult:
    """Count triangles of ``graph`` mutated by ``delta``, incrementally.

    ``delta`` is a :class:`repro.pipeline.EdgeDelta` in **original**
    vertex ids.  The base plan is taken from ``artifact`` (the
    ``TCResult.artifact`` of a previous count — thread it through to
    stream deltas) or planned fresh from ``graph``;
    :func:`repro.pipeline.apply_delta` then splices / re-packs only the
    dirty blocks (DESIGN.md §4.7) and the count runs from the derived
    artifact, reusing unchanged device buffers and compiled engines.
    The result's ``delta`` field carries the apply report and its
    ``artifact`` the derived artifact for the next round; ``triangles``
    is exact — identical to a cold count of the mutated graph.  The
    whole call is one ``tc.count``; the splice runs in a ``tc.plan`` of
    its own, before the count's.
    """
    from ..pipeline.delta import apply_delta

    if kwargs.get("autotune") == "measured":
        raise ValueError(
            "autotune='measured' re-times shapes per plan; the delta "
            "path reuses engines and is keyed analytically — use the "
            "default percentile mode"
        )
    if artifact is None:
        base = count_triangles(graph, mesh, cache=cache, **kwargs)
        artifact = base.artifact
        if artifact is None:
            raise ValueError(
                "count_triangles_delta needs a pipeline-planned base "
                "(schedule registered with plans_itself=True and no "
                "caller-supplied raw plan)"
            )
    with span("tc.plan"):
        art2 = apply_delta(
            artifact, delta, cache=cache, rebase_every=rebase_every
        )
    # the derived artifact already fixed its relabeling, rebalance seed
    # and hub cut at plan time — re-count kwargs that would re-plan are
    # dropped (hub_split included: the derived plan either carries its
    # repacked hub side or was rebased with the cfg's knob)
    for drop in ("reorder", "cyclic_p", "rebalance_trials", "hub_split"):
        kwargs.pop(drop, None)
    res = count_triangles(
        art2.graph, mesh, plan=art2, reorder=False, rebalance_trials=0,
        cache=cache, **kwargs,
    )
    res.delta = art2.delta_report
    return res


def count_triangles_many(graphs, mesh=None, **kwargs):
    """Count triangles of many graphs in one compiled engine call.

    Thin re-export of :func:`repro.pipeline.count_triangles_many` (the
    batched front-end): graphs are padded to shared shapes, stacked on a
    leading batch axis, and run through the engine once; results match
    the per-graph :func:`count_triangles` totals exactly.
    """
    from ..pipeline import count_triangles_many as _many

    return _many(graphs, mesh, **kwargs)
