"""End-to-end distributed triangle-counting driver (the paper's app).

    PYTHONPATH=src python -m repro.launch.tc_run --graph rmat:18 --grid 2 \
        [--schedule cannon|summa|oned] \
        [--method auto|search|search2|global|dense|tile|fused] \
        [--autotune percentile|measured] [--no-compact] [--time-split] \
        [--ckpt-dir /tmp/tc_ckpt] [--resume] [--rebalance]

Generates (or loads) the graph, plans through the cached pipeline
(degree ordering + 2D-cyclic decomposition + schedule compaction), runs
the selected schedule on a device grid, and verifies against the host
oracle for small graphs.  Reports carry the engine's sparsity
accounting (``skipped_steps``, ``live_steps``/``elided_steps``) and —
under ``--method auto`` — the autotuned kernel shapes.  With
``--ckpt-dir`` it runs shift-at-a-time with checkpoints, resumable
mid-Cannon-loop (compacted schedules iterate live steps only).
``--graphs a,b,c`` counts a whole *batch* of graphs in one compiled
engine call (``count_triangles_many``).
"""
import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat:14", help="rmat:<scale>[,<ef>[,<seed>]] | er:<n>,<deg> | named:<id>")
    ap.add_argument("--graphs", default=None,
                    help="';'-separated specs: batched count via "
                         "count_triangles_many (one compiled call)")
    ap.add_argument("--grid", type=int, default=1, help="sqrt(p): grid is q x q")
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--schedule", default="cannon")
    ap.add_argument("--method", default="search",
                    choices=["auto", "search", "search2", "global",
                             "dense", "tile", "fused"],
                    help="count kernel; 'auto' runs the deterministic "
                         "autotune stage and picks search2 on "
                         "heavy-tailed graphs; 'fused' is the Pallas "
                         "probe-gather+intersection mega-kernel "
                         "(two-sided maxfrag split)")
    ap.add_argument("--autotune", default="percentile",
                    choices=["percentile", "measured"],
                    help="'percentile' derives kernel shapes "
                         "analytically from the probe-length "
                         "distribution; 'measured' times fused vs "
                         "search2 candidates once per shape bucket, "
                         "persists the verdict to the measured table, "
                         "and lets --method auto resolve to 'fused' "
                         "when the table predicts it wins")
    ap.add_argument("--measured-dir", default=None,
                    help="measured-autotune table directory (default "
                         "$REPRO_TC_MEASURED_DIR or "
                         "~/.cache/repro/tc_measured)")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--opt", action="store_true",
                    help="enable §Perf H1a+H1b (bucketed probes + "
                         "uint16-length blobs)")
    ap.add_argument("--no-probe-shorter", action="store_true")
    ap.add_argument("--no-skip-mask", action="store_true",
                    help="disable sparsity-aware step skipping")
    ap.add_argument("--no-double-buffer", action="store_true",
                    help="disable the communication-overlapped Cannon body")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable the compacted kept-step schedule "
                         "(dead-shift elision + fused multi-hop "
                         "ppermutes); mirrors --no-skip-mask")
    ap.add_argument("--time-split", action="store_true",
                    help="also time a comm-only run (all-False mask, "
                         "collectives + conds intact) and a count-only "
                         "run (shifts/broadcasts elided) so the overlap "
                         "column is attributable, and report "
                         "per-collective-phase HLO bytes "
                         "(coll_{shift,broadcast,reduce,other}_bytes); "
                         "any schedule")
    ap.add_argument("--reduce-strategy", default="auto",
                    choices=["auto", "flat", "tree"],
                    help="final-reduction collective: 'flat' psums over "
                         "every mesh axis; 'tree' is the 2.5D staged "
                         "reduce (joint grid psum then log2(pods) "
                         "masked ppermute rounds); 'auto' picks tree "
                         "when --pods > 1")
    ap.add_argument("--broadcast", default=None,
                    choices=["auto", "onehot", "chain"],
                    help="summa panel-broadcast collective: 'onehot' "
                         "psums owner-masked panels; 'chain' is the "
                         "masked ppermute doubling chain (half the "
                         "bytes); 'auto' picks chain for unrolled "
                         "bodies")
    ap.add_argument("--repeat", type=int, default=1,
                    help="count this many times (plan-cache warm after the "
                         "first); tct_seconds reports the MINIMUM over the "
                         "warm runs (2..N), i.e. warm dispatch without "
                         "trace/compile and robust to host timer noise")
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-at-shift", type=int, default=None,
                    help="inject one failure at this shift (FT demo)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic typed fault injection (DESIGN.md "
                         "§8): ';'-separated sites "
                         "point[@STEP][=FAULT[:LOST]][*TIMES] over points "
                         "plan_stage|device_stage|step|fused|delta_splice|"
                         "ckpt_save, e.g. 'step@1' or "
                         "'step@0=devicelost:5;ckpt_save=ckptcorrupt'; "
                         "implies supervised execution — the run must "
                         "still produce the exact count")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the restart supervisor (backoff + "
                         "jitter, restart budget, degradation ladder, "
                         "DeviceLost regrid) even without injected "
                         "faults; the report gains supervision_* fields")
    ap.add_argument("--restart-budget", type=int, default=5,
                    help="supervised runs: max restarts before giving up")
    ap.add_argument("--attempt-deadline", type=float, default=None,
                    help="supervised runs: cooperative per-attempt "
                         "deadline in seconds (checked at step/attempt "
                         "boundaries)")
    ap.add_argument("--rebalance", type=int, default=0,
                    help="skip-aware rebalance trials: search this many "
                         "relabeling seeds for the lowest masked critical "
                         "path (straggler mitigation, any schedule)")
    ap.add_argument("--hub-split", nargs="?", const=True, default=None,
                    type=float, metavar="C", dest="hub_split",
                    help="hub-split planning (DESIGN.md §4.8): count rows "
                         "with degree > C x the average degree (bare flag "
                         "= the default C) as replicated column-strided "
                         "fragments outside the 2D schedule; the residual "
                         "takes the normal path with a far smaller "
                         "critical path on heavy-tailed graphs")
    ap.add_argument("--stream", default=None, metavar="DELTA_FILE",
                    help="streaming mode: count --graph once, then apply "
                         "each JSONL line ({\"add\": [[u,v],...], "
                         "\"remove\": [...]}, original vertex ids) as an "
                         "edge delta via the incremental re-plan path "
                         "(DESIGN.md §4.7) and re-count; the report "
                         "carries per-round dirty-block / replanned-stage "
                         "accounting")
    ap.add_argument("--rebase-every", type=int, default=8,
                    help="streaming: cold re-plan (rebase the delta "
                         "lineage) after this many chained deltas")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    import jax

    from .compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    from ..core import (
        available_schedules,
        count_triangles,
        get_schedule,
        graph_from_spec,
        triangle_count_oracle,
    )

    if args.schedule not in available_schedules():
        raise SystemExit(
            f"unknown --schedule {args.schedule!r}; "
            f"registered: {available_schedules()}"
        )

    if args.rebalance and (args.graphs or args.ckpt_dir):
        raise SystemExit(
            "--rebalance is not supported with --graphs or --ckpt-dir; "
            "rebalance single full-engine runs"
        )

    if args.stream and (args.graphs or args.ckpt_dir or args.opt
                        or args.time_split or args.autotune == "measured"):
        raise SystemExit(
            "--stream composes with single-graph pipeline runs only: "
            "drop --graphs/--ckpt-dir/--opt/--time-split/"
            "--autotune measured"
        )

    if args.hub_split is not None:
        if args.graphs:
            raise SystemExit(
                "--hub-split is a single-graph pipeline stage; the "
                "batched engine shares one set of statics across graphs "
                "and takes no hub side — drop --graphs"
            )
        if args.ckpt_dir:
            raise SystemExit(
                "--hub-split is not supported with --ckpt-dir: the "
                "checkpointed stepper counts one shift at a time and "
                "has no slot for the hub-split partial"
            )
        if args.opt:
            raise SystemExit(
                "--hub-split is not wired through the --opt bucketized "
                "path; use the default path (the hub side composes with "
                "--rebalance, --no-compact and every schedule there)"
            )
        if args.method in ("dense", "tile"):
            raise SystemExit(
                f"--hub-split is not supported with --method "
                f"{args.method}: the {args.method} operand store stages "
                "its own blocks and would drop the hub-split partial"
            )

    supervised = bool(args.inject_faults or args.supervise)
    if supervised and (args.graphs or args.opt or args.time_split
                       or args.stream):
        raise SystemExit(
            "--inject-faults/--supervise cover single-graph engine runs "
            "and --ckpt-dir stepper runs; drop --graphs/--opt/"
            "--time-split/--stream (the serve front-end has its own "
            "per-request supervision)"
        )
    fault_plan = None
    if args.inject_faults:
        from ..runtime import FaultPlan

        fault_plan = FaultPlan.parse(args.inject_faults)

    if args.graphs:
        return _run_batched(args)

    g = graph_from_spec(args.graph)

    if args.stream:
        return _run_stream(g, args)

    report = {"graph": args.graph, "n": g.n, "m": g.m}

    if args.ckpt_dir:
        total, timings = _run_checkpointed(g, args, fault_plan=fault_plan)
        report.update(timings)
    else:
        t0 = time.perf_counter()
        if args.opt and args.schedule == "cannon":
            # §Perf H1a+H1b: bucketed probes + compressed shift blobs
            import jax.numpy as jnp

            from .. import compat
            from ..core.api import make_grid_mesh
            from ..core.plan import bucketize_plan

            build_cannon_fn = get_schedule("cannon").build_fn
            # plan through the pipeline (with or without rebalance) so
            # the compaction stage runs and --no-compact has a lever
            from ..pipeline import plan_cannon

            art = plan_cannon(
                g, args.grid, chunk=args.chunk, keep_blocks=True,
                rebalance_trials=args.rebalance, aug_keys=True,
                compact=not args.no_compact,
            )
            if args.rebalance:
                report.update(_rebalance_fields(art.rebalance))
            bplan = bucketize_plan(art.plan)
            # host planning done: ppt = t1o - t0; engine build+trace stay
            # inside tct for repeat==1, as before
            t1o = time.perf_counter()
            mesh = make_grid_mesh(args.grid, npods=args.pods)
            fn = build_cannon_fn(
                bplan, mesh, method="search2", compress_lengths=True,
                count_dtype=compat.default_count_dtype(),
                use_step_mask=False if args.no_skip_mask else None,
                double_buffer=not args.no_double_buffer,
                compact=False if args.no_compact else None,
            )
            staged = {
                k: jnp.asarray(v) for k, v in bplan.device_arrays().items()
            }
            times = []
            for i in range(max(1, args.repeat)):
                t_run = time.perf_counter()
                total = int(fn(**staged))
                times.append(time.perf_counter() - t_run)
            report.update(
                triangles=total,
                ppt_seconds=round(t1o - t0, 4),
                tct_seconds=round(
                    min(times[1:]) if len(times) > 1 else times[0], 4
                ),
                optimized=True,
                bucket_reduction=round(bplan.bucket_stats["reduction"], 3),
            )
            report.update(_skip_fields(bplan, args.no_skip_mask))
            report.update(_compact_fields(bplan))
            if args.verify:
                from ..core import triangle_count_oracle

                exp = triangle_count_oracle(g)
                report["expected"] = exp
                report["correct"] = bool(total == exp)
                assert total == exp
            import json as _json

            print(_json.dumps(report) if args.json else
                  "\n".join(f"{k}: {v}" for k, v in report.items()))
            return
        count_kwargs = dict(
            q=args.grid,
            npods=args.pods,
            schedule=args.schedule,
            method=args.method,
            chunk=args.chunk,
            probe_shorter=not args.no_probe_shorter,
            use_step_mask=False if args.no_skip_mask else None,
            double_buffer=not args.no_double_buffer,
            compact=False if args.no_compact else None,
            rebalance_trials=args.rebalance,
            hub_split=(
                args.hub_split if args.hub_split is not None else False
            ),
            reduce_strategy=args.reduce_strategy,
            broadcast=args.broadcast,
            autotune=args.autotune,
            measured_dir=args.measured_dir,
        )
        times = []
        if supervised:
            from ..runtime import BackoffPolicy, Supervisor, supervised_count

            sup = Supervisor(
                max_restarts=args.restart_budget,
                attempt_deadline=args.attempt_deadline,
                backoff=BackoffPolicy(base=0.02, max_delay=0.5),
            )
            res = supervised_count(
                g, supervisor=sup, fault_plan=fault_plan, **count_kwargs
            )
            times.append(res.count_seconds)
            report.update(_supervision_fields(res.supervision))
        else:
            for _ in range(max(1, args.repeat)):
                res = count_triangles(g, **count_kwargs)
                times.append(res.count_seconds)
        if res.rebalance is not None:
            report.update(_rebalance_fields(res.rebalance))
        if args.hub_split is not None:
            report.update(_hub_fields(res.hub))
        report.update(
            triangles=res.triangles,
            ppt_seconds=round(res.preprocess_seconds, 4),
            tct_seconds=round(min(times[1:]) if len(times) > 1 else times[0], 4),
            total_seconds=round(time.perf_counter() - t0, 4),
            grid=res.grid,
            method=res.method,
        )
        report.update(_skip_fields(res.plan, args.no_skip_mask))
        report.update(_compact_fields(res.plan))
        report.update(_autotune_fields(res.plan))
        if res.autotune_mode is not None:
            report["autotune_mode"] = res.autotune_mode
        if res.measured_table_hit is not None:
            report["measured_table_hit"] = res.measured_table_hit
        if args.time_split:
            report.update(_time_split(g, args))
        total = res.triangles

    from ..pipeline import default_cache

    report["plan_cache"] = default_cache().stats()

    if args.verify:
        expected = triangle_count_oracle(g)
        report["expected"] = expected
        report["correct"] = bool(total == expected)
        assert total == expected, (total, expected)

    if args.json:
        print(json.dumps(report))
    else:
        for k, v in report.items():
            print(f"{k}: {v}")


def _skip_fields(plan, no_skip_mask: bool) -> dict:
    """Per-(device, step) skip-mask accounting shared by the --opt and
    default report paths."""
    sk = getattr(plan, "step_keep", None)
    if sk is None:
        return {}
    return dict(
        schedule_steps=int(sk.size),
        skipped_steps=0 if no_skip_mask else int(sk.size - sk.sum()),
    )


def _compact_fields(plan) -> dict:
    """Schedule-compaction accounting: live schedule steps and the
    device-step scan slots the compacted engine no longer executes
    (``(n_total - n_live) * ndev``, commensurable with
    ``schedule_steps``/``skipped_steps``).  Plans made under
    ``--no-compact`` carry no ``CompactSchedule``, so such runs simply
    omit the fields."""
    cs = getattr(plan, "compact", None)
    sk = getattr(plan, "step_keep", None)
    if cs is None or sk is None:
        return {}
    ndev = sk.size // max(1, cs.n_total)
    return dict(
        live_steps=cs.n_live,
        elided_steps=cs.n_elided * ndev,
    )


def _autotune_fields(plan) -> dict:
    at = getattr(plan, "autotune", None)
    if not at:
        return {}
    return dict(
        autotuned_chunk=at["chunk"],
        autotuned_d_small=at["d_small"],
        autotuned_tail_heavy=at["tail_heavy"],
    )


def _time_split(g, args) -> dict:
    """Comm/count attribution probes (any schedule):

    * comm-only — the masked engine fed an all-False mask: every
      collective (shift rotation or panel broadcast) and cond executes,
      every count kernel is skipped;
    * count-only — the same engine with its data collectives elided
      (``elide_shifts`` / ``elide_broadcast``): every count kernel
      executes against the locally-held panels (a timing proxy —
      counts are wrong for p > 1, so the result is discarded).

    Both run the *uncompacted* body with the caller's flags, warm
    (timed call preceded by a compile call), so
    ``tct − comm_only − count_only`` exposes what the overlap buys.
    The per-phase byte columns come from
    :func:`repro.launch.roofline.collective_phases` over the compiled
    HLO of the *production* configuration: the engine tags its
    collectives with named scopes (tc_shift / tc_broadcast /
    tc_reduce), and permutes are charged pairs-aware — this is what
    makes tree-vs-flat and chain-vs-onehot A/Bs comparable in bytes,
    not just seconds (DESIGN.md §4.5).
    """
    import jax.numpy as jnp

    from ..core.api import make_grid_mesh
    from .roofline import collective_phases

    out = {}

    def timed_min(fn, arrays, warm=1, iters=3):
        for _ in range(warm):
            fn(**arrays)  # compile + warm
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(**arrays)
            best = min(best, time.perf_counter() - t0)
        return round(best, 4)

    if args.schedule == "cannon":
        from ..core.cannon import build_cannon_fn, pod_stack_arrays
        from ..pipeline import plan_cannon

        art = plan_cannon(g, args.grid, chunk=args.chunk)
        plan = art.plan
        if plan.step_keep is None:
            return {}
        mesh = make_grid_mesh(args.grid, npods=args.pods)
        if args.pods > 1:
            staged = {
                k: jnp.asarray(v)
                for k, v in pod_stack_arrays(
                    plan.device_arrays(), args.pods, plan.q
                ).items()
            }
        else:
            staged = dict(art.staged())
        common = dict(
            pod_axis="pod" if args.pods > 1 else None,
            double_buffer=not args.no_double_buffer,
            reduce_strategy=args.reduce_strategy,
        )
        fcomm = build_cannon_fn(
            plan, mesh, use_step_mask=True, compact=False, **common
        )
        zeros = dict(staged, step_keep=jnp.zeros_like(staged["step_keep"]))
        out["tct_shift_only"] = timed_min(fcomm, zeros)
        fcount = build_cannon_fn(
            plan, mesh, use_step_mask=False, compact=False,
            elide_shifts=True, **common
        )
        no_mask = {k: v for k, v in staged.items() if k != "step_keep"}
        out["tct_count_only"] = timed_min(fcount, no_mask)
        fprod = build_cannon_fn(
            plan, mesh,
            use_step_mask=False if args.no_skip_mask else None,
            compact=False if args.no_compact else None, **common
        )
    elif args.schedule == "summa":
        from ..core.summa import build_summa_fn
        from ..pipeline import plan_summa

        art = plan_summa(
            g, args.grid, args.grid, chunk=args.chunk,
            broadcast=args.broadcast or "auto",
        )
        plan = art.plan
        if plan.step_keep is None:
            return {}
        mesh = make_grid_mesh(args.grid)
        staged = dict(art.staged())
        fcomm = build_summa_fn(
            plan, mesh, broadcast=args.broadcast, use_step_mask=True,
            compact=False,
        )
        zeros = dict(staged, step_keep=jnp.zeros_like(staged["step_keep"]))
        out["tct_broadcast_only"] = timed_min(fcomm, zeros)
        fcount = build_summa_fn(
            plan, mesh, broadcast=args.broadcast, use_step_mask=False,
            compact=False, elide_broadcast=True,
        )
        no_mask = {k: v for k, v in staged.items() if k != "step_keep"}
        out["tct_count_only"] = timed_min(fcount, no_mask)
        fprod = build_summa_fn(
            plan, mesh, broadcast=args.broadcast,
            use_step_mask=False if args.no_skip_mask else None,
            compact=False if args.no_compact else None,
        )
    elif args.schedule == "oned":
        from .. import compat
        from ..core.onedim import build_oned_fn
        from ..pipeline import plan_oned

        p = args.grid * args.grid * args.pods
        art = plan_oned(g, p, chunk=args.chunk)
        plan = art.plan
        if plan.step_keep is None:
            return {}
        mesh = compat.make_mesh((p,), ("flat",))
        staged = dict(art.staged())
        fcomm = build_oned_fn(
            plan, mesh, use_step_mask=True, compact=False,
        )
        zeros = dict(staged, step_keep=jnp.zeros_like(staged["step_keep"]))
        out["tct_shift_only"] = timed_min(fcomm, zeros)
        fcount = build_oned_fn(
            plan, mesh, use_step_mask=False, compact=False,
            elide_shifts=True,
        )
        no_mask = {k: v for k, v in staged.items() if k != "step_keep"}
        out["tct_count_only"] = timed_min(fcount, no_mask)
        fprod = build_oned_fn(
            plan, mesh,
            use_step_mask=False if args.no_skip_mask else None,
            compact=False if args.no_compact else None,
            reduce_strategy=args.reduce_strategy,
        )
    else:  # a registered schedule this probe doesn't know how to split
        return {}

    hlo = fprod.lower(**staged).compile().as_text()
    phases = collective_phases(hlo)
    out.update(
        coll_shift_bytes=round(phases["shift"]),
        coll_broadcast_bytes=round(phases["broadcast"]),
        coll_reduce_bytes=round(phases["reduce"]),
        coll_other_bytes=round(phases["other"]),
    )
    return out


def _hub_fields(hub: "dict | None") -> dict:
    """Flatten a TCResult.hub report into tc_run report fields.

    ``hub is None`` with the flag on means no row crossed the threshold
    (the stage no-opped) — reported as ``hub_rows=0`` rather than
    omitted, so scripted consumers can tell "off" from "found nothing".
    """
    if hub is None:
        return dict(hub_rows=0, hub_nnz_frac=0.0)
    out = dict(
        hub_rows=int(hub["hub_rows"]),
        hub_nnz_frac=round(float(hub["hub_nnz_frac"]), 4),
    )
    if hub.get("residual_mcp") is not None:
        out["residual_mcp"] = hub["residual_mcp"]
    return out


def _rebalance_fields(rb: dict) -> dict:
    """Flatten a pipeline rebalance report into tc_run report fields:
    masked-critical-path improvement and the skipped-step delta vs the
    seed-0 baseline."""
    import math

    impr = rb["improvement"]
    return dict(
        rebalance_trials=len(rb["trials"]),
        rebalance_best_seed=rb["best_seed"],
        rebalance_baseline_critical_path=rb["baseline_masked_critical_path"],
        rebalance_masked_critical_path=rb["best_masked_critical_path"],
        # inf (best path hit literal zero) is not valid JSON: emit null
        rebalance_improvement=round(impr, 4) if math.isfinite(impr) else None,
        rebalance_skipped_delta=(
            rb["skipped_steps"] - rb["baseline_skipped_steps"]
        ),
    )


def _supervision_fields(sup: "dict | None") -> dict:
    """Flatten a TCResult.supervision record (or a SupervisionReport
    dict) into tc_run report fields.  Attempt-by-attempt detail stays
    nested under ``supervision_attempts``; demotions/regrids are emitted
    only when non-empty so fault-free supervised runs stay compact."""
    if not sup:
        return {}
    out = dict(
        supervision_attempts=sup.get("attempts", []),
        supervision_restarts=sup.get("restarts", 0),
        supervision_backoff_seconds=sup.get("total_backoff_seconds", 0.0),
    )
    if sup.get("demotions"):
        out["supervision_demotions"] = sup["demotions"]
    if sup.get("regrids"):
        out["supervision_regrids"] = sup["regrids"]
    if sup.get("fault_log"):
        out["supervision_fault_log"] = sup["fault_log"]
    if sup.get("gave_up"):
        out["supervision_gave_up"] = True
    return out


def _run_batched(args):
    """Batched mode: count every spec in --graphs with one engine call."""
    from ..core import count_triangles_many, triangle_count_oracle
    from ..core.generators import graph_from_spec, split_specs

    if args.no_skip_mask or args.no_double_buffer:
        raise SystemExit(
            "--no-skip-mask/--no-double-buffer are not supported with "
            "--graphs (the batched engine always follows the plans' "
            "staged masks); use single-graph runs to A/B the levers"
        )
    if args.time_split:
        raise SystemExit(
            "--time-split is not supported with --graphs (one compiled "
            "call spans every plan, so there is no per-graph comm/count "
            "attribution); use single-graph runs"
        )
    if args.autotune == "measured":
        raise SystemExit(
            "--autotune measured is not supported with --graphs: the "
            "measured table is keyed per shape bucket, so a mixed batch "
            "would hit a cold table (and pay a timing run) per graph "
            "inside the one compiled call; warm the table with "
            "single-graph runs first, then batch with --autotune "
            "percentile"
        )
    if args.method == "fused":
        raise SystemExit(
            "--method fused is not supported with --graphs (the batched "
            "engine plans without the two-sided maxfrag split the fused "
            "kernel needs); use single-graph runs"
        )
    if args.broadcast == "chain" or args.reduce_strategy != "auto":
        raise SystemExit(
            "--broadcast chain/--reduce-strategy are not supported with "
            "--graphs (the batched engine keeps the uniform scan body, "
            "which needs traced round indices — chain broadcasts and "
            "staged reductions need the unrolled body); use "
            "single-graph runs to A/B the collectives"
        )
    specs = split_specs(args.graphs)
    graphs = [graph_from_spec(s) for s in specs]
    # the batched engine keeps the uniform scan body (per-graph masks
    # differ, so there is no shared live-step list to compact) and takes
    # only CSR kernels: resolve 'auto' to the flat search path
    method = "search" if args.method == "auto" else args.method
    t0 = time.perf_counter()
    for _ in range(max(1, args.repeat)):  # later rounds hit the program cache
        res = count_triangles_many(
            graphs,
            q=args.grid,
            schedule=args.schedule,
            method=method,
            chunk=args.chunk,
        )
    report = {
        "graphs": specs,
        "batch": res.batch,
        "triangles": res.triangles,
        "ppt_seconds": round(res.plan_seconds, 4),
        "tct_seconds": round(res.count_seconds, 4),
        "total_seconds": round(time.perf_counter() - t0, 4),
        "padding_overhead": round(res.padding_overhead, 4),
        "grid": res.grid,
    }
    if args.verify:
        expected = [triangle_count_oracle(g) for g in graphs]
        report["expected"] = expected
        report["correct"] = bool(res.triangles == expected)
        assert res.triangles == expected, (res.triangles, expected)
    if args.json:
        print(json.dumps(report))
    else:
        for k, v in report.items():
            print(f"{k}: {v}")


def _run_stream(g, args):
    """Streaming mode: one base count, then one incremental re-count per
    delta line.

    Each JSONL line of ``--stream`` is an :class:`repro.pipeline.EdgeDelta`
    in **original** vertex ids (the lineage's composed relabeling is
    applied internally).  The derived artifact is threaded round to
    round, so unchanged device buffers and compiled engines carry over;
    after ``--rebase-every`` chained deltas the lineage rebases onto a
    cold re-plan.  ``--verify`` checks every round against the host
    oracle of the mutated graph.
    """
    from ..core import count_triangles, count_triangles_delta
    from ..core.graph import triangle_count_oracle
    from ..pipeline import EdgeDelta, default_cache

    kwargs = dict(
        q=args.grid,
        npods=args.pods,
        schedule=args.schedule,
        method=args.method,
        chunk=args.chunk,
        probe_shorter=not args.no_probe_shorter,
        use_step_mask=False if args.no_skip_mask else None,
        double_buffer=not args.no_double_buffer,
        compact=False if args.no_compact else None,
        reduce_strategy=args.reduce_strategy,
        broadcast=args.broadcast,
    )
    t0 = time.perf_counter()
    base = count_triangles(
        g, rebalance_trials=args.rebalance,
        hub_split=args.hub_split if args.hub_split is not None else False,
        **kwargs,
    )
    report = {
        "graph": args.graph, "n": g.n, "m": g.m, "stream": args.stream,
        "triangles_base": base.triangles,
        "base_seconds": round(time.perf_counter() - t0, 4),
        "grid": base.grid, "method": base.method,
    }
    if args.hub_split is not None:
        report.update(_hub_fields(base.hub))
    if args.verify:
        exp = triangle_count_oracle(g)
        assert base.triangles == exp, (base.triangles, exp)

    art, g_cur, rounds = base.artifact, g, []
    with open(args.stream) as fh:
        lines = [ln for ln in (s.strip() for s in fh) if ln]
    for i, line in enumerate(lines):
        spec = json.loads(line)
        delta = EdgeDelta(
            add=spec.get("add") or None, remove=spec.get("remove") or None
        )
        t1 = time.perf_counter()
        res = count_triangles_delta(
            g_cur, delta, artifact=art,
            rebase_every=args.rebase_every, **kwargs,
        )
        dt = time.perf_counter() - t1
        art, rep = res.artifact, res.delta
        g_cur = delta.apply_to(g_cur)
        entry = dict(
            round=i,
            triangles=res.triangles,
            edges_added=rep["edges_added"],
            edges_removed=rep["edges_removed"],
            level=rep["level"],
            dirty_blocks=rep["dirty_blocks"],
            replanned_stages=rep["replanned_stages"],
            rebased=rep["rebased"],
            round_seconds=round(dt, 4),
        )
        if args.verify:
            exp = triangle_count_oracle(g_cur)
            entry["correct"] = bool(res.triangles == exp)
            assert res.triangles == exp, (i, res.triangles, exp)
        rounds.append(entry)

    last = rounds[-1] if rounds else {}
    report.update(
        rounds=rounds,
        deltas_applied=len(rounds),
        triangles=last.get("triangles", base.triangles),
        dirty_blocks=last.get("dirty_blocks", 0),
        replanned_stages=last.get("replanned_stages", []),
        rebased=last.get("rebased", False),
        plan_cache=default_cache().stats(),
    )
    if args.json:
        print(json.dumps(report))
    else:
        for k, v in report.items():
            print(f"{k}: {v}")


def _run_checkpointed(g, args, fault_plan=None):
    """Shift-at-a-time execution with mid-loop checkpoint/restart.

    The checkpointed state is the engine's *scan carry* (with the
    double-buffered Cannon body: two payload generations, built once by
    ``stepper.prime``) plus the per-device partial counts; the host loop
    owns the shift index and passes it to each step so the sparsity skip
    mask stays aligned after a resume.

    Under a compacted plan the loop iterates ``stepper.live_steps``
    only (single-generation carry, one fused hop per call).  Checkpoints
    store the *original* next-shift index plus the step-list signature:
    same-mode resumes filter the step list to ``>= saved`` (the fused
    hop left the carry exactly at the next live step), while a
    *cross-mode* restore (compacted checkpoint under ``--no-compact`` or
    vice versa) is refused loudly — the carry's position and arity
    (one generation vs two) do not transfer between step sequences, so
    a silent resume would count misaligned panels.

    Supervised runs (``--inject-faults``/``--supervise``) drive the same
    loop under :class:`repro.runtime.Supervisor`: each restart restores
    the latest intact checkpoint (the manager quarantines corrupt steps)
    and a ``DeviceLost`` re-factorizes the surviving devices via
    :func:`repro.runtime.best_grid`, re-plans through the pipeline, and
    restarts the count on the smaller grid — mid-schedule per-device
    partials are **refused** across grids (DESIGN.md §8), so the regrid
    counts from shift 0 into a fresh ``regrid_{q}x{q}`` subdirectory.
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import compat
    from ..ckpt import CheckpointManager
    from ..core.api import make_grid_mesh
    from ..core.cannon import build_cannon_stepper
    from ..pipeline import plan_cannon
    from ..runtime import faultinject

    t0 = time.perf_counter()
    cross_mode = (
        "checkpoint in {d} was written by a run with a different "
        "schedule shape ({why}) — the saved carry's position and arity "
        "do not transfer across step sequences (compacted vs "
        "--no-compact, double- vs single-buffered), and partial counts "
        "accumulated under one collective strategy must not be summed "
        "under another: resume with the original flags or start from a "
        "fresh --ckpt-dir"
    )
    coll_sig = (
        f"reduce={args.reduce_strategy},broadcast={args.broadcast or 'auto'}"
    )

    def setup(q, ckpt_dir):
        """Plan + stepper + checkpoint manager for one grid size.  Runs
        once up front and again per DeviceLost regrid."""
        art = plan_cannon(
            g, q, chunk=args.chunk, compact=not args.no_compact,
        )
        plan = art.plan
        mesh = make_grid_mesh(q)
        stepper = build_cannon_stepper(
            plan, mesh,
            use_step_mask=False if args.no_skip_mask else None,
            double_buffer=not args.no_double_buffer,
            compact=False if args.no_compact else None,
        )
        arrays = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
        statics = {
            k: arrays[k]
            for k in ("m_ti", "m_tj", "m_cnt", "step_keep")
            if k in arrays
        }
        steps = (
            list(stepper.live_steps)
            if stepper.live_steps is not None
            else list(range(q))
        )
        mgr = CheckpointManager(ckpt_dir, keep=2, async_save=False)
        n_carry = stepper.n_carry
        # shape/dtype template for restore: carry leaves are
        # operand-shaped (two payload generations when double-buffered)
        # — no need to run the prime dispatch just to describe the
        # checkpoint structure
        ops = [arrays[k] for k in ("a_indptr", "a_indices", "b_indptr",
                                   "b_indices")]
        state_like = {f"carry{i}": ops[i % len(ops)] for i in range(n_carry)}
        state_like["acc"] = jnp.zeros((q, q), compat.default_count_dtype())
        return dict(
            q=q, ckpt_dir=ckpt_dir, stepper=stepper, arrays=arrays,
            statics=statics, steps=steps, mgr=mgr, n_carry=n_carry,
            state_like=state_like, step_sig=",".join(map(str, steps)),
            grid_sig=f"{q}x{q}",
        )

    env = setup(args.grid, args.ckpt_dir)
    t1 = time.perf_counter()

    def restore_or_prime(env):
        from ..runtime.supervisor import check_partials_portable

        try:
            _, restored, extra = env["mgr"].restore_latest(env["state_like"])
        except KeyError as e:  # carry arity mismatch: fewer/more leaves
            raise SystemExit(
                cross_mode.format(d=env["ckpt_dir"], why=f"missing {e}")
            ) from e
        if restored is None:
            carry0 = env["stepper"].prime(env["arrays"])
            st = {f"carry{i}": c for i, c in enumerate(carry0)}
            st["acc"] = env["state_like"]["acc"]
            return st, 0
        check_partials_portable(extra, env["grid_sig"])
        if extra.get("steps", env["step_sig"]) != env["step_sig"]:
            raise SystemExit(
                cross_mode.format(
                    d=env["ckpt_dir"],
                    why=f"steps [{extra['steps']}] vs [{env['step_sig']}]",
                )
            )
        if extra.get("collectives", coll_sig) != coll_sig:
            raise SystemExit(
                cross_mode.format(
                    d=env["ckpt_dir"],
                    why=(
                        f"collectives [{extra['collectives']}] vs "
                        f"[{coll_sig}]"
                    ),
                )
            )
        start = int(extra["shift"])
        print(f"resumed at shift {start}")
        return restored, start

    failed = {"done": False}

    def attempt(attempt_index, guard):
        st, start = restore_or_prime(env)
        stepper, statics = env["stepper"], env["statics"]
        n_carry, mgr, steps = env["n_carry"], env["mgr"], env["steps"]
        todo = [s for s in steps if s >= start]
        while todo:
            guard()
            s = todo.pop(0)
            if (
                args.fail_at_shift is not None
                and s == args.fail_at_shift
                and not failed["done"]
            ):
                failed["done"] = True
                print(
                    f"(injected failure at shift {s}; restarting from ckpt)"
                )
                _, restored, extra = mgr.restore_latest(env["state_like"])
                if restored is not None:
                    st = restored  # noqa: PLW2901
                    saved = int(extra["shift"])  # next shift to execute
                    todo = [t for t in steps if t >= saved]
                    s = todo.pop(0)  # noqa: PLW2901
            faultinject.fire("step", step=s)
            out = stepper(
                tuple(st[f"carry{i}"] for i in range(n_carry))
                + (st["acc"],),
                statics,
                step=s,
            )
            st = {f"carry{i}": out[i] for i in range(n_carry)}
            st["acc"] = out[n_carry]
            mgr.save(
                s + 1, st,
                extra={"shift": s + 1, "steps": env["step_sig"],
                       "collectives": coll_sig,
                       "grid": env["grid_sig"]},
            )
        return st

    if fault_plan is not None or args.supervise:
        from ..runtime import (
            BackoffPolicy,
            DeviceLost,
            Supervisor,
            best_grid,
        )
        from ..runtime.supervisor import (
            GridTransferRefused,
            check_partials_portable,
        )

        sup = Supervisor(
            max_restarts=args.restart_budget,
            attempt_deadline=args.attempt_deadline,
            backoff=BackoffPolicy(base=0.02, max_delay=0.5),
        )

        def on_fault(e, rec):
            if fault_plan is not None and fault_plan.log:
                last = fault_plan.log[-1]
                rec.point, rec.step = last.get("point"), last.get("step")
            if not isinstance(e, DeviceLost):
                return None
            remaining = len(jax.devices()) - e.lost
            # the stepper substrate is Cannon-only: square survivors
            r, _ = best_grid(remaining, require_square=True)
            if r < 1:
                raise RuntimeError(
                    f"cannot regrid: {e.lost} devices lost, "
                    f"{remaining} remaining"
                )
            # surface the refusal loudly: probe the old grid's latest
            # checkpoint against the new signature, then drop it
            try:
                _, restored, extra = env["mgr"].restore_latest(
                    env["state_like"]
                )
                if restored is not None:
                    check_partials_portable(extra, f"{r}x{r}")
            except GridTransferRefused as refuse:
                print(f"(device lost: {refuse})")
            except Exception:  # old-grid dir unreadable: nothing to move
                pass
            env["mgr"].close()
            new_dir = os.path.join(args.ckpt_dir, f"regrid_{r}x{r}")
            env.clear()
            env.update(setup(r, new_dir))
            sup.report.regrids.append(
                dict(lost=e.lost, grid=[r, r], ckpt_dir=new_dir)
            )
            return f"regrid to {r}x{r}"

        with faultinject.armed(fault_plan):
            st = sup.run(attempt, on_fault=on_fault)
        sup_dict = sup.report.to_dict()
        if fault_plan is not None:
            sup_dict["fault_log"] = list(fault_plan.log)
    else:
        st = attempt(0, lambda: None)
        sup_dict = None

    total = int(np.asarray(st["acc"]).sum())
    t2 = time.perf_counter()
    env["mgr"].close()
    out = dict(
        triangles=total,
        ppt_seconds=round(t1 - t0, 4),
        tct_seconds=round(t2 - t1, 4),
        checkpointed=True,
        live_steps=len(env["steps"]),
        schedule_shifts=env["q"],
    )
    if sup_dict is not None:
        if sup_dict.get("regrids"):
            out["final_grid"] = [env["q"], env["q"]]
        out.update(_supervision_fields(sup_dict))
    return total, out


if __name__ == "__main__":
    main()
