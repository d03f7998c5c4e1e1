import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be run as its own process (the XLA flag above is consumed at first
jax initialization; the first two lines run before any jax import).

For each cell this prints/records:
  * ``compiled.memory_analysis()``  — proves the sharded program fits;
  * ``compiled.cost_analysis()``    — FLOPs/bytes for §Roofline;
  * parsed per-device collective bytes (roofline third term).

Usage:
  python -m repro.launch.dryrun --cell <arch>:<shape>:<mesh>   # one cell
  python -m repro.launch.dryrun --list                         # all cells
  (the sweep driver benchmarks/dryrun_sweep.py runs cells in subprocesses)

Mesh names: "pod" = 16x16 (256 chips), "multipod" = 2x16x16 (512 chips).

It compiles for forced *CPU* devices, so it says nothing about the TPU
compiler; the roofline prices each cell at the published peaks of the
chip the meshes model (``TARGET_KIND``).
"""
import argparse
import json
import sys
import traceback

# the chip the pod meshes model
TARGET_KIND = "TPU v5 lite"


def all_cells():
    """Every (arch, shape, mesh) cell of the assignment matrix."""
    from ..configs import ASSIGNED_ARCHS, TC_GRAPHS, get_config

    cells = []
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name, shape in cfg.shapes.items():
            if cfg.family == "lm" and shape.get("skip_full_attention"):
                continue  # long_500k skipped: all LM archs are full-attn
            for mesh_name in ("pod", "multipod"):
                cells.append((arch, shape_name, mesh_name))
    for g in TC_GRAPHS:
        for sched in ("cannon", "cannon25d", "oned"):
            mesh_name = "multipod" if sched == "cannon25d" else "pod"
            cells.append((g, sched, mesh_name))
    return cells


def run_cell(arch: str, shape_name: str, mesh_name: str) -> dict:
    import jax

    from ..configs import get_config
    from .mesh import make_production_mesh
    from .roofline import model_flops_lm, roofline_from_compiled

    cfg = get_config(arch)
    multi = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    chips = 512 if multi else 256
    label = f"{arch}:{shape_name}:{mesh_name}"

    if cfg.family == "tc":
        return _run_tc_cell(cfg, shape_name, mesh, chips, label)

    if cfg.family == "lm":
        from ..models.steps import (
            build_lm_decode_step,
            build_lm_prefill_step,
            build_lm_train_step,
            lm_input_specs,
        )

        shape = cfg.shapes[shape_name]
        kind = shape["kind"]
        dummy_params = jax.eval_shape(
            lambda k: __import__(
                "repro.models.transformer", fromlist=["lm_init"]
            ).lm_init(k, cfg),
            jax.random.key(0),
        )
        if kind == "train":
            fn, info = build_lm_train_step(cfg, mesh)
            specs = lm_input_specs(cfg, shape, step="train")
            opt_shape = info["opt_shape"]
            lowered = fn.lower(
                info["dummy"], opt_shape, specs["batch"], 0
            )
            mf = model_flops_lm(cfg, shape)
        elif kind == "prefill":
            fn, info = build_lm_prefill_step(cfg, mesh)
            specs = lm_input_specs(cfg, shape, step="prefill")
            lowered = fn.lower(info["dummy"], specs["tokens"])
            mf = model_flops_lm(cfg, shape)
        else:  # decode
            fn, info = build_lm_decode_step(cfg, mesh)
            specs = lm_input_specs(cfg, shape, step="decode")
            lowered = fn.lower(
                info["dummy"], specs["cache"], specs["token"], specs["cache_len"]
            )
            mf = model_flops_lm(cfg, shape)
        compiled = lowered.compile()
        rep = roofline_from_compiled(
            label, compiled, device_kind=TARGET_KIND, mesh_name=mesh_name, chips=chips, model_flops=mf
        )
        return rep.row()

    if cfg.family == "gnn":
        from ..models.gnn_steps import (
            build_gnn_train_step,
            gnn_feat_dim,
            gnn_input_specs,
        )

        shape = cfg.shapes[shape_name]
        d_feat = gnn_feat_dim(cfg, shape)
        batch = gnn_input_specs(cfg, shape)
        build, info = build_gnn_train_step(cfg, mesh, d_feat)
        fn = build(batch)
        opt_shape = jax.eval_shape(info["opt_init"], info["dummy"])
        lowered = fn.lower(info["dummy"], opt_shape, batch, 0)
        compiled = lowered.compile()
        rep = roofline_from_compiled(
            label, compiled, device_kind=TARGET_KIND, mesh_name=mesh_name, chips=chips,
            model_flops=_gnn_model_flops(cfg, shape),
        )
        return rep.row()

    if cfg.family == "recsys":
        from ..models.gnn_steps import (
            build_dlrm_retrieval_step,
            build_dlrm_serve_step,
            build_dlrm_train_step,
            recsys_input_specs,
        )

        shape = cfg.shapes[shape_name]
        specs = recsys_input_specs(cfg, shape)
        if shape["kind"] == "train":
            fn, info = build_dlrm_train_step(cfg, mesh)
            opt_shape = jax.eval_shape(info["opt_init"], info["dummy"])
            lowered = fn.lower(info["dummy"], opt_shape, specs, 0)
        elif shape["kind"] == "retrieval":
            fn, info = build_dlrm_retrieval_step(cfg, mesh)
            lowered = fn.lower(info["dummy"], specs["dense"], specs["cand_ids"])
        else:
            fn, info = build_dlrm_serve_step(cfg, mesh)
            lowered = fn.lower(
                info["dummy"], specs["dense"], specs["sparse_ids"]
            )
        compiled = lowered.compile()
        rep = roofline_from_compiled(
            label, compiled, device_kind=TARGET_KIND, mesh_name=mesh_name, chips=chips,
            model_flops=_recsys_model_flops(cfg, shape),
        )
        return rep.row()

    raise ValueError(cfg.family)


def _run_tc_cell(cfg, sched: str, mesh, chips: int, label: str) -> dict:
    """TC dry-run from the analytic plan (shape-only, no 1B-edge alloc)."""
    import jax
    import jax.numpy as jnp

    from ..core.api import get_schedule
    from ..core.plan import analytic_plan
    from .roofline import roofline_from_compiled

    build_cannon_fn = get_schedule("cannon").build_fn

    q = 16
    plan = analytic_plan(
        cfg.n_vertices,
        cfg.n_edges,
        q,
        dmax_block=cfg.dmax_block_est,
        chunk=512,
    )
    structs = plan.shape_structs()
    if sched == "cannon":
        fn = build_cannon_fn(plan, mesh, method="search")
        lowered = fn.lower(**structs)
        nshifts = q
    elif sched == "cannonopt":
        # beyond-paper variant: uint16-length blob compression (§Perf H1b)
        fn = build_cannon_fn(plan, mesh, method="search", compress_lengths=True)
        lowered = fn.lower(**structs)
        nshifts = q
    elif sched == "cannon2l":
        # §Perf H1a projection: two-level bucketed probes + gather-free
        # keys + H1b blobs.  Analytic plans carry no blocks, so the long
        # fraction is assumed 20% at d_small=64 (measured 0.9% at s16,
        # 15% at s18, q=4 — 20% is conservative for s26 at q=16).
        plan.n_long = max(1, int(0.20 * plan.tmax))  # type: ignore
        plan.d_small = 64  # type: ignore
        fn = build_cannon_fn(
            plan, mesh, method="search2", compress_lengths=True
        )
        lowered = fn.lower(**structs)
        nshifts = q
    elif sched == "cannon25d":
        # pod-stacked operands: add the leading pod dim to A/B structs
        npods = 2
        st = dict(structs)
        for k in ("a_indptr", "a_indices", "b_indptr", "b_indices"):
            s = structs[k]
            st[k] = jax.ShapeDtypeStruct((npods,) + s.shape, s.dtype)
        fn = build_cannon_fn(plan, mesh, pod_axis="pod", method="search")
        lowered = fn.lower(**st)
        nshifts = q // npods
    elif sched == "oned":
        from ..core.onedim import OneDPlan
        import numpy as np

        build_oned_fn = get_schedule("oned").build_fn

        p = chips
        nb = -(-cfg.n_vertices // p)
        nnz_pad = int(cfg.n_edges / p * 1.25)
        gmax = max(1, int(cfg.n_edges / (p * p) * 2.0))
        oplan = OneDPlan(
            n=cfg.n_vertices,
            m=cfg.n_edges,
            p=p,
            nb=nb,
            nnz_pad=nnz_pad,
            gmax=gmax,
            dmax=cfg.dmax_block_est * q,  # full rows: no /√p shrink
            chunk=512,
            indptr=np.zeros((1,), np.int32),
            indices=np.zeros((1,), np.int32),
            t_i=np.zeros((1,), np.int32),
            t_j=np.zeros((1,), np.int32),
            t_cnt=np.zeros((1,), np.int32),
        )
        from .. import compat

        flat_mesh = compat.make_mesh((p,), ("flat",))
        fn = build_oned_fn(oplan, flat_mesh)
        structs = {
            "indptr": jax.ShapeDtypeStruct((p, nb + 1), jnp.int32),
            "indices": jax.ShapeDtypeStruct((p, nnz_pad), jnp.int32),
            "t_i": jax.ShapeDtypeStruct((p, p, gmax), jnp.int32),
            "t_j": jax.ShapeDtypeStruct((p, p, gmax), jnp.int32),
            "t_cnt": jax.ShapeDtypeStruct((p, p), jnp.int32),
        }
        lowered = fn.lower(**structs)
        nshifts = p
    else:
        raise ValueError(sched)

    compiled = lowered.compile()
    # useful ops ~ paper's probe count: m * (d_avg/2) log2(d) per full pass
    import math

    d_avg = 2.0 * cfg.n_edges / cfg.n_vertices
    useful = cfg.n_edges * (d_avg / 2.0) * max(1.0, math.log2(max(2, d_avg)))
    rep = roofline_from_compiled(
        label,
        compiled,
        device_kind=TARGET_KIND,
        mesh_name="multipod" if sched == "cannon25d" else "pod",
        chips=chips,
        model_flops=useful,
    )
    row = rep.row()
    row["nshifts"] = nshifts
    row["nnz_pad_per_device"] = plan.nnz_pad
    return row


def _gnn_model_flops(cfg, shape) -> float:
    if shape["kind"] == "sampled":
        b = shape["batch_nodes"]
        f1, f2 = shape["fanout"]
        e = b * f1 + b * f1 * f2
        n = b * (1 + f1 + f1 * f2)
    elif shape["kind"] == "batched":
        n = shape["n_nodes"] * shape["batch"]
        e = shape["n_edges"] * shape["batch"]
    else:
        n, e = shape["n_nodes"], shape["n_edges"]
    d = cfg.d_hidden
    if cfg.arch == "gat":
        per_layer = 2 * n * d * d * cfg.n_heads + 6 * e * d * cfg.n_heads
    elif cfg.arch == "graphcast":
        per_layer = 2 * e * (2 * d) * d * 2 + 2 * n * (2 * d) * d * 2
    else:  # equivariant: TP/eSCN dominated
        s = (cfg.l_max + 1) ** 2
        per_layer = 6 * e * d * d * s
    return 3.0 * cfg.n_layers * per_layer  # fwd + bwd ~ 3x fwd


def _recsys_model_flops(cfg, shape) -> float:
    if shape["kind"] == "retrieval":
        return 2.0 * shape["n_candidates"] * cfg.embed_dim
    b = shape["batch"]
    mlp = 0
    dims = cfg.bot_mlp
    for i in range(len(dims) - 1):
        mlp += 2 * dims[i] * dims[i + 1]
    dims = cfg.top_mlp
    for i in range(len(dims) - 1):
        mlp += 2 * dims[i] * dims[i + 1]
    inter = 2 * (cfg.n_sparse + 1) ** 2 * cfg.embed_dim
    mult = 3.0 if shape["kind"] == "train" else 1.0
    return mult * b * (mlp + inter)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape:mesh")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            print(":".join(c))
        return

    arch, shape_name, mesh_name = args.cell.split(":")
    try:
        row = run_cell(arch, shape_name, mesh_name)
        row["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        row = {
            "name": args.cell,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc()[-2000:],
        }
    line = json.dumps(row)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    sys.exit(0 if row["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
