"""Kernel microbenchmark: tc_tile popcount vs MXU vs jnp ref, plus the
fused-vs-search2-vs-tile count-kernel comparison on the dense-ish block
fixture.  On CPU the tile kernel runs in interpret mode and every time
is directional only.

    python -m benchmarks.kernels [--quick]
    python -m benchmarks.kernels --smoke   # CI guard: fails if the fused
        kernel miscounts on the fixture or its warm count-side tct
        regresses more than FUSED_REGRESSION_SLACK vs search2

The parent never imports JAX: the tile timings run in a child of their
own (``--tile-timings``) and the fused fixture in ``tc_run`` children,
so each child can hold the chip in turn.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile

from .common import REPO, csv_row, run_tc_subprocess, timeit

# dense-ish block fixture: every block-pair task is a real clique
# intersection, so the short bucket dominates and the fused panel is on
# its home turf (the same fixture engine_baseline uses for the skip A/B)
FUSED_GRAPH = "cliques:3,60"
# fused warm tct must not exceed search2's by more than this (both are
# min-over-warm dispatch times; small slack absorbs host timer noise)
FUSED_REGRESSION_SLACK = 1.05


def main(quick=False):
    cmd = [sys.executable, "-m", "benchmarks.kernels", "--tile-timings"]
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        cmd + (["--quick"] if quick else []), cwd=REPO, env=env,
        capture_output=True, text=True, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stdout[-1000:] + out.stderr[-1000:])
    print(out.stdout, end="")
    return fused_fixture(repeat=3 if quick else 5)


def tile_timings(quick=False):
    """tc_tile popcount / MXU kernel vs the jnp reference, in this
    process; interpret mode everywhere but on a TPU."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.tc_tile.ops import tile_pair_count
    from repro.kernels.tc_tile.ref import tile_triple_counts_ref

    interpret = jax.default_backend() != "tpu"

    nt, ntr = (4, 8) if quick else (16, 64)
    ka, kb, km = jax.random.split(jax.random.key(0), 3)
    A = jax.random.bits(ka, (nt, 128, 4), dtype=jnp.uint32)
    B = jax.random.bits(kb, (nt, 128, 4), dtype=jnp.uint32)
    M = jax.random.bits(km, (nt, 128, 4), dtype=jnp.uint32)
    trips = jnp.concatenate(
        [
            jax.random.randint(jax.random.key(1), (ntr, 3), 0, nt),
            jnp.ones((ntr, 1), jnp.int32),
        ],
        axis=1,
    ).astype(jnp.int32)

    rows = []
    for mode in ("popcount", "mxu"):
        t = timeit(
            lambda: tile_pair_count(
                trips, A, B, M, mode=mode, interpret=interpret
            ).block_until_ready()
        )
        rows.append((f"kernels/tc_tile_{mode}", t * 1e6))
    t = timeit(
        lambda: jnp.sum(
            tile_triple_counts_ref(trips, A, B, M)
        ).block_until_ready()
    )
    rows.append(("kernels/tc_tile_ref", t * 1e6))
    for name, us in rows:
        print(csv_row(name, us, f"triples={ntr}"))
    return rows


def fused_fixture(
    graph: str = FUSED_GRAPH,
    grid: int = 1,
    table_dir: "str | None" = None,
    repeat: int = 5,
) -> dict:
    """Warm count-side tct of the three count kernels on the dense-ish
    fixture, every run oracle-verified in its subprocess:

    * ``fused``   — the Pallas mega-kernel with its tile shape selected
      by the measured-autotune table (``--autotune measured``; the first
      run pays the cold timing pass, the table persists in
      ``table_dir``);
    * ``search2`` — the two-level bucketed search (the incumbent);
    * ``tile``    — the bit-packed 128x128 tile join.
    """
    table_dir = table_dir or tempfile.mkdtemp(prefix="tc_measured_bench_")
    runs = {
        "fused": ("--autotune", "measured", "--measured-dir", table_dir),
        "search2": (),
        "tile": (),
    }
    out = {"graph": graph, "grid": grid}
    counts = {}
    for name, extra in runs.items():
        r = run_tc_subprocess(
            graph, grid, method=name,
            extra=("--verify", "--repeat", str(repeat)) + extra,
        )
        counts[name] = r["triangles"]
        cell = dict(
            tct_seconds=r["tct_seconds"],
            triangles=r["triangles"],
            method=r["method"],
        )
        for key in ("autotune_mode", "measured_table_hit",
                    "autotuned_d_small", "autotuned_chunk"):
            if key in r:
                cell[key] = r[key]
        out[name] = cell
        print(csv_row(f"kernels/fused_fixture/{name}",
                      r["tct_seconds"] * 1e6,
                      f"triangles={r['triangles']}"))
    assert len(set(counts.values())) == 1, (
        f"count kernels disagree on {graph}: {counts}"
    )
    return out


def fused_smoke() -> dict:
    """CI guard: the fused kernel must count the fixture correctly
    (asserted via --verify inside each subprocess plus cross-kernel
    agreement) and must not regress vs search2 beyond the slack."""
    table_dir = tempfile.mkdtemp(prefix="tc_measured_smoke_")
    fx = fused_fixture(table_dir=table_dir)
    fused_t = fx["fused"]["tct_seconds"]
    search2_t = fx["search2"]["tct_seconds"]
    if fused_t > search2_t * FUSED_REGRESSION_SLACK:
        # single-host wall times on shared CI machines are noisy; one
        # re-measure (warm measured table) before declaring a regression
        fx2 = fused_fixture(table_dir=table_dir)
        fused_t = min(fused_t, fx2["fused"]["tct_seconds"])
        search2_t = max(search2_t, fx2["search2"]["tct_seconds"])
        if fused_t > search2_t * FUSED_REGRESSION_SLACK:
            raise SystemExit(
                f"kernels smoke FAILED: fused tct {fused_t:.4f}s "
                f"regresses vs search2 {search2_t:.4f}s on "
                f"{fx['graph']} (slack {FUSED_REGRESSION_SLACK}x)"
            )
    print(
        f"# kernels smoke ok: fused {fused_t:.4f}s vs search2 "
        f"{search2_t:.4f}s vs tile {fx['tile']['tct_seconds']:.4f}s on "
        f"{fx['graph']}, all kernels agree "
        f"({fx['fused']['triangles']} triangles)"
    )
    return fx


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        fused_smoke()
    elif "--tile-timings" in sys.argv:
        tile_timings("--quick" in sys.argv)
    else:
        main("--quick" in sys.argv)
