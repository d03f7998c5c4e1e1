"""Paper Fig. 3: fraction of runtime attributable to communication.

Derived from the roofline terms of the compiled program at each grid size:
comm_fraction = t_collective / (t_collective + max(t_compute, t_memory)) —
the same quantity the paper measures by timing MPI calls, here from the
loop-aware HLO parse (per-shift blob bytes x shifts / ICI bw)."""
from __future__ import annotations

import sys

from .common import csv_row


_CODE = """
import json
from repro.core import build_plan, preprocess, rmat
from repro.core.api import get_schedule, make_grid_mesh
from repro.launch.roofline import hlo_cost, peaks_for
build_cannon_fn = get_schedule("cannon").build_fn

g, _ = preprocess(rmat({scale}, 16))
plan = build_plan(g, {q})
fn = build_cannon_fn(plan, make_grid_mesh({q}))
comp = fn.lower(**plan.shape_structs()).compile()
cost = hlo_cost(comp.as_text())
hw = peaks_for("TPU v5 lite")  # projected onto the v5e's peaks
t_coll = sum(cost["collectives"].values()) / hw["link_bw"]
t_mem = cost["bytes"] / hw["hbm_bw"]
print(json.dumps({{"frac": t_coll / max(t_coll + t_mem, 1e-12)}}))
"""


def main(quick=False):
    import json

    from .common import run_py_subprocess

    scale = 11 if quick else 13
    out = []
    for q in (2,) if quick else (2, 3, 4):
        r = json.loads(
            run_py_subprocess(_CODE.format(scale=scale, q=q), ndev=q * q)
            .strip()
            .splitlines()[-1]
        )
        out.append((q * q, r["frac"]))
        print(
            csv_row(f"fig3/ranks{q*q}", 0.0, f"comm_fraction={r['frac']:.3f}")
        )
    return out


if __name__ == "__main__":
    main("--quick" in sys.argv)
