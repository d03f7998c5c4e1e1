"""CPU tests of the ``chunglu-s18-2x2`` configuration and its cell.

    python -m pytest tests/benchmark/test_chunglu.py

The Chung–Lu generator against a direct draw of its law, the reference
on a Chung–Lu graph, the cell's plan shapes over relabelings, sound and
broken runs of the cell on four forced host devices, and
``count_device_max_s`` on recorded traces.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference, trace  # noqa: E402
from bench.generators import chunglu, simple_edges  # noqa: E402

CELL = "chunglu-s18-2x2.warm"
CHUNGLU = dict(cell=CELL, overrides={"scale": 10}, chips=4)
KRON_FIXTURE = ROOT / "bench" / "fixtures" / "trace_kron-s16.json"
# three warm counts of chunglu-s18-2x2 on the 2x2 mesh of one v5e host
MESH_FIXTURE = ROOT / "bench" / "fixtures" / "trace_chunglu-s18-2x2.json"
BIG_SEED = 2**31 + 12345


def _run(spec: dict) -> dict:
    out = subprocess.run(
        [sys.executable, str(Path(__file__).with_name("cpu_run.py")),
         json.dumps(spec)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cfg(scale: int) -> dict:
    return dict(harness.load_cell(CELL, ROOT).config, scale=scale)


# ----------------------------------------------------------------------
# the generator
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 3, BIG_SEED])
def test_chunglu_is_a_direct_draw_of_its_law_with_nothing_cut(seed):
    cfg = _cfg(10)
    n, src, dst = chunglu.sample(cfg, np.random.default_rng(seed))
    again = chunglu.sample(cfg, np.random.default_rng(seed))
    np.testing.assert_array_equal(src, again[1])
    np.testing.assert_array_equal(dst, again[2])
    # every one of edge_factor * n pairs, each endpoint drawn on its own
    # with p[v] ∝ (v + 1)^(-1/(alpha - 1))
    w = (np.arange(n) + 1.0) ** (-1.0 / (cfg["alpha"] - 1.0))
    rng = np.random.default_rng(seed)
    m = cfg["edge_factor"] * n
    np.testing.assert_array_equal(src, rng.choice(n, size=m, p=w / w.sum()))
    np.testing.assert_array_equal(dst, rng.choice(n, size=m, p=w / w.sum()))
    assert n == 1024 and src.shape == dst.shape == (m,)


def test_chunglu_weights_follow_the_power_law():
    p = chunglu.weights(1 << 12, 2.1)
    assert p.sum() == pytest.approx(1.0)
    v = np.array([1, 10, 100, 1000])
    np.testing.assert_allclose(
        p[v - 1] / p[0], v ** (-1 / 1.1), rtol=1e-12
    )
    assert np.all(np.diff(p) < 0)  # the lowest ids are the hubs
    assert p[0] / p[-1] == pytest.approx(len(p) ** (1 / 1.1))


def test_reference_equals_the_oracle():
    from repro.core import Graph, triangle_count_oracle

    n, src, dst = chunglu.sample(_cfg(9), np.random.default_rng(0))
    g = Graph(n=n, edges=simple_edges(n, src, dst))
    assert reference.count(g.n, g.edges) == triangle_count_oracle(g) > 0


# ----------------------------------------------------------------------
# the cell's graph: one shape key over its relabelings
# ----------------------------------------------------------------------
def test_the_cells_plans_take_at_most_two_shape_keys_over_seeds_0_to_7():
    from repro.core import Graph
    from repro.pipeline import PlanCache, plan_cannon

    cell = harness.load_cell(CELL, ROOT)
    n, src, dst = chunglu.sample(
        cell.config, np.random.default_rng(cell.config["graph_seed"])
    )
    edges = simple_edges(n, src, dst)
    assert (n, edges.shape[0]) == (1 << 18, 3_847_936)
    keys, exact = set(), set()
    for seed in range(8):
        g = Graph(n=n, edges=harness.relabel(n, edges, seed))
        plan = plan_cannon(g, cell.config["mesh"]["q"],
                           cache=PlanCache(0)).plan
        keys.add(plan.shape_key())
        exact.add((int(plan.m_cnt.max()), plan.dmax))
    # per-block maxima of 978,682-979,563 entries and rows of 127-129
    # all pad to 983,040 and 136
    assert len(keys) == 1 < len(exact)


# ----------------------------------------------------------------------
# the comparison on the cell: a sound run passes, each fault fails
# ----------------------------------------------------------------------
@pytest.mark.parametrize("base", [CHUNGLU], ids=["chunglu"])
def test_a_sound_run_is_correct(base):
    res = _run(dict(base, seed=BIG_SEED, seconds=0.3, fault="none"))
    assert res["correct"] and res["failed"] == 0
    assert res["checks"] == {"count_gap": {"value": 0, "limit": 0}}
    assert res["device"]["count"] == 4
    cell = harness.load_cell(base["cell"], ROOT)
    assert list(res["metrics"]) == [m["name"] for m in cell.end_to_end]
    assert "setup_s" in res["metrics"] and len(res["metrics"]) > 1


@pytest.mark.parametrize("fault", ["control", "answer", "exchange"])
@pytest.mark.parametrize("base", [CHUNGLU], ids=["chunglu"])
def test_a_broken_run_is_not_correct(base, fault):
    res = _run(dict(base, seed=11, seconds=0.3, fault=fault))
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
    assert res["checks"]["count_gap"]["value"] > 0


# ----------------------------------------------------------------------
# count_device_max_s on recorded traces
# ----------------------------------------------------------------------
def _run_of(path: Path, cell_name: str):
    tr = trace.load(str(path))
    counts = sum(o.name == harness.COUNT_SPAN for o in tr.host)
    return tr, harness.Run(
        cell=harness.load_cell(cell_name, ROOT), setup_s=1.0,
        backend_start_s=0.5, first_count_s=1.0, plan_s=0.5,
        count_times=[1.0] * counts, window_s=1.0,
        trace=tr, trace_window=tr.span(harness.WINDOW_SPAN),
    )


def test_count_device_max_s_reads_the_slowest_chip():
    tr, run = _run_of(MESH_FIXTURE, CELL)
    lo, hi = run.trace_window
    assert len(tr.devices) == 4
    per_chip = [
        trace.covered_ns(ops, lo, hi) / 1e9 / len(run.count_times)
        for ops in tr.devices.values()
    ]
    slowest = harness.reader(run.cell, "count_device_max_s")(run)
    mean = harness.reader(run.cell, "count_device_s")(run)
    assert slowest == pytest.approx(max(per_chip))
    assert min(per_chip) < mean < slowest


def test_count_device_max_s_is_count_device_s_on_one_chip():
    _, run = _run_of(KRON_FIXTURE, "kron-s16.warm")
    read = lambda name: harness.reader(run.cell, name)(run)  # noqa: E731
    assert read("count_device_max_s") == pytest.approx(
        read("count_device_s"), rel=1e-12
    )
    assert read("count_device_max_s") > 0
