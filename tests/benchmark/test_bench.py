"""CPU tests of the chip benchmark under ``bench/``.

    python -m pytest tests/benchmark

They pin the benchmark's own generators and reference to the program's,
check the trace reduction on a recorded trace, show that a cell, a mix
and a metric are found by name, that a run without a TPU prints nothing,
and that the comparison fails each fault a cell can have, and fails the
control.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference, trace  # noqa: E402
from bench.generators import kronecker, simple_edges, uniform  # noqa: E402

# three warm counts of kron-s16 on one v5e, traced as a --trace 1 run traces
FIXTURE = ROOT / "bench" / "fixtures" / "trace_kron-s16.json"
KRON = dict(edge_factor=16, initiator=[0.57, 0.19, 0.19, 0.05])
BIG_SEED = 2**31 + 12345


def _run(spec: dict) -> dict:
    out = subprocess.run(
        [sys.executable, str(Path(__file__).with_name("cpu_run.py")),
         json.dumps(spec)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ----------------------------------------------------------------------
# the benchmark's copies against the program's originals
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 3, BIG_SEED])
def test_kronecker_draws_the_programs_rmat(seed):
    from repro.core import rmat

    n, src, dst = kronecker.sample(
        dict(KRON, scale=10), np.random.default_rng(seed)
    )
    np.testing.assert_array_equal(
        simple_edges(n, src, dst), rmat(10, 16, seed=seed).edges
    )


@pytest.mark.parametrize("seed", [0, 3, BIG_SEED])
def test_uniform_is_the_programs_er_before_its_cut(seed):
    from repro.core import Graph, erdos_renyi

    n, src, dst = uniform.sample(
        dict(scale=10, edge_factor=16), np.random.default_rng(seed)
    )
    edges = simple_edges(n, src, dst)
    np.testing.assert_array_equal(edges, Graph.from_edges(n, src, dst).edges)
    # the program's G(n, m) draws the same samples, then keeps 8 n edges
    np.testing.assert_array_equal(
        edges[: 8 * n], erdos_renyi(n, 16, seed=seed).edges
    )


@pytest.mark.parametrize(
    "spec", ["named:karate", "rmat:10", "er:500,8", "empty"]
)
def test_reference_equals_the_oracle(spec):
    from repro.core import Graph, graph_from_spec, triangle_count_oracle

    g = Graph(n=10, edges=np.zeros((0, 2), np.int64)) if spec == "empty" \
        else graph_from_spec(spec)
    assert reference.count(g.n, g.edges) == triangle_count_oracle(g)


@pytest.mark.parametrize("generator", ["kronecker", "uniform"])
def test_relabel_moves_the_graph_and_keeps_the_plan_shapes(generator):
    from repro.core import Graph
    from repro.core.plan import build_plan
    from repro.pipeline import relabel_stage

    gen = kronecker if generator == "kronecker" else uniform
    n, src, dst = gen.sample(dict(KRON, scale=11), np.random.default_rng(0))
    edges = simple_edges(n, src, dst)
    longest = harness.longest_row(n, edges, np.arange(n))
    ranked, shapes = [], []
    for seed in (1, 2, BIG_SEED):
        moved = harness.relabel(n, edges, seed)
        assert not np.array_equal(moved, edges)
        assert reference.count(n, moved) == reference.count(n, edges)
        g, _ = relabel_stage(Graph(n=n, edges=moved), reorder=True)
        plan = build_plan(g, 1)
        assert plan.dmax == longest
        ranked.append(g.edges)
        shapes.append({k: getattr(v, "shape", v) for k, v in vars(plan).items()
                       if isinstance(v, (int, np.ndarray))})
    # the program is handed a differently oriented graph on every seed
    assert not np.array_equal(ranked[0], ranked[1])
    assert shapes[0] == shapes[1] == shapes[2]


def test_relabel_is_the_seeds():
    cell = harness.load_cell("urand-s16.warm", ROOT)
    cell.config = dict(cell.config, scale=9)
    a, b, c = (harness.make_graph(cell, s)[1] for s in (7, 7, 8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
def _ops(*spans):
    return [trace.Op("op", s, e - s) for s, e in spans]


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 13)]) == [
        (0, 3), (5, 10), (12, 13)
    ]


def test_covered_and_gaps_within_the_window():
    ops = _ops((0, 4), (2, 6), (10, 12), (15, 30))
    # window [1, 20]: covered 1..6, 10..12, 15..20
    assert trace.covered_ns(ops, 1, 20) == 5 + 2 + 5
    assert trace.gaps(ops, 1, 20) == [(6, 10), (12, 15)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_gaps_are_named_by_the_innermost_host_span():
    tr = trace.Trace(
        devices={"/device:TPU:0": _ops((0, 10), (14, 20))},
        host=[trace.Op("bench.window", 0, 30), trace.Op("digest", 11, 2)],
    )
    assert trace.top_gaps(tr, 0, 30) == [
        ["bench.window", 10e-9], ["digest", 4e-9]
    ]


def test_recorded_trace_reduces_to_busy_idle_and_breakdown():
    tr = trace.load(str(FIXTURE))
    lo, hi = tr.span(harness.WINDOW_SPAN)
    assert list(tr.devices) == ["/device:TPU:0"]
    for ops in tr.devices.values():
        busy = trace.covered_ns(ops, lo, hi)
        idle = sum(e - s for s, e in trace.gaps(ops, lo, hi))
        assert 0 < busy < hi - lo
        assert busy + idle == pytest.approx(hi - lo)
    top = trace.top_ops(tr, lo, hi)
    assert top[0][0].startswith("jit_")
    gaps = trace.top_gaps(tr, lo, hi)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # between programs the host was in the count call, outside the jit
    assert [g[0] for g in gaps[:3]] == [harness.COUNT_SPAN] * 3


def test_trace_metrics_read_the_recorded_trace():
    tr = trace.load(str(FIXTURE))
    cell = harness.load_cell("kron-s16.warm", ROOT)
    window = tr.span(harness.WINDOW_SPAN)
    counts = sum(o.name == harness.COUNT_SPAN for o in tr.host)
    run = harness.Run(
        cell=cell, setup_s=1.0, backend_start_s=0.5, first_count_s=1.0,
        plan_s=0.5, count_times=[1.0] * counts, window_s=1.0,
        trace=tr, trace_window=window,
    )
    idle = harness.reader(cell, "device_idle_pct")(run)
    per_count = harness.reader(cell, "count_device_s")(run)
    assert 0 < idle < 100
    busy_s = (1 - idle / 100) * (window[1] - window[0]) / 1e9
    assert per_count * counts == pytest.approx(busy_s)


# ----------------------------------------------------------------------
# found by name, data-driven
# ----------------------------------------------------------------------
def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    before = {
        p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()
    }
    cfg = json.loads((root / "bench/configs/kron-s16.json").read_text())
    cfg.update(name="tiny", scale=8)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tinymix.json").write_text(
        json.dumps({"warmup_counts": 2})
    )
    (root / "bench/metrics/tiny_counts.py").write_text(
        "def read(run):\n    return float(len(run.count_times))\n"
    )
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="tiny",
                                file="bench/configs/tiny.json"))
    spec["workloads"].append(dict(name="tiny.tinymix", config="tiny",
                                  traffic="tinymix", chips=1, why="test"))
    spec["end_to_end"].append(dict(
        name="tiny_counts", unit="1", better="higher", bound=0.01,
        source="host_clock", workloads=["tiny.tinymix"],
    ))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny.tinymix", root)
    assert cell.config["scale"] == 8 and cell.traffic["warmup_counts"] == 2
    assert [m["name"] for m in cell.end_to_end][-1] == "tiny_counts"
    assert "tiny_counts" not in [
        m["name"] for m in harness.load_cell("kron-s16.warm", root).end_to_end
    ]
    n, edges = harness.make_graph(cell, 3)
    assert n == 256 and edges.shape[1] == 2
    run = harness.Run(cell, setup_s=0, backend_start_s=0, first_count_s=0,
                      plan_s=0, count_times=[1.0, 1.0], window_s=2.0)
    assert harness.reader(cell, "tiny_counts")(run) == 2.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_count_s_takes_every_stall_and_p95_the_tail():
    cell = harness.load_cell("kron-s16.warm", ROOT)
    times = [1.0] * 19 + [4.0]
    run = harness.Run(cell, setup_s=9.0, backend_start_s=6.0,
                      first_count_s=2.0, plan_s=0.5, count_times=times,
                      window_s=23.5)
    assert harness.reader(cell, "count_s")(run) == pytest.approx(23.5 / 20)
    # nearest rank: the 19th of 20, so one stall leaves the p95 alone
    assert harness.reader(cell, "count_p95_s")(run) == 1.0
    assert harness.reader(cell, "count_p95_s")(
        dataclasses.replace(run, count_times=[1.0] * 18 + [4.0, 4.0])
    ) == 4.0
    assert harness.reader(cell, "backend_start_s")(run) == 6.0


def test_the_command_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron-s16.warm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env,
    )
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_without_the_program_the_harness_cannot_start(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    code = (
        "import sys; from pathlib import Path; "
        f"sys.path.insert(0, {str(tmp_path)!r}); from bench import harness; "
        f"harness.configure_jax(Path({str(tmp_path)!r}))"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path, env=env)
    assert out.returncode != 0 and out.stdout == ""
    assert "repro" in out.stderr


# ----------------------------------------------------------------------
# the comparison: sound runs pass, the control and each fault fail
# ----------------------------------------------------------------------
ONE_CHIP = dict(cell="kron-s16.warm", overrides={"scale": 10}, chips=1)
# the 2x2 layout of a Kronecker graph on four forced host devices
MESH = dict(cell="kron-s16.warm", overrides={"scale": 10, "mesh": {"q": 2}},
            chips=4)
URAND = dict(cell="urand-s16.warm", overrides={"scale": 12}, chips=1)


@pytest.mark.parametrize("base", [ONE_CHIP, MESH, URAND],
                         ids=["one_chip", "mesh", "urand"])
def test_a_sound_run_is_correct(base):
    res = _run(dict(base, seed=BIG_SEED, seconds=0.3, fault="none"))
    assert res["correct"] and res["failed"] == 0
    assert res["checks"] == {"count_gap": {"value": 0, "limit": 0}}
    assert list(res)[-1] == "checks"
    cell = harness.load_cell(base["cell"], ROOT)
    assert list(res["metrics"]) == [m["name"] for m in cell.end_to_end]
    assert "setup_s" in res["metrics"] and len(res["metrics"]) > 1


@pytest.mark.parametrize(
    "base,fault",
    [(ONE_CHIP, "control"), (URAND, "control"), (MESH, "control"),
     (ONE_CHIP, "answer"), (MESH, "answer"), (MESH, "exchange")],
    ids=["control_one_chip", "control_urand", "control_mesh",
         "answer_one_chip", "answer_mesh", "exchange_mesh"],
)
def test_a_broken_run_is_not_correct(base, fault):
    res = _run(dict(base, seed=11, seconds=0.3, fault=fault))
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
    assert res["checks"]["count_gap"]["value"] > 0


def test_peaks_are_the_programs_and_unknown_kinds_are_refused():
    from repro.launch.roofline import PEAKS

    from bench import peaks

    assert peaks.PEAKS == PEAKS
    with pytest.raises(RuntimeError, match="no published peaks"):
        peaks.for_kind("TPU v99")
