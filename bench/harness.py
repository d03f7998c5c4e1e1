"""The benchmark's harness: cells, their graphs, one run, its result.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own and is found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives): the
  deployment, its graph generator and layout;
* ``generators/<generator>.py``: ``sample(cfg, rng) -> (n, src, dst)``;
* ``traffic/<traffic>.json``: the mix's parameters (``warmup_counts``:
  the counts made in set-up after the first; the window is a closed loop
  with one caller);
* ``metrics/<metric>.py``: ``read(run) -> float | None`` for every
  metric, end to end and per layer; ``None`` leaves it out of the line.

:func:`run_cell` makes one run: the cell's graph from the seed, the first
count, the warm-up counts, the measured window, then the reference.  The
program is reached only through the ``counter`` it is given, so the
control and the fault tests can put something else in its place.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COUNT_SPAN = "bench.count"
WINDOW_SPAN = "bench.window"
MAX_DRAWS = 100  # permutations tried per seed; Urand needs at most 5 in 200 seeds


# ----------------------------------------------------------------------
# cells, found by name
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def bench(self) -> Path:
        return self.root / "bench"


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json``, with its
    configuration and traffic files read."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name,
        config=config,
        traffic=traffic,
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root,
    )


def load_module(path: Path):
    """Import the file ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(cell: Cell, metric: str) -> Callable:
    return load_module(cell.bench / "metrics" / f"{metric}.py").read


# ----------------------------------------------------------------------
# the cell's graph
# ----------------------------------------------------------------------
def longest_row(n: int, edges: np.ndarray, ids: np.ndarray) -> int:
    """Longest row of the graph whose vertex ``v`` is renamed ``ids[v]``,
    with every edge oriented from the lower to the higher (degree, id)
    rank, as a degree-ordered planner orients it."""
    deg = np.bincount(edges.ravel(), minlength=n)
    rank = np.empty(n, dtype=np.int64)
    rank[np.lexsort((ids, deg))] = np.arange(n)
    low = np.minimum(rank[edges[:, 0]], rank[edges[:, 1]])
    return int(np.bincount(low, minlength=n).max())


def relabel(n: int, edges: np.ndarray, seed: int) -> np.ndarray:
    """The graph with its vertex ids permuted uniformly from ``seed``, as
    GAP's generator permutes them, drawn again from the same stream until
    its longest degree-ordered row is the generated graph's.

    A permutation moves the tie-break between neighbours of equal degree,
    so every seed hands the program a differently oriented graph.  The
    program plans exact shapes and pads every row to the longest one; on
    Urand that row is 29 or 30 long by the permutation.  Holding it gives
    every seed the same plan shapes, the same compiled program and the
    same amount of work.
    """
    rng = np.random.default_rng(seed % (1 << 64))
    want = longest_row(n, edges, np.arange(n))
    for _ in range(MAX_DRAWS):
        ids = rng.permutation(n)
        if longest_row(n, edges, ids) == want:
            lo = np.minimum(ids[edges[:, 0]], ids[edges[:, 1]])
            hi = np.maximum(ids[edges[:, 0]], ids[edges[:, 1]])
            key = np.sort(lo * np.int64(n) + hi)
            return np.stack([key // n, key % n], axis=1)
    raise RuntimeError(
        f"no permutation in {MAX_DRAWS} draws keeps the longest row {want}"
    )


def make_graph(cell: Cell, seed: int):
    """``(n, edges)``: the configuration's graph, relabeled by ``seed``."""
    from bench.generators import simple_edges

    cfg = cell.config
    gen = load_module(cell.bench / "generators" / f"{cfg['generator']}.py")
    n, src, dst = gen.sample(cfg, np.random.default_rng(cfg["graph_seed"]))
    return n, relabel(n, simple_edges(n, src, dst), seed)


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------
def program_counter(cell: Cell) -> Callable:
    """``count(n, edges) -> (triangles, plan_seconds)`` through the
    program's entry, with the deployment's layout and nothing else."""
    from repro.core import Graph, count_triangles, make_grid_mesh

    q = int(cell.config["mesh"]["q"])
    layout = dict(q=1) if q == 1 else dict(mesh=make_grid_mesh(q))
    graphs: Dict[int, Graph] = {}

    def count(n, edges):
        g = graphs.get(id(edges))
        if g is None:  # one Graph object per edge list, as a caller holds
            g = graphs[id(edges)] = Graph(n=n, edges=edges, name=cell.name)
        res = count_triangles(g, **layout)
        return res.triangles, res.preprocess_seconds

    return count


class CompileClock:
    """Counts and times XLA backend compiles, from JAX's monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read it."""

    cell: Cell
    setup_s: float
    backend_start_s: float
    first_count_s: float
    plan_s: float
    count_times: List[float]
    window_s: float
    trace: Optional[object] = None  # bench.trace.Trace of the window
    trace_window: Optional[tuple] = None  # (start_ns, end_ns) on its clock


def nearest_rank(values: List[float], pct: float) -> float:
    ranked = sorted(values)
    return ranked[max(1, math.ceil(pct / 100 * len(ranked))) - 1]


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    devices: list,
    backend_start_s: float = 0.0,
    counter: Optional[Callable] = None,
    started: Optional[float] = None,
    log=sys.stderr,
) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    from bench import reference

    started = time.perf_counter() if started is None else started
    counter = counter or program_counter(cell)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        t = time.perf_counter()
        n, edges = make_graph(cell, seed)
        print(f"graph: n {n} m {edges.shape[0]} in "
              f"{time.perf_counter() - t:.3f} s", file=log)

        answers = []
        t = time.perf_counter()
        tri, plan_s = counter(n, edges)
        first_count_s = time.perf_counter() - t
        answers.append(tri)
        for _ in range(int(cell.traffic["warmup_counts"])):
            answers.append(counter(n, edges)[0])
        setup_compiles = clock.count
        setup_s = time.perf_counter() - started

        with tempfile.TemporaryDirectory() as tdir:
            if trace:
                jax.profiler.start_trace(tdir)
            times = []
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                start = time.perf_counter()
                while time.perf_counter() - start < seconds:
                    t = time.perf_counter()
                    with jax.profiler.TraceAnnotation(COUNT_SPAN):
                        answers.append(counter(n, edges)[0])
                    times.append(time.perf_counter() - t)
                window_s = time.perf_counter() - start
            tr = tr_window = None
            if trace:
                jax.profiler.stop_trace()
                tr, tr_window = _read_trace(tdir)
        window_compiles = clock.count - setup_compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)

    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    t = time.perf_counter()
    expected = reference.count(n, edges)
    reference_s = time.perf_counter() - t

    run = Run(
        cell=cell,
        setup_s=setup_s,
        backend_start_s=backend_start_s,
        first_count_s=first_count_s,
        plan_s=plan_s,
        count_times=times,
        window_s=window_s,
        trace=tr,
        trace_window=tr_window,
    )
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    gap = max(abs(a - expected) for a in answers)
    failed = sum(a != expected for a in answers)
    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": failed == 0,
        "attempted": len(answers),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        from bench import trace as trace_mod

        lo, hi = tr_window
        busy = [trace_mod.covered_ns(ops, lo, hi) for ops in tr.devices.values()]
        device["busy_s"] = sum(busy) / max(1, len(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr, lo, hi),
            "idle_gaps": trace_mod.top_gaps(tr, lo, hi),
        }
    print(
        f"triangles {expected}; counts {len(answers)} "
        f"({len(times)} in {window_s:.3f} s, slowest {max(times):.3f} s); "
        f"compiles in set-up {setup_compiles} ({clock.seconds:.3f} s), in "
        f"the window {window_compiles}; reference {reference_s:.3f} s",
        file=log,
    )
    result["checks"] = {"count_gap": {"value": gap, "limit": 0}}
    print(f"check count_gap {gap} limit 0", file=log)
    return result


def _read_trace(tdir: str):
    """The window's trace and its bounds on the trace's clock."""
    from bench import trace as trace_mod

    paths = sorted(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    tr = trace_mod.read_xplane(str(paths[-1]), (WINDOW_SPAN,))
    if not tr.devices:
        raise RuntimeError("the trace holds no device operations")
    return tr, tr.span(WINDOW_SPAN)


# ----------------------------------------------------------------------
# the devices
# ----------------------------------------------------------------------
def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; raises where JAX finds no TPU,
    fewer chips, or a kind of chip the table of peaks does not hold."""
    import jax

    from bench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's platform is {devs[0].platform!r}; the "
            "benchmark only measures on the chip"
        )
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks.for_kind(devs[0].device_kind)
    return devs[:chips]


def configure_jax(root: Path = ROOT) -> None:
    """The program's settings, as its entry points make them: 64-bit
    counts and the persistent compile cache in the checkout."""
    import jax

    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    # every program, however quick to compile, is served from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_x64", True)
