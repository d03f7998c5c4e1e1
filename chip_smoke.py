#!/usr/bin/env python3
"""Chip smoke run: the 2D triangle count end to end on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # Cannon on the 2x2 mesh only
    python chip_smoke.py --scale 21    # a larger Graph500 graph

One process drives the chip through the entry points ``tc_run`` and
``serve`` use (``count_triangles``, ``count_triangles_many``) and checks
every count against an independent host reference (a scipy masked
SpGEMM over the degree-oriented upper triangle).  Phases on one chip:

* ``cannon``  — a Graph500 Kronecker graph (``rmat(scale, 16)``, the
  Graph500 initiator) counted cold, then warm, at the default method;
* ``fused``   — ``method="fused"`` with ``fused_impl="pallas"`` (a VMEM
  gate failure raises, never demotes);
* ``tile``    — ``method="tile"``, the bit-tile Pallas kernel;
* ``serving`` — ``count_triangles_many`` over three graphs, cold then
  warm, as ``serve --tc-graphs`` does.

``--chips 4`` runs Cannon on ``make_grid_mesh(2)`` at the same scale and
checks that every staged operand is sharded over four devices.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every count
matched and nothing was demoted.  Without a TPU the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
WARM_RUNS = 2
SERVING_GRAPHS = ("rmat:16", "rmat:16,16,1", "er:65536,16")
FUSED_SCALE = 16
FUSED_IMPL = "pallas"  # explicit: a VMEM gate failure raises, never demotes
TILE_SCALE = 10
REFERENCE_ROWS = 1 << 16  # rows per masked-SpGEMM block


def reference_count(graph) -> int:
    """Exact triangle count on the host, independent of the code under
    test: orient every edge from lower to higher (degree, id) rank, then
    sum ``(U @ U) .* U`` in row blocks (each triangle once, at its
    lowest-ranked vertex)."""
    import scipy.sparse as sp

    n, e = graph.n, np.asarray(graph.edges)
    if e.shape[0] == 0:
        return 0
    deg = np.bincount(e.ravel(), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    u, v = rank[e[:, 0]], rank[e[:, 1]]
    upper = sp.csr_matrix(
        (np.ones(e.shape[0], np.int64), (np.minimum(u, v), np.maximum(u, v))),
        shape=(n, n),
    )
    total = 0
    for r0 in range(0, n, REFERENCE_ROWS):
        blk = upper[r0 : r0 + REFERENCE_ROWS]
        total += int((blk @ upper).multiply(blk).sum())
    return total


def _device_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX platform {devs[0].platform!r}); "
            "this run only counts on the chip",
            file=sys.stderr,
        )
        sys.exit(2)
    if len(devs) < chips:
        print(
            f"chip_smoke: needs {chips} TPU devices, JAX sees {len(devs)}",
            file=sys.stderr,
        )
        sys.exit(2)
    return devs


class _CompileClock:
    """Sums XLA backend-compile seconds reported by JAX's monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


class Smoke:
    """Per-phase bookkeeping shared by every phase."""

    def __init__(self, devs):
        import jax

        self.jax = jax
        self.devs = devs
        self.clock = _CompileClock()
        jax.monitoring.register_event_duration_secs_listener(self.clock)

    def close(self):
        self.jax.monitoring.unregister_event_duration_listener(self.clock)

    def _peak_bytes(self):
        stats = self.devs[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def emit(self, record: dict) -> dict:
        d0 = self.devs[0]
        record = dict(
            record,
            platform=d0.platform,
            device_kind=d0.device_kind,
            peak_bytes_in_use=self._peak_bytes(),
        )
        print(json.dumps(record), flush=True)
        return record

    def audited(self, fn):
        """Run ``fn`` failing on any demotion: a ``note_demotion`` record
        or a ``RuntimeWarning`` raised from the package."""
        from repro.runtime.supervisor import collecting_demotions

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with collecting_demotions() as demotions:
                out = fn()
        ours = [
            w for w in caught
            if issubclass(w.category, RuntimeWarning)
            and f"{os.sep}repro{os.sep}" in str(w.filename)
        ]
        if demotions or ours:
            raise RuntimeError(
                "demoted on the main path: "
                f"{demotions or [str(w.message) for w in ours]}"
            )
        return out

    def count_phase(self, phase, graph, spec, extra=(), **kwargs):
        """Cold count, then ``WARM_RUNS`` warm counts, of one graph, with
        the host reference computed meanwhile on a second thread; returns
        the printed record (plus ``extra`` fields) and the cold
        ``TCResult``."""
        from repro.core import count_triangles

        with ThreadPoolExecutor(max_workers=1) as pool:
            reference = pool.submit(_reference, graph)
            before = self.clock.seconds
            t0 = time.perf_counter()
            cold = self.audited(lambda: count_triangles(graph, **kwargs))
            cold_s = time.perf_counter() - t0
            compile_s = self.clock.seconds - before
            expected, ref_s = reference.result()
        _check(phase, cold.triangles, expected)
        warm = []
        for _ in range(WARM_RUNS):
            t0 = time.perf_counter()
            res = self.audited(lambda: count_triangles(graph, **kwargs))
            warm.append(time.perf_counter() - t0)
            _check(phase, res.triangles, expected)
        return self.emit(dict(
            phase=phase,
            graph=spec,
            n=graph.n,
            m=graph.m,
            triangles=cold.triangles,
            expected=expected,
            method=cold.method,
            schedule=cold.schedule,
            grid=list(cold.grid),
            plan_seconds=cold.preprocess_seconds,
            compile_seconds=compile_s,
            cold_seconds=cold_s,
            cold_count_seconds=cold.count_seconds,
            warm_seconds=warm,
            warm_median_seconds=statistics.median(warm),
            warm_compile_seconds=self.clock.seconds - before - compile_s,
            reference_seconds=ref_s,
            **dict(extra),
        )), cold


def _check(phase, got, expected):
    if got != expected:
        raise AssertionError(f"{phase}: counted {got}, reference {expected}")


def _graph(spec):
    from repro.core import graph_from_spec

    t0 = time.perf_counter()
    g = graph_from_spec(spec)
    return g, time.perf_counter() - t0


def _reference(g):
    t0 = time.perf_counter()
    exp = reference_count(g)
    return exp, time.perf_counter() - t0


def cannon_phase(smoke, scale, seed, mesh=None, phase="cannon"):
    spec = f"rmat:{scale},16,{seed}"
    g, gen_s = _graph(spec)
    kwargs = dict(q=1) if mesh is None else dict(mesh=mesh)
    return smoke.count_phase(
        phase, g, spec, extra=dict(generate_seconds=gen_s), **kwargs
    )


def fused_phase(smoke, seed):
    spec = f"rmat:{FUSED_SCALE},16,{seed}"
    g, _ = _graph(spec)
    record, res = smoke.count_phase(
        "fused", g, spec, q=1, method="fused", fused_impl=FUSED_IMPL,
    )
    if res.method != "fused":
        raise AssertionError(f"fused phase ran {res.method!r}")
    return record


def tile_phase(smoke, seed):
    spec = f"rmat:{TILE_SCALE},16,{seed}"
    g, _ = _graph(spec)
    record, res = smoke.count_phase("tile", g, spec, q=1, method="tile")
    if res.method != "tile":
        raise AssertionError(f"tile phase ran {res.method!r}")
    return record


def serving_phase(smoke):
    from repro.core import count_triangles_many

    graphs = [_graph(s)[0] for s in SERVING_GRAPHS]
    expected = [reference_count(g) for g in graphs]

    def request():
        return smoke.audited(
            lambda: count_triangles_many(
                graphs, q=1, schedule="cannon", method="search"
            )
        )

    before = smoke.clock.seconds
    t0 = time.perf_counter()
    cold = request()
    cold_s = time.perf_counter() - t0
    compile_s = smoke.clock.seconds - before
    _check("serving", cold.triangles, expected)
    warm = []
    for _ in range(WARM_RUNS):
        t0 = time.perf_counter()
        res = request()
        warm.append(time.perf_counter() - t0)
        _check("serving", res.triangles, expected)
        if not res.cache_hit:
            raise AssertionError("serving: warm request missed the plan cache")
    return smoke.emit(dict(
        phase="serving",
        graphs=list(SERVING_GRAPHS),
        n=[g.n for g in graphs],
        m=[g.m for g in graphs],
        triangles=cold.triangles,
        expected=expected,
        method="search",
        plan_seconds=cold.plan_seconds,
        compile_seconds=compile_s,
        cold_seconds=cold_s,
        warm_seconds=warm,
        warm_median_seconds=statistics.median(warm),
        padding_overhead=cold.padding_overhead,
    ))


def check_spread(staged: dict, n_devices: int) -> int:
    """Every staged operand must be sharded over ``n_devices`` distinct
    devices; returns how many operands were checked."""
    if not staged:
        raise AssertionError("no staged operands to check")
    for name, arr in staged.items():
        devices = arr.sharding.device_set
        if len(devices) != n_devices:
            raise AssertionError(
                f"staged operand {name!r} lives on {len(devices)} "
                f"device(s), expected {n_devices}"
            )
    return len(staged)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=20,
                    help="Graph500 scale of the Cannon phase (2^scale "
                         "vertices, edge factor 16)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = _device_or_exit(args.chips)
    import jax

    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_enable_x64", True)  # as tc_run sets it

    smoke = Smoke(devs)
    try:
        if args.chips == 4:
            from repro.core.api import make_grid_mesh

            record, res = cannon_phase(
                smoke, args.scale, args.seed, mesh=make_grid_mesh(2),
                phase="cannon_2x2",
            )
            checked = check_spread(res.staged, 4)
            print(json.dumps(dict(
                phase="cannon_2x2_spread", staged_operands=checked,
                devices_per_operand=4,
            )), flush=True)
        else:
            cannon_phase(smoke, args.scale, args.seed)
            fused_phase(smoke, args.seed)
            tile_phase(smoke, args.seed)
            serving_phase(smoke)
    finally:
        smoke.close()

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
