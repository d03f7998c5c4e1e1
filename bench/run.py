#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload kron-s16.warm --seed 7 --seconds 30 --trace 0

Generates the cell's graph from ``--seed``, makes the first count and the
warm-up counts (set-up), counts the same graph back to back for
``--seconds`` in a closed loop with one caller, then checks every count
against the benchmark's own reference.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics from a profiler trace of the window), ``device`` and ``checks``.

It runs only on a TPU: where JAX finds no TPU, or fewer chips than the
cell needs, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_FLAGS = "--xla_enable_hlo_trace=false"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.trace:
        # per-op trace points fill the device's trace buffer within about
        # one count (the count kernel loops over every task); the traced
        # run compiles without them and records one event per program run
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            filter(None, (os.environ.get("LIBTPU_INIT_ARGS"), TRACE_FLAGS))
        )
    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    t = time.perf_counter()
    try:
        devices = harness.tpu_devices(cell.chips)
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    backend_start_s = time.perf_counter() - t
    harness.configure_jax(ROOT)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        devices=devices, backend_start_s=backend_start_s, started=STARTED,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
