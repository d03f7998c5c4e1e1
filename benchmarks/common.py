"""Shared benchmark helpers: subprocess multi-device runs + timing.

The multi-device helpers force ``grid*grid*pods`` (or ``ndev``) *CPU*
host devices through ``XLA_FLAGS``: they are a CPU rehearsal of the
mesh paths, and their times are XLA CPU times, not chip times.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_tc_subprocess(
    graph: str,
    grid: int,
    *,
    schedule: str = "cannon",
    method: str = "search",
    pods: int = 1,
    chunk: int = 512,
    extra=(),
    timeout: int = 1200,
) -> dict:
    """Run tc_run in a subprocess with grid*grid*pods CPU host devices."""
    ndev = grid * grid * pods
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    cmd = [
        sys.executable, "-m", "repro.launch.tc_run",
        "--graph", graph, "--grid", str(grid), "--pods", str(pods),
        "--schedule", schedule, "--method", method, "--chunk", str(chunk),
        "--json", *extra,
    ]
    out = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout
    )
    if out.returncode != 0:
        raise RuntimeError(out.stdout[-1000:] + out.stderr[-1000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_py_subprocess(code: str, ndev: int, timeout: int = 1200) -> str:
    """Run a python snippet with ndev CPU host devices; return stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stdout[-800:] + out.stderr[-800:])
    return out.stdout


def timeit(fn, *, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
