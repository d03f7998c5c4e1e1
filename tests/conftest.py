"""Shared fixtures.  NOTE: device count is deliberately left at the
default (1 CPU device) — multi-device tests spawn subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=N so smoke tests and
benchmarks always see a single device."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_distributed(code: str, ndev: int, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess with ndev host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"distributed subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def distributed_runner():
    return run_distributed
