"""One run of a benchmark cell on the CPU, for the benchmark's tests.

    python tests/benchmark/cpu_run.py '<json>'

The JSON names the ``cell``, ``seed``, ``seconds``, optional config
``overrides`` (a smaller ``scale``), the ``chips`` (forced host devices)
and a ``fault`` to plant underneath the run: ``none``, ``answer`` (the
engine's count altered where it is produced), ``exchange`` (the shifts
between chips left out) or ``control`` (the reference's control in the
program's place).  It skips the harness's look for a TPU and prints the
result line.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def plant(fault: str):
    """Break the timed path underneath the harness; returns the counter
    to use (``None`` for the program's own)."""
    if fault == "none":
        return None
    if fault == "control":
        from bench import reference

        return lambda n, edges: (reference.control_count(n, edges), 0.0)
    if fault == "answer":
        from repro.core import cannon

        build = cannon.build_cannon_fn

        class Altered:
            def __init__(self, fn):
                self.fn = fn

            def __call__(self, *args, **kwargs):
                return self.fn(*args, **kwargs) + 1

            def __getattr__(self, name):
                return getattr(self.fn, name)

        cannon.build_cannon_fn = lambda *a, **k: Altered(build(*a, **k))
        return None
    if fault == "exchange":
        from repro import compat

        compat.ppermute = lambda x, axis, perm: x
        return None
    raise ValueError(f"unknown fault {fault!r}")


def main(spec: dict) -> dict:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.get('chips', 1)}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_enable_x64", True)
    from bench import harness

    cell = harness.load_cell(spec["cell"], ROOT)
    cell.config = dict(cell.config, **spec.get("overrides", {}))
    counter = plant(spec.get("fault", "none"))
    return harness.run_cell(
        cell, spec["seed"], spec["seconds"], False,
        devices=jax.devices()[: spec.get("chips", cell.chips)],
        counter=counter,
    )


if __name__ == "__main__":
    print(json.dumps(main(json.loads(sys.argv[1]))))
