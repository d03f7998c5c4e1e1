"""End-to-end distributed triangle counting (the paper's application).

A CPU rehearsal: spawns itself with 16 forced XLA *CPU* host devices and
runs the 4x4 Cannon grid, the SUMMA rectangular schedule, the 2.5D
two-pod variant, and the 1D baseline on the same graph — all must agree
with the oracle.  It does not run on the chip; ``chip_smoke.py`` is the
chip run (one chip, or the 2x2 mesh with ``--chips 4``).

    PYTHONPATH=src python examples/distributed_tc.py
"""
import os
import subprocess
import sys

CHILD = """
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import count_triangles, rmat, triangle_count_oracle

g = rmat(12, 16, seed=3)
exp = triangle_count_oracle(g)
print(f"graph n={g.n} m={g.m} expected={exp}")

r = count_triangles(g, q=4, schedule="cannon")
print(f"cannon 4x4      : {r.triangles}  tct={r.count_seconds:.3f}s")
assert r.triangles == exp

r = count_triangles(g, q=2, npods=2, schedule="cannon")
print(f"2.5D 2x(2x2)    : {r.triangles}  tct={r.count_seconds:.3f}s")
assert r.triangles == exp

from repro import compat
mesh = compat.make_mesh((2, 8), ("data", "model"))
r = count_triangles(g, mesh=mesh, schedule="summa")
print(f"summa 2x8       : {r.triangles}  tct={r.count_seconds:.3f}s")
assert r.triangles == exp

r = count_triangles(g, q=4, schedule="oned")
print(f"1D baseline p=16: {r.triangles}  tct={r.count_seconds:.3f}s")
assert r.triangles == exp
print("all schedules agree ✓")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env)
    sys.exit(proc.returncode)


if __name__ == "__main__":
    main()
