"""Entry points: chip_smoke.py's host reference and its refusal off the
chip, serve's exit code, and where the compile cache goes."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from repro.core import graph_from_spec, triangle_count_oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, tmp_path, **env):
    """Run a command of this checkout on the CPU, its compile cache in
    ``tmp_path``."""
    full = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        **env,
    )
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=full, capture_output=True,
        text=True, timeout=600,
    )


@pytest.mark.parametrize(
    "spec", ["named:karate", "rmat:10", "powerlaw:600,2.2", "er:40,0"]
)
def test_chip_smoke_reference_matches_oracle(spec):
    g = graph_from_spec(spec)
    assert _chip_smoke().reference_count(g) == triangle_count_oracle(g)


def test_chip_smoke_refuses_cpu(tmp_path):
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize(
    "faults,failed", [("plan_stage*100", 3), ("plan_stage*3", 1), ("", 0)]
)
def test_serve_exit_code_counts_failed_requests(tmp_path, faults, failed):
    """Any failed request makes ``serve --tc-graphs`` exit non-zero, also
    when the failures stay within ``--failure-budget`` (default 3)."""
    args = ["-m", "repro.launch.serve", "--tc-graphs", "named:karate",
            "--rounds", "3", "--verify"]
    out = _run(args + (["--inject-faults", faults] if faults else []),
               tmp_path)
    assert f"{3 - failed} ok, {failed} failed" in out.stdout
    assert (out.returncode != 0) == (failed > 0), out.stderr[-2000:]


def test_serve_without_mode_exits_with_usage(tmp_path):
    """``serve`` with neither mode exits non-zero and names both."""
    out = _run(["-m", "repro.launch.serve", "--rounds", "1"], tmp_path)
    assert out.returncode != 0
    assert "usage:" in out.stderr
    assert "--tc-graphs" in out.stderr and "--tc-stream" in out.stderr
    assert "triangles=" not in out.stdout


def test_compile_cache_placed_from_env(tmp_path, monkeypatch):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, ``tc_run`` writes its
    compiles there; unset, the cache goes to ``<checkout>/.jax_cache``."""
    from repro.launch.compile_cache import ENV_VAR, compile_cache_dir

    out = _run(
        ["-m", "repro.launch.tc_run", "--graph", "named:karate", "--json"],
        tmp_path, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1])["triangles"] == 45
    assert os.listdir(tmp_path / "jax_cache")
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")
