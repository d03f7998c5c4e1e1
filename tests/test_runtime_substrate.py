"""Tests: checkpointing, restarts, elastic re-planning, rebalancing."""
import os

import jax.numpy as jnp
import numpy as np
import pytest


# ----------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import load_checkpoint, save_checkpoint

    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": {"c": jnp.ones((5,), jnp.int32)},
    }
    save_checkpoint(str(tmp_path), 7, tree, extra={"x": 1})
    out, extra = load_checkpoint(str(tmp_path), 7, tree)
    assert extra == {"x": 1}
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(
        np.asarray(out["b"]["c"]), np.asarray(tree["b"]["c"])
    )


def test_checkpoint_corruption_detected(tmp_path):
    from repro.ckpt import load_checkpoint, save_checkpoint

    tree = {"a": jnp.ones((4,))}
    path = save_checkpoint(str(tmp_path), 1, tree)
    payload = os.path.join(str(tmp_path), "step_0000000001.npz")
    with open(payload, "r+b") as f:
        f.seek(30)
        f.write(b"\x00\x01\x02")
    with pytest.raises(IOError, match="corruption"):
        load_checkpoint(str(tmp_path), 1, tree)


def test_manager_rotation_and_restore(tmp_path):
    from repro.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in range(5):
        mgr.save(s, {"w": jnp.full((3,), float(s))}, extra={"next_step": s})
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 2  # rotated
    step, tree, extra = mgr.restore_latest({"w": jnp.zeros((3,))})
    assert step == 4 and float(tree["w"][0]) == 4.0


def test_async_manager(tmp_path):
    from repro.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    for s in range(3):
        mgr.save(s, {"w": jnp.full((2,), float(s))})
    mgr.wait()
    mgr.close()
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".npz")]) == 3


def test_run_with_restarts(tmp_path):
    from repro.runtime import run_with_restarts

    calls = {"failures": 0}

    def injector(step):
        if step == 5 and calls["failures"] == 0:
            calls["failures"] += 1
            raise RuntimeError("injected device loss")

    state = run_with_restarts(
        lambda: {"x": jnp.zeros(())},
        lambda st, i: {"x": st["x"] + 1.0},
        n_steps=10,
        ckpt_dir=str(tmp_path),
        ckpt_every=2,
        fault_injector=injector,
    )
    assert float(state["x"]) == 10.0
    assert calls["failures"] == 1


# ----------------------------------------------------------------------
# elastic + rebalance
# ----------------------------------------------------------------------
def test_best_grid():
    from repro.runtime import best_grid

    assert best_grid(16) == (4, 4)
    assert best_grid(8) == (2, 4)
    # 12 = 3x4 violates the SUMMA panel-slot constraint (4 % 3 != 0);
    # the most-square admissible factorization is 2x6
    assert best_grid(12) == (2, 6)
    assert best_grid(256) == (16, 16)
    assert best_grid(255, require_square=True) == (15, 15)


def test_replan_elastic_counts_correctly():
    from repro.core import rmat, triangle_count_oracle
    from repro.runtime import replan_elastic

    g = rmat(9, 8, seed=2)
    sched, plan, (r, c) = replan_elastic(g, 4)
    assert sched == "cannon" and (r, c) == (2, 2)
    sched, plan, (r, c) = replan_elastic(g, 8)
    assert sched == "summa" and r * c <= 8


def test_rebalance_improves_or_equal():
    from repro.core import rmat
    from repro.pipeline import PlanCache
    from repro.runtime import rebalance_plan

    g = rmat(10, 8, seed=1)
    plan, report = rebalance_plan(g, 3, trials=4, cache=PlanCache(0))
    # seed 0 is the identity baseline, so the search can never lose
    assert report["improvement"] >= 1.0
    assert (
        report["best_masked_critical_path"]
        <= report["baseline_masked_critical_path"]
    )
    assert "skipped_steps" in report
    assert [t["seed"] for t in report["trials"]] == [0, 1, 2, 3]
    assert plan.stats is not None and plan.step_keep is not None


def test_rebalance_lowers_masked_critical_path_all_schedules():
    """Acceptance fixture: on the skewed powerlaw graph every schedule's
    rebalance search strictly beats the seed-0 masked critical path, and
    the winning relabel preserves the triangle count."""
    from repro.core import powerlaw, triangle_count_oracle
    from repro.pipeline import PlanCache, plan_cannon, plan_oned, plan_summa

    g = powerlaw(600, 2.2, seed=0)
    exp = triangle_count_oracle(g)
    cache = PlanCache(maxsize=0)
    arts = dict(
        cannon=plan_cannon(
            g, 3, keep_blocks=False, rebalance_trials=8, cache=cache
        ),
        summa=plan_summa(g, 2, 3, rebalance_trials=8, cache=cache),
        oned=plan_oned(g, 4, rebalance_trials=8, cache=cache),
    )
    for name, art in arts.items():
        rb = art.rebalance
        assert rb["best_seed"] != 0, name
        assert (
            rb["best_masked_critical_path"]
            < rb["baseline_masked_critical_path"]
        ), (name, rb)
        assert rb["improvement"] > 1.0, name
        assert triangle_count_oracle(art.graph) == exp, name
        deg = art.graph.degrees()
        assert np.all(deg[1:] >= deg[:-1]), name


def test_tc_run_rebalance_end_to_end():
    """tc_run --rebalance on the skewed fixture: the report carries the
    rebalance fields and the count matches the unrebalanced run."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(repo, "src"),
    )

    def run(extra):
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.tc_run",
             "--graph", "powerlaw:600,2.2", "--grid", "2", "--verify",
             "--json", *extra],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert out.returncode == 0, out.stdout[-800:] + out.stderr[-800:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    rb = run(["--rebalance", "4"])
    plain = run([])
    assert rb["correct"] and plain["correct"]
    assert rb["triangles"] == plain["triangles"]
    assert rb["rebalance_trials"] == 4
    assert rb["rebalance_improvement"] >= 1.0
    assert (
        rb["rebalance_masked_critical_path"]
        <= rb["rebalance_baseline_critical_path"]
    )
    assert rb["rebalance_skipped_delta"] >= 0
    assert "rebalance_best_seed" in rb
    assert "rebalance_improvement" not in plain
