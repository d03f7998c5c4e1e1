"""Supervised fault-tolerant execution (DESIGN.md §8).

Every test here injects *deterministic* typed faults and asserts the
recovered count is byte-identical to the fault-free run within the
restart budget — recovery re-executes the deterministic pipeline, it
never patches partial state.  Single-device tests run inline; the
multi-device recovery/regrid matrix runs in subprocesses via
``distributed_runner`` (conftest keeps the main process at 1 device).

Run just this suite with ``pytest -m fault``.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.fault


# ----------------------------------------------------------------------
# fault plan: grammar + fire semantics
# ----------------------------------------------------------------------
def test_fault_spec_grammar():
    from repro.runtime.faultinject import (
        CkptCorrupt,
        DeviceLost,
        FaultPlan,
        StageFault,
        StepFault,
    )

    plan = FaultPlan.parse(
        "step@2;step@1=devicelost:5;fused=stepfault*-1;ckpt_save;"
        "plan_stage=stage_fault*3"
    )
    s = plan.sites
    assert (s[0].point, s[0].step, s[0].fault, s[0].times) == (
        "step", 2, StepFault, 1
    )
    assert (s[1].fault, s[1].lost) == (DeviceLost, 5)
    assert (s[2].fault, s[2].times) == (StepFault, -1)
    # point-only tokens take the point's default fault type
    assert s[3].fault is CkptCorrupt
    assert (s[4].fault, s[4].times) == (StageFault, 3)
    # describe() round-trips through parse()
    assert FaultPlan.parse(plan.describe()).describe() == plan.describe()

    with pytest.raises(ValueError, match="unknown injection point"):
        FaultPlan.parse("warp_core@3")
    with pytest.raises(ValueError, match="unknown fault type"):
        FaultPlan.parse("step=gremlin")
    with pytest.raises(ValueError, match="empty fault spec"):
        FaultPlan.parse(" ; ")


def test_fault_site_fire_semantics():
    from repro.runtime import faultinject as fi

    plan = fi.FaultPlan.parse("step@1;device_stage=stagefault*2")
    with fi.armed(plan):
        assert fi.is_armed()
        fi.fire("step", step=0)  # wrong step: no-op
        with pytest.raises(fi.StepFault):
            fi.fire("step", step=1)
        fi.fire("step", step=1)  # one-shot: spent after one firing
        for _ in range(2):
            with pytest.raises(fi.StageFault):
                fi.fire("device_stage")
        fi.fire("device_stage")  # times=2 exhausted
    assert not fi.is_armed()
    assert plan.spent()
    assert [e["point"] for e in plan.log] == [
        "step", "device_stage", "device_stage"
    ]
    # unarmed fire is a no-op even at a matching point
    fi.fire("step", step=1)


def test_live_step_indices_compose_with_compaction():
    from repro.core import rmat
    from repro.pipeline import plan_cannon
    from repro.runtime.faultinject import live_step_indices

    g = rmat(9, 8, seed=2)
    art = plan_cannon(g, 3)
    steps = live_step_indices(art.plan)
    assert steps and all(0 <= s < 3 for s in steps)
    if art.plan.compact is not None and art.plan.compact.n_elided > 0:
        assert steps == list(art.plan.compact.live_steps)
    # compaction off: every original step is live
    assert live_step_indices(art.plan, compact_enabled=False) == [0, 1, 2]


# ----------------------------------------------------------------------
# supervisor: backoff / budget / deadline with a fake clock
# ----------------------------------------------------------------------
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


def _fake_supervisor(**kw):
    from repro.runtime import BackoffPolicy, Supervisor

    clk = _FakeClock()
    kw.setdefault(
        "backoff", BackoffPolicy(base=1.0, factor=2.0, max_delay=8.0,
                                 jitter=0.0)
    )
    return Supervisor(clock=clk, sleep=clk.sleep, **kw), clk


def test_supervisor_backoff_sequence_and_recovery():
    from repro.runtime import StepFault

    sup, clk = _fake_supervisor(max_restarts=5)
    calls = {"n": 0}

    def attempt(i, guard):
        calls["n"] += 1
        if calls["n"] <= 3:
            raise StepFault(f"boom {i}")
        return 42

    assert sup.run(attempt) == 42
    rep = sup.report
    assert rep.restarts == 3 and not rep.gave_up
    assert [a.outcome for a in rep.attempts] == [
        "fault", "fault", "fault", "ok"
    ]
    # exponential, jitter-free: 1, 2, 4
    assert [a.backoff for a in rep.attempts[:3]] == [1.0, 2.0, 4.0]
    assert rep.total_backoff_seconds == pytest.approx(7.0)
    assert clk.t == pytest.approx(7.0)


def test_supervisor_budget_exhaustion():
    from repro.runtime import StepFault

    sup, _ = _fake_supervisor(max_restarts=2)

    def attempt(i, guard):
        raise StepFault("always")

    with pytest.raises(StepFault):
        sup.run(attempt)
    assert sup.report.gave_up
    assert len(sup.report.attempts) == 3  # initial + 2 restarts


def test_supervisor_deadline_cooperative():
    sup, clk = _fake_supervisor(max_restarts=3, attempt_deadline=5.0)
    state = {"slow": True}

    def attempt(i, guard):
        if state["slow"]:
            state["slow"] = False
            clk.t += 10.0  # a slow first attempt blows the deadline
        guard()
        return "done"

    assert sup.run(attempt) == "done"
    assert [a.outcome for a in sup.report.attempts] == ["deadline", "ok"]
    assert sup.report.attempts[0].fault == "AttemptDeadlineExceeded"


def test_supervisor_non_retryable_propagates():
    sup, _ = _fake_supervisor(max_restarts=5)

    def attempt(i, guard):
        raise KeyError("not a fault")

    with pytest.raises(KeyError):
        sup.run(attempt)
    assert sup.report.restarts == 0  # never recorded as a restartable


def test_backoff_jitter_is_deterministic_per_seed():
    import random

    from repro.runtime import BackoffPolicy

    pol = BackoffPolicy(base=1.0, factor=2.0, max_delay=64.0, jitter=0.5)
    a = [pol.delay(i, random.Random(7)) for i in range(1, 5)]
    b = [pol.delay(i, random.Random(7)) for i in range(1, 5)]
    assert a == b
    assert all(1.0 * 2 ** (i - 1) <= d < 1.5 * 2 ** (i - 1)
               for i, d in enumerate(a, 1))


# ----------------------------------------------------------------------
# degradation ladder + cross-grid portability
# ----------------------------------------------------------------------
def test_next_demotion_ladder_order():
    from repro.runtime.supervisor import next_demotion

    cfg = dict(method="fused", reduce_strategy="tree", hub_split=True)
    rungs = []
    while True:
        demo = next_demotion(cfg)
        if demo is None:
            break
        rungs.append((demo["rung"], demo["frm"], demo["to"]))
    assert rungs == [
        ("method", "fused", "search2"),
        ("method", "search2", "search"),
        ("compact", "auto", "off"),
        ("reduce", "tree", "flat"),
        ("hub_split", "on", "off"),
    ]
    assert cfg == dict(
        method="search", compact=False, reduce_strategy="flat",
        hub_split=False,
    )
    # oned has no two-level kernel: fused demotes straight to search
    cfg = dict(method="fused", schedule="oned")
    assert next_demotion(cfg)["to"] == "search"


def test_check_partials_portable():
    from repro.runtime import GridTransferRefused
    from repro.runtime.supervisor import check_partials_portable

    check_partials_portable({"grid": "3x3"}, "3x3")
    check_partials_portable({}, "2x2")  # pre-PR-10 checkpoints: no sig
    with pytest.raises(GridTransferRefused, match="decomposition-specific"):
        check_partials_portable({"grid": "3x3"}, "2x2")


# ----------------------------------------------------------------------
# supervised_count: single-device recovery across schedules
# ----------------------------------------------------------------------
@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
def test_supervised_count_recovers_every_point_inline(schedule):
    from repro.core import rmat, triangle_count_oracle
    from repro.runtime import FaultPlan, Supervisor
    from repro.runtime.supervisor import supervised_count

    g = rmat(8, 8, seed=3)
    exp = triangle_count_oracle(g)
    for compact in (None, False):
        for spec in ("plan_stage", "device_stage", "step@0"):
            sup = Supervisor(max_restarts=3)
            res = supervised_count(
                g,
                supervisor=sup,
                fault_plan=FaultPlan.parse(spec),
                q=1,
                schedule=schedule,
                compact=compact,
            )
            key = (schedule, compact, spec)
            assert res.triangles == exp, key
            assert res.supervision["restarts"] == 1, key
            assert not res.supervision["gave_up"], key
            assert res.supervision["fault_log"], key


def test_supervised_count_demotes_persistent_fused_fault():
    from repro.core import rmat, triangle_count_oracle
    from repro.runtime import FaultPlan, Supervisor
    from repro.runtime.supervisor import supervised_count

    g = rmat(8, 8, seed=3)
    exp = triangle_count_oracle(g)
    sup = Supervisor(max_restarts=5)
    res = supervised_count(
        g,
        supervisor=sup,
        fault_plan=FaultPlan.parse("fused=stepfault*-1"),
        q=1,
        schedule="cannon",
        method="fused",
        demote_after=2,
    )
    assert res.triangles == exp
    demos = res.supervision["demotions"]
    assert demos and demos[0]["rung"] == "method"
    assert demos[0]["frm"] == "fused" and demos[0]["to"] == "search2"
    assert "persistent StepFault" in demos[0]["reason"]
    assert res.method != "fused"


def test_supervised_count_gives_up_within_budget():
    from repro.core import rmat
    from repro.runtime import FaultPlan, StageFault, Supervisor
    from repro.runtime.supervisor import supervised_count

    g = rmat(8, 8, seed=3)
    sup = Supervisor(max_restarts=2)
    with pytest.raises(StageFault):
        supervised_count(
            g,
            supervisor=sup,
            fault_plan=FaultPlan.parse("plan_stage=stagefault*-1"),
            ladder=False,  # planning has no ladder rung to demote
            q=1,
        )
    assert sup.report.gave_up
    assert len(sup.report.attempts) == 3


# ----------------------------------------------------------------------
# checkpoint corruption: quarantine + fall back
# ----------------------------------------------------------------------
def test_restore_latest_quarantines_bitflipped_step(tmp_path):
    from repro.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    for s in (1, 2):
        mgr.save(s, {"w": jnp.full((3,), float(s))},
                 extra={"next_step": s})
    payload = os.path.join(str(tmp_path), "step_0000000002.npz")
    size = os.path.getsize(payload)
    with open(payload, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    step, tree, extra = mgr.restore_latest({"w": jnp.zeros((3,))})
    assert step == 1 and float(tree["w"][0]) == 1.0
    corrupt = [f for f in os.listdir(tmp_path) if f.endswith(".corrupt")]
    assert len(corrupt) == 2  # both the .json and .npz of step 2
    # quarantine=False restores the pre-PR-10 crash-on-corruption
    mgr2 = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr2.save(3, {"w": jnp.full((3,), 3.0)}, extra={"next_step": 3})
    with open(os.path.join(str(tmp_path), "step_0000000003.npz"),
              "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xfe")
    with pytest.raises(IOError, match="corruption"):
        mgr2.restore_latest({"w": jnp.zeros((3,))}, quarantine=False)


def test_ckpt_save_fault_corrupts_payload_post_write(tmp_path):
    from repro.ckpt import CheckpointManager
    from repro.runtime.faultinject import FaultPlan, armed

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(1, {"w": jnp.full((2,), 1.0)}, extra={"next_step": 1})
    plan = FaultPlan.parse("ckpt_save=ckptcorrupt")
    with armed(plan):
        # a CkptCorrupt site does NOT raise at save time: it flips a
        # byte of the just-written payload so *restore* pays
        mgr.save(2, {"w": jnp.full((2,), 2.0)}, extra={"next_step": 2})
    assert plan.spent()
    step, tree, _ = mgr.restore_latest({"w": jnp.zeros((2,))})
    assert step == 1 and float(tree["w"][0]) == 1.0


def test_restore_arity_mismatch_is_not_swallowed(tmp_path):
    """KeyError (cross-mode carry-arity detection) must pass through the
    quarantine net untouched — tc_run turns it into a loud refusal."""
    from repro.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    mgr.save(1, {"a": jnp.zeros((2,))})
    with pytest.raises(KeyError):
        mgr.restore_latest({"a": jnp.zeros((2,)), "b": jnp.zeros((2,))})
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".corrupt")]


# ----------------------------------------------------------------------
# async writer error surfacing
# ----------------------------------------------------------------------
def test_async_writer_error_surfaces_on_next_save(tmp_path, monkeypatch):
    import repro.ckpt.manager as M

    mgr = M.CheckpointManager(str(tmp_path), keep=2, async_save=True)
    mgr.save(0, {"w": jnp.zeros((2,))})
    mgr.wait()

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(M, "save_checkpoint", boom)
    mgr.save(1, {"w": jnp.zeros((2,))})
    mgr._q.join()
    with pytest.raises(RuntimeError, match="writer failed"):
        mgr.save(2, {"w": jnp.zeros((2,))})
    # the error was consumed: the manager is usable again
    monkeypatch.undo()
    mgr.save(3, {"w": jnp.zeros((2,))})
    mgr.close()


def test_async_writer_error_surfaces_on_close(tmp_path, monkeypatch):
    import repro.ckpt.manager as M

    mgr = M.CheckpointManager(str(tmp_path), keep=2, async_save=True)
    monkeypatch.setattr(
        M, "save_checkpoint",
        lambda *a, **k: (_ for _ in ()).throw(OSError("enospc")),
    )
    mgr.save(0, {"w": jnp.zeros((2,))})
    mgr._q.join()
    with pytest.raises(RuntimeError, match="writer failed"):
        mgr.close()


# ----------------------------------------------------------------------
# elastic re-plan through the pipeline
# ----------------------------------------------------------------------
def test_replan_elastic_pipeline_parity():
    """The elastic re-plan is *exactly* a cold pipeline plan at the new
    grid: masks, compaction and rebalance all survive (the legacy path
    silently dropped every one of them)."""
    from repro.core import rmat
    from repro.pipeline import PlanCache, plan_cannon
    from repro.runtime import replan_elastic

    g = rmat(9, 8, seed=2)
    cache = PlanCache(maxsize=8)
    sched, art, (r, c) = replan_elastic(
        g, 4, rebalance_trials=2, cache=cache
    )
    assert sched == "cannon" and (r, c) == (2, 2)
    cold = plan_cannon(g, 2, rebalance_trials=2, cache=PlanCache(0))
    assert art.plan.step_keep is not None
    np.testing.assert_array_equal(
        np.asarray(art.plan.step_keep), np.asarray(cold.plan.step_keep)
    )
    if cold.plan.compact is not None:
        assert art.plan.compact is not None
        assert tuple(art.plan.compact.live_steps) == tuple(
            cold.plan.compact.live_steps
        )
    assert art.rebalance is not None
    assert art.rebalance["best_seed"] == cold.rebalance["best_seed"]
    # same cache, same knobs: the second elastic re-plan is a cache hit
    misses = cache.stats()["misses"]
    replan_elastic(g, 4, rebalance_trials=2, cache=cache)
    assert cache.stats()["misses"] == misses
    assert cache.stats()["hits"] >= 1
    # rectangular survivor count falls back to SUMMA, still an artifact
    sched, art8, (r, c) = replan_elastic(g, 8, cache=cache)
    assert sched == "summa" and r * c <= 8
    assert art8.plan.step_keep is not None
    # forcing cannon squares down instead
    sched, _, (r, c) = replan_elastic(g, 8, schedule="cannon", cache=cache)
    assert sched == "cannon" and r == c == 2


def test_replan_elastic_legacy_path_deprecated():
    from repro.core import rmat
    from repro.runtime import replan_elastic

    g = rmat(9, 8, seed=2)
    with pytest.deprecated_call():
        sched, plan, (r, c) = replan_elastic(g, 4, legacy=True)
    assert sched == "cannon" and (r, c) == (2, 2)
    # the legacy raw plan is the old bare-planner output: no schedule
    # compaction (and no cache/rebalance) — which is why it is deprecated
    assert getattr(plan, "compact", None) is None


# ----------------------------------------------------------------------
# multi-device recovery matrix (subprocesses)
# ----------------------------------------------------------------------
def test_fault_at_every_live_step_all_schedules(distributed_runner):
    """A StepFault at each live step in turn, for all three schedules at
    their 9-device shapes, compacted and not: every run recovers to the
    byte-exact count with exactly one restart."""
    out = distributed_runner(
        """
        import jax
        jax.config.update("jax_enable_x64", True)
        from repro.core import rmat, triangle_count_oracle
        from repro.pipeline import plan_cannon, plan_oned, plan_summa
        from repro.runtime import FaultPlan, Supervisor
        from repro.runtime.faultinject import live_step_indices
        from repro.runtime.supervisor import supervised_count

        g = rmat(9, 8, seed=2)
        exp = triangle_count_oracle(g)
        plans = dict(
            cannon=plan_cannon(g, 3).plan,
            summa=plan_summa(g, 3, 3).plan,
            oned=plan_oned(g, 9).plan,
        )
        checked = 0
        for schedule, plan in plans.items():
            for compact in (None, False):
                kw = dict(q=3, schedule=schedule, compact=compact)
                if schedule == "oned":
                    kw.update(q=3, npods=1)
                steps = live_step_indices(plan, compact is not False)
                for s in steps:
                    sup = Supervisor(max_restarts=3)
                    res = supervised_count(
                        g, supervisor=sup,
                        fault_plan=FaultPlan.parse(f"step@{s}"), **kw,
                    )
                    key = (schedule, compact, s)
                    assert res.triangles == exp, (key, res.triangles, exp)
                    assert res.supervision["restarts"] == 1, key
                    checked += 1
        print("CHECKED", checked)
        """,
        9,
    )
    n = int(out.strip().split()[-1])
    assert n >= 12  # >= 2 live steps per (schedule, compact) pair


def test_devicelost_regrids_9_to_4(distributed_runner):
    """Losing 5 of 9 devices mid-count re-factorizes to 2x2 through the
    pipeline planner and recovers the exact count."""
    out = distributed_runner(
        """
        import json
        import jax
        jax.config.update("jax_enable_x64", True)
        from repro.core import rmat, triangle_count_oracle
        from repro.runtime import FaultPlan, Supervisor
        from repro.runtime.supervisor import supervised_count

        g = rmat(9, 8, seed=2)
        exp = triangle_count_oracle(g)
        sup = Supervisor(max_restarts=3)
        res = supervised_count(
            g, supervisor=sup,
            fault_plan=FaultPlan.parse("step@0=devicelost:5"),
            q=3, schedule="cannon",
        )
        assert res.triangles == exp, (res.triangles, exp)
        print(json.dumps(res.supervision))
        """,
        9,
    )
    sup = json.loads(out.strip().splitlines()[-1])
    assert sup["restarts"] == 1 and not sup["gave_up"]
    assert sup["regrids"] == [
        {"lost": 5, "grid": [2, 2], "schedule": "cannon"}
    ]


def test_tc_run_inject_faults_e2e(distributed_runner, tmp_path):
    """The CLI acceptance path: a checkpointed 4-device run with a step
    fault AND a checkpoint-corruption fault still reports the verified
    count, with the recovery visible in the report."""
    out = distributed_runner(
        f"""
        import sys
        sys.argv = [
            "tc_run", "--graph", "rmat:9", "--grid", "2",
            "--ckpt-dir", {str(tmp_path)!r},
            "--inject-faults", "step@1;ckpt_save=ckptcorrupt",
            "--verify", "--json",
        ]
        from repro.launch.tc_run import main
        main()
        """,
        4,
    )
    rep = json.loads(
        [ln for ln in out.splitlines() if ln.startswith("{")][-1]
    )
    assert rep["correct"] and rep["checkpointed"]
    assert rep["supervision_restarts"] >= 1
    assert any(
        e["fault"] == "CkptCorrupt" for e in rep["supervision_fault_log"]
    )
    corrupt = [f for f in os.listdir(tmp_path) if f.endswith(".corrupt")]
    assert corrupt  # the flipped step was quarantined, not reused
