"""Communication-avoiding collectives (DESIGN.md §4.5).

Covers: the masked ppermute primitives (binomial tree all-reduce,
doubling-chain broadcast) against their collective semantics on a flat
mesh, count equivalence across (reduce strategy × schedule × store ×
npods ∈ {1, 2, 4}) including compacted schedules and edgeless graphs,
loud rejection of unsupported strategy combinations, the checkpoint
cross-strategy resume guard, and the roofline's pairs-aware permute
accounting + per-phase byte attribution.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.core import count_triangles, triangle_count_oracle
from repro.core.generators import graph_from_spec

ER = "er:300,16,5"
CLIQUES = "cliques:2,40"  # block-diagonal: compaction elides steps


# ======================================================================
# primitive semantics (flat 4-device mesh, subprocess)
# ======================================================================
PRIMITIVES_CODE = """
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.engine import chain_broadcast, pod_tree_allreduce

mesh = compat.make_mesh((4,), ("flat",))
x = jnp.arange(1.0, 5.0)  # device d holds d + 1

tree = compat.shard_map(
    lambda v: pod_tree_allreduce(v, "flat", 4),
    mesh=mesh, in_specs=P("flat"), out_specs=P("flat"),
)(x)
assert tree.tolist() == [10.0] * 4, tree  # every device holds the sum

for owner in range(4):
    got = compat.shard_map(
        lambda v: chain_broadcast(v, "flat", 4, owner),
        mesh=mesh, in_specs=P("flat"), out_specs=P("flat"),
    )(x)
    assert got.tolist() == [owner + 1.0] * 4, (owner, got)
print("PRIMITIVES_OK")
"""


def test_tree_and_chain_primitives(distributed_runner):
    out = distributed_runner(PRIMITIVES_CODE, 4)
    assert "PRIMITIVES_OK" in out


def test_pod_tree_allreduce_rejects_non_pow2():
    from repro.core.engine import pod_tree_allreduce

    with pytest.raises(AssertionError):
        pod_tree_allreduce(0.0, "pod", 3)


# ======================================================================
# count equivalence: strategy × schedule × store × npods
# ======================================================================
CANNON_EQUIV_CODE = """
from repro.core import count_triangles, triangle_count_oracle
from repro.core.generators import graph_from_spec

for spec in ({specs}):
    g = graph_from_spec(spec)
    exp = triangle_count_oracle(g)
    for strat in {strategies}:
        for compact in (None, False):
            r = count_triangles(
                g, q={q}, npods={npods}, method="search",
                reduce_strategy=strat, compact=compact,
            )
            assert r.triangles == exp, (spec, strat, compact, r.triangles, exp)
print("CANNON_OK")
"""


@pytest.mark.parametrize("npods,q", [(1, 2), (2, 2), (4, 4)])
def test_cannon_counts_equal_across_strategies(distributed_runner, npods, q):
    """CSR cannon: every applicable strategy agrees with the oracle on
    dense-ish and block-diagonal (compacted) fixtures, compaction on
    and off, at every pod count (explicit tree needs a pod axis, so the
    single-pod grid runs flat/auto only — see
    test_tree_rejected_without_pods)."""
    specs = (ER, CLIQUES) if npods < 4 else ("karate",)
    strategies = ("flat", "auto") if npods == 1 else ("flat", "tree", "auto")
    code = CANNON_EQUIV_CODE.format(
        specs=repr(specs), strategies=repr(strategies), q=q, npods=npods
    )
    out = distributed_runner(code, q * q * npods)
    assert "CANNON_OK" in out


DENSE_EQUIV_CODE = """
from repro.core import count_triangles, triangle_count_oracle
from repro.core.generators import graph_from_spec

g = graph_from_spec({spec!r})
exp = triangle_count_oracle(g)
for strat in ("flat", "auto"):
    r = count_triangles(g, q=2, npods={npods}, method="dense",
                        reduce_strategy=strat)
    assert r.triangles == exp, (strat, r.triangles, exp)

# the dense store replicates whole rounds per pod — it has no pod
# decomposition to tree over, so an explicit tree is refused loudly
if {npods} > 1:
    try:
        count_triangles(g, q=2, npods={npods}, method="dense",
                        reduce_strategy="tree")
    except ValueError as e:
        assert "pod axis" in str(e), e
    else:
        raise AssertionError("dense + tree should have been rejected")
print("DENSE_OK")
"""


@pytest.mark.parametrize("npods", [1, 2])
def test_dense_store_strategies(distributed_runner, npods):
    code = DENSE_EQUIV_CODE.format(spec=ER, npods=npods)
    out = distributed_runner(code, 4 * npods)
    assert "DENSE_OK" in out


SUMMA_EQUIV_CODE = """
from repro.core import count_triangles, triangle_count_oracle
from repro.core.generators import graph_from_spec

for spec in ({er!r}, {cliques!r}):
    g = graph_from_spec(spec)
    exp = triangle_count_oracle(g)
    for bc in (None, "auto", "onehot", "chain"):
        for compact in (None, False):
            r = count_triangles(
                g, q=3, schedule="summa", broadcast=bc, compact=compact,
            )
            assert r.triangles == exp, (spec, bc, compact, r.triangles, exp)
r = count_triangles(g, q=3, schedule="oned", reduce_strategy="flat")
assert r.triangles == exp
print("SUMMA_OK")
"""


def test_summa_counts_equal_across_broadcasts(distributed_runner):
    """SUMMA: every broadcast strategy × compaction agrees with the
    oracle (the chain forces the unrolled body; compacted chains elide
    dead rounds' collectives entirely); plus the oned flat baseline."""
    code = SUMMA_EQUIV_CODE.format(er=ER, cliques=CLIQUES)
    out = distributed_runner(code, 9)
    assert "SUMMA_OK" in out


EDGELESS_CODE = """
from repro.core import count_triangles
from repro.core.generators import graph_from_spec

g = graph_from_spec("er:20,0")
assert g.m == 0
for strat in ("flat", "tree", "auto"):
    assert count_triangles(g, q=2, npods=2, reduce_strategy=strat).triangles == 0
for bc in ("onehot", "chain"):
    assert count_triangles(g, q=2, schedule="summa", broadcast=bc).triangles == 0
print("EDGELESS_OK")
"""


def test_edgeless_graph_all_strategies(distributed_runner):
    out = distributed_runner(EDGELESS_CODE, 8)
    assert "EDGELESS_OK" in out


# ======================================================================
# validation: unsupported combinations are refused loudly
# ======================================================================
def test_tree_rejected_without_pods():
    g = graph_from_spec("karate")
    with pytest.raises(ValueError, match="pod axis"):
        count_triangles(g, q=1, reduce_strategy="tree")
    with pytest.raises(ValueError, match="pod axis"):
        count_triangles(g, q=1, schedule="oned", reduce_strategy="tree")


def test_unknown_strategy_rejected():
    g = graph_from_spec("karate")
    with pytest.raises(ValueError, match="reduce strategy"):
        count_triangles(g, q=1, reduce_strategy="bogus")
    with pytest.raises(ValueError, match="broadcast"):
        count_triangles(g, q=1, schedule="summa", broadcast="bogus")


def test_chain_rejected_for_batched_bodies():
    from repro.core.plan import resolve_broadcast
    from repro.core.summa import SummaPlan

    plan = SummaPlan.__new__(SummaPlan)
    plan.broadcast = "auto"
    assert resolve_broadcast(plan, None, batched=True) == "onehot"
    assert resolve_broadcast(plan, None, batched=False) == "chain"
    with pytest.raises(ValueError, match="chain"):
        resolve_broadcast(plan, "chain", batched=True)


# ======================================================================
# checkpoint cross-strategy guard
# ======================================================================
def test_ckpt_refuses_cross_strategy_resume(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(repo, "src"),
    )

    def run(extra):
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.tc_run",
             "--graph", ER, "--grid", "2", "--json",
             "--ckpt-dir", str(tmp_path), *extra],
            env=env, capture_output=True, text=True, timeout=600,
        )

    first = run([])
    assert first.returncode == 0, first.stdout[-800:] + first.stderr[-800:]
    r = json.loads(first.stdout.strip().splitlines()[-1])
    assert r["checkpointed"]

    # same flags resume fine (the final checkpoint leaves nothing to do)
    again = run([])
    assert again.returncode == 0, again.stdout[-800:] + again.stderr[-800:]

    # a different reduction strategy must be refused, not silently summed
    crossed = run(["--reduce-strategy", "tree"])
    assert crossed.returncode != 0
    assert "collectives" in crossed.stderr
    assert "reduce=tree" in crossed.stderr


# ======================================================================
# names the trace reads: the engine's program and the count kernel
# ======================================================================
def test_engine_program_and_count_kernel_are_named():
    """The device program is ``jit_tc_engine`` and the count kernel's ops
    sit under ``tc_count``, as the tc_shift / tc_reduce scopes do."""
    from repro.core import rmat
    from repro.core.api import make_grid_mesh
    from repro.core.cannon import build_cannon_fn
    from repro.pipeline import PlanCache, plan_cannon

    plan = plan_cannon(rmat(6, 8), 1, cache=PlanCache(maxsize=0)).plan
    fn = build_cannon_fn(plan, make_grid_mesh(1))
    arrays = plan.device_arrays()
    text = fn.lower(**{k: arrays[k] for k in fn.shardings}).as_text(
        debug_info=True
    )
    assert "\nmodule @jit_tc_engine " in text
    assert "jit(tc_engine)/" in text
    assert "/tc_count/" in text


# ======================================================================
# roofline: pairs-aware permutes + per-phase attribution
# ======================================================================
_HLO = """\
HloModule jit_fn, entry_computation_layout={(f32[8]{0})->f32[8]{0}}, num_partitions=4

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %cp = f32[8]{0} collective-permute(%p0), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, metadata={op_name="jit(fn)/tc_shift/ppermute"}
  %cp2 = f32[8]{0} collective-permute(%cp), source_target_pairs={{0,1}}, metadata={op_name="jit(fn)/tc_broadcast/ppermute"}
  ROOT %ar = f32[8]{0} all-reduce(%cp2), replica_groups={{0,1,2,3}}, metadata={op_name="jit(fn)/tc_reduce/psum"}
}
"""


def test_roofline_pairs_aware_permutes():
    from repro.launch.roofline import collective_bytes, infer_num_devices

    assert infer_num_devices(_HLO) == 4
    # headerless module: N falls back to max named device id + 1
    assert infer_num_devices(_HLO.replace(", num_partitions=4", "")) == 4

    out = collective_bytes(_HLO)
    # full rotation (4 pairs / 4 devices) costs its payload; the masked
    # single-pair hop costs a quarter; all-reduce keeps the ring cost
    assert out["collective-permute"] == pytest.approx(32.0 + 8.0)
    assert out["all-reduce"] == pytest.approx(2 * 32.0 * 3 / 4)

    # explicit num_devices overrides the header
    out8 = collective_bytes(_HLO, num_devices=8)
    assert out8["collective-permute"] == pytest.approx(16.0 + 4.0)


def test_roofline_collective_phases():
    from repro.launch.roofline import collective_phases

    phases = collective_phases(_HLO)
    assert phases == {
        "shift": pytest.approx(32.0),
        "broadcast": pytest.approx(8.0),
        "reduce": pytest.approx(2 * 32.0 * 3 / 4),
        "other": 0.0,
    }
    # untagged collectives land in "other", not a phase bucket
    untagged = collective_phases(_HLO.replace("tc_reduce", "psum_impl"))
    assert untagged["reduce"] == 0.0
    assert untagged["other"] == pytest.approx(2 * 32.0 * 3 / 4)


def test_roofline_phases_loop_aware():
    from repro.launch.roofline import collective_phases

    hlo = """\
HloModule jit_fn, num_partitions=2

%cond (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (b: (s32[], f32[4])) -> (s32[], f32[4]) {
  %b = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%b), index=1
  %cp = f32[4]{0} collective-permute(%x), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(fn)/tc_shift/ppermute"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %cp)
}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %w = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4]{0} get-tuple-element(%w), index=1
}
"""
    phases = collective_phases(hlo)
    # 5 trips x 16B payload x (2 pairs / 2 devices)
    assert phases["shift"] == pytest.approx(5 * 16.0)
    assert phases["broadcast"] == phases["reduce"] == phases["other"] == 0.0
