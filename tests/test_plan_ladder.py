"""The plan-shape ladder: Cannon plans pad blocks and fragments to a
coarse ladder (``ladder_nnz``, ``ladder_dpad``), so the relabelings of a
graph share one shape key and one compiled program, and counts stay
exact."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core import (
    Graph,
    count_triangles,
    count_triangles_many,
    triangle_count_oracle,
)
from repro.core.plan import _build_plan_loops, ladder_dpad, ladder_nnz
from repro.pipeline import EdgeDelta, PlanCache, apply_delta, plan_cannon
from repro.pipeline.batch import _build_batch_program, _lifted
from repro.pipeline.cache import graph_digest
from repro.pipeline.stages import pack_tc_plan


def chung_lu(scale: int, edge_factor: int = 19, alpha: float = 2.1,
             seed: int = 0) -> Graph:
    """Endpoints drawn with p ∝ (v + 1)^(-1/(alpha-1)), every pair kept."""
    n = 1 << scale
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1.0))
    rng = np.random.default_rng(seed)
    src = rng.choice(n, size=edge_factor * n, p=w / w.sum())
    dst = rng.choice(n, size=edge_factor * n, p=w / w.sum())
    return Graph.from_edges(n, src, dst)


def relabeled(g: Graph, seed: int) -> Graph:
    perm = np.random.default_rng(seed).permutation(g.n)
    return Graph.from_edges(g.n, perm[g.edges[:, 0]], perm[g.edges[:, 1]])


G = chung_lu(12)  # n 4,096, m 53,039: blocks of ~13.7k entries on q = 2


# ----------------------------------------------------------------------
# the ladder itself
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "nnz", [1, 2, 127, 128, 129, 1000, 65537, 909_538, 1_048_320]
)
def test_ladder_rounds_up_within_its_bound(nnz):
    pad = ladder_nnz(nnz)
    step = max(1, (1 << (nnz - 1).bit_length()) >> 7)
    assert nnz <= pad < nnz + step and pad % step == 0
    assert (pad - nnz) / nnz < 1 / 64


def test_ladder_exact_values():
    assert ladder_nnz(909_538) == 917_504  # kron-s16's one block
    assert ladder_nnz(1_048_320) == 1_048_576  # urand-s16's
    assert [ladder_dpad(d) for d in (1, 8, 9, 30, 120, 121, 128, 129, 136,
                                     247, 249)] == [
        8, 8, 16, 32, 120, 136, 136, 136, 136, 248, 264
    ]
    assert all(ladder_dpad(d) % 128 for d in range(1, 1000))


@pytest.mark.parametrize("q", [1, 2, 3])
def test_plan_records_the_padding_the_ladder_adds(q):
    plan = pack_tc_plan(G, q)
    nnz_max = int(plan.m_cnt.max())
    st = plan.stats
    assert plan.nnz_pad == plan.tmax == ladder_nnz(nnz_max)
    assert plan.dpad == ladder_dpad(plan.dmax) >= plan.dmax
    assert st.ladder_task_share == pytest.approx(plan.tmax / nnz_max - 1)
    assert 0 <= st.ladder_task_share < 1 / 64
    assert st.ladder_dpad2_share == pytest.approx(
        (plan.dpad / plan.dmax) ** 2 - 1
    )
    assert plan.dpad - plan.dmax < 16
    # the loop reference pads the same way
    ref = _build_plan_loops(G, q)
    assert (ref.nnz_pad, ref.dmax) == (plan.nnz_pad, plan.dmax)
    assert ref.stats.ladder_task_share == st.ladder_task_share


# ----------------------------------------------------------------------
# relabelings share shape keys, engines and compiled programs
# ----------------------------------------------------------------------
def test_relabelings_fall_into_at_most_two_shape_keys():
    keys, exact = set(), set()
    for seed in range(8):
        plan = plan_cannon(relabeled(G, seed), 2, cache=PlanCache(0)).plan
        keys.add(plan.shape_key())
        exact.add((int(plan.m_cnt.max()), plan.dmax))
    assert len(keys) <= 2 < len(exact)


def _same_key_pair():
    """Two relabelings of ``G`` whose one-device plans share a shape key."""
    seen = {}
    for seed in range(16):
        g = relabeled(G, seed)
        key = plan_cannon(g, 1, cache=PlanCache(0)).plan.shape_key()
        if key in seen:
            return seen[key], g
        seen[key] = g
    raise AssertionError("no two relabelings share a shape key")


def test_a_relabeling_with_the_same_key_compiles_nothing():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import CompileClock

    g1, g2 = _same_key_pair()
    cache = PlanCache()
    assert count_triangles(g1, q=1, cache=cache).triangles == \
        triangle_count_oracle(g1)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        res = count_triangles(g2, q=1, cache=cache)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    assert res.triangles == triangle_count_oracle(g2)
    assert not res.artifact.cache_hit  # a new plan ...
    assert clock.count == 0  # ... on the program already compiled


def test_plans_with_one_key_lower_to_one_program():
    from repro.core import make_grid_mesh
    from repro.core.cannon import build_cannon_fn

    g1, g2 = _same_key_pair()
    texts = []
    for g in (g1, g2):
        plan = plan_cannon(g, 1, cache=PlanCache(0)).plan
        fn = build_cannon_fn(plan, make_grid_mesh(1))
        texts.append(fn.lower(**plan.device_arrays()).as_text())
    # the persistent compile cache keys on this text: a new process
    # counting the other relabeling loads the program
    assert texts[0] == texts[1]


LADDER_4DEV = r"""
import numpy as np
from repro.core import Graph, count_triangles, make_grid_mesh
from repro.core import triangle_count_oracle
from repro.pipeline import PlanCache

n = 1 << 10
w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / 1.1)
rng = np.random.default_rng(0)
g = Graph.from_edges(n, rng.choice(n, 19 * n, p=w / w.sum()),
                     rng.choice(n, 19 * n, p=w / w.sum()))
mesh = make_grid_mesh(2)
cache = PlanCache()
for seed in range(3):
    perm = np.random.default_rng(seed).permutation(n)
    gs = Graph.from_edges(n, perm[g.edges[:, 0]], perm[g.edges[:, 1]])
    res = count_triangles(gs, mesh, cache=cache)
    plan = res.plan
    assert plan.nnz_pad > int(plan.m_cnt.max()) or plan.dpad > plan.dmax
    assert res.triangles == triangle_count_oracle(gs), seed
print("OK", len(mesh.devices.ravel()))
"""


def test_laddered_counts_equal_the_oracle_on_one_and_four_devices(
    distributed_runner,
):
    for seed in range(3):
        g = relabeled(G, seed)
        plan = plan_cannon(g, 1, cache=PlanCache(0)).plan
        assert plan.nnz_pad > g.m or plan.dpad > plan.dmax
        assert count_triangles(g, q=1, cache=PlanCache(0)).triangles == \
            triangle_count_oracle(g)
    assert "OK 4" in distributed_runner(LADDER_4DEV, 4)


# ----------------------------------------------------------------------
# other packers agree with a cold pack
# ----------------------------------------------------------------------
def _adds_to_fullest_block(g, plan, k):
    """``k`` absent edges that all land in the plan's fullest block."""
    q = plan.q
    bx, by = np.unravel_index(int(np.argmax(plan.m_cnt)), (q, q))
    have = set(map(tuple, g.edges.tolist()))
    rng = np.random.default_rng(k)
    out = set()
    while len(out) < k:
        u, v = sorted(rng.integers(0, g.n, size=2).tolist())
        if u % q == bx and v % q == by and u != v and (u, v) not in have:
            out.add((u, v))
    return EdgeDelta(add=sorted(out))


def test_delta_splice_stays_byte_identical_across_ladder_steps():
    g = relabeled(G, 0)
    art = plan_cannon(g, 2, reorder=False, cache=PlanCache(0))
    plan = art.plan
    room = plan.nnz_pad - int(plan.m_cnt.max())
    assert room > 1
    for k, crosses in [(1, False), (room + 1, True)]:
        d = _adds_to_fullest_block(g, plan, k)
        art2 = apply_delta(art, d, cache=PlanCache(0))
        assert art2.delta_report["level"] == "splice"
        ref = pack_tc_plan(d.apply_to(g), 2, skew_perm=plan.skew_perm,
                           keep_blocks=False)
        got = art2.plan
        assert int(got.m_cnt.max()) == int(plan.m_cnt.max()) + k
        assert (got.nnz_pad != plan.nnz_pad) == crosses
        assert (got.nnz_pad, got.tmax, got.dmax, got.dpad) == (
            ref.nnz_pad, ref.tmax, ref.dmax, ref.dpad
        )
        for name, arr in ref.device_arrays().items():
            assert np.array_equal(got.device_arrays()[name], arr), name
        assert got.stats.ladder_task_share == ref.stats.ladder_task_share


def test_batch_padding_follows_the_batch_maxima():
    from repro.core import make_grid_mesh

    graphs = [relabeled(G, s) for s in range(3)] + [chung_lu(9, seed=1)]
    cache = PlanCache(0)
    digests = [graph_digest(g) for g in graphs]
    lifted = _lifted(graphs, digests, reorder=True, cyclic_p=None,
                     cache=cache)
    plans = [pack_tc_plan(g, 1, with_stats=False) for g in lifted]
    _, stacked, _, _ = _build_batch_program(
        lifted, make_grid_mesh(1), q=1, schedule="cannon", method="search",
        chunk=512, probe_shorter=True, count_dtype=np.int32,
    )
    nnz_pad = max(p.nnz_pad for p in plans)
    assert nnz_pad == ladder_nnz(max(int(p.m_cnt.max()) for p in plans))
    assert stacked["a_indices"].shape[-1] == nnz_pad
    assert stacked["m_ti"].shape[-1] == nnz_pad
    res = count_triangles_many(graphs, q=1, cache=cache)
    assert res.triangles == [triangle_count_oracle(g) for g in graphs]
