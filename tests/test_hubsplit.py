"""Hub-split decomposition tests (DESIGN.md §4.8).

The invariant under test everywhere: counts with ``hub_split`` on are
byte-identical to counts with it off — across schedules, methods,
compaction, rebalance, grids, and the delta ladder.  The suite also
pins the satellite bugfixes that rode along: the spec-list splitter's
greedy comma parse, the fused VMEM gate's hub-driven diagnosis, and the
delta path's loud refusal to splice hub-split artifacts.
"""
import numpy as np
import pytest

from repro.core import count_triangles, graph_from_spec, triangle_count_oracle
from repro.core.generators import split_specs
from repro.core.graph import Graph
from repro.pipeline import plan_cannon, plan_oned, plan_summa
from repro.pipeline.delta import EdgeDelta, apply_delta
from repro.pipeline.hubsplit import (
    DEFAULT_HUB_C,
    detect_hub_cut,
    hubsplit_stage,
    normalize_hub_split,
)

SPECS = ["powerlaw:600,2.2", "powerlaw:600,1.8", "star:50", "cliques:6,8"]


# ----------------------------------------------------------------------
# knob + cut detection
# ----------------------------------------------------------------------
def test_normalize_hub_split():
    assert normalize_hub_split(False) is None
    assert normalize_hub_split(None) is None
    assert normalize_hub_split(True) == DEFAULT_HUB_C
    assert normalize_hub_split(3) == 3.0
    assert normalize_hub_split(0.0) == 0.0
    with pytest.raises(ValueError):
        normalize_hub_split(-1.0)


def test_detect_hub_cut_degenerates():
    from repro.core.preprocess import degree_order

    g = Graph.from_edges(10, [], [])
    assert detect_hub_cut(g, DEFAULT_HUB_C) == g.n  # edgeless: no hubs
    g = graph_from_spec("karate")
    # c=0: every vertex with degree > 0 is a hub (threshold 0)
    h0 = detect_hub_cut(g.relabel(degree_order(g)), 0.0)
    assert h0 == int((g.degrees() == 0).sum())


def test_hubsplit_stage_noop_below_threshold():
    from repro.core.preprocess import degree_order

    # karate's max degree (17) is under 8x its average degree: no-op
    g = graph_from_spec("karate")
    g2 = g.relabel(degree_order(g))
    res, hub = hubsplit_stage(g2, (2, 2))
    assert hub is None and res is g2


def test_hubsplit_residual_plus_hub_partition_edges():
    from repro.core.preprocess import degree_order

    g = graph_from_spec("powerlaw:600,2.2")
    g2 = g.relabel(degree_order(g))
    res, hub = hubsplit_stage(g2, (3, 3))
    assert hub is not None
    assert res.edges.shape[0] + hub.hub_nnz == g2.m
    assert (res.edges[:, 1] < hub.h0).all()
    assert hub.hub_rows == g2.n - hub.h0
    rep = hub.report()
    assert rep["hub_rows"] == hub.hub_rows
    assert 0.0 < rep["hub_nnz_frac"] <= 1.0


# ----------------------------------------------------------------------
# count parity: hub on == hub off (single device; grids in the
# distributed test below)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
def test_hub_split_count_parity(spec, schedule):
    g = graph_from_spec(spec)
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, schedule=schedule, hub_split=True)
    assert r.triangles == exp
    # threshold sweep, incl. c=0 (everything with degree > 0 is a hub)
    for c in (0.0, 2.0):
        assert count_triangles(
            g, q=1, schedule=schedule, hub_split=c
        ).triangles == exp


@pytest.mark.parametrize("method", ["search", "search2", "global", "fused"])
def test_hub_split_methods_parity(method):
    g = graph_from_spec("powerlaw:600,2.2")
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, method=method, hub_split=True)
    assert r.triangles == exp
    assert r.hub is not None and r.hub["hub_rows"] > 0


@pytest.mark.parametrize("compact", [None, False])
def test_hub_split_compact_parity(compact):
    g = graph_from_spec("powerlaw:600,1.8")
    exp = triangle_count_oracle(g)
    assert count_triangles(
        g, q=1, hub_split=True, compact=compact
    ).triangles == exp


def test_hub_split_edgeless_and_empty_residual():
    g = Graph.from_edges(16, [], [])
    assert count_triangles(g, q=1, hub_split=True).triangles == 0
    # c=0 on a star: the residual keeps no triangle apexes below the cut
    g = graph_from_spec("star:50")
    assert count_triangles(g, q=1, hub_split=0.0).triangles == 0


def test_hub_split_with_rebalance_stays_exact():
    g = graph_from_spec("powerlaw:600,2.2")
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, hub_split=True, rebalance_trials=3)
    assert r.triangles == exp
    assert r.hub is not None and r.hub.get("residual_mcp") is not None


def test_hub_report_in_result():
    g = graph_from_spec("powerlaw:600,2.2")
    r = count_triangles(g, q=1, hub_split=True)
    assert r.hub["hub_rows"] > 0 and 0 < r.hub["hub_nnz_frac"] < 1
    assert r.artifact.hubsplit["h0"] == r.hub["h0"]
    # flag off -> no report
    assert count_triangles(g, q=1).hub is None


# ----------------------------------------------------------------------
# validation: loud rejections
# ----------------------------------------------------------------------
def test_hub_split_requires_reorder():
    g = graph_from_spec("powerlaw:600,2.2")
    with pytest.raises(ValueError, match="reorder"):
        plan_cannon(g, 1, hub_split=True, reorder=False)


def test_hub_split_rejects_cyclic_p():
    g = graph_from_spec("powerlaw:600,2.2")
    with pytest.raises(ValueError, match="cyclic_p"):
        plan_summa(g, 1, 1, hub_split=True, cyclic_p=2)


def test_hub_split_rejects_caller_plan():
    g = graph_from_spec("powerlaw:600,2.2")
    plan = plan_cannon(g, 1).plan
    with pytest.raises(ValueError, match="hub_split"):
        count_triangles(g, q=1, plan=plan, hub_split=True)


@pytest.mark.parametrize("method", ["dense", "tile"])
def test_hub_split_rejects_blockwise_stores(method):
    g = graph_from_spec("powerlaw:600,2.2")
    with pytest.raises(ValueError, match="hub-split"):
        count_triangles(g, q=1, method=method, hub_split=True)


def test_hub_split_rejects_batched_engine():
    from repro.core.engine import HubCount

    art = plan_cannon(graph_from_spec("powerlaw:600,2.2"), 1, hub_split=True)
    assert art.plan.hub is not None
    from repro.core.cannon import build_cannon_fn
    from repro.core.api import make_grid_mesh

    with pytest.raises(AssertionError, match="batched"):
        build_cannon_fn(art.plan, make_grid_mesh(1), batched=True)
    assert HubCount.from_plan(art.plan) is not None


# ----------------------------------------------------------------------
# residual padding shrinks (the fused gate's "hub-driven dmax" claim)
# ----------------------------------------------------------------------
def test_residual_dmax_shrinks_under_hub_split():
    g = graph_from_spec("powerlaw:600,2.2")
    full = plan_cannon(g, 1, autotune=True).plan
    split = plan_cannon(g, 1, hub_split=True, autotune=True).plan
    assert split.hub is not None
    assert split.dmax < full.dmax  # hub rows no longer inflate padding
    if full.d_small is not None and split.d_small is not None:
        assert split.d_small <= full.d_small
    # dmax is the true block-local maximum fragment length, not a stale
    # whole-graph bound: per-block padding claims hold in both modes
    for plan in (full, split):
        frag = max(
            int(np.diff(plan.a_indptr, axis=-1).max()),
            int(np.diff(plan.b_indptr, axis=-1).max()),
        )
        assert plan.dmax == frag


def test_fused_gate_flags_hub_driven_overflow():
    from repro.kernels.tc_fused import VMEM_BUDGET_BYTES, fused_gate

    deep = VMEM_BUDGET_BYTES // 64  # an 8-task tile this deep overflows
    over = fused_gate(8, deep, dmax=8 * deep, d_small=deep)
    assert not over["fits"] and over["hub_driven"]
    assert over["need_bytes"] > over["budget_bytes"]
    uniform = fused_gate(8, deep, dmax=deep, d_small=deep)
    assert not uniform["fits"] and not uniform["hub_driven"]
    small = fused_gate(8, 4, dmax=512, d_small=4)
    assert small["fits"] and small["hub_driven"]


def test_fused_pallas_overflow_error_names_hub_split():
    import jax.numpy as jnp

    from repro.kernels.tc_fused import VMEM_BUDGET_BYTES, count_pair_fused

    deep = VMEM_BUDGET_BYTES // 64  # panels this deep exceed the budget
    indptr = jnp.zeros(3, jnp.int32)
    indices = jnp.zeros(deep, jnp.int32)
    t = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError, match="hub_split=True"):
        count_pair_fused(
            indptr, indices, indptr, indices, t, t, jnp.int32(0),
            n_long=0, d_small=deep, dpad_long=8 * deep, chunk=64,
            impl="pallas",
        )


def test_fused_auto_demotion_warns(monkeypatch):
    import jax.numpy as jnp

    from repro.kernels.tc_fused import ops

    # force the auto resolution to "pallas" so the gate runs on CPU
    monkeypatch.setattr(ops, "resolve_fused_impl", lambda impl: "pallas")
    deep = ops.VMEM_BUDGET_BYTES // 64
    indptr = jnp.zeros(3, jnp.int32)
    indices = jnp.zeros(deep, jnp.int32)
    t = jnp.zeros(8, jnp.int32)
    with pytest.warns(RuntimeWarning, match="demoted to the lax reference"):
        out = ops.count_pair_fused(
            indptr, indices, indptr, indices, t, t, jnp.int32(0),
            n_long=0, d_small=deep, dpad_long=8 * deep, chunk=64,
            impl="auto",
        )
    assert int(out) == 0


# ----------------------------------------------------------------------
# delta ladder regressions: hub-row deltas must never splice
# ----------------------------------------------------------------------
def _hub_delta(g):
    """A delta that adds an edge onto the heaviest (hub) row and removes
    one existing edge."""
    deg = np.bincount(g.edges.reshape(-1), minlength=g.n)
    hub_v = int(np.argmax(deg))
    have = set(map(tuple, g.edges.tolist()))
    add = next(
        [min(u, hub_v), max(u, hub_v)]
        for u in range(g.n)
        if u != hub_v and (min(u, hub_v), max(u, hub_v)) not in have
    )
    return EdgeDelta(add=[add], remove=[g.edges[0].tolist()])


def _mutated(g, delta):
    keep = np.array(
        [e for e in g.edges.tolist()
         if tuple(e) not in set(map(tuple, delta.remove.tolist()))]
    ).reshape(-1, 2)
    e2 = np.concatenate([keep, delta.add.reshape(-1, 2)])
    return Graph.from_edges(g.n, e2[:, 0], e2[:, 1])


def test_delta_refuses_splice_on_hub_plan():
    g = graph_from_spec("powerlaw:600,2.2")
    art = plan_cannon(g, 1, hub_split=True)
    assert art.plan.hub is not None
    d = _hub_delta(g)
    art2 = apply_delta(art, d)
    rep = art2.delta_report
    assert rep["level"] == "repack"  # never "splice"
    assert rep["reason"] == "hub_split"
    assert "hubsplit" in rep["replanned_stages"]
    assert art2.plan.hub is not None
    assert art2.plan.hub.h0 == art.plan.hub.h0  # parent cut reused
    exp = triangle_count_oracle(_mutated(g, d))
    assert count_triangles(art2.graph, q=1, plan=art2).triangles == exp


def test_delta_rebases_misaligned_hub_plan():
    # planning is host-side: a 3x3 plan needs no devices, and on this
    # fixture the rebalancer picks a non-identity seed, so the hub side
    # is misaligned with the artifact id space (the exactness of the
    # rebased count itself runs in the distributed parity test below)
    g = graph_from_spec("powerlaw:600,2.2")
    art = plan_cannon(g, 3, hub_split=True, rebalance_trials=3)
    assert not art.plan.hub.aligned, "fixture drift: rebalance kept seed 0"
    d = _hub_delta(g)
    art2 = apply_delta(art, d)
    rep = art2.delta_report
    assert rep["level"] == "rebase"
    assert rep["reason"] == "hub_split_misaligned"
    # the rebased plan carries a fresh hub side (possibly again
    # misaligned if its own rebalance won a non-identity seed — exact
    # for counting either way; the ladder will rebase the next delta)
    assert art2.plan.hub is not None


def test_delta_hub_free_plan_still_splices():
    # guard against over-refusal: a hub-free cannon artifact keeps its
    # splice fast path even when the cfg carries hub_split (no-op split)
    g = graph_from_spec("karate")
    art = plan_cannon(g, 1, hub_split=True)
    assert art.plan.hub is None  # no row crossed the threshold
    d = EdgeDelta(add=[[0, 21]], remove=[[0, 1]])
    art2 = apply_delta(art, d)
    assert art2.delta_report["level"] in ("splice", "repack")
    assert "reason" not in art2.delta_report
    exp = triangle_count_oracle(_mutated(g, d))
    assert count_triangles(art2.graph, q=1, plan=art2).triangles == exp


def test_delta_stream_on_hub_plan_stays_exact():
    g = graph_from_spec("powerlaw:600,1.8")
    art = plan_cannon(g, 1, hub_split=True)
    rng = np.random.default_rng(7)
    g_cur = g
    for i in range(4):
        have = set(map(tuple, g_cur.edges.tolist()))
        while True:
            u, v = sorted(rng.integers(0, g.n, size=2).tolist())
            if u != v and (u, v) not in have:
                break
        d = EdgeDelta(
            add=[[u, v]],
            remove=[g_cur.edges[int(rng.integers(g_cur.m))].tolist()],
        )
        art = apply_delta(art, d)
        g_cur = _mutated(g_cur, d)
        exp = triangle_count_oracle(g_cur)
        got = count_triangles(art.graph, q=1, plan=art).triangles
        assert got == exp, (i, got, exp)


# ----------------------------------------------------------------------
# spec-list splitter (front-end bugfix)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("specs,want", [
    ("karate", ["karate"]),
    ("rmat:10,8,1", ["rmat:10,8,1"]),
    ("rmat:10,8,1;karate", ["rmat:10,8,1", "karate"]),
    ("karate,powerlaw:600,2.2", ["karate", "powerlaw:600,2.2"]),
    ("delta:5,0,powerlaw:600,2.2", ["delta:5,0,powerlaw:600,2.2"]),
    ("karate,delta:5,0,powerlaw:600,2.2",
     ["karate", "delta:5,0,powerlaw:600,2.2"]),
    ("powerlaw:600,2.2,star:50,cliques:6,8",
     ["powerlaw:600,2.2", "star:50", "cliques:6,8"]),
    ("er:100,5,karate", ["er:100,5", "karate"]),
])
def test_split_specs_greedy_longest_match(specs, want):
    got = split_specs(specs)
    assert got == want
    # round-trip: every split element is itself a one-element list
    for s in got:
        assert split_specs(s) == [s]


def test_split_specs_bad_fragment_surfaces_loudly():
    from repro.core.generators import graphs_from_specs

    assert split_specs("karate,bogus:1") == ["karate", "bogus:1"]
    with pytest.raises(ValueError, match="bogus"):
        graphs_from_specs("karate,bogus:1")


# ----------------------------------------------------------------------
# multi-device parity (subprocess grids)
# ----------------------------------------------------------------------
def test_distributed_hub_split_parity(distributed_runner):
    code = """
from repro.core import count_triangles, graph_from_spec, \\
    triangle_count_oracle
for spec in ("powerlaw:600,2.2", "star:50"):
    g = graph_from_spec(spec)
    exp = triangle_count_oracle(g)
    for sched in ("cannon", "summa", "oned"):
        for hs in (True, 0.0):
            r = count_triangles(g, q=2, schedule=sched, hub_split=hs,
                                rebalance_trials=2)
            assert r.triangles == exp, (spec, sched, hs, r.triangles, exp)
print("OK")
"""
    assert "OK" in distributed_runner(code, ndev=4)


def test_distributed_delta_on_misaligned_hub_plan(distributed_runner):
    # the q=3 fixture rebalances to a non-identity seed: the hub-row
    # delta must route through the loud rebase and stay exact
    code = """
import numpy as np
from repro.core import count_triangles, graph_from_spec, \\
    triangle_count_oracle
from repro.core.graph import Graph
from repro.pipeline.delta import EdgeDelta, apply_delta
from repro.pipeline import plan_cannon

g = graph_from_spec("powerlaw:600,2.2")
art = plan_cannon(g, 3, hub_split=True, rebalance_trials=3)
assert not art.plan.hub.aligned
deg = np.bincount(g.edges.reshape(-1), minlength=g.n)
hub_v = int(np.argmax(deg))
have = set(map(tuple, g.edges.tolist()))
add = next([min(u, hub_v), max(u, hub_v)] for u in range(g.n)
           if u != hub_v and (min(u, hub_v), max(u, hub_v)) not in have)
d = EdgeDelta(add=[add], remove=[g.edges[0].tolist()])
art2 = apply_delta(art, d)
assert art2.delta_report["reason"] == "hub_split_misaligned"
keep = np.array([e for e in g.edges.tolist()
                 if tuple(e) != tuple(g.edges[0].tolist())]).reshape(-1, 2)
e2 = np.concatenate([keep, np.array([add])])
g2 = Graph.from_edges(g.n, e2[:, 0], e2[:, 1])
exp = triangle_count_oracle(g2)
got = count_triangles(art2.graph, q=3, plan=art2).triangles
assert got == exp, (got, exp)
print("OK")
"""
    assert "OK" in distributed_runner(code, ndev=9)
