"""System tests: every schedule/path must match the exact oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_plan,
    count_triangles,
    erdos_renyi,
    named_graph,
    preprocess,
    rmat,
    triangle_count_oracle,
)
from repro.core.graph import triangle_count_dense_oracle

NAMED = ["triangle", "k4", "k10", "star", "path", "bull", "karate"]


@pytest.mark.parametrize("name", NAMED)
def test_named_graphs_q1(name):
    g = named_graph(name)
    exp = triangle_count_dense_oracle(g)
    assert count_triangles(g, q=1).triangles == exp


@pytest.mark.parametrize("name", ["k10", "karate"])
def test_oracles_agree(name):
    g = named_graph(name)
    assert triangle_count_dense_oracle(g) == triangle_count_oracle(g)


def test_networkx_oracle_agreement():
    import networkx as nx

    g = rmat(8, 8, seed=11)
    nxg = nx.Graph()
    nxg.add_nodes_from(range(g.n))
    nxg.add_edges_from(map(tuple, g.edges))
    exp = sum(nx.triangles(nxg).values()) // 3
    assert triangle_count_oracle(g) == exp
    assert count_triangles(g, q=1).triangles == exp


def test_rmat_q1_search_and_probe_directions():
    g = rmat(9, 8, seed=1)
    exp = triangle_count_oracle(g)
    assert count_triangles(g, q=1, probe_shorter=True).triangles == exp
    assert count_triangles(g, q=1, probe_shorter=False).triangles == exp


def test_reorder_invariance():
    """Degree reordering must not change the count (paper §5.3)."""
    g = erdos_renyi(300, 12.0, seed=7)
    assert (
        count_triangles(g, q=1, reorder=True).triangles
        == count_triangles(g, q=1, reorder=False).triangles
    )


def test_dense_oracle_path_matches_search():
    from repro.core.cannon import build_cannon_dense_fn
    from repro.core.api import make_grid_mesh

    g = rmat(8, 8, seed=2)
    g2, _ = preprocess(g)
    plan = build_plan(g2, 1)
    mesh = make_grid_mesh(1)
    dense = plan.dense_blocks()  # includes the step_keep skip mask
    fn = build_cannon_dense_fn(plan, mesh)
    got = int(fn(*(jnp.asarray(dense[k]) for k in fn.ordered)))
    assert got == triangle_count_oracle(g)


@pytest.mark.parametrize("mode", ["popcount", "mxu"])
def test_tile_path_matches(mode):
    from repro.core.tiles import build_tile_plan
    from repro.core.cannon import build_cannon_tile_fn
    from repro.core.api import make_grid_mesh

    g = rmat(8, 8, seed=3)
    exp = triangle_count_oracle(g)
    g2, _ = preprocess(g)
    plan = build_plan(g2, 1)
    tp = build_tile_plan(plan)
    mesh = make_grid_mesh(1)
    fn = build_cannon_tile_fn(plan, tp, mesh, mode=mode, interpret=True)
    got = int(fn(**{k: jnp.asarray(v) for k, v in tp.device_arrays().items()}))
    assert got == exp, mode


def test_summa_q1():
    g = rmat(8, 8, seed=4)
    exp = triangle_count_oracle(g)
    assert count_triangles(g, q=1, schedule="summa").triangles == exp


def test_oned_p1():
    g = rmat(8, 8, seed=4)
    exp = triangle_count_oracle(g)
    assert count_triangles(g, q=1, schedule="oned").triangles == exp


# ----------------------------------------------------------------------
# multi-device (subprocess) system tests
# ----------------------------------------------------------------------
DIST_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import count_triangles, rmat, triangle_count_oracle
g = rmat(9, 8, seed=42)
exp = triangle_count_oracle(g)
{body}
print("OK", exp)
"""


def test_distributed_cannon_q3(distributed_runner):
    body = """
r = count_triangles(g, q=3)
assert r.triangles == exp, (r.triangles, exp)
"""
    out = distributed_runner(DIST_CODE.format(body=body), ndev=9)
    assert "OK" in out


def test_distributed_cannon_q4_and_pods(distributed_runner):
    body = """
r = count_triangles(g, q=4)
assert r.triangles == exp, (r.triangles, exp)
r = count_triangles(g, q=2, npods=2)
assert r.triangles == exp
r = count_triangles(g, q=2, npods=2)
"""
    out = distributed_runner(DIST_CODE.format(body=body), ndev=16)
    assert "OK" in out


def test_distributed_summa_rect(distributed_runner):
    body = """
from repro import compat
mesh = compat.make_mesh((2, 4), ("data", "model"))
r = count_triangles(g, mesh=mesh, schedule="summa")
assert r.triangles == exp, (r.triangles, exp)
"""
    out = distributed_runner(DIST_CODE.format(body=body), ndev=8)
    assert "OK" in out


def test_distributed_oned(distributed_runner):
    body = """
r = count_triangles(g, q=2, schedule="oned")  # p = 4 ring
assert r.triangles == exp, (r.triangles, exp)
"""
    out = distributed_runner(DIST_CODE.format(body=body), ndev=4)
    assert "OK" in out


def test_distributed_tile_kernel(distributed_runner):
    body = """
import jax.numpy as jnp
from repro.core import build_plan, preprocess
from repro.core.tiles import build_tile_plan
from repro.core.cannon import build_cannon_tile_fn
from repro.core.api import make_grid_mesh
g2, _ = preprocess(g)
plan = build_plan(g2, 2)
tp = build_tile_plan(plan)
fn = build_cannon_tile_fn(plan, tp, make_grid_mesh(2), interpret=True)
got = int(fn(**{k: jnp.asarray(v) for k, v in tp.device_arrays().items()}))
assert got == exp, (got, exp)
"""
    out = distributed_runner(DIST_CODE.format(body=body), ndev=4)
    assert "OK" in out


# ----------------------------------------------------------------------
# skewed-degree fixture (powerlaw) — generator + spec parsing
# ----------------------------------------------------------------------
def test_powerlaw_generator_is_skewed_and_deterministic():
    from repro.core import powerlaw

    g = powerlaw(400, 2.3, seed=3)
    assert g.n == 400
    deg = g.degrees()
    # heavy-tailed: the hub dwarfs the mean, yet most vertices tie at
    # low degree (the regime the rebalancer's within-degree shuffles
    # need); deterministic given the seed
    assert deg.max() > 10 * deg.mean()
    assert np.array_equal(g.edges, powerlaw(400, 2.3, seed=3).edges)
    assert not np.array_equal(g.edges, powerlaw(400, 2.3, seed=4).edges)
    assert count_triangles(g, q=1).triangles == triangle_count_oracle(g)


def test_powerlaw_spec_parsing():
    from repro.core import graph_from_spec, powerlaw
    from repro.core.generators import split_specs

    g = graph_from_spec("powerlaw:300,2.5")
    assert g.n == 300 and np.array_equal(g.edges, powerlaw(300, 2.5).edges)
    g7 = graph_from_spec("powerlaw:300,2.5,7")
    assert np.array_equal(g7.edges, powerlaw(300, 2.5, seed=7).edges)
    # well-formed single specs survive comma-splitting heuristics
    assert split_specs("powerlaw:300,2.5") == ["powerlaw:300,2.5"]
    assert split_specs("powerlaw:300,2.5,7") == ["powerlaw:300,2.5,7"]
    assert split_specs("powerlaw:300,2.5;karate") == [
        "powerlaw:300,2.5", "karate",
    ]
    with pytest.raises(ValueError):
        graph_from_spec("powerlaw:")
