"""Pipeline tests: vectorized packer byte-equivalence, plan-cache
hit/miss semantics, cyclic-relabel round trip, and the batched
front-end (``count_triangles_many``) against per-graph counts."""
import pickle

import numpy as np
import pytest

from repro.core import (
    Graph,
    build_plan,
    count_triangles,
    count_triangles_many,
    named_graph,
    preprocess,
    rmat,
    triangle_count_oracle,
)
from repro.core.plan import _build_plan_loops
from repro.core.preprocess import cyclic_relabel
from repro.pipeline import (
    PlanCache,
    count_triangles_many as pipeline_many,
    digest_stats,
    graph_digest,
    plan_cannon,
    plan_oned,
    plan_summa,
)

GRAPHS = ["bull", "karate", "rmat"]


def _graph(name):
    if name == "rmat":
        return rmat(9, 8, seed=42)
    return named_graph(name)


# ======================================================================
# vectorized packer == loop reference, byte for byte
# ======================================================================
@pytest.mark.parametrize("graph_name", GRAPHS)
@pytest.mark.parametrize("q", [1, 2, 3])
@pytest.mark.parametrize("skew", [True, False])
def test_vectorized_packer_byte_identical(graph_name, q, skew):
    g, _ = preprocess(_graph(graph_name))
    fast = build_plan(g, q, skew=skew)
    ref = _build_plan_loops(g, q, skew=skew)
    assert (fast.nb, fast.nnz_pad, fast.tmax, fast.dmax, fast.chunk) == (
        ref.nb, ref.nnz_pad, ref.tmax, ref.dmax, ref.chunk
    )
    for name, arr in fast.device_arrays().items():
        refarr = ref.device_arrays()[name]
        assert arr.dtype == refarr.dtype, name
        assert arr.shape == refarr.shape, name
        assert arr.tobytes() == refarr.tobytes(), (graph_name, q, skew, name)


def test_vectorized_packer_stats_and_blocks_match():
    g, _ = preprocess(_graph("rmat"))
    fast = build_plan(g, 3)
    ref = _build_plan_loops(g, 3)
    assert np.array_equal(
        fast.stats.tasks_per_device, ref.stats.tasks_per_device
    )
    assert np.array_equal(
        fast.stats.probe_work_per_device_shift,
        ref.stats.probe_work_per_device_shift,
    )
    assert (
        fast.stats.intersection_tasks_total
        == ref.stats.intersection_tasks_total
    )
    for x in range(3):
        for y in range(3):
            fb, rb = fast.blocks[x][y], ref.blocks[x][y]
            assert np.array_equal(fb.indptr, rb.indptr)
            assert np.array_equal(fb.indices, rb.indices)
            assert np.array_equal(fb.active_rows, rb.active_rows)


# ======================================================================
# content-addressed plan cache
# ======================================================================
def test_graph_digest_is_content_addressed():
    g = rmat(8, 8, seed=0)
    # same edge set, shuffled construction order -> same digest
    rng = np.random.default_rng(0)
    order = rng.permutation(g.m)
    g_shuffled = Graph.from_edges(
        g.n, g.edges[order, 1], g.edges[order, 0], name="other"
    )
    assert graph_digest(g) == graph_digest(g_shuffled)
    # one edge edit -> different digest
    g_edit = Graph.from_edges(
        g.n,
        np.concatenate([g.edges[:, 0], [0]]),
        np.concatenate([g.edges[:, 1], [g.n - 1]]),
    )
    assert graph_digest(g) != graph_digest(g_edit)


def _digest_delta(before):
    after = digest_stats()
    return {k: after[k] - before[k] for k in after}


def test_digest_is_computed_once_per_graph():
    cache = PlanCache()
    g = rmat(8, 8, seed=5)
    before = digest_stats()
    counts = {count_triangles(g, q=1, cache=cache).triangles for _ in range(3)}
    assert _digest_delta(before) == {"computed": 1, "memo_hits": 2}
    assert counts == {triangle_count_oracle(g)}
    # the kept value is the content digest: an equal graph hashed afresh
    twin = Graph.from_edges(g.n, g.edges[:, 0], g.edges[:, 1], name="twin")
    before = digest_stats()
    assert graph_digest(twin) == graph_digest(g)
    assert _digest_delta(before) == {"computed": 1, "memo_hits": 1}


def test_graph_edges_are_read_only():
    g = rmat(6, 8, seed=6)
    with pytest.raises(ValueError):
        g.edges[0, 1] = 0
    assert g.frozen


def test_edges_made_writable_again_are_hashed_again():
    cache = PlanCache()
    g = rmat(7, 8, seed=7)
    digest = graph_digest(g)
    assert not count_triangles(g, q=1, cache=cache).artifact.cache_hit
    present = {tuple(e) for e in g.edges.tolist()}
    absent = next(
        (u, v) for u in range(g.n) for v in range(u + 1, g.n)
        if (u, v) not in present
    )
    g.edges.setflags(write=True)
    g.edges[0] = absent
    before = digest_stats()
    assert graph_digest(g) != digest
    assert _digest_delta(before) == {"computed": 1, "memo_hits": 0}
    res = count_triangles(g, q=1, cache=cache)
    assert not res.artifact.cache_hit
    assert res.triangles == triangle_count_oracle(g)


def test_graph_on_a_writable_view_keeps_its_own_edges():
    g0 = rmat(7, 8, seed=8)
    buf = np.array(g0.edges)  # writable, owns its memory
    g = Graph(n=g0.n, edges=buf[:], name="view")
    assert not np.shares_memory(g.edges, buf)
    digest, tri = graph_digest(g), count_triangles(g, q=1).triangles
    buf[:, 1] = buf[:, 0] + 1  # a later write to the caller's buffer
    assert np.array_equal(g.edges, g0.edges)
    assert graph_digest(g) == digest == graph_digest(g0)
    assert count_triangles(g, q=1).triangles == tri == triangle_count_oracle(g0)


def test_graph_on_a_read_only_view_is_not_copied():
    g0 = rmat(7, 8, seed=9)
    g = Graph(n=g0.n, edges=g0.edges[: g0.m // 2], name="prefix")
    assert np.shares_memory(g.edges, g0.edges)
    assert g.frozen and g.edges.base is g0.edges


def test_unpickled_graph_is_hashed_on_every_call():
    g = rmat(6, 8, seed=10)
    digest = graph_digest(g)
    g2 = pickle.loads(pickle.dumps(g))
    assert g2.edges.flags.writeable and not g2.frozen
    before = digest_stats()
    assert graph_digest(g2) == graph_digest(g2) == digest
    assert _digest_delta(before) == {"computed": 2, "memo_hits": 0}


def test_plan_cache_hit_and_miss_semantics():
    cache = PlanCache()
    g = rmat(8, 8, seed=1)
    a1 = plan_cannon(g, 2, cache=cache)
    assert not a1.cache_hit and cache.stats()["hits"] == 0
    a2 = plan_cannon(g, 2, cache=cache)
    assert a2 is a1 and a2.cache_hit and cache.stats()["hits"] == 1

    # different planning params -> miss (relabel is still shared)
    a3 = plan_cannon(g, 3, cache=cache)
    assert a3 is not a1
    assert a3.graph is a1.graph  # relabel stage hit the cache

    # edge edit -> digest change -> miss
    g_edit = Graph.from_edges(
        g.n,
        np.concatenate([g.edges[:, 0], [0]]),
        np.concatenate([g.edges[:, 1], [g.n - 1]]),
    )
    a4 = plan_cannon(g_edit, 2, cache=cache)
    assert a4 is not a1 and a4.digest != a1.digest

    # other plan kinds cache independently but share the relabel
    s1 = plan_summa(g, 2, 2, cache=cache)
    o1 = plan_oned(g, 4, cache=cache)
    assert s1.graph is a1.graph and o1.graph is a1.graph


def test_plan_cache_disabled_and_lru():
    g = rmat(7, 8, seed=2)
    off = PlanCache(maxsize=0)
    a1 = plan_cannon(g, 2, cache=off)
    a2 = plan_cannon(g, 2, cache=off)
    assert a2 is not a1 and len(off) == 0

    tiny = PlanCache(maxsize=2)
    plan_cannon(g, 2, cache=tiny)  # relabel + plan entries
    plan_cannon(g, 3, cache=tiny)
    assert tiny.stats()["evictions"] > 0


def test_cache_hit_skips_planning_and_staging():
    cache = PlanCache()
    g = rmat(9, 8, seed=3)
    r1 = count_triangles(g, q=1, cache=cache)
    r2 = count_triangles(g, q=1, cache=cache)
    assert r2.triangles == r1.triangles
    assert r2.plan is r1.plan  # same artifact -> same plan object
    # warm re-plan is drastically cheaper than the cold one
    assert r2.preprocess_seconds < r1.preprocess_seconds


# ======================================================================
# cyclic relabel stage (paper §5.3 step 1)
# ======================================================================
@pytest.mark.parametrize("n,p", [(12, 4), (256, 3), (10, 3)])
def test_cyclic_relabel_round_trip(n, p):
    perm = cyclic_relabel(n, p)
    assert np.array_equal(np.sort(perm), np.arange(n))  # true permutation
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    assert np.array_equal(inv[perm], np.arange(n))
    if n % p == 0:  # exact paper positions when p | n
        v = np.arange(n)
        assert np.array_equal(perm, (v % p) * (n // p) + v // p)


def test_cyclic_relabel_graph_round_trip_and_count():
    g = rmat(8, 8, seed=4)
    perm = cyclic_relabel(g.n, 3)
    inv = np.empty(g.n, dtype=np.int64)
    inv[perm] = np.arange(g.n)
    back = g.relabel(perm).relabel(inv)
    assert np.array_equal(back.edges, g.edges)
    # wired into the pipeline as the optional first stage
    exp = triangle_count_oracle(g)
    assert count_triangles(g, q=1, cyclic_p=3).triangles == exp
    art = plan_cannon(g, 2, cyclic_p=4, cache=PlanCache())
    assert art.perm is not None
    assert np.array_equal(np.sort(art.perm), np.arange(g.n))


# ======================================================================
# batched front-end
# ======================================================================
def _mixed_batch():
    return [
        named_graph("bull"),
        named_graph("karate"),
        rmat(8, 8, seed=2),
        rmat(7, 8, seed=3),
    ]


@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
def test_count_triangles_many_matches_individual(schedule):
    graphs = _mixed_batch()
    expected = [
        count_triangles(g, q=1, schedule=schedule).triangles for g in graphs
    ]
    assert expected == [triangle_count_oracle(g) for g in graphs]
    res = count_triangles_many(graphs, q=1, schedule=schedule)
    assert res.triangles == expected
    assert res.batch == len(graphs)
    assert res.padding_overhead >= 0.0


def test_count_triangles_many_program_cache_and_search2():
    cache = PlanCache()
    graphs = _mixed_batch()
    expected = [triangle_count_oracle(g) for g in graphs]
    r1 = pipeline_many(graphs, q=1, method="search2", cache=cache)
    assert r1.triangles == expected and not r1.cache_hit
    r2 = pipeline_many(graphs, q=1, method="search2", cache=cache)
    assert r2.triangles == expected and r2.cache_hit

    with pytest.raises(ValueError, match="CSR methods"):
        pipeline_many(graphs, q=1, method="dense")
    with pytest.raises(ValueError, match="cannon-schedule"):
        pipeline_many(graphs, q=1, schedule="summa", method="search2")


def test_split_specs_heuristics():
    """Launch-layer spec lists: ';' separates; a lone comma-parameter
    spec stays whole; comma-separated simple specs still split."""
    from repro.core.generators import graphs_from_specs, split_specs

    assert split_specs("rmat:10,8,1") == ["rmat:10,8,1"]
    assert split_specs("rmat:10,karate") == ["rmat:10", "karate"]
    assert split_specs("rmat:10,8,1;karate") == ["rmat:10,8,1", "karate"]
    assert split_specs("karate") == ["karate"]
    assert [g.n for g in graphs_from_specs("rmat:8,8,1;bull")] == [256, 5]


DIST_BATCH_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import (count_triangles_many, named_graph, rmat,
                        triangle_count_oracle)

graphs = [named_graph("bull"), named_graph("karate"),
          rmat(8, 8, seed=2), rmat(7, 8, seed=3)]
expected = [triangle_count_oracle(g) for g in graphs]
for schedule in ("cannon", "summa", "oned"):
    res = count_triangles_many(graphs, q=2, schedule=schedule)
    assert res.triangles == expected, (schedule, res.triangles, expected)
    print(f"{schedule}: {res.triangles} ok")
print("ALL-OK")
"""


def test_count_triangles_many_distributed(distributed_runner):
    out = distributed_runner(DIST_BATCH_CODE, ndev=4, timeout=1200)
    assert "ALL-OK" in out
