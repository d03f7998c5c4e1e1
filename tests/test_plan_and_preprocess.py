"""Unit tests for preprocessing, decomposition, planning, and the blob."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_plan, degree_order, preprocess, rmat, erdos_renyi
from repro.core.decomp import cyclic_blocks
from repro.core.generators import named_graph
from repro.core.blob import blob_layout, pack_blob, unpack_blob
from repro.core.cannon import pod_stack_arrays


def test_degree_order_nondecreasing():
    g = rmat(9, 8, seed=0)
    perm = degree_order(g)
    deg = g.degrees()
    new_deg = np.zeros_like(deg)
    new_deg[perm] = deg
    assert np.all(np.diff(new_deg) >= 0)
    # perm is a permutation
    assert np.array_equal(np.sort(perm), np.arange(g.n))


def test_degree_order_stability():
    g = named_graph("star")
    perm = degree_order(g)
    leaves = np.arange(1, 8)
    # all leaves have degree 1 and keep their relative order
    assert np.all(np.diff(perm[leaves]) > 0)


def test_preprocess_u_rows_shrink():
    """After degree ordering, U-row lengths are bounded by the ordering
    property: row i only points to later (>= degree) vertices."""
    g = rmat(10, 8, seed=1)
    g2, _ = preprocess(g)
    u = g2.upper_csr()
    # max U row length should be <= max degree and typically much smaller
    assert np.max(np.diff(u.indptr)) <= np.max(g.degrees())


@pytest.mark.parametrize("r,c", [(2, 2), (3, 3), (2, 4)])
def test_cyclic_blocks_cover_all_edges(r, c):
    g = rmat(8, 8, seed=2)
    blocks = cyclic_blocks(g, r, c)
    total = sum(blocks[x][y].nnz for x in range(r) for y in range(c))
    assert total == g.m
    # ownership: each edge's block is (i % r, j % c)
    for x in range(r):
        for y in range(c):
            blk = blocks[x][y]
            rows = np.repeat(
                np.arange(blk.n_rows), np.diff(blk.indptr)
            )
            gi = rows * r + x
            gj = blk.indices * c + y
            assert np.all(gi < gj)  # U is strictly upper triangular


def test_plan_balance_stats():
    g = rmat(10, 8, seed=3)
    g2, _ = preprocess(g)
    plan = build_plan(g2, 4)
    st = plan.stats
    # paper Table 3: cyclic task imbalance should be small (<6% there;
    # allow slack for our smaller graphs)
    assert st.task_imbalance < 1.6
    assert st.intersection_tasks_total > 0
    assert 0.0 <= st.padding_fraction_indices < 0.9


def test_plan_cannon_pairing_identity():
    """A/B pre-skew: at shift s the device holds U_{x,(x+y+s)%q} and
    U_{y,(x+y+s)%q} — verified by replaying the ppermute on the host."""
    g = rmat(8, 8, seed=4)
    g2, _ = preprocess(g)
    q = 3
    plan = build_plan(g2, q)
    blocks = plan.blocks
    a = plan.a_indptr.copy()
    b = plan.b_indptr.copy()
    for s in range(q):
        for x in range(q):
            for y in range(q):
                z = (x + y + s) % q
                assert np.array_equal(a[x, y], blocks[x][z].indptr)
                assert np.array_equal(b[x, y], blocks[y][z].indptr)
        a = np.roll(a, -1, axis=1)  # shift left along grid columns
        b = np.roll(b, -1, axis=0)  # shift up along grid rows


def test_pod_stack_covers_all_shifts():
    g = rmat(8, 8, seed=5)
    g2, _ = preprocess(g)
    q, npods = 4, 2
    plan = build_plan(g2, q)
    arrays = pod_stack_arrays(plan.device_arrays(), npods, q)
    blocks = plan.blocks
    for t in range(npods):
        a = arrays["a_indptr"][t].copy()
        for s_local in range(q // npods):
            s = t + s_local * npods
            for x in range(q):
                for y in range(q):
                    z = (x + y + s) % q
                    assert np.array_equal(a[x, y], blocks[x][z].indptr)
            a = np.roll(a, -npods, axis=1)


def test_blob_roundtrip():
    arrs = [
        jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
        jnp.arange(5, dtype=jnp.int32),
        jnp.ones((2, 2, 2), dtype=jnp.int32),
    ]
    layout, total = blob_layout([a.shape for a in arrs])
    blob = pack_blob(arrs)
    assert blob.shape == (total,)
    back = unpack_blob(blob, layout)
    for a, b in zip(arrs, back):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_distributed_counting_sort_matches_host(distributed_runner):
    code = """
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.preprocess import distributed_degree_rank, degree_order
from repro.core import rmat
g = rmat(6, 6, seed=9)
deg = g.degrees()
p = 4
n = g.n
from repro import compat
mesh = compat.make_mesh((p,), ("x",))
chunk = n // p
fn = jax.jit(compat.shard_map(
    lambda d: distributed_degree_rank(d, "x"),
    mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
ranks = np.asarray(fn(jnp.asarray(deg, dtype=jnp.int32)))
perm = degree_order(g)
assert np.array_equal(ranks, perm), (ranks[:10], perm[:10])
print("OK")
"""
    out = distributed_runner(code, ndev=4)
    assert "OK" in out


def test_analytic_plan_shapes():
    from repro.core import analytic_plan

    plan = analytic_plan(n=1 << 20, m=1 << 24, q=16, dmax_block=512)
    structs = plan.shape_structs()
    assert structs["a_indices"].shape == (16, 16, plan.nnz_pad)
    assert plan.nnz_pad == int(np.ceil((1 << 24) / 256 * 1.25))
