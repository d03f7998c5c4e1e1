"""Sparsity-aware step skipping + the double-buffered Cannon engine.

Covers: mask derivation (shapes, exactness on block-sparse fixtures),
masked-vs-unmasked equivalence across (schedule × operand store) on
graphs with empty blocks, the int32 hash-key-width guard, and the
stepper's double-buffered-carry checkpoint round trip.
"""
import numpy as np
import pytest

from repro.core import (
    Graph,
    build_plan,
    count_triangles,
    count_triangles_many,
    named_graph,
    preprocess,
    residue_cliques,
    rmat,
    star,
    triangle_count_oracle,
)
from repro.core.cannon import pod_stack_arrays
from repro.core.count import aug_key_dtype
from repro.core.onedim import build_oned_plan
from repro.core.summa import build_summa_plan

# graphs engineered to leave blocks empty under the cyclic decomposition:
# karate (q=3 does not divide n=34), a star (all edges in the hub's block
# column), residue cliques (block-diagonal: only q of q^2 blocks live)
SPARSE_FIXTURES = {
    "karate": lambda: named_graph("karate"),
    "star": lambda: star(37),
    "cliques": lambda: residue_cliques(3, 8),
    "rmat": lambda: rmat(8, 8, seed=6),
}

COMBOS = [
    ("cannon", "search"),
    ("cannon", "global"),
    ("cannon", "dense"),
    ("cannon", "tile"),
    ("summa", "search"),
    ("oned", "search"),
]


# ======================================================================
# mask derivation
# ======================================================================
def test_mask_shapes_and_staging():
    g, _ = preprocess(residue_cliques(3, 8))
    q = 3
    plan = build_plan(g, q)
    assert plan.step_keep is not None
    assert plan.step_keep.shape == (q, q, q)
    assert plan.step_keep.dtype == np.bool_
    assert "step_keep" in plan.device_arrays()
    splan = build_summa_plan(g, 2, 2)
    assert splan.step_keep.shape == (2, 2, 2)
    oplan = build_oned_plan(g, 4)
    assert oplan.step_keep.shape == (4, 4)

    nomask = build_plan(g, q, step_masks=False)
    assert nomask.step_keep is None
    assert "step_keep" not in nomask.device_arrays()


def test_block_diagonal_mask_is_maximally_sparse():
    """On residue cliques over q classes, each diagonal device has
    exactly one live shift: q of q^3 (device, shift) entries survive."""
    q = 3
    g, _ = preprocess(residue_cliques(q, 10))
    plan = build_plan(g, q)
    assert int(plan.step_keep.sum()) == q
    assert int(plan.step_keep.size) == q ** 3


def test_mask_is_exact_no_live_step_dropped():
    """Every (device, shift) with non-zero probe work must be kept —
    the mask may only drop provably-zero steps."""
    g, _ = preprocess(rmat(8, 8, seed=9))
    plan = build_plan(g, 3)
    probe = plan.stats.probe_work_per_device_shift
    assert np.all(plan.step_keep[probe > 0])
    assert not np.any(plan.step_keep[probe == 0])


def test_resolve_step_mask_demands_masks():
    import jax.numpy as jnp  # noqa: F401

    from repro.core.api import make_grid_mesh
    from repro.core.cannon import build_cannon_fn

    g, _ = preprocess(named_graph("karate"))
    plan = build_plan(g, 1, step_masks=False)
    with pytest.raises(ValueError, match="no step_keep"):
        build_cannon_fn(plan, make_grid_mesh(1), use_step_mask=True)


def test_pod_stack_strides_mask():
    """Pod t's local step s is global shift t + s*npods."""
    g, _ = preprocess(rmat(7, 8, seed=5))
    q, npods = 4, 2
    plan = build_plan(g, q)
    arrays = pod_stack_arrays(plan.device_arrays(), npods, q)
    sk = arrays["step_keep"]
    assert sk.shape == (npods, q, q, q // npods)
    for t in range(npods):
        for sl in range(q // npods):
            assert np.array_equal(
                sk[t, :, :, sl], plan.step_keep[:, :, t + sl * npods]
            )


# ======================================================================
# masked == unmasked equivalence (q=1 in-process; q=2,3 subprocesses)
# ======================================================================
@pytest.mark.parametrize("graph_name", sorted(SPARSE_FIXTURES))
@pytest.mark.parametrize("schedule,method", COMBOS)
def test_masked_equals_unmasked_q1(graph_name, schedule, method):
    g = SPARSE_FIXTURES[graph_name]()
    exp = triangle_count_oracle(g)
    masked = count_triangles(g, q=1, schedule=schedule, method=method)
    unmasked = count_triangles(
        g, q=1, schedule=schedule, method=method, use_step_mask=False
    )
    assert masked.triangles == unmasked.triangles == exp


@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
def test_masked_engine_on_edgeless_graph(schedule):
    """m=0 masks off every step — the cond's zero branch must run."""
    g = Graph.from_edges(6, [], [], name="empty")
    assert count_triangles(g, q=1, schedule=schedule).triangles == 0


def test_single_buffer_body_matches():
    g = SPARSE_FIXTURES["cliques"]()
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, schedule="cannon", double_buffer=False)
    assert r.triangles == exp


def test_batched_engine_with_sparse_fixtures():
    graphs = [residue_cliques(2, 6), star(13), named_graph("karate")]
    expected = [triangle_count_oracle(g) for g in graphs]
    res = count_triangles_many(graphs, q=1)
    assert res.triangles == expected


DIST_MASK_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import (count_triangles, residue_cliques, star, named_graph,
                        triangle_count_oracle)

q = {q}
fixtures = [residue_cliques(q, 12), star(10 * q + 1), named_graph("karate")]
for g in fixtures:
    exp = triangle_count_oracle(g)
    for schedule, method in {combos}:
        m = count_triangles(g, q=q, schedule=schedule, method=method)
        u = count_triangles(g, q=q, schedule=schedule, method=method,
                            use_step_mask=False)
        s = count_triangles(g, q=q, schedule=schedule, method=method,
                            double_buffer=False)
        assert m.triangles == u.triangles == s.triangles == exp, (
            g.name, schedule, method, m.triangles, u.triangles, s.triangles, exp)
        sk = getattr(m.plan, "step_keep", None)
        assert sk is not None
        if g.name.startswith("cliques"):
            assert sk.size - sk.sum() > 0, (g.name, schedule, "no skips")
        print(f"{{g.name}}/{{schedule}}/{{method}} ok")
print("ALL-OK")
"""


@pytest.mark.parametrize("q", [2, 3])
def test_masked_equivalence_distributed(q, distributed_runner):
    combos = [("cannon", "search"), ("cannon", "global"),
              ("summa", "search"), ("oned", "search")]
    out = distributed_runner(
        DIST_MASK_CODE.format(q=q, combos=combos), ndev=q * q, timeout=1200
    )
    assert "ALL-OK" in out


# ======================================================================
# stepper: double-buffered carry checkpoint round trip
# ======================================================================
DIST_STEPPER_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from repro.core import build_plan, preprocess, rmat, triangle_count_oracle
from repro.core.api import make_grid_mesh
from repro.core.cannon import build_cannon_fn, build_cannon_stepper

q = 2
g = rmat(8, 8, seed=11)
exp = triangle_count_oracle(g)
g2, _ = preprocess(g)
plan = build_plan(g2, q)
mesh = make_grid_mesh(q)
stepper = build_cannon_stepper(plan, mesh)
arrays = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
statics = {k: arrays[k] for k in ("m_ti", "m_tj", "m_cnt", "step_keep")}

carry = list(stepper.prime(arrays))
assert stepper.n_carry == 8  # double-buffered: 2 generations x 4 arrays
acc = jnp.zeros((q, q), jnp.int64)

saved = None
for s in range(q):
    if s == 1:  # checkpoint mid-loop: host numpy round trip, bytes exact
        saved = ([np.asarray(c).copy() for c in carry], np.asarray(acc).copy())
    out = stepper(tuple(carry) + (acc,), statics, step=s)
    carry, acc = list(out[:-1]), out[-1]
total_uninterrupted = int(np.asarray(acc).sum())

# resume from the step-1 checkpoint and replay the tail
carry2 = [jnp.asarray(c) for c in saved[0]]
acc2 = jnp.asarray(saved[1])
for s in range(1, q):
    out = stepper(tuple(carry2) + (acc2,), statics, step=s)
    carry2, acc2 = list(out[:-1]), out[-1]
total_resumed = int(np.asarray(acc2).sum())

assert total_uninterrupted == total_resumed == exp, (
    total_uninterrupted, total_resumed, exp)
# the resumed double-buffered carry must be byte-identical to the
# uninterrupted one
for a, b in zip(carry, carry2):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

# and the stepper agrees with the scan engine
fn = build_cannon_fn(plan, mesh)
assert int(fn(**arrays)) == exp
print("STEPPER-OK")
"""


def test_stepper_double_buffer_checkpoint_roundtrip(distributed_runner):
    out = distributed_runner(DIST_STEPPER_CODE, ndev=4, timeout=1200)
    assert "STEPPER-OK" in out


# ======================================================================
# hash-key width guard (int32 truncation regression)
# ======================================================================
def test_aug_key_dtype_boundary():
    import jax.numpy as jnp

    from repro import compat

    assert aug_key_dtype(46340) == jnp.int32  # 46340^2 - 1 < 2^31
    if compat.x64_enabled():
        assert aug_key_dtype(46341) == jnp.int64
    else:
        with pytest.raises(OverflowError, match="int64"):
            aug_key_dtype(46341)


DIST_KEY_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from repro.core.count import aug_key_dtype, count_pair_search_global

assert aug_key_dtype(46341) == jnp.int64

# synthetic CSR block just past the int32 key boundary: rows near nb
# produce keys row * (nb+1) + col > 2^31, which int32 keys would wrap
# into collisions/mis-sorts
nb = 50000
rows_b = {46290: [10, 20, 30], 49000: [5, 10, 40]}
rows_a = {7: [10, 20, 999], 8: [5, 40, 41]}

def to_csr(rows, nnz_pad):
    indptr = np.zeros(nb + 1, dtype=np.int32)
    for r, cols in rows.items():
        indptr[r + 1] = len(cols)
    indptr = np.cumsum(indptr, dtype=np.int32)
    indices = np.full(nnz_pad, nb, dtype=np.int32)
    at = 0
    for r in sorted(rows):
        cols = rows[r]
        indices[at:at + len(cols)] = cols
        at += len(cols)
    return jnp.asarray(indptr), jnp.asarray(indices)

a_ptr, a_idx = to_csr(rows_a, 8)
b_ptr, b_idx = to_csr(rows_b, 8)
tasks = [(7, 46290), (7, 49000), (8, 49000), (8, 46290)]
expected = sum(
    len(set(rows_a[i]) & set(rows_b[j])) for i, j in tasks
)
ti = jnp.asarray(np.array([t[0] for t in tasks], np.int32))
tj = jnp.asarray(np.array([t[1] for t in tasks], np.int32))
got = int(count_pair_search_global(
    a_ptr, a_idx, b_ptr, b_idx, ti, tj, jnp.asarray(len(tasks)),
    dpad=4, chunk=4,
))
assert got == expected, (got, expected)
print("KEYS-OK", got)
"""


def test_global_keys_past_int32_boundary(distributed_runner):
    out = distributed_runner(DIST_KEY_CODE, ndev=1, timeout=600)
    assert "KEYS-OK" in out
