"""Tests for the §Perf hillclimb code paths (H1a/H1b)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_plan,
    erdos_renyi,
    preprocess,
    rmat,
    triangle_count_oracle,
)
from repro.core.api import make_grid_mesh
from repro.core.cannon import build_cannon_fn
from repro.core.count import (
    _lane_view,
    _window_rows,
    build_aug_keys,
    count_pair_search,
    count_pair_search_global,
    gather_rows,
)
from repro.core.plan import bucketize_plan


def _plan(seed=3, q=1, graph=None):
    g = rmat(9, 8, seed=seed) if graph is None else graph
    exp = triangle_count_oracle(g)
    g2, _ = preprocess(g)
    return g, exp, build_plan(g2, q)


def test_global_search_matches_flat():
    _, _, plan = _plan()
    a = plan.device_arrays()
    args = [
        jnp.asarray(a[k][0, 0])
        for k in ("a_indptr", "a_indices", "b_indptr", "b_indices",
                  "m_ti", "m_tj")
    ] + [jnp.asarray(a["m_cnt"][0, 0])]
    flat = count_pair_search(*args, dpad=plan.dmax, chunk=128)
    glob = count_pair_search_global(*args, dpad=plan.dmax, chunk=128)
    assert int(flat) == int(glob)


@pytest.mark.parametrize("probe_shorter", [True, False])
@pytest.mark.parametrize(
    "graph",
    [None, erdos_renyi(200, 150, seed=3)],
    ids=["rmat9", "er200-dmax135"],
)
def test_equality_search_matches_binary_search(
    monkeypatch, probe_shorter, graph
):
    """The TPU formulation of ``search`` (lane-row windows + dense
    equality) counts exactly what the CPU binary search counts, for a
    kernel call and for a whole ``count_triangles``; the dense graph's
    rows (``dmax`` 135) cross a 128-lane row."""
    from repro.core import count as count_mod
    from repro.core import count_triangles
    from repro.pipeline import PlanCache

    g, exp, plan = _plan(graph=graph)
    assert graph is None or plan.dmax > 128
    a = plan.device_arrays()
    args = [
        jnp.asarray(a[k][0, 0])
        for k in ("a_indptr", "a_indices", "b_indptr", "b_indices",
                  "m_ti", "m_tj")
    ]
    kw = dict(dpad=plan.dmax, chunk=128, probe_shorter=probe_shorter)
    want = [int(count_pair_search(*args, c, **kw)) for c in (0, 1, 500)]
    monkeypatch.setattr(count_mod, "_equality_intersect", lambda: True)
    got = [int(count_pair_search(*args, c, **kw)) for c in (0, 1, 500)]
    assert got == want
    res = count_triangles(g, q=1, cache=PlanCache(maxsize=0))
    assert res.triangles == exp


def _window_csr(dpad, rng):
    """CSR rows of at most ``dpad`` sorted distinct columns whose starts
    cover every residue mod 128, with zero-length rows, and whose last
    row is ``dpad`` long and ends the index array."""
    lens, seen, start = [], set(), 0
    while len(seen) < 128 or len(lens) < max(256, dpad + 1):
        n = 0 if len(lens) % 7 == 3 else int(rng.integers(0, dpad + 1))
        seen.add(start % 128)
        lens.append(n)
        start += n
    lens.append(dpad)
    n_rows = len(lens)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices = np.concatenate(
        [np.sort(rng.choice(n_rows, k, replace=False)) for k in lens]
    )
    return indptr, indices.astype(np.int32)


@pytest.mark.parametrize("x64", [False, True], ids=["indptr32", "indptr64"])
@pytest.mark.parametrize("dpad", [1, 30, 127, 128, 129, 247])
def test_window_rows_match_gather_rows(monkeypatch, dpad, x64):
    """The TPU path's lane-row window fetch returns exactly the padded
    fragments of :func:`gather_rows`, at every start residue mod 128, for
    empty rows and the index array's last row, with int32 or (under x64)
    int64 ``indptr``; and the equality count over those rows matches the
    binary search."""
    from repro.core import count as count_mod

    rng = np.random.default_rng(dpad)
    indptr, indices = _window_csr(dpad, rng)
    n_rows = indptr.shape[0] - 1
    sentinel = n_rows + 1
    rows = np.concatenate([rng.permutation(n_rows), [n_rows - 1]])
    with jax.enable_x64(x64):
        ptr = jnp.asarray(indptr.astype(np.int64 if x64 else np.int32))
        idx = jnp.asarray(indices)
        r = jnp.asarray(rows.astype(np.int32))
        want, want_len = gather_rows(ptr, idx, r, dpad, sentinel)
        got, got_len = _window_rows(
            ptr, _lane_view(idx, dpad, sentinel), r, dpad, sentinel
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got_len), np.asarray(want_len))

        # half the tasks pair a row with itself, so the count is not 0
        tj = np.where(
            rng.random(rows.shape[0]) < 0.5, rows, rng.permutation(rows)
        )
        tj = jnp.asarray(tj.astype(np.int32))
        args = (ptr, idx, ptr, idx, r, tj, r.shape[0])
        kw = dict(dpad=dpad, chunk=128)
        binary = int(count_pair_search(*args, **kw))
        assert binary > 0
        monkeypatch.setattr(count_mod, "_equality_intersect", lambda: True)
        assert int(count_pair_search(*args, **kw)) == binary


def test_aug_keys_sorted_and_unique_rows():
    _, _, plan = _plan()
    aug = np.asarray(
        build_aug_keys(
            jnp.asarray(plan.b_indptr[0, 0]), jnp.asarray(plan.b_indices[0, 0])
        )
    )
    assert np.all(np.diff(aug) >= 0)  # sorted => binary search is valid


@pytest.mark.parametrize("d_small", [4, 16, 64])
def test_bucketed_matches_oracle(d_small):
    g, exp, plan = _plan(seed=7, q=1)
    bplan = bucketize_plan(plan, d_small=d_small)
    mesh = make_grid_mesh(1)
    fn = build_cannon_fn(bplan, mesh, method="search2")
    got = int(fn(**{k: jnp.asarray(v) for k, v in bplan.device_arrays().items()}))
    assert got == exp


def test_compressed_blob_matches_oracle(distributed_runner):
    code = """
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import build_plan, preprocess, rmat, triangle_count_oracle
from repro.core.api import make_grid_mesh
from repro.core.cannon import build_cannon_fn
from repro.core.plan import bucketize_plan
g = rmat(10, 8, seed=11)
exp = triangle_count_oracle(g)
g2, _ = preprocess(g)
plan = bucketize_plan(build_plan(g2, 2), d_small=32)
mesh = make_grid_mesh(2)
for kw in (dict(method="search", compress_lengths=True),
           dict(method="search2", compress_lengths=True)):
    fn = build_cannon_fn(plan, mesh, count_dtype=jnp.int64, **kw)
    got = int(fn(**{k: jnp.asarray(v) for k, v in plan.device_arrays().items()}))
    assert got == exp, (kw, got, exp)
print("OK")
"""
    assert "OK" in distributed_runner(code, ndev=4)


# NOTE: the hypothesis-based bucketed-probe property test lives in
# test_property.py so this module stays collectible without hypothesis.
