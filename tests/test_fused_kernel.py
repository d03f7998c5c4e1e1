"""Fused Pallas mega-kernel suite (DESIGN.md §5.1 / §4.6).

Marked ``fused`` so CI can run it as its own lane (``pytest -m fused``);
it also runs in tier-1, where the Pallas body executes under the
interpreter (single CPU device — see conftest).
"""
import dataclasses
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_plan,
    count_triangles,
    erdos_renyi,
    graph_from_spec,
    named_graph,
    preprocess,
    rmat,
    triangle_count_oracle,
)

pytestmark = pytest.mark.fused


def _fixture(name):
    return {
        "edgeless": lambda: erdos_renyi(24, 0.0, seed=0),
        "star": lambda: named_graph("star"),
        "cliques": lambda: graph_from_spec("cliques:2,10"),
        "rmat": lambda: rmat(8, 8, seed=5),
    }[name]()


# ----------------------------------------------------------------------
# count equivalence: fused ≡ incumbent ≡ oracle on every schedule
# ----------------------------------------------------------------------
@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
@pytest.mark.parametrize("fixture", ["edgeless", "star", "cliques", "rmat"])
def test_fused_matches_incumbent_q1(schedule, fixture):
    g = _fixture(fixture)
    exp = triangle_count_oracle(g)
    got = count_triangles(g, q=1, schedule=schedule, method="fused")
    assert got.triangles == exp, (schedule, fixture)
    # the incumbent must agree: two-level search2 on Cannon, plain
    # search on the ring (global ids, no row-encoded keys) and on SUMMA
    # (which never wired explicit search2 at the api level)
    incumbent = "search2" if schedule == "cannon" else "search"
    ref = count_triangles(g, q=1, schedule=schedule, method=incumbent)
    assert ref.triangles == exp, (schedule, fixture)


def test_fused_matches_dense_oracle_path():
    g = rmat(8, 8, seed=2)
    exp = triangle_count_oracle(g)
    assert count_triangles(g, q=1, method="fused").triangles == exp
    assert count_triangles(g, q=1, method="dense").triangles == exp


def test_fused_distributed_q3(distributed_runner):
    code = """
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import count_triangles, rmat, triangle_count_oracle
g = rmat(9, 8, seed=42)
exp = triangle_count_oracle(g)
for schedule in ("cannon", "summa", "oned"):
    r = count_triangles(g, q=3, schedule=schedule, method="fused")
    assert r.triangles == exp, (schedule, r.triangles, exp)
print("OK", exp)
"""
    out = distributed_runner(code, ndev=9)
    assert "OK" in out


# ----------------------------------------------------------------------
# interpreter-mode parity: Pallas body vs the independent lax reference
# ----------------------------------------------------------------------
def _random_csr(rng, nrows, maxd, n, pad=7):
    rows = [
        np.sort(rng.choice(n, size=rng.integers(0, maxd + 1), replace=False))
        for _ in range(nrows)
    ]
    indptr = np.zeros(nrows + 1, np.int32)
    indptr[1:] = np.cumsum([len(r) for r in rows])
    idx = np.concatenate(rows + [np.zeros(pad)]).astype(np.int32)
    return jnp.asarray(indptr), jnp.asarray(idx)


@pytest.mark.parametrize("tcount", [0, 1, 250])
def test_short_panel_interpret_parity(tcount):
    from repro.kernels.tc_fused.ref import fused_short_ref
    from repro.kernels.tc_fused.tc_fused import fused_short_counts

    rng = np.random.default_rng(0)
    nrows, maxd, n = 40, 12, 500
    ap, ai = _random_csr(rng, nrows, maxd, n)
    bp, bi = _random_csr(rng, nrows, maxd, n)
    ti = jnp.asarray(rng.integers(0, nrows, 300).astype(np.int32))
    tj = jnp.asarray(rng.integers(0, nrows, 300).astype(np.int32))
    # dense oracle over the same blocks
    dense = {}
    for tag, (ptr, idx) in (("a", (ap, ai)), ("b", (bp, bi))):
        m = np.zeros((nrows, n))
        ptr, idx = np.asarray(ptr), np.asarray(idx)
        for r in range(nrows):
            m[r, idx[ptr[r]:ptr[r + 1]]] = 1
        dense[tag] = m
    exp = int(
        sum(
            (dense["a"][i] * dense["b"][j]).sum()
            for i, j in zip(np.asarray(ti)[:tcount], np.asarray(tj)[:tcount])
        )
    )
    ref = int(
        fused_short_ref(ap, ai, bp, bi, ti, tj, tcount, d=maxd, tile=32)
    )
    pal = int(
        jnp.sum(
            fused_short_counts(
                ap, ai, bp, bi, ti, tj, tcount,
                tile=32, d=maxd, interpret=True,
            )
        )
    )
    assert exp == ref == pal, (tcount, exp, ref, pal)


def test_engine_fused_pallas_interpret_matches():
    g = rmat(8, 8, seed=2)
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, method="fused", fused_impl="pallas-interpret")
    assert r.triangles == exp


# ----------------------------------------------------------------------
# guard rails: the fused kernel refuses plans it would miscount on
# ----------------------------------------------------------------------
def test_check_fused_split_refuses_probe_split():
    from repro.core.engine import check_fused_split

    g2, _ = preprocess(rmat(7, 8, seed=3))
    plan = build_plan(g2, 1)  # no autotune report at all
    with pytest.raises(ValueError, match="maxfrag"):
        check_fused_split(plan)


def test_fused_factory_requires_split_fields():
    from repro.core.engine import make_csr_kernel

    with pytest.raises(ValueError, match="maxfrag"):
        make_csr_kernel(
            "fused", dpad=8, chunk=8, probe_shorter=True,
            count_dtype=jnp.int32, sentinel=9,
            n_long=None, d_small=None,
        )


@pytest.mark.parametrize(
    "module,name,fields",
    [
        ("repro.core.plan", "TCPlan", {"n_long", "d_small", "bucket_stats"}),
        ("repro.core.summa", "SummaPlan", {"n_long", "d_small"}),
        ("repro.core.onedim", "OneDPlan", {"n_long", "d_small"}),
    ],
)
def test_plan_split_fields_are_real_dataclass_fields(module, name, fields):
    cls = getattr(importlib.import_module(module), name)
    assert fields <= {f.name for f in dataclasses.fields(cls)}, cls


def test_two_sided_split_report():
    from repro.pipeline import plan_cannon

    g = graph_from_spec("cliques:2,10")
    art = plan_cannon(g, 1, chunk=64, autotune="fused")
    plan = art.plan
    assert plan.autotune["split"] == "maxfrag"
    assert plan.n_long == plan.autotune["n_long"]
    assert plan.d_small == plan.autotune["d_small"]


# ----------------------------------------------------------------------
# measured autotune: table keying, cold/warm persistence, roofline
# ----------------------------------------------------------------------
def test_measured_table_key_buckets():
    from repro.kernels.tc_fused.autotune import measured_table_key

    base = dict(
        kind="cannon", backend="cpu", dtype="int32", nb=100,
        nnz_pad=1000, tmax=500, dmax=64, d_small=16, tail_heavy=False,
    )
    k = measured_table_key(**base)
    # same power-of-two bucket -> same key (reusable across graphs of
    # the same size class); crossing the bucket or changing a split
    # parameter or backend re-keys
    assert measured_table_key(**{**base, "nnz_pad": 900}) == k
    assert measured_table_key(**{**base, "nnz_pad": 1025}) != k
    assert measured_table_key(**{**base, "d_small": 24}) != k
    assert measured_table_key(**{**base, "backend": "tpu"}) != k
    assert measured_table_key(**{**base, "tail_heavy": True}) != k


def test_measured_table_cold_then_warm(tmp_path):
    g = graph_from_spec("cliques:2,12")
    exp = triangle_count_oracle(g)
    r1 = count_triangles(
        g, q=1, method="auto", autotune="measured",
        measured_dir=str(tmp_path),
    )
    assert r1.autotune_mode == "measured"
    assert r1.measured_table_hit is False
    assert r1.triangles == exp
    assert len(list(tmp_path.glob("*.json"))) == 1
    r2 = count_triangles(
        g, q=1, method="auto", autotune="measured",
        measured_dir=str(tmp_path),
    )
    assert r2.measured_table_hit is True
    assert r2.triangles == exp
    assert len(list(tmp_path.glob("*.json"))) == 1


def test_measured_entry_requires_split(tmp_path):
    from repro.kernels.tc_fused.autotune import measured_entry

    g2, _ = preprocess(rmat(7, 8, seed=3))
    plan = build_plan(g2, 1)
    with pytest.raises(ValueError, match="maxfrag"):
        measured_entry(plan, table_dir=str(tmp_path))


def test_roofline_prediction_matches_measurement(tmp_path, monkeypatch):
    """On the dense-ish bench fixture the analytic roofline, priced at
    the v5e's published peaks, and the measured table must agree on the
    winner (and it is the fused kernel — the acceptance bar the
    benchmark records).  A device kind without published peaks (the
    CPU) records no roofline verdict."""
    from repro.kernels.tc_fused import autotune
    from repro.pipeline import plan_cannon

    g = graph_from_spec("cliques:3,60")
    art = plan_cannon(g, 1, chunk=512, autotune="fused")
    entry, hit = autotune.measured_entry(
        art.plan, table_dir=str(tmp_path), force=True
    )
    assert not hit
    assert entry["roofline"] is None
    monkeypatch.setattr(autotune, "_device_kind", lambda: "TPU v5 lite")
    entry, _ = autotune.measured_entry(
        art.plan, table_dir=str(tmp_path), force=True
    )
    assert entry["winner"] == "fused"
    assert entry["roofline"]["predicted_winner"] == "fused"
    assert entry["roofline"]["hbm_bw"] == 819e9
    assert autotune.predict_fused_wins(entry)
